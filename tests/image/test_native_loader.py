"""Native C++ loader tests (skipped when the toolchain can't build it)."""

from io import BytesIO

import numpy as np
import pytest
from PIL import Image

from sparkdl_tpu.native import loader

pytestmark = pytest.mark.skipif(not loader.available(),
                                reason="native loader not built")


def _png_bytes(arr):
    buf = BytesIO()
    Image.fromarray(arr).save(buf, format="PNG")
    return buf.getvalue()


def _jpeg_bytes(arr, quality=95):
    buf = BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def test_png_lossless_roundtrip(rng):
    arr = rng.integers(0, 255, (57, 43, 3), dtype=np.uint8)
    out = loader.decode(_png_bytes(arr))
    np.testing.assert_array_equal(out, arr)


def test_jpeg_matches_pil(rng):
    arr = rng.integers(0, 255, (64, 48, 3), dtype=np.uint8)
    data = _jpeg_bytes(arr)
    out = loader.decode(data)
    pil = np.asarray(Image.open(BytesIO(data)))
    # libjpeg decode should be bit-identical (same library under PIL)
    assert int(np.abs(out.astype(int) - pil.astype(int)).max()) <= 1


def test_grayscale_png(rng):
    arr = rng.integers(0, 255, (20, 20), dtype=np.uint8)
    out = loader.decode(_png_bytes(arr))
    assert out.shape == (20, 20, 1)
    np.testing.assert_array_equal(out[:, :, 0], arr)


def test_rgba_png(rng):
    arr = rng.integers(0, 255, (10, 12, 4), dtype=np.uint8)
    out = loader.decode(_png_bytes(arr))
    assert out.shape == (10, 12, 4)
    np.testing.assert_array_equal(out, arr)


def test_resize_target(rng):
    arr = rng.integers(0, 255, (100, 80, 3), dtype=np.uint8)
    out = loader.decode(_png_bytes(arr), target_size=(32, 32))
    assert out.shape == (32, 32, 3)


def test_jpeg_dct_scaling_path(rng):
    # Target much smaller than source -> exercises scale_denom shortcut.
    arr = rng.integers(0, 255, (512, 512, 3), dtype=np.uint8)
    out = loader.decode(_jpeg_bytes(arr), target_size=(64, 64))
    assert out.shape == (64, 64, 3)


def test_corrupt_returns_none():
    assert loader.decode(b"not an image") is None


def test_batch_decode(rng):
    blobs = [
        _jpeg_bytes(rng.integers(0, 255, (40 + i, 30, 3), dtype=np.uint8))
        for i in range(5)
    ]
    out = loader.decode_batch(blobs, (24, 24))
    assert out.shape == (5, 24, 24, 3) and out.dtype == np.uint8


def test_batch_decode_with_failure_returns_none(rng):
    blobs = [_png_bytes(rng.integers(0, 255, (8, 8, 3), dtype=np.uint8)),
             b"garbage"]
    assert loader.decode_batch(blobs, (8, 8)) is None


def test_batch_grayscale_promoted_to_rgb(rng):
    gray = rng.integers(0, 255, (16, 16), dtype=np.uint8)
    out = loader.decode_batch([_png_bytes(gray)], (16, 16))
    assert out.shape == (1, 16, 16, 3)
    np.testing.assert_array_equal(out[0, :, :, 0], out[0, :, :, 1])


def test_grayscale_png_with_trns_probe_matches_decode(rng):
    # Regression: probe undercounted channels for gray+tRNS -> heap overflow.
    arr = rng.integers(0, 255, (16, 16), dtype=np.uint8)
    buf = BytesIO()
    Image.fromarray(arr, mode="L").save(buf, format="PNG", transparency=128)
    out = loader.decode(buf.getvalue())
    assert out is not None and out.shape == (16, 16, 2)


# ---------------------------------------------------------------------------
# Build-on-load: stale libraries are rebuilt, failed builds are loud
# ---------------------------------------------------------------------------


@pytest.fixture
def private_native_dir(tmp_path, monkeypatch):
    """A private copy of the native sources with fresh loader state, so a
    test can build (or fail to build) without touching the package's own
    library that every other test in this process has loaded."""
    import os
    import shutil

    for src in loader._SOURCES:
        shutil.copy(os.path.join(loader._NATIVE_DIR, src), tmp_path / src)
    monkeypatch.setattr(loader, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(loader, "_lib", None)
    monkeypatch.setattr(loader, "_load_attempted", False)
    monkeypatch.delenv("SPARKDL_TPU_NO_NATIVE_BUILD", raising=False)
    return tmp_path


def test_stale_library_is_rebuilt(private_native_dir, rng):
    """A library older than image_loader.cc/build.sh counts as absent: the
    (here unloadable) leftover is replaced by a build from the sources."""
    import os

    lib = private_native_dir / loader._LIB_NAME
    lib.write_bytes(b"built somewhere else, from older sources")
    src_mtime = os.path.getmtime(private_native_dir / "image_loader.cc")
    os.utime(lib, (src_mtime - 60, src_mtime - 60))
    assert not loader._is_current(str(lib))
    assert loader.available()  # rebuilt (subprocess bounded at 120 s)
    assert loader._is_current(str(lib))
    arr = rng.integers(0, 255, (9, 7, 3), dtype=np.uint8)
    np.testing.assert_array_equal(loader.decode(_png_bytes(arr)), arr)


def test_current_library_is_not_rebuilt(private_native_dir):
    import os

    assert loader.available()
    lib = private_native_dir / loader._LIB_NAME
    built = os.path.getmtime(lib)
    loader._lib, loader._load_attempted = None, False
    (private_native_dir / "build.sh").write_text("exit 1\n")  # must not run
    os.utime(private_native_dir / "build.sh", (built - 60, built - 60))
    assert loader.available()
    assert os.path.getmtime(lib) == built


def test_failed_build_logs_compiler_output(private_native_dir, caplog):
    (private_native_dir / "image_loader.cc").write_text(
        "this is not C++ @@@\n")
    with caplog.at_level("WARNING", logger=loader.logger.name):
        assert not loader.available()
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert "build failed" in text and "error" in text.lower()
    assert "image_loader.cc" in text  # the compiler's own stderr, kept
