"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (mesh/sharding logic is
validated on faked host devices exactly as SURVEY.md §4 prescribes; the
chip is reached through ``chip_smoke.py``, never from here). These env
vars MUST be set before jax is first imported, hence they live at module
import time in conftest.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# The package places JAX's persistent compilation cache inside the
# checkout by default (sparkdl_tpu/__init__.py); the CPU suite would only
# slow down and fill the tree with it, so it is off here (and in every
# subprocess a test spawns, which inherits this) unless the directory was
# placed from outside.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

# Repo root on sys.path so `import sparkdl_tpu` works without install.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Debug hardening (SURVEY.md §5.2): SPARKDL_DEBUG=1 runs the whole suite
# under jax_debug_nans + tracer-leak checking (slow: op-by-op; off by
# default). The NaN regression test enables it locally either way.
if os.environ.get("SPARKDL_DEBUG", "") not in ("", "0"):
    jax.config.update("jax_debug_nans", True)
    jax.config.update("jax_check_tracer_leaks", True)

# The suite's numeric contract is BIT-identity (chaos/durability/replay
# tests compare exact bytes), so the test default pins the inference
# path to float32 and the blind power-of-two ladder — at conftest IMPORT
# time, before any test module's EngineConfig snapshot runs, so every
# snapshot/restore fixture captures the pinned values. The library
# defaults stay bfloat16 + tuned (engine/dataframe.py); precision and
# planner tests opt back in explicitly.
from sparkdl_tpu.engine.dataframe import EngineConfig  # noqa: E402

EngineConfig.inference_precision = "float32"
EngineConfig.bucket_ladder = "pow2"


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tiny_image_dir(tmp_path):
    """A directory of small deterministic JPEG+PNG fixtures."""
    from PIL import Image

    rng = np.random.default_rng(0)
    paths = []
    for i in range(4):
        arr = rng.integers(0, 255, size=(32 + 8 * i, 40, 3), dtype=np.uint8)
        p = tmp_path / f"img_{i}.jpg"
        Image.fromarray(arr).save(p, quality=95)
        paths.append(p)
    arr = rng.integers(0, 255, size=(24, 24, 3), dtype=np.uint8)
    p = tmp_path / "img_png.png"
    Image.fromarray(arr).save(p)
    paths.append(p)
    (tmp_path / "not_an_image.txt").write_text("hello")
    return tmp_path
