"""The fused kernels compile for the TPU v5e at the main path's real site
shapes (core/kernels.py).

Interpret mode (tests/core/test_kernels.py) checks numerics; it cannot
see what the chip's compiler refuses — ``preproc_resize`` had passed every
interpret-mode test while Mosaic rejected its in-kernel ``uint8 ->
float32`` cast at every shape. These tests compile each audition's own
Pallas candidate ahead of time for a DESCRIBED ``v5e:2x2`` (no chip
attached, nothing runs) and assert a Mosaic kernel is in the program.

The topology is described inside a fixture of this file and nowhere else:
only one process at a time may load the TPU's library, and under
pytest-xdist every worker imports every test file, so a call made at
import (or in a ``skipif``/``parametrize`` argument, or in conftest)
would take the library in the wrong worker. Compiles run in this test
process, with the persistent compilation cache off around them (an
ahead-of-time entry cannot be read back without a chip).
"""

import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from sparkdl_tpu.core import kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # or the TPU compiler logs under /tmp; setdefault keeps an outside
    # choice, and the variable is only read when the library loads here
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    # the library's failure modes (absent, locked by another process) are
    # not one exception type; any of them means "cannot be described here"
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache(topo):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _site(kernel, shape, dtype):
    return kernels.Site(kernel, "v5e-compile", shape, dtype)


# (kernel, launch geometry, dtype): the InceptionV3 1x1 ConvBN sites, the
# Xception middle/exit-flow separable convs, and the fused-preprocess
# prologue at a small, a model-sized and a typical-photo source.
_SITES = [
    _site("sep2d", (128, 19, 19, 728, 728), "bfloat16"),
    _site("sep2d", (8, 19, 19, 728, 728), "float32"),
    _site("sep2d", (128, 10, 10, 1024, 1536), "bfloat16"),
    _site("pw1x1_relu", (128, 35, 35, 192, 64), "bfloat16"),
    _site("pw1x1_relu", (8, 35, 35, 288, 48), "float32"),
    _site("pw1x1_relu", (128, 17, 17, 768, 192), "bfloat16"),
    _site("pw1x1_relu", (128, 8, 8, 1280, 320), "bfloat16"),
    _site("pw1x1", (8, 8, 8, 2048, 192), "float32"),
    _site("pw1x1_relu", (8, 73, 73, 64, 80), "bfloat16"),
    _site("preproc", (8, 64, 64, 3, 32, 32), "uint8->float32"),
    _site("preproc", (8, 64, 64, 3, 32, 32), "uint8->bfloat16"),
    _site("preproc", (8, 224, 224, 3, 299, 299), "uint8->float32"),
    _site("preproc", (8, 224, 224, 3, 299, 299), "uint8->bfloat16"),
    _site("preproc", (8, 375, 500, 3, 299, 299), "uint8->float32"),
    _site("preproc", (128, 375, 500, 3, 299, 299), "uint8->bfloat16"),
]


@pytest.mark.parametrize(
    "site", _SITES,
    ids=lambda s: f"{s.kernel}-{'x'.join(map(str, s.shape))}-{s.dtype}")
def test_pallas_candidate_compiles_for_v5e(site, one_chip,
                                           no_persistent_cache):
    pallas_fn, _, x = kernels._build_shootout(site)
    arg = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    compiled = jax.jit(pallas_fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()
