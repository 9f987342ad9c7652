"""Ahead-of-time compiles of the IRC kernels for a TPU v5e chip that is
described, not attached: Mosaic refuses here what interpret mode cannot see
(unsupported primitives, lane-splitting reshapes, VMEM over budget).

The topology is described inside a module fixture — never at import, in a
`skipif` or in a `parametrize` argument — so every pytest-xdist worker
collects the same tests and only the worker that runs this file loads the
TPU compiler.  All such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.autotune import DEFAULT_CANDIDATES
from repro.kernels.ops import irc_mvm, irc_mvm_chips
from repro.kernels.ref import IrcEpilogueParams

# the detector's group crossbar: 9 * 60 im2col rows + 32 bias rows, one
# 60-channel group per column block
R, N = 572, 60
C, M = 4, 512


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache, so it stays off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def shape(topo):
    """f32 ShapeDtypeStructs placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32,
                                              sharding=one_chip)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("per_chip_x", [False, True],
                         ids=["shared_x", "per_chip_x"])
@pytest.mark.parametrize("blocks", DEFAULT_CANDIDATES,
                         ids=lambda b: "bm{}_bn{}_bk{}".format(*b))
def test_chips_kernel_compiles_for_v5e(shape, blocks, per_chip_x):
    bm, bn, bk = blocks
    x = shape(C, M, R) if per_chip_x else shape(M, R)
    compiled = irc_mvm_chips.lower(
        x, shape(C, R, N), shape(C, R, N), shape(R, N), shape(R, N),
        shape(C, M, N), shape(C, M, N), params=IrcEpilogueParams(),
        bm=bm, bn=bn, bk=bk, interpret=False).compile()
    _assert_mosaic(compiled)


def test_single_chip_kernel_compiles_for_v5e(shape):
    compiled = irc_mvm.lower(
        shape(M, R), shape(R, N), shape(R, N), shape(R, N), shape(R, N),
        shape(M, N), shape(M, N), params=IrcEpilogueParams(),
        interpret=False).compile()
    _assert_mosaic(compiled)
