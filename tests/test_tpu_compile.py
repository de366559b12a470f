"""The fused aggregation kernels compiled for a TPU v5e that is described,
not attached: Mosaic must lower them at the sizes the executors use.

Interpret mode (every other kernel test) runs the kernel bodies on the CPU
and never sees the chip's limits; these compiles do. The VMEM-sized block
(``kernels/cc_delta_update.py::_block_and_pad``) has to fit the chip's
scoped-VMEM limit at N=8 clients of the full-width ResNet-18-GN
(P = 11,220,480 after the executors' 512-padding) and at N=64, P=2^20.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cc_delta_update import cc_epilogue_update_fwd
from repro.kernels.cc_delta_update_q8 import cc_delta_update_q8_fwd

RESNET18_GN_P = 11_220_480          # 11,220,132 params padded to 512


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2`` topology, with the persistent
    compile cache off around the compiles (an entry written for a chip
    that is not attached cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(sharding, n, p, *, q8, stale):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows = [s((n,)) for _ in range(6)]        # train .. store_scale
    scalars = [s(()), s(())]                  # denom, post_scale
    if q8:
        head = [s((n, p)), s((n, p), jnp.int8), s((n,)), s((p,))]
    else:
        head = [s((n, p)), s((n, p)), s((p,))]
    return head + rows + scalars + ([s((n, p))] if stale else [])


@pytest.mark.parametrize("n,p", [(8, RESNET18_GN_P), (64, 2 ** 20)],
                         ids=["resnet18gn-n8", "n64-p2e20"])
@pytest.mark.parametrize("kernel,stale", [
    ("epilogue", False),
    ("epilogue", True),       # the s2/ccc path that reads the stale model
    ("q8", False),
    ("q8", True),
])
def test_fused_kernel_lowers_for_v5e(one_chip, n, p, kernel, stale):
    q8 = kernel == "q8"
    fwd = cc_delta_update_q8_fwd if q8 else cc_epilogue_update_fwd
    compiled = jax.jit(fwd).lower(
        *_args(one_chip, n, p, q8=q8, stale=stale)).compile()
    assert "tpu_custom_call" in compiled.as_text()
