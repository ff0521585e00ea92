"""Every kernel entry point compiles for a TPU v5e chip at basket size.

Nothing runs: each entry point is lowered and compiled for one chip of a
described (not attached) v5e:2x2, which refuses what interpret mode
accepts (unsupported reductions, reshapes and primitives in Mosaic).  The
topology is described inside a fixture, so collection never loads the TPU
library and every test worker collects the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 1 << 20                    # elements of a basket-sized tensor
QSHAPE = (4096, 2048)          # a bf16 weight slab for the int8 quantizer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # keep compiler logs off disk
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# entry point -> (function of arrays, [(shape, dtype) of each argument])
ENTRIES = {
    "bitshuffle_bytes": (ops.bitshuffle_bytes, [((N,), jnp.float32)]),
    "bitunshuffle_bytes": (lambda y: ops.bitunshuffle_bytes(y, jnp.float32, N),
                           [((32, N // 8), jnp.uint8)]),
    "byteshuffle_bytes": (ops.byteshuffle_bytes, [((N,), jnp.float32)]),
    "byteunshuffle_bytes": (
        lambda y: ops.byteunshuffle_bytes(y, jnp.float32, N),
        [((4, N), jnp.uint8)]),
    "delta_u32": (ops.delta_u32, [((N,), jnp.uint32)]),
    "undelta_u32": (ops.undelta_u32, [((N,), jnp.uint32)]),
    "quantize_int8": (lambda x: ops.quantize_int8(x)[:2],
                      [(QSHAPE, jnp.bfloat16)]),
    "dequantize_int8": (
        lambda q, s: ops.dequantize_int8(q, s, QSHAPE, jnp.bfloat16),
        [(QSHAPE, jnp.int8), ((QSHAPE[0], 1), jnp.float32)]),
}


@pytest.mark.parametrize("name", list(ENTRIES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = ENTRIES[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
