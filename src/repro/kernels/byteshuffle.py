"""Pallas TPU kernel: byte Shuffle preconditioner (paper §2.2, Blosc-style).

A strided byte transpose: (N, itemsize) -> (itemsize, N).  This is the
paper's worked example (big-endian ints 1,2: ``00 00 00 01 00 00 00 02`` ->
``00 00 00 00 00 00 01 02``) as device-resident VPU work.

TPU mapping: a pure relayout.  Each grid step moves a (block_n x itemsize)
byte tile through VMEM and writes its transpose; XLA's own transpose would
do the same data movement, but routing it through Pallas keeps the
preconditioner fused with the quantize/pack stage of the compressed
collective (see kernels/ops.py: ``shuffle_qpack``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["byteshuffle", "byteunshuffle"]

_DEF_BLOCK = 16384


def _t_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def byteshuffle(x: jnp.ndarray, *, block_n: int = _DEF_BLOCK,
                interpret: bool = False) -> jnp.ndarray:
    """(N, itemsize) uint8 -> (itemsize, N) uint8."""
    n, itemsize = x.shape
    block_n = min(block_n, n)
    assert n % block_n == 0
    return pl.pallas_call(
        _t_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n, itemsize), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((itemsize, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((itemsize, n), jnp.uint8),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def byteunshuffle(y: jnp.ndarray, *, block_n: int = _DEF_BLOCK,
                  interpret: bool = False) -> jnp.ndarray:
    """(itemsize, N) uint8 -> (N, itemsize) uint8."""
    itemsize, n = y.shape
    block_n = min(block_n, n)
    assert n % block_n == 0
    return pl.pallas_call(
        _t_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((itemsize, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_n, itemsize), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, itemsize), jnp.uint8),
        interpret=interpret,
    )(y)
