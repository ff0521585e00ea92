"""Pallas TPU kernel: Delta preconditioner for offset-array-like streams.

The paper's Fig. 6 mechanism: offset arrays are near-arithmetic sequences;
delta turns them into near-constant streams any LZ77 codec collapses.

Kernel semantics are *block-local* (each grid step deltas within its block;
``out[0] = x[0]`` per block); the jit'd wrapper in ``ops.py`` applies the
O(grid)-sized cross-block boundary fix-up so the composed op equals the
global ``ref.delta_ref``.  This keeps the kernel embarrassingly parallel —
no cross-block carry chain — which is the right TPU shape for what is
logically a scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["delta_block", "undelta_block"]

_DEF_BLOCK = 4096
_LANES = 128


def _delta_kernel(x_ref, o_ref):
    x = x_ref[...]                          # (bn,) unsigned int
    shifted = jnp.concatenate([x[:1] * 0, x[:-1]])
    o_ref[...] = x - shifted                # out[0] = x[0] (block-local)


def _undelta_kernel(d_ref, o_ref):
    # inclusive prefix sum of a row-major (rows, 128) block by log-step
    # shifted adds (Hillis-Steele): along the lanes, then the row totals
    # down the rows.  Mosaic lowers no cumsum and no 1-D shift that leaves
    # the first tile; lane and sublane rolls it does lower.
    x = d_ref[...]
    rows, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    zero = jnp.zeros_like(x)
    s = 1
    while s < lanes:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), zero)
        s *= 2
    tot = jnp.broadcast_to(x[:, lanes - 1:], x.shape)
    carry = tot
    s = 1
    while s < rows:
        carry = carry + jnp.where(row >= s, pltpu.roll(carry, s, 0), zero)
        s *= 2
    o_ref[...] = x + (carry - tot)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def delta_block(x: jnp.ndarray, *, block_n: int = _DEF_BLOCK,
                interpret: bool = False) -> jnp.ndarray:
    """Block-local delta of a 1-D unsigned-int array; N % block_n == 0."""
    (n,) = x.shape
    block_n = min(block_n, n)
    assert n % block_n == 0
    return pl.pallas_call(
        _delta_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def undelta_block(d: jnp.ndarray, *, block_n: int = _DEF_BLOCK,
                  interpret: bool = False) -> jnp.ndarray:
    """Block-local inclusive cumsum (inverse of delta_block).
    N % block_n == 0 and block_n % 128 == 0."""
    (n,) = d.shape
    block_n = min(block_n, n)
    assert n % block_n == 0 and block_n % _LANES == 0, (n, block_n)
    rows = block_n // _LANES
    return pl.pallas_call(
        _undelta_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // _LANES, _LANES), d.dtype),
        interpret=interpret,
    )(d.reshape(n // _LANES, _LANES)).reshape(n)
