"""Pallas TPU kernel: BitShuffle preconditioner (paper §2.2, Blosc-style).

Device-side bit transpose so tensors can be preconditioned *in HBM, before
they leave the chip* — used by the compressed-collective path and by
zero-copy checkpoint staging.  The host-side numpy twin lives in
``repro.core.precond``; semantics are defined by ``ref.bitshuffle_ref``.

TPU mapping notes (DESIGN.md §3): bitshuffle is pure VPU work — shifts,
masks and ORs on int32 words; no MXU involvement.  Output byte ``(p, c)``
gathers bit ``p`` of elements ``8c .. 8c+7``, so the wrapper hands the
kernel each element as one int32 word laid out ``(8, N/8)``: row ``k``
holds elements ``8c+k``.  The group-of-8 then runs along sublanes, every
op in the kernel is a 2-D elementwise op on lane-dense tiles, and the
kernel needs no reshape and no reduction (Mosaic lowers neither for this
layout).  A grid step moves ``block_n`` elements: an ``(8, block_n/8)``
int32 tile in and an ``(8*itemsize, block_n/8)`` uint8 tile out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bitshuffle", "bitunshuffle"]

_DEF_BLOCK = 8192  # elements per grid step


def _bitshuffle_kernel(w_ref, o_ref):
    w = w_ref[...]                                   # (8, bc) int32 words
    nbits, bc = o_ref.shape
    plane = jax.lax.broadcasted_iota(jnp.int32, (nbits, bc), 0)
    acc = jnp.zeros((nbits, bc), jnp.int32)
    for k in range(8):                               # element 8c+k -> bit k
        acc = acc | (((w[k:k + 1, :] >> plane) & 1) << k)
    o_ref[...] = acc.astype(jnp.uint8)


def _bitunshuffle_kernel(y_ref, o_ref):
    y = y_ref[...].astype(jnp.int32)                 # (8I, bc) bit planes
    nbits, bc = y.shape
    elem = jax.lax.broadcasted_iota(jnp.int32, (8, bc), 0)
    acc = jnp.zeros((8, bc), jnp.int32)
    for p in range(nbits):                           # plane p -> word bit p
        acc = acc | (((y[p:p + 1, :] >> elem) & 1) << p)
    o_ref[...] = acc


def _words(x: jnp.ndarray) -> jnp.ndarray:
    """(N, itemsize) uint8 -> (8, N/8) int32: element 8c+k at [k, c]."""
    n, itemsize = x.shape
    if itemsize == 1:
        w = x.reshape(n)
    else:
        w = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * itemsize}"))
    return w.astype(jnp.int32).reshape(n // 8, 8).T


def _unwords(t: jnp.ndarray, itemsize: int) -> jnp.ndarray:
    """Inverse of :func:`_words`."""
    w = t.T.reshape(-1).astype(jnp.dtype(f"uint{8 * itemsize}"))
    return jax.lax.bitcast_convert_type(w, jnp.uint8).reshape(-1, itemsize)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def bitshuffle(x: jnp.ndarray, *, block_n: int = _DEF_BLOCK,
               interpret: bool = False) -> jnp.ndarray:
    """(N, itemsize) uint8 -> (8*itemsize, N//8) uint8.  N % block_n == 0;
    itemsize <= 4."""
    n, itemsize = x.shape
    block_n = min(block_n, n)
    assert n % block_n == 0 and block_n % 8 == 0 and itemsize <= 4, \
        (n, block_n, itemsize)
    bc = block_n // 8
    return pl.pallas_call(
        _bitshuffle_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((8, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8 * itemsize, bc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8 * itemsize, n // 8), jnp.uint8),
        interpret=interpret,
    )(_words(x))


@functools.partial(jax.jit, static_argnames=("itemsize", "block_n", "interpret"))
def bitunshuffle(y: jnp.ndarray, itemsize: int, *, block_n: int = _DEF_BLOCK,
                 interpret: bool = False) -> jnp.ndarray:
    """(8*itemsize, N//8) uint8 -> (N, itemsize) uint8."""
    nbits, nover8 = y.shape
    assert nbits == 8 * itemsize and itemsize <= 4
    n = nover8 * 8
    block_n = min(block_n, n)
    assert n % block_n == 0 and block_n % 8 == 0
    bc = block_n // 8
    t = pl.pallas_call(
        _bitunshuffle_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((nbits, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, bc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, nover8), jnp.int32),
        interpret=interpret,
    )(y)
    return _unwords(t, itemsize)
