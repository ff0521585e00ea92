"""Pallas TPU kernel: block int8 quantize/pack — the compressed-collective
payload stage.

The paper's core observation is that *structured numeric data is cheap to
move once preconditioned*.  Applied to the collective roofline term: before
a data-parallel gradient reduction, each (row) block of the gradient is
quantized to int8 with a per-row f32 scale (4x fewer bytes on the wire than
bf16->f32 reductions, 2x fewer than bf16).  ``repro.parallel.compressed``
wires this into a shard_map all-reduce with error feedback.

TPU mapping: per-row amax is a lane reduction (VPU); the divide+round is
elementwise.  Block rows are tiled through VMEM; the (rows, 1) scale output
rides in SMEM-sized blocks.  MXU is untouched — this kernel lives in the
bandwidth domain, which is exactly where the paper's technique applies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["qpack", "qunpack"]

_DEF_ROWS = 256


def _qpack_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # (br, C)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    q_ref[...] = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _qunpack_kernel(q_ref, s_ref, o_ref, *, dtype):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def qpack(x: jnp.ndarray, *, block_rows: int = _DEF_ROWS,
          interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (R, C) float -> (int8 (R, C), f32 scale (R, 1)). R % block_rows == 0."""
    r, c = x.shape
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    grid = (r // block_rows,)
    return pl.pallas_call(
        _qpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("dtype", "block_rows", "interpret"))
def qunpack(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32, *,
            block_rows: int = _DEF_ROWS, interpret: bool = False) -> jnp.ndarray:
    """Inverse of :func:`qpack` (lossy): q * scale, cast to ``dtype``."""
    r, c = q.shape
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    grid = (r // block_rows,)
    return pl.pallas_call(
        functools.partial(_qunpack_kernel, dtype=dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), dtype),
        interpret=interpret,
    )(q, scale)
