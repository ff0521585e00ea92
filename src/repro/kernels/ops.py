"""Public jit'd entry points for the Pallas kernels.

These present *global* semantics (exactly ``ref.py``) on top of the
block-parallel kernels and handle padding/viewing arbitrary tensors as byte
streams.  Every entry point compiles for the TPU unless the caller passes
``interpret=True`` (the CPU tests do); there is no silent fallback, so a
run without a TPU fails instead of timing the Pallas interpreter.

The composition the compressed-collective path uses::

    grads (R, C) bf16
      --qpack-->          int8 (R, C) + f32 scales (R, 1)       [4x fewer bits]
      --bitshuffle-->     bit-planes of the int8 stream          [entropy grouping]
      (wire / psum)
      --bitunshuffle/qunpack-->  grads' (lossy, error fed back)

bitshuffle-after-quantize is the paper's preconditioner insight applied on
device: int8 gradient mantissas share high bits, so bit-plane grouping makes
the stream compressible/reducible; for the collective path we use the
quantize stage only (psum needs arithmetic), but checkpoint staging uses
both (see repro.checkpoint).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import bitshuffle as _bs
from . import byteshuffle as _bys
from . import delta as _delta
from . import qpack as _qp
from . import ref

__all__ = [
    "bitshuffle_bytes", "bitunshuffle_bytes",
    "byteshuffle_bytes", "byteunshuffle_bytes",
    "delta_u32", "undelta_u32",
    "quantize_int8", "dequantize_int8",
]


def _pick_block(n: int, pref: int, mult: int) -> int:
    """Largest divisor of n that is <= pref and a multiple of ``mult``."""
    b = min(pref, n)
    b -= b % mult
    while b > mult and n % b:
        b -= mult
    return max(b, mult)


# ---------------------------------------------------------------------------
# byte-stream views
# ---------------------------------------------------------------------------

def _as_byte_matrix(x: jnp.ndarray, itemsize: int) -> jnp.ndarray:
    """View a tensor as an (N, itemsize) uint8 matrix (bitcast, no copy)."""
    flat = x.reshape(-1)
    u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8)  # (N, itemsize) for multi-byte
    if u8.ndim == 1:
        u8 = u8.reshape(-1, 1)
    if itemsize != u8.shape[-1]:
        u8 = u8.reshape(-1, itemsize)
    return u8


def bitshuffle_bytes(x: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Bit-plane transpose of any tensor whose element count is a multiple
    of 8; returns (8*itemsize, N//8) uint8."""
    itemsize = x.dtype.itemsize
    mat = _as_byte_matrix(x, itemsize)
    n = mat.shape[0]
    block = _pick_block(n, _bs._DEF_BLOCK, 8)
    return _bs.bitshuffle(mat, block_n=block, interpret=interpret)


def bitunshuffle_bytes(y: jnp.ndarray, dtype, n_elems: int,
                       interpret: bool = False) -> jnp.ndarray:
    itemsize = jnp.dtype(dtype).itemsize
    block = _pick_block(n_elems, _bs._DEF_BLOCK, 8)
    mat = _bs.bitunshuffle(y, itemsize, block_n=block, interpret=interpret)
    flat = jax.lax.bitcast_convert_type(mat.reshape(-1, itemsize), dtype)
    return flat.reshape(n_elems)


def byteshuffle_bytes(x: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    itemsize = x.dtype.itemsize
    mat = _as_byte_matrix(x, itemsize)
    block = _pick_block(mat.shape[0], _bys._DEF_BLOCK, 1)
    return _bys.byteshuffle(mat, block_n=block, interpret=interpret)


def byteunshuffle_bytes(y: jnp.ndarray, dtype, n_elems: int,
                        interpret: bool = False) -> jnp.ndarray:
    itemsize = jnp.dtype(dtype).itemsize
    block = _pick_block(n_elems, _bys._DEF_BLOCK, 1)
    mat = _bys.byteunshuffle(y, block_n=block, interpret=interpret)
    return jax.lax.bitcast_convert_type(mat.reshape(-1, itemsize), dtype).reshape(n_elems)


# ---------------------------------------------------------------------------
# delta with cross-block fix-up (global semantics == ref.delta_ref)
# ---------------------------------------------------------------------------

def delta_u32(x: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Global delta of a 1-D uint32/uint64 array via block-local kernel +
    O(n/block) boundary correction."""
    (n,) = x.shape
    block = _pick_block(n, _delta._DEF_BLOCK, 1)
    d = _delta.delta_block(x, block_n=block, interpret=interpret)
    if block == n:
        return d
    # fix block heads: d[k*block] should be x[k*block] - x[k*block-1]
    heads = jnp.arange(block, n, block)
    return d.at[heads].subtract(x[heads - 1])


def undelta_u32(d: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Global cumsum via block-local cumsum + carry propagation.  The
    kernel works on whole (rows, 128) tiles, so the stream is zero-padded
    to a multiple of the block (trailing zeros leave the prefix intact)."""
    (n,) = d.shape
    block = min(_delta._DEF_BLOCK, -(-n // 128) * 128)
    m = -(-n // block) * block
    partial = _delta.undelta_block(jnp.pad(d, (0, m - n)), block_n=block,
                                   interpret=interpret)
    if block < m:
        tails = partial[block - 1::block]                  # (m/block,)
        carry = jnp.cumsum(tails, dtype=d.dtype) - tails   # exclusive
        partial = partial + jnp.repeat(carry, block)
    return partial[:n]


# ---------------------------------------------------------------------------
# int8 block quantization (the compressed-collective payload)
# ---------------------------------------------------------------------------

def quantize_int8(x: jnp.ndarray, block_rows: int = 256,
                  interpret: bool = False):
    """Any-shape float tensor -> (int8 same-shape, f32 scales, orig shape).

    Rows of the internal (R, C) view are quantization groups; C is the
    trailing dim (or the whole tensor for 1-D).
    """
    shape = x.shape
    mat = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    r = mat.shape[0]
    block = _pick_block(r, block_rows, 1)
    q, s = _qp.qpack(mat, block_rows=block, interpret=interpret)
    return q, s, shape


def dequantize_int8(q: jnp.ndarray, s: jnp.ndarray, shape, dtype=jnp.float32,
                    interpret: bool = False) -> jnp.ndarray:
    block = _pick_block(q.shape[0], 256, 1)
    out = _qp.qunpack(q, s, dtype, block_rows=block, interpret=interpret)
    return out.reshape(shape)
