"""Compressed tensor-parallel reduction — the paper's wire profile applied
to on-device collectives (DESIGN.md §2.3, beyond-paper).

A row-parallel projection y @ W with the contraction dim TP-sharded needs
an all-reduce of bf16 partial sums: wire = 2*N*(k-1)/k bytes.  Here each
rank instead int8-quantizes its partial (per-token scales — qpack
semantics, same math as kernels/ref.qpack_ref), all-gathers the int8
payload + scales, and dequant-sums locally:

    wire = (N_int8 + scales)*(k-1)/k  ~=  1/4 of the bf16 all-reduce.

Intended for inference paths (prefill/decode); the quantization error is
~0.2-0.4% rms per partial (measured in tests/test_compressed_tp.py).
Requires an active activation context (repro.parallel.actctx) whose mesh
names the TP axis; silently falls back to a plain einsum + GSPMD
all-reduce otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .actctx import _CTX

__all__ = ["rowparallel_einsum_compressed"]


def _quantize_rows(x):
    """Per-(…, row) int8 quantization over the last dim (qpack_ref math)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def rowparallel_einsum_compressed(y, w, out_dtype=None):
    """y: (B, S, E) with E TP-sharded; w: (E, D).  Returns (B, S, D)
    replicated over the TP axis, reduced through an int8 wire."""
    mesh = _CTX["mesh"]
    tp = _CTX["tp"]
    out_dtype = out_dtype or y.dtype
    if mesh is None or tp not in getattr(mesh, "axis_names", ()):
        return jnp.einsum("bse,ed->bsd", y, w.astype(y.dtype))
    k = mesh.shape[tp]
    B, S, E = y.shape
    D = w.shape[1]
    dp = _CTX["dp"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if E % k or B % dp_size:
        return jnp.einsum("bse,ed->bsd", y, w.astype(y.dtype))
    dp_spec = dp if len(dp) > 1 else dp[0]

    def body(y_loc, w_loc):
        part = jnp.einsum("bse,ed->bsd", y_loc, w_loc.astype(y_loc.dtype),
                          preferred_element_type=jnp.float32)
        q, s = _quantize_rows(part)
        qg = jax.lax.all_gather(q, tp)                 # (k, b, s, D) int8
        sg = jax.lax.all_gather(s, tp)                 # (k, b, s, 1) f32
        out = jnp.einsum("kbsd,kbsu->bsd", qg.astype(jnp.float32), sg)
        return out.astype(out_dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, tp), P(tp, None)),
        out_specs=P(dp_spec, None, None), check_vma=False,
    )(y, w)
