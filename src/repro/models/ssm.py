"""Mamba (selective SSM) mixer — jamba's attention-free layer.

TPU adaptation notes (DESIGN.md §3): the CUDA reference fuses the selective
scan so the (d_inner, d_state) hidden state never leaves SRAM.  On TPU we
express the same recurrence as a *chunked associative scan*: an outer
``lax.scan`` over sequence chunks carries the (B, d_inner, d_state) state in
registers/VMEM-resident arrays, and the inner ``lax.associative_scan`` gives
log-depth parallelism within a chunk.  The chunk size bounds the transient
(chunk, B, d_inner, d_state) decay/input tensors — the TPU analogue of the
kernel's SRAM blocking — and the outer scan is the remat boundary.

Recurrence (Mamba-1, per channel c and state n):
    h_t = exp(dt_t[c] * A[c, n]) * h_{t-1} + dt_t[c] * B_t[n] * x_t[c]
    y_t[c] = sum_n C_t[n] * h_t[c, n] + D[c] * x_t[c]

As in Jamba, the low-rank dt input, B and C each pass an RMSNorm of their
own (``dt_norm``, ``b_norm``, ``c_norm``) right after ``x_proj``.  A_log,
D and dt_bias start as Mamba starts them: A = 1..d_state in every channel,
D = 1, and dt_bias the inverse softplus of a dt drawn log-uniformly from
[1e-3, 1e-1].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import norm_specs, rms_norm
from .specs import ParamSpec
from repro.parallel.actctx import constrain

__all__ = ["mamba_specs", "mamba", "mamba_step", "init_mamba_state"]

PERF_FLAGS = {"mamba_bf16_y": False}   # §Perf C (see layers.PERF_FLAGS)


def mamba_specs(cfg) -> dict:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    dt_rank = max(d // 16, 1)
    return {
        "in_proj": ParamSpec((d, 2 * di), ("embed", "inner2")),
        "conv_w": ParamSpec((cfg.ssm_conv, di), (None, "inner"), scale=0.5),
        "conv_b": ParamSpec((di,), ("inner",), init="zeros"),
        "x_proj": ParamSpec((di, dt_rank + 2 * n), ("inner", None)),
        "dt_proj": ParamSpec((dt_rank, di), (None, "inner")),
        "dt_bias": ParamSpec((di,), ("inner",), init="dt_bias"),
        "a_log": ParamSpec((di, n), ("inner", None), init="s4d_real"),
        "d_skip": ParamSpec((di,), ("inner",), init="ones"),
        "out_proj": ParamSpec((di, d), ("inner", "embed")),
        "dt_norm": norm_specs(dt_rank),
        "b_norm": norm_specs(n),
        "c_norm": norm_specs(n),
    }


def _ssm_params(p, x, cfg):
    """x: (B, S, di) -> dt (B,S,di), a=exp(dt*A) (B,S,di,n), bx (B,S,di,n), c (B,S,n)."""
    di, n = cfg.d_inner, cfg.ssm_state
    dt_rank = p["x_proj"].shape[1] - 2 * n
    xp = jnp.einsum("bsc,cr->bsr", x, p["x_proj"].astype(x.dtype))
    dt_in, b_in, c_in = jnp.split(xp, [dt_rank, dt_rank + n], axis=-1)
    dt_in = rms_norm(p["dt_norm"], dt_in, cfg.norm_eps)
    b_in = rms_norm(p["b_norm"], b_in, cfg.norm_eps)
    c_in = rms_norm(p["c_norm"], c_in, cfg.norm_eps)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_in, p["dt_proj"].astype(x.dtype)).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))                              # (B,S,di)
    a_mat = -jnp.exp(p["a_log"].astype(jnp.float32))                     # (di,n)
    a = jnp.exp(dt[..., None] * a_mat[None, None])                       # (B,S,di,n)
    bx = (dt * x.astype(jnp.float32))[..., None] * b_in.astype(jnp.float32)[:, :, None, :]
    return a, bx, c_in.astype(jnp.float32)


def _chunk_scan(a, bx, h0):
    """One chunk of the recurrence via associative scan.

    a, bx: (L, B, di, n) fp32; h0: (B, di, n).  Returns (h_all (L,B,di,n),
    h_last)."""
    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    a_s, b_s = jax.lax.associative_scan(combine, (a, bx), axis=0)
    h_all = a_s * h0[None] + b_s
    return h_all, h_all[-1]


def _conv1d(p, x, cfg):
    """Depthwise causal conv via shifted adds.  x: (B, S, di).

    fp32 accumulation so the full pass matches ``mamba_step``'s einsum
    (which accumulates in fp32) — bf16 accumulation here caused ~1e-2
    per-layer train/decode drift."""
    w = p["conv_w"].astype(jnp.float32)                                  # (K, di)
    K = w.shape[0]
    xf = x.astype(jnp.float32)
    out = xf * w[K - 1]
    for k in range(1, K):
        shifted = jnp.pad(xf, ((0, 0), (k, 0), (0, 0)))[:, :-k]
        out = out + shifted * w[K - 1 - k]
    return (out + p["conv_b"].astype(jnp.float32)).astype(x.dtype)


def mamba(p: dict, x: jnp.ndarray, cfg, chunk: int = 64,
          return_state: bool = False):
    """Full-sequence mamba mixer.  x: (B, S, d) -> (B, S, d)
    (+ decode-ready state when ``return_state``)."""
    B, S, _ = x.shape
    cdt = x.dtype
    xz = constrain(jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(cdt)),
                   ("dp", None, "tp"))
    xin_pre, z = jnp.split(xz, 2, axis=-1)                               # (B,S,di)
    xin = jax.nn.silu(_conv1d(p, xin_pre, cfg).astype(jnp.float32)).astype(cdt)
    xin = constrain(xin, ("dp", None, "tp"))

    chunk = min(chunk, S)
    if S % chunk:
        chunk = S  # fallback: single chunk (smoke-test sizes)
    nc = S // chunk
    # per-chunk SSM-param computation: the (L, B, di, n) decay/input tensors
    # exist only inside one scan step (the TPU analogue of the CUDA kernel's
    # SRAM blocking); the checkpointed step keeps backward residuals to the
    # (B, di, n) carries.
    x_c = xin.reshape(B, nc, chunk, cfg.d_inner).transpose(1, 0, 2, 3)   # (nc,B,L,di)

    h0 = jnp.zeros((B, cfg.d_inner, cfg.ssm_state), jnp.float32)

    @jax.checkpoint
    def outer(h, xc):
        a, bx, c = _ssm_params(p, xc, cfg)                               # (B,L,di,n)
        h_all, h_last = _chunk_scan(a.transpose(1, 0, 2, 3),
                                    bx.transpose(1, 0, 2, 3), h)         # (L,B,di,n)
        yc = jnp.einsum("lbcn,bln->blc", h_all, c)                       # (B,L,di)
        if PERF_FLAGS["mamba_bf16_y"]:
            yc = yc.astype(cdt)        # §Perf C: halve the stacked y traffic
        return h_last, yc

    h_fin, y_chunks = jax.lax.scan(outer, h0, x_c)                       # (nc,B,L,di)
    y = y_chunks.transpose(1, 0, 2, 3).reshape(B, S, cfg.d_inner).astype(jnp.float32)
    y = y + xin.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
    y = y.astype(cdt) * jax.nn.silu(z.astype(jnp.float32)).astype(cdt)
    out = jnp.einsum("bsc,cd->bsd", y, p["out_proj"].astype(cdt))
    if not return_state:
        return out
    ktail = cfg.ssm_conv - 1
    conv_state = jnp.pad(xin_pre, ((0, 0), (max(ktail - S, 0), 0), (0, 0)))[:, -ktail:]
    return out, {"conv": conv_state, "ssm": h_fin}


def init_mamba_state(cfg, batch: int, dtype=jnp.float32, abstract: bool = False):
    """Decode-time carried state: causal-conv tail + SSM hidden."""
    shapes = {
        "conv": ((batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
        "ssm": ((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}


def mamba_step(p: dict, x: jnp.ndarray, state: dict, cfg):
    """One decode step.  x: (B, 1, d); state from init_mamba_state."""
    B = x.shape[0]
    cdt = x.dtype
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(cdt))
    xin, z = jnp.split(xz, 2, axis=-1)                                   # (B,1,di)

    # conv over (tail ++ current)
    window = jnp.concatenate([state["conv"].astype(cdt), xin], axis=1)   # (B,K,di)
    w = p["conv_w"].astype(cdt)
    conv = jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"].astype(cdt)
    xin1 = jax.nn.silu(conv.astype(jnp.float32)).astype(cdt)[:, None]    # (B,1,di)
    new_conv = window[:, 1:]

    a, bx, c = _ssm_params(p, xin1, cfg)                                 # (B,1,di,n)
    h = a[:, 0] * state["ssm"] + bx[:, 0]                                # (B,di,n)
    y = jnp.einsum("bcn,bn->bc", h, c[:, 0]) \
        + xin1[:, 0].astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
    y = y.astype(cdt) * jax.nn.silu(z[:, 0].astype(jnp.float32)).astype(cdt)
    out = jnp.einsum("bc,cd->bd", y, p["out_proj"].astype(cdt))[:, None]
    return out, {"conv": new_conv.astype(state["conv"].dtype), "ssm": h}
