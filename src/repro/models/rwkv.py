"""RWKV-6 ("Finch") mixer — data-dependent decay linear attention.

Recurrence per head (state S is (d_k, d_v)):
    y_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t)
    S_t = diag(w_t) S_{t-1} + k_tᵀ v_t
with w_t = exp(-exp(w0 + tanh(x̂_t W_a) W_b)) — the *data-dependent* decay
that distinguishes RWKV-6 from RWKV-4/5 (paper: arXiv:2404.05892).

TPU mapping: chunked linear attention.  Within a chunk of L tokens the
pairwise decay products are exp(cum[t] - cum[i]) so the intra-chunk part is
two decay-weighted matmuls (MXU-friendly (L, D) x (D, L)); the inter-chunk
part carries the (H, D, D) state through a ``lax.scan``.  fp32 throughout
the decay algebra; L is kept small (32) so exp(±cum) stays bounded.

Token shift (the x̂ above) is the RWKV "shift by one" mix:
    x̂_t = x_t + mu * (x_{t-1} - x_t)      (x_{-1} = 0, or decode carry)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .specs import ParamSpec
from repro.parallel.actctx import constrain

_LW_FLOOR = -25.0 / 32.0   # per-step log-decay floor (see rwkv_time_mix)

# §Perf: int8-compressed TP reduction on the row-parallel projections
# (the paper's wire codec profile applied to collectives; inference paths)
PERF_FLAGS = {"compressed_tp": False}

__all__ = [
    "rwkv_time_specs", "rwkv_channel_specs",
    "rwkv_time_mix", "rwkv_time_step",
    "rwkv_channel_mix", "rwkv_channel_step",
    "init_rwkv_state",
]


def rwkv_time_specs(cfg) -> dict:
    d = cfg.d_model
    lora = cfg.rwkv_decay_lora
    return {
        "mu": ParamSpec((5, d), (None, "embed"), init="zeros"),   # r,k,v,w,g shifts
        "w_r": ParamSpec((d, d), ("embed", "heads_d")),
        "w_k": ParamSpec((d, d), ("embed", "heads_d")),
        "w_v": ParamSpec((d, d), ("embed", "heads_d")),
        "w_g": ParamSpec((d, d), ("embed", "heads_d")),
        "w_o": ParamSpec((d, d), ("heads_d", "embed")),
        "decay_base": ParamSpec((d,), ("embed",), init="ones", scale=-6.0),
        "decay_a": ParamSpec((d, lora), ("embed", None), scale=0.1),
        "decay_b": ParamSpec((lora, d), (None, "embed"), scale=0.1),
        "bonus_u": ParamSpec((d,), ("embed",), init="zeros"),
        "ln_scale": ParamSpec((d,), ("embed",), init="ones"),     # per-head groupnorm
    }


def rwkv_channel_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mu": ParamSpec((2, d), (None, "embed"), init="zeros"),   # k, r shifts
        "w_k": ParamSpec((d, f), ("embed", "ff")),
        "w_v": ParamSpec((f, d), ("ff", "embed")),
        "w_r": ParamSpec((d, d), ("embed", "embed_o")),
    }


def _shift(x: jnp.ndarray, carry: jnp.ndarray | None = None) -> jnp.ndarray:
    """x_{t-1}; first position takes ``carry`` (decode) or zeros (train)."""
    if carry is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([carry[:, None], x[:, :-1]], axis=1)


def _decay(p, xw: jnp.ndarray) -> jnp.ndarray:
    """log-decay lw_t = -exp(w0 + tanh(xw A) B)  (negative, fp32)."""
    lora = jnp.einsum("bsd,dr->bsr", xw.astype(jnp.float32), p["decay_a"].astype(jnp.float32))
    lw = p["decay_base"].astype(jnp.float32) + jnp.einsum(
        "bsr,rd->bsd", jnp.tanh(lora), p["decay_b"].astype(jnp.float32))
    return -jnp.exp(lw)


def _heads(x, H, D):
    return x.reshape(*x.shape[:-1], H, D)


def _group_norm(x, scale, eps):
    """Per-head layernorm on (..., H, D)."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    xn = (xf - mean) * jax.lax.rsqrt(var + eps)
    return xn * scale.astype(jnp.float32).reshape(*([1] * (x.ndim - 2)), *x.shape[-2:])


def rwkv_time_mix(p: dict, x: jnp.ndarray, cfg, chunk: int = 32,
                  shift_carry=None, state0=None):
    """x: (B, S, d) -> (out (B, S, d), (last_x, last_state))."""
    B, S, d = x.shape
    D = cfg.rwkv_head_dim
    H = d // D
    cdt = x.dtype

    xprev = _shift(x, shift_carry)
    mu = p["mu"].astype(cdt)                                             # (5, d)
    xr, xk, xv, xw, xg = (x + mu[i] * (xprev - x) for i in range(5))

    def proj(xi, w):
        return constrain(_heads(jnp.einsum("bsd,de->bse", xi, w.astype(cdt)),
                                H, D), ("dp", None, "tp", None))

    r = proj(xr, p["w_r"]).astype(jnp.float32)
    k = proj(xk, p["w_k"]).astype(jnp.float32)
    v = proj(xv, p["w_v"]).astype(jnp.float32)
    g = constrain(jnp.einsum("bsd,de->bse", xg, p["w_g"].astype(cdt)),
                  ("dp", None, "tp"))
    lw = _heads(constrain(_decay(p, xw), ("dp", None, "tp")), H, D)      # fp32 <0
    u = _heads(p["bonus_u"].astype(jnp.float32), H, D)                   # (H,D)

    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    nc = S // chunk
    # -> (nc, B, L, H, D)
    def c5(t):
        return t.reshape(B, nc, chunk, H, D).transpose(1, 0, 2, 3, 4)
    r_c, k_c, v_c, lw_c = c5(r), c5(k), c5(v), c5(lw)

    if state0 is None:
        state0 = jnp.zeros((B, H, D, D), jnp.float32)

    causal = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)         # strictly lower

    def chunk_fn(S_in, rkvw):
        rc, kc, vc, lwc = rkvw                                           # (B,L,H,D)
        # stability: the factored exp(-cum) must stay in fp32 range, so floor
        # the *per-step* log-decay.  The floor is a fixed constant (not a
        # function of chunk length) so train (chunk=32) and decode (chunk=1)
        # compute the *same* recurrence; telescoping stays exact for the
        # floored decay, and decays faster than e^-0.78/step are ~0 within a
        # chunk anyway (secondary chunking would lift this; GLA §4).
        lwc = jnp.maximum(lwc, _LW_FLOOR)
        cum = jnp.cumsum(lwc, axis=1)                                    # inclusive
        cum_ex = cum - lwc                                               # exclusive
        # intra-chunk: A[t,i] = sum_d r_t e^{cum_ex[t]} * k_i e^{-cum[i]}, i<t
        r_dec = rc * jnp.exp(cum_ex)
        k_dec = kc * jnp.exp(-cum)
        scores = jnp.einsum("blhd,bmhd->bhlm", r_dec, k_dec) * causal[None, None]
        diag = jnp.einsum("blhd,blhd->bhl", rc, u[None, None] * kc)
        y = jnp.einsum("bhlm,bmhd->blhd", scores, vc) + diag.transpose(0, 2, 1)[..., None] * vc
        # inter-chunk: state contribution
        y = y + jnp.einsum("blhk,bhkv->blhv", r_dec, S_in)
        # state update to end of chunk
        decay_all = jnp.exp(cum[:, -1])                                  # (B,H,D)
        k_tail = kc * jnp.exp(cum[:, -1][:, None] - cum)                 # decay to chunk end
        S_out = decay_all[..., None] * S_in + jnp.einsum("blhk,blhv->bhkv", k_tail, vc)
        return S_out, y

    S_fin, y_c = jax.lax.scan(chunk_fn, state0, (r_c, k_c, v_c, lw_c))
    y = y_c.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)
    y = _group_norm(y, _heads(p["ln_scale"], H, D), cfg.norm_eps)
    y = (y.reshape(B, S, d).astype(cdt)
         * jax.nn.silu(g.astype(jnp.float32)).astype(cdt))
    if PERF_FLAGS["compressed_tp"]:
        from repro.parallel.compressed import rowparallel_einsum_compressed
        out = rowparallel_einsum_compressed(y, p["w_o"])
    else:
        out = jnp.einsum("bse,ed->bsd", y, p["w_o"].astype(cdt))
    return out, (x[:, -1], S_fin)


def rwkv_time_step(p: dict, x: jnp.ndarray, cfg, shift_carry, state):
    """One decode step: x (B, 1, d)."""
    out, (last_x, S_fin) = rwkv_time_mix(p, x, cfg, chunk=1,
                                         shift_carry=shift_carry, state0=state)
    return out, (last_x, S_fin)


def rwkv_channel_mix(p: dict, x: jnp.ndarray, cfg, shift_carry=None):
    """Squared-ReLU channel mix.  Returns (out, last_x)."""
    cdt = x.dtype
    xprev = _shift(x, shift_carry)
    mu = p["mu"].astype(cdt)
    xk = x + mu[0] * (xprev - x)
    xr = x + mu[1] * (xprev - x)
    k = constrain(jnp.einsum("bsd,df->bsf", xk, p["w_k"].astype(cdt)),
                  ("dp", None, "tp"))
    k = jnp.square(jax.nn.relu(k.astype(jnp.float32))).astype(cdt)
    if PERF_FLAGS["compressed_tp"]:
        from repro.parallel.compressed import rowparallel_einsum_compressed
        kv = rowparallel_einsum_compressed(k, p["w_v"])
    else:
        kv = jnp.einsum("bsf,fd->bsd", k, p["w_v"].astype(cdt))
    rgate = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", xr, p["w_r"].astype(cdt)).astype(jnp.float32)).astype(cdt)
    return rgate * kv, x[:, -1]


def rwkv_channel_step(p, x, cfg, shift_carry):
    return rwkv_channel_mix(p, x, cfg, shift_carry=shift_carry)


def init_rwkv_state(cfg, batch: int, dtype=jnp.bfloat16, abstract: bool = False):
    d = cfg.d_model
    D = cfg.rwkv_head_dim
    H = d // D
    shapes = {
        "tm_shift": ((batch, d), dtype),
        "tm_state": ((batch, H, D, D), jnp.float32),
        "cm_shift": ((batch, d), dtype),
    }
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, dt) for k, (s, dt) in shapes.items()}
    return {k: jnp.zeros(s, dt) for k, (s, dt) in shapes.items()}
