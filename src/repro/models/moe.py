"""Mixture-of-Experts FFN (llama4-scout/maverick top-1, jamba top-2).

GSPMD-friendly *per-row* capacity dispatch: every batch row routes its own
S tokens independently, so with batch sharded over ("pod","data") the
dispatch gather/scatter is device-local — the only MoE collectives are the
ones the chosen weight sharding induces (TP reduce on d_ff; FSDP all-gather
when expert weights are ZeRO-sharded).  See DESIGN.md §7 for why this
formulation was chosen over global-sort EP-a2a (which remains a
hillclimb variant in repro.parallel.ep_a2a).

Dispatch mechanics per row:
  1. router top-k over the softmax; the top-k gates are renormalized to
     sum to 1 where ``cfg.norm_topk_prob`` (llama4), and used as they are
     otherwise (jamba)
  2. position-in-expert = exclusive cumsum of expert one-hot over S
  3. source-token index buffer (E, C) built by scatter; over-capacity
     assignments drop (Switch semantics, ``capacity_factor``).  With
     ``capacity_factor=None`` the layer is dropless: C = S, since a token's
     top-k experts are distinct and no expert can receive more than S
     assignments of a row
  4. expert_in = gather  ->  (E, C, d) ;  batched expert einsums
  5. combine: gather back per (token, k) slot, gate-weight, sum over k

Aux outputs: Switch load-balance loss and router z-loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .specs import ParamSpec
from repro.parallel.actctx import constrain

__all__ = ["moe_specs", "moe_ffn"]


def moe_specs(cfg) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff_expert or cfg.d_ff, cfg.n_experts
    sp = {
        "router": ParamSpec((d, E), ("embed", "experts_r"), scale=0.1),
        "w_gate": ParamSpec((E, d, f), ("experts", "embed", "ff")),
        "w_up": ParamSpec((E, d, f), ("experts", "embed", "ff")),
        "w_down": ParamSpec((E, f, d), ("experts", "ff", "embed")),
    }
    if cfg.shared_expert:
        sp["shared"] = {
            "w_gate": ParamSpec((d, f), ("embed", "ff")),
            "w_up": ParamSpec((d, f), ("embed", "ff")),
            "w_down": ParamSpec((f, d), ("ff", "embed")),
        }
    return sp


# ---------------------------------------------------------------------------
# scatter-free dispatch/combine gathers.
#
# jax.grad of a gather is a scatter-add, which GSPMD cannot batch-shard (it
# replicates the whole tensor across the mesh — measured 32 GiB/device at
# jamba scale).  But the dispatch and combine gathers are *mutually inverse*
# permutations (up to capacity drops), so each one's backward is the other's
# forward shape: custom_vjp lets us express both directions as pure batched
# gathers, which GSPMD shards perfectly.
# ---------------------------------------------------------------------------


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch_gather(K, x, src, slot_valid, slot, valid):
    """x: (B,S,d) token stream; src: (B,EC) flat assignment index (t*K+k)
    or sentinel; returns (B,EC,d)."""
    tok = jnp.minimum(src // K, x.shape[1] - 1)
    out = jnp.take_along_axis(x, tok[..., None], axis=1)
    return jnp.where(slot_valid[..., None], out, jnp.zeros((), x.dtype))


def _dispatch_fwd(K, x, src, slot_valid, slot, valid):
    return (_dispatch_gather(K, x, src, slot_valid, slot, valid),
            (jnp.zeros((), x.dtype), slot, valid))


def _dispatch_bwd(K, res, g):
    # dx[b,t] = sum_k valid[b,t,k] * g[b, slot[b,t,k]]  — a gather by slot
    (xmark, slot, valid) = res
    xdtype = xmark.dtype
    B, SK = slot.shape
    safe = jnp.minimum(slot, g.shape[1] - 1)
    gk = jnp.take_along_axis(g, safe[..., None], axis=1)          # (B,SK,d)
    gk = jnp.where(valid[..., None], gk, jnp.zeros((), g.dtype))
    dx = gk.reshape(B, SK // K, K, g.shape[-1]).sum(axis=2).astype(xdtype)
    return dx, None, None, None, None


_dispatch_gather.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_gather(y, slot, valid, src, slot_valid):
    """y: (B,EC,d) expert outputs; slot: (B,SK); returns (B,SK,d)."""
    safe = jnp.minimum(slot, y.shape[1] - 1)
    out = jnp.take_along_axis(y, safe[..., None], axis=1)
    return jnp.where(valid[..., None], out, jnp.zeros((), y.dtype))


def _combine_fwd(y, slot, valid, src, slot_valid):
    return (_combine_gather(y, slot, valid, src, slot_valid),
            (jnp.zeros((), y.dtype), src, slot_valid))


def _combine_bwd(res, g):
    # dy[b,j] = slot_valid[b,j] * g[b, src[b,j]]  — a gather by src
    (ymark, src, slot_valid) = res
    ydtype = ymark.dtype
    safe = jnp.minimum(src, g.shape[1] - 1)
    dy = jnp.take_along_axis(g, safe[..., None], axis=1)
    dy = jnp.where(slot_valid[..., None], dy, jnp.zeros((), g.dtype))
    return dy.astype(ydtype), None, None, None, None


_combine_gather.defvjp(_combine_fwd, _combine_bwd)


def _dense_ffn(p, x, act):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    g = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) if act == "silu" \
        else jax.nn.gelu(g.astype(jnp.float32), approximate=True).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", g * u, p["w_down"].astype(x.dtype))


def moe_ffn(p: dict, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, dict]:
    """x: (B, S, d) -> (out (B, S, d), {"lb_loss", "z_loss"})."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    cdt = x.dtype
    C = S if cfg.capacity_factor is None else int(
        min(max(1, round(S * K / E * cfg.capacity_factor)), S * K))

    with jax.named_scope("moe.router"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))           # (B,S,E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_k, idx_k = jax.lax.top_k(probs, K)                        # (B,S,K)
        if cfg.norm_topk_prob:
            gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    # --- aux losses (Switch): load balance + z-loss
    me = probs.mean(axis=(0, 1))                                       # (E,)
    onehot = jax.nn.one_hot(idx_k, E, dtype=jnp.float32)               # (B,S,K,E)
    ce = onehot.mean(axis=(0, 1, 2))
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # --- position of each (s, k) assignment within its expert, per row.
    # flatten (S, K) in token-major order; exclusive cumsum of one-hot.
    # (cumsum/gather/top_k only — NO scatter: GSPMD cannot batch-shard
    # coordinate scatters and would replicate the whole dispatch, verified
    # catastrophic at 400B scale; see DESIGN.md §7.)
    oh_flat = onehot.reshape(B, S * K, E)                              # (B,SK,E)
    pos_incl = jnp.cumsum(oh_flat, axis=1)
    pos = (pos_incl - oh_flat)                                         # exclusive
    pos_k = jnp.einsum("bte,bte->bt", pos, oh_flat).astype(jnp.int32)  # (B,SK)
    e_flat = idx_k.reshape(B, S * K)
    valid = pos_k < C
    slot = jnp.where(valid, e_flat * C + pos_k, E * C)                 # (B,SK)

    # --- expert-major source indices via top_k (first-come-first-serve):
    # score[b,e,t] = t if assignment t chose e else SK; the C smallest
    # scores per (b,e) are that expert's capacity slots in arrival order.
    tpos = jnp.arange(S * K, dtype=jnp.int32)
    score = jnp.where(oh_flat.transpose(0, 2, 1) > 0,                  # (B,E,SK)
                      tpos[None, None, :], S * K)
    neg_vals, src = jax.lax.top_k(-score, C)                           # (B,E,C)
    src = src.reshape(B, E * C)
    slot_valid = (neg_vals.reshape(B, E * C) > -(S * K))

    # --- gather tokens -> (B, E, C, d)  (scatter-free custom-vjp gather)
    xg = _dispatch_gather(K, x, src, slot_valid, slot, valid)          # (B,EC,d)
    expert_in = constrain(xg.reshape(B, E, C, d), ("dp", None, None, None))

    # --- expert FFN: batched einsums over E; f sharded = TP, E ZeRO/FSDP
    # (activations pinned to DP so the partitioner gathers *weights*)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("becd,edf->becf", expert_in, p["w_gate"].astype(cdt))
        u = jnp.einsum("becd,edf->becf", expert_in, p["w_up"].astype(cdt))
        act = jax.nn.silu(g.astype(jnp.float32)).astype(cdt) if cfg.ffn_act == "silu" \
            else jax.nn.gelu(g.astype(jnp.float32), approximate=True).astype(cdt)
        expert_out = jnp.einsum("becf,efd->becd", act * u, p["w_down"].astype(cdt))
    out_flat = constrain(expert_out.reshape(B, E * C, d), ("dp", None, None))

    # --- combine: per (token, k) read its slot back, gate-weight, sum over k
    back = _combine_gather(out_flat, slot, valid, src, slot_valid)    # (B,SK,d)
    back = back.reshape(B, S, K, d) * gate_k[..., None].astype(cdt)
    out = back.sum(axis=2)

    if cfg.shared_expert:
        out = out + _dense_ffn(p["shared"], x, cfg.ffn_act)
    return out, {"lb_loss": lb_loss, "z_loss": z_loss}
