"""The unified Model: every assigned architecture is an instance of this
one stack, driven by ModelConfig.pattern.

Layer stacking: the config's ``pattern`` (a tuple of LayerPattern entries)
is unrolled *inside* a group function; groups are scanned with
``jax.lax.scan`` over group-stacked params (leading dim = n_groups), and the
group function is the remat boundary.  This keeps the HLO one-group-sized
for any depth and makes heterogeneous stacks (gemma2 local/global pairs,
jamba's 8-layer mamba/attn/MoE blocks) compile compactly.

Entry points:
  forward(params, batch)                -> (hidden (B,S,d), aux)
  loss(params, batch)                   -> (scalar, metrics)     [train_step]
  prefill(params, batch, max_len)       -> (last logits, cache)  [serve]
  decode_step(params, cache, token, pos)-> (logits, new cache)   [serve]
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L
from . import moe as M
from . import rwkv as R
from . import ssm as S
from .specs import ParamSpec, init_params, abstract_params, tree_paths
from repro.parallel.actctx import constrain

__all__ = ["Model"]


def _stack_specs(tree, n: int):
    """Prefix every ParamSpec leaf with a (n,) 'layers' group dim."""
    if isinstance(tree, ParamSpec):
        return ParamSpec((n,) + tuple(tree.shape), ("layers",) + tuple(tree.axes),
                         init=tree.init, scale=tree.scale, dtype=tree.dtype)
    return {k: _stack_specs(v, n) for k, v in tree.items()}


def _zero_aux():
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # specs / init
    # ------------------------------------------------------------------

    def _layer_specs(self, pe) -> dict:
        cfg = self.cfg
        sp: dict = {"ln1": L.norm_specs(cfg.d_model)}
        if pe.mixer in ("attn", "local"):
            sp["attn"] = L.attn_specs(cfg)
            if cfg.post_norm:
                sp["post_ln1"] = L.norm_specs(cfg.d_model)
        elif pe.mixer == "mamba":
            sp["mamba"] = S.mamba_specs(cfg)
        elif pe.mixer == "rwkv":
            sp["tm"] = R.rwkv_time_specs(cfg)
        else:  # pragma: no cover
            raise ValueError(pe.mixer)
        if cfg.cross_attn:
            sp["ln_x"] = L.norm_specs(cfg.d_model)
            sp["xattn"] = L.attn_specs(cfg, cross=True)
        if pe.ffn != "none":
            sp["ln2"] = L.norm_specs(cfg.d_model)
            if pe.ffn == "dense":
                sp["ffn"] = L.ffn_specs(cfg.d_model, cfg.d_ff)
            elif pe.ffn == "moe":
                sp["moe"] = M.moe_specs(cfg)
            elif pe.ffn == "rwkv_cm":
                sp["cm"] = R.rwkv_channel_specs(cfg)
            else:  # pragma: no cover
                raise ValueError(pe.ffn)
            if cfg.post_norm and pe.ffn in ("dense", "moe"):
                sp["post_ln2"] = L.norm_specs(cfg.d_model)
        return sp

    def param_specs(self) -> dict:
        cfg = self.cfg
        group = {f"l{j}": self._layer_specs(pe) for j, pe in enumerate(cfg.pattern)}
        sp = {
            "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=1.0),
            "layers": _stack_specs(group, cfg.n_groups),
            "final_norm": L.norm_specs(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            sp["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
        if cfg.is_encdec:
            enc_cfg_layer = self._enc_layer_specs()
            sp["encoder"] = {
                "layers": _stack_specs({"l0": enc_cfg_layer}, cfg.n_enc_layers),
                "final_norm": L.norm_specs(cfg.d_model),
            }
        return sp

    def _enc_layer_specs(self) -> dict:
        cfg = self.cfg
        return {
            "ln1": L.norm_specs(cfg.d_model),
            "attn": L.attn_specs(cfg),
            "ln2": L.norm_specs(cfg.d_model),
            "ffn": L.ffn_specs(cfg.d_model, cfg.d_ff),
        }

    def init(self, key, dtype=jnp.float32):
        return init_params(self.param_specs(), key, param_dtype=dtype)

    def abstract(self, dtype=jnp.float32):
        return abstract_params(self.param_specs(), param_dtype=dtype)

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------

    def embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return constrain(x, ("dp", None, None))

    def unembed(self, params, h):
        cfg = self.cfg
        w = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
        # bf16 operands, fp32 accumulate — no fp32 copy of the (d, V) matrix
        logits = jnp.einsum("...d,dv->...v", h, w.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        if cfg.final_softcap:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        return logits

    # ------------------------------------------------------------------
    # one group of layers (train / prefill / decode variants share this)
    # ------------------------------------------------------------------

    def _apply_group(self, gp, x, *, positions, prefix_len, enc_out,
                     cache_g=None, cache_pos=None, build_cache=0):
        """Unrolled pattern application.  Returns (x, aux, new_cache_g)."""
        cfg = self.cfg
        aux = _zero_aux()
        new_cache = {}
        decoding = cache_g is not None and cache_pos is not None
        x = constrain(x, ("dp", None, None))    # pin residual: batch over DP
        for j, pe in enumerate(cfg.pattern):
            sub = gp[f"l{j}"]
            key = f"l{j}"
            lcache = (cache_g or {}).get(key, {})
            nc: dict = {}
            # ---- mixer
            h = L.rms_norm(sub["ln1"], x, cfg.norm_eps)
            if pe.mixer in ("attn", "local"):
                mode = "sliding" if pe.mixer == "local" else (
                    "prefix" if (cfg.n_img_tokens and not decoding) else "causal")
                with jax.named_scope("attention"):
                    attn_out, kv = L.attention(
                        sub["attn"], h, cfg, mode=mode, positions=positions,
                        cache=lcache.get("self"), cache_pos=cache_pos,
                        build_cache=build_cache,
                        window=cfg.local_window, prefix_len=prefix_len,
                        q_chunk=getattr(cfg, "q_chunk", 0) or 0)
                if kv is not None:
                    nc["self"] = kv
                if cfg.post_norm:
                    attn_out = L.rms_norm(sub["post_ln1"], attn_out, cfg.norm_eps)
                x = x + attn_out
            elif pe.mixer == "mamba":
                with jax.named_scope("mamba"):
                    if decoding:
                        mx, mstate = S.mamba_step(sub["mamba"], h,
                                                  lcache["ssm_state"], cfg)
                        nc["ssm_state"] = mstate
                    elif build_cache:
                        mx, mstate = S.mamba(sub["mamba"], h, cfg, return_state=True)
                        nc["ssm_state"] = mstate
                    else:
                        mx = S.mamba(sub["mamba"], h, cfg)
                x = x + mx
            elif pe.mixer == "rwkv":
                carry = lcache.get("tm_shift") if (decoding or build_cache) else None
                state0 = lcache.get("tm_state") if decoding else None
                tmx, (last_x, s_fin) = R.rwkv_time_mix(
                    sub["tm"], h, cfg, shift_carry=carry if decoding else None,
                    state0=state0)
                if decoding or build_cache:
                    nc["tm_shift"] = last_x
                    nc["tm_state"] = s_fin
                x = x + tmx
            # ---- cross attention (enc-dec decoder)
            if cfg.cross_attn:
                hx = L.rms_norm(sub["ln_x"], x, cfg.norm_eps)
                if decoding:
                    xout, _ = L.attention(sub["xattn"], hx, cfg, mode="bidir",
                                          cache=lcache["cross"], update_cache=False)
                    nc["cross"] = lcache["cross"]
                else:
                    xout, xkv = L.attention(sub["xattn"], hx, cfg, mode="bidir",
                                            kv_input=enc_out,
                                            build_cache=0)
                    if build_cache:
                        # cross kv cache: recompute enc projections once
                        cdt = hx.dtype
                        xk = jnp.einsum("btd,dhk->bthk", enc_out,
                                        sub["xattn"]["wk"].astype(cdt))
                        xv = jnp.einsum("btd,dhk->bthk", enc_out,
                                        sub["xattn"]["wv"].astype(cdt))
                        nc["cross"] = {"k": xk.astype(jnp.bfloat16),
                                       "v": xv.astype(jnp.bfloat16)}
                x = x + xout
            # ---- ffn
            if pe.ffn != "none":
                h2 = L.rms_norm(sub["ln2"], x, cfg.norm_eps)
                if pe.ffn == "dense":
                    f = L.ffn(sub["ffn"], h2, cfg.ffn_act)
                elif pe.ffn == "moe":
                    f, moe_aux = M.moe_ffn(sub["moe"], h2, cfg)
                    aux = {k: aux[k] + moe_aux[k] for k in aux}
                else:  # rwkv channel mix
                    carry = lcache.get("cm_shift") if decoding else None
                    f, cm_last = R.rwkv_channel_mix(sub["cm"], h2, cfg,
                                                    shift_carry=carry)
                    if decoding or build_cache:
                        nc["cm_shift"] = cm_last
                if cfg.post_norm and pe.ffn in ("dense", "moe"):
                    f = L.rms_norm(sub["post_ln2"], f, cfg.norm_eps)
                x = x + f
            x = constrain(x, ("dp", None, None))
            new_cache[key] = nc
        return x, aux, new_cache

    # ------------------------------------------------------------------
    # encoder (enc-dec archs)
    # ------------------------------------------------------------------

    def encode(self, params, frames):
        """frames: (B, T, d) precomputed modality embeddings (stub frontend)."""
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.dtype))
        enc = params["encoder"]

        def group_fn(gp, x):
            sub = gp["l0"]
            h = L.rms_norm(sub["ln1"], x, cfg.norm_eps)
            a, _ = L.attention(sub["attn"], h, cfg, mode="bidir")
            x = x + a
            h2 = L.rms_norm(sub["ln2"], x, cfg.norm_eps)
            return x + L.ffn(sub["ffn"], h2, cfg.ffn_act)

        group_fn = self._maybe_remat(group_fn)

        def body(carry, gp):
            return group_fn(gp, carry), None

        x, _ = jax.lax.scan(body, x, enc["layers"])
        return L.rms_norm(enc["final_norm"], x, cfg.norm_eps)

    def _maybe_remat(self, fn):
        r = self.cfg.remat
        if r == "none":
            return fn
        if r == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return jax.checkpoint(fn)

    # ------------------------------------------------------------------
    # forward (training)
    # ------------------------------------------------------------------

    def _inputs_to_x(self, params, batch):
        """tokens (+patches/frames) -> (x, positions, prefix_len, enc_out)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self.embed(params, tokens)
        prefix_len = 0
        enc_out = None
        if cfg.n_img_tokens and "patches" in batch:
            patches = batch["patches"].astype(x.dtype)       # (B, P, d) stub
            x = jnp.concatenate([patches, x], axis=1)
            prefix_len = patches.shape[1]
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"])
        B, S2 = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S2, dtype=jnp.int32)[None], (B, S2))
        return x, positions, prefix_len, enc_out

    def forward(self, params, batch):
        cfg = self.cfg
        x, positions, prefix_len, enc_out = self._inputs_to_x(params, batch)

        def group_fn(gp, x):
            x, aux, _ = self._apply_group(gp, x, positions=positions,
                                          prefix_len=prefix_len, enc_out=enc_out)
            return x, aux

        group_fn = self._maybe_remat(group_fn)

        def body(carry, gp):
            x, aux = group_fn(gp, carry)
            return x, aux

        x, auxs = jax.lax.scan(body, x, params["layers"])
        aux = jax.tree.map(lambda a: a.sum(0), auxs)
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return constrain(x, ("dp", None, None)), aux

    # ------------------------------------------------------------------
    # loss (chunked cross-entropy — no (B,S,V) fp32 materialization)
    # ------------------------------------------------------------------

    def loss(self, params, batch, s_chunk: int = 512):
        cfg = self.cfg
        h, aux = self.forward(params, batch)
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if cfg.n_img_tokens and "patches" in batch:
            h = h[:, batch["patches"].shape[1]:]             # loss on text only
        B, Sl, d = h.shape
        if mask is None:
            mask = jnp.ones((B, Sl), jnp.float32)
        w = (params["lm_head"] if not cfg.tie_embeddings
             else params["embed"].T)                          # (d, V)

        c = min(s_chunk, Sl)
        if Sl % c:
            c = Sl
        nc = Sl // c
        h_c = h.reshape(B, nc, c, d).transpose(1, 0, 2, 3)
        t_c = targets.reshape(B, nc, c).transpose(1, 0, 2)
        m_c = mask.reshape(B, nc, c).transpose(1, 0, 2)

        def chunk_fn(carry, htm):
            hc, tc, mc = htm
            logits = jnp.einsum("bsd,dv->bsv", hc, w.astype(hc.dtype),
                                preferred_element_type=jnp.float32)
            if cfg.final_softcap:
                logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
            nll = (lse - tgt) * mc
            correct = (jnp.argmax(logits, -1) == tc) * mc
            return carry, (nll.sum(), mc.sum(), correct.sum())

        chunk_fn = jax.checkpoint(chunk_fn) if cfg.remat != "none" else chunk_fn
        _, (nll, cnt, corr) = jax.lax.scan(chunk_fn, 0, (h_c, t_c, m_c))
        total = jnp.maximum(cnt.sum(), 1.0)
        xent = nll.sum() / total
        loss = xent + cfg.router_aux_weight * aux["lb_loss"] \
                    + cfg.router_z_weight * aux["z_loss"]
        metrics = {"loss": loss, "xent": xent, "accuracy": corr.sum() / total,
                   "lb_loss": aux["lb_loss"], "z_loss": aux["z_loss"],
                   "tokens": total}
        return loss, metrics

    # ------------------------------------------------------------------
    # serving: cache init / prefill / decode
    # ------------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int, enc_len: int = 0,
                   abstract: bool = False, cache_dtype=jnp.bfloat16):
        cfg = self.cfg

        def mk(shape, dtype):
            return (jax.ShapeDtypeStruct(shape, dtype) if abstract
                    else jnp.zeros(shape, dtype))

        def one_group():
            g = {}
            for j, pe in enumerate(cfg.pattern):
                e: dict = {}
                if pe.mixer in ("attn", "local"):
                    kv_shape = (batch_size, max_len, cfg.n_kv_heads, cfg.d_head)
                    e["self"] = {"k": mk(kv_shape, cache_dtype),
                                 "v": mk(kv_shape, cache_dtype)}
                elif pe.mixer == "mamba":
                    e["ssm_state"] = S.init_mamba_state(cfg, batch_size,
                                                        abstract=abstract)
                elif pe.mixer == "rwkv":
                    st = R.init_rwkv_state(cfg, batch_size, abstract=abstract)
                    e["tm_shift"], e["tm_state"] = st["tm_shift"], st["tm_state"]
                if cfg.cross_attn:
                    xs = (batch_size, enc_len, cfg.n_kv_heads, cfg.d_head)
                    e["cross"] = {"k": mk(xs, cache_dtype), "v": mk(xs, cache_dtype)}
                if pe.ffn == "rwkv_cm":
                    e["cm_shift"] = mk((batch_size, cfg.d_model), cache_dtype)
                g[f"l{j}"] = e
            return g

        g = one_group()
        # stack group cache n_groups times (leading scan dim)
        def stack(leaf):
            if abstract:
                return jax.ShapeDtypeStruct((cfg.n_groups,) + tuple(leaf.shape),
                                            leaf.dtype)
            return jnp.broadcast_to(leaf[None], (cfg.n_groups,) + leaf.shape).copy()

        return jax.tree.map(stack, g)

    def prefill(self, params, batch, max_len: int):
        """Run the prompt, build the cache.  Returns (last-pos logits, cache)."""
        cfg = self.cfg
        x, positions, prefix_len, enc_out = self._inputs_to_x(params, batch)

        def body(carry, gp):
            x = carry
            x, _, nc = self._apply_group(gp, x, positions=positions,
                                         prefix_len=prefix_len, enc_out=enc_out,
                                         build_cache=max_len)
            return x, nc

        x, cache = jax.lax.scan(body, x, params["layers"])
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.unembed(params, x[:, -1])
        return logits, cache

    def decode_step(self, params, cache, token, pos):
        """token: (B, 1) int32; pos: scalar int32 (next position index).
        Returns (logits (B, V), new cache)."""
        cfg = self.cfg
        x = self.embed(params, token)
        B = x.shape[0]
        positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)

        def body(carry, gp_cache):
            x = carry
            gp, cg = gp_cache
            x, _, nc = self._apply_group(gp, x, positions=positions,
                                         prefix_len=0, enc_out=None,
                                         cache_g=cg, cache_pos=pos)
            return x, nc

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.unembed(params, x[:, -1])
        return logits, new_cache
