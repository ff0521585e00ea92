"""jamba family: the program's model built from a configuration file, the
bfloat16 weights the benchmark makes from the seed, and a plain float32
reference of the forward pass.

The reference imports nothing of the program.  It follows the equations of
``transformers``' ``modeling_jamba.py`` (``JambaMambaMixer.slow_forward``,
``JambaAttention``, ``JambaSparseMoeBlock``, ``JambaMLP``): pre-norm
residual layers, RMSNorm everywhere,

    Mamba-1   x, z = in_proj(h);  u = silu(causal depthwise conv(x) + b)
              dt_in, B, C = RMSNorm each (x_proj(u))
              dt = softplus(dt_proj(dt_in) + dt_bias);  A = -exp(A_log)
              s_t = exp(dt_t A) s_{t-1} + dt_t B_t u_t;  y_t = C_t . s_t + D u_t
              out = out_proj(y * silu(z))
    attention GQA, causal, no positional encoding, scale 1/sqrt(head_dim)
    MoE       p = softmax(router(h)) over every expert; the top-k p as they
              are (no renormalisation); SwiGLU experts; no token dropped
    MLP       SwiGLU

with attention at ``i % attn_layer_period == attn_layer_offset`` and MoE at
``i % expert_layer_period == expert_layer_offset``.  It steps the selective
scan through time (the program scans in chunks), computes every expert on
every token and masks by the gates (the program dispatches), and runs one
layer at a time, each expert's weights upcast on their own, so that it fits
beside the weights on the chips that hold them.  Every contraction runs at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.models.rwkv6 import seed_key  # noqa: F401  (the family's API)

HI = jax.lax.Precision.HIGHEST
STD = 0.02                  # transformers' JambaConfig.initializer_range


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": H,
            "KV": cfg["num_key_value_heads"], "Dh": d // H,
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "E": cfg["num_experts"], "K": cfg["num_experts_per_tok"],
            "di": cfg["mamba_expand"] * d, "n": cfg["mamba_d_state"],
            "r": cfg["mamba_dt_rank"], "conv": cfg["mamba_d_conv"],
            "eps": cfg["rms_norm_eps"],
            "P": math.lcm(cfg["attn_layer_period"], cfg["expert_layer_period"])}


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """(mixer, ffn) of every layer, from the period and offset keys."""
    return [("attn" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
             else "mamba",
             "moe" if i % cfg["expert_layer_period"] == cfg["expert_layer_offset"]
             else "dense")
            for i in range(cfg["num_hidden_layers"])]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_model(cfg: dict):
    """The program's ``Model`` for this configuration: the program's own
    Jamba2-Mini config, at this file's depth and widths."""
    import dataclasses
    from repro.configs.jamba2_mini import CONFIG, layer_pattern
    from repro.models import Model
    n = dims(cfg)
    if (cfg["mamba_dt_rank"] != n["d"] // 16 or not cfg["mamba_conv_bias"]
            or cfg["mamba_proj_bias"] or cfg["hidden_act"] != "silu"):
        raise ValueError(f"{cfg['name']}: the program's Mamba has dt_rank "
                         "hidden/16, a conv bias, no projection bias and SiLU")
    return Model(dataclasses.replace(
        CONFIG, name=cfg["name"], n_layers=n["L"], d_model=n["d"],
        n_heads=n["H"], n_kv_heads=n["KV"], d_head=n["Dh"], d_ff=n["f"],
        d_ff_expert=n["f"], vocab=n["V"], n_experts=n["E"],
        experts_per_token=n["K"], ssm_state=n["n"], ssm_conv=n["conv"],
        ssm_expand=cfg["mamba_expand"], norm_eps=n["eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["compute_dtype"],
        pattern=layer_pattern(cfg["attn_layer_period"], cfg["attn_layer_offset"],
                              cfg["expert_layer_period"],
                              cfg["expert_layer_offset"])))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _layer_shapes(n: dict, mixer: str, ffn: str) -> dict:
    """{leaf: (shape, init)} of one layer in the program's layout."""
    d, di, r, nn, f, E = n["d"], n["di"], n["r"], n["n"], n["f"], n["E"]
    sh = {"ln1.scale": ((d,), "ones"), "ln2.scale": ((d,), "ones")}
    if mixer == "mamba":
        sh.update({
            "mamba.in_proj": ((d, 2 * di), "normal"),
            "mamba.conv_w": ((n["conv"], di), "conv"),
            "mamba.conv_b": ((di,), "conv"),
            "mamba.x_proj": ((di, r + 2 * nn), "normal"),
            "mamba.dt_proj": ((r, di), "dt_proj"),
            "mamba.dt_bias": ((di,), "dt_bias"),
            "mamba.a_log": ((di, nn), "a_log"),
            "mamba.d_skip": ((di,), "ones"),
            "mamba.out_proj": ((di, d), "normal"),
            "mamba.dt_norm.scale": ((r,), "ones"),
            "mamba.b_norm.scale": ((nn,), "ones"),
            "mamba.c_norm.scale": ((nn,), "ones"),
        })
    else:
        H, KV, Dh = n["H"], n["KV"], n["Dh"]
        sh.update({"attn.wq": ((d, H, Dh), "normal"), "attn.wk": ((d, KV, Dh), "normal"),
                   "attn.wv": ((d, KV, Dh), "normal"), "attn.wo": ((H, Dh, d), "normal")})
    if ffn == "moe":
        sh.update({"moe.router": ((d, E), "normal"),
                   "moe.w_gate": ((E, d, f), "normal"),
                   "moe.w_up": ((E, d, f), "normal"),
                   "moe.w_down": ((E, f, d), "normal")})
    else:
        sh.update({"ffn.w_gate": ((d, f), "normal"), "ffn.w_up": ((d, f), "normal"),
                   "ffn.w_down": ((f, d), "normal")})
    return sh


def _draw(key, shape, init: str, n: dict):
    """Float32 values of one leaf.  Linear and embedding weights are normal
    with std 0.02 (transformers' Jamba init); the Mamba leaves follow the
    Mamba reference code: A_log = log(1..d_state) in every channel, D = 1,
    dt_proj uniform in +-dt_rank^-0.5, dt_bias the inverse softplus of a
    dt drawn log-uniformly from [1e-3, 1e-1], the depthwise conv PyTorch's
    default, uniform in +-1/sqrt(kernel)."""
    if init == "normal":
        return STD * jax.random.normal(key, shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
                                shape)
    if init == "conv":
        lim = 1.0 / math.sqrt(n["conv"])
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
    if init == "dt_proj":
        lim = n["r"] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
    if init == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(init)


def leaf_shapes(cfg: dict) -> dict:
    """{dotted path: (shape, init)} of every weight, in the program's
    layout (layers stacked by group along a leading axis)."""
    n = dims(cfg)
    G = n["L"] // n["P"]
    out = {"embed": ((n["V"], n["d"]), "normal"),
           "final_norm.scale": ((n["d"],), "ones")}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((n["d"], n["V"]), "normal")
    for j, (mixer, ffn) in enumerate(layer_kinds(cfg)[:n["P"]]):
        for leaf, (shape, init) in _layer_shapes(n, mixer, ffn).items():
            out[f"layers.l{j}.{leaf}"] = ((G,) + shape, init)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        *head, last = path.split(".")
        node = tree
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def init_params(cfg: dict, key) -> dict:
    """The weights in the configuration's ``param_dtype`` (bfloat16 as
    released), one key per leaf folded from ``key`` in sorted path order."""
    n = dims(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    flat = {path: _draw(jax.random.fold_in(key, i), shape, init, n).astype(dtype)
            for i, (path, (shape, init)) in enumerate(sorted(leaf_shapes(cfg).items()))}
    return _nest(flat)


def make_init(cfg: dict, shardings=None):
    """One jitted call, ``init(seed_key(seed))``, that makes the weights on
    the devices, each leaf already in its sharding."""
    return jax.jit(functools.partial(init_params, cfg), out_shardings=shardings)


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------

# leaves that are not weight matrices: the float8 control leaves them as they are
NOT_MATRICES = frozenset({"scale", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip"})


@jax.jit
def fp8(tree):
    """Every weight matrix of ``tree`` rounded to float8 (e4m3), the
    precision below bfloat16, and kept in that type: a result of its own,
    so that no compiler can fold the rounding into the use."""
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if path[-1].key in NOT_MATRICES
        else w.astype(jnp.float8_e4m3fn), tree)


def _w(p: dict, name: str, g: int):
    """Leaf ``name`` of group ``g`` in float32."""
    return p[name][g].astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _mamba(p, x, g, eps):
    di = p["in_proj"].shape[-1] // 2
    nn = p["a_log"].shape[-1]
    r = p["dt_proj"].shape[1]
    xz = jnp.einsum("bsd,de->bse", x, _w(p, "in_proj", g), precision=HI)
    xs, z = xz[..., :di], xz[..., di:]
    cw, cb = _w(p, "conv_w", g), _w(p, "conv_b", g)
    K, S = cw.shape[0], x.shape[1]
    xpad = jnp.pad(xs, ((0, 0), (K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(xpad[:, k:k + S] * cw[k] for k in range(K)) + cb)
    xp = jnp.einsum("bsc,cr->bsr", u, _w(p, "x_proj", g), precision=HI)
    dt_in = _rms(xp[..., :r], p["dt_norm"]["scale"][g], eps)
    bm = _rms(xp[..., r:r + nn], p["b_norm"]["scale"][g], eps)
    cm = _rms(xp[..., r + nn:], p["c_norm"]["scale"][g], eps)
    dt = jax.nn.softplus(jnp.einsum("bsr,rc->bsc", dt_in, _w(p, "dt_proj", g),
                                    precision=HI) + _w(p, "dt_bias", g))
    a = -jnp.exp(_w(p, "a_log", g))                          # (di, n)

    def step(s, t):
        dt_t, b_t, c_t, u_t = t
        s = jnp.exp(dt_t[..., None] * a) * s + (dt_t * u_t)[..., None] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], -1)

    s0 = jnp.zeros((x.shape[0], di, nn), jnp.float32)
    _, ys = jax.lax.scan(step, s0, tuple(jnp.swapaxes(t, 0, 1)
                                         for t in (dt, bm, cm, u)))
    y = (jnp.swapaxes(ys, 0, 1) + u * _w(p, "d_skip", g)) * jax.nn.silu(z)
    return jnp.einsum("bsc,cd->bsd", y, _w(p, "out_proj", g), precision=HI)


def _attention(p, x, g):
    wq, wk, wv = (_w(p, k, g) for k in ("wq", "wk", "wv"))
    H, KV, Dh = wq.shape[1], wk.shape[1], wq.shape[2]
    S = x.shape[1]
    qh = jnp.einsum("bsd,dhk->bshk", x, wq, precision=HI)
    kh = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, wk, precision=HI), H // KV, 2)
    vh = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, wv, precision=HI), H // KV, 2)
    s = jnp.einsum("bshk,bthk->bhst", qh, kh, precision=HI) / math.sqrt(Dh)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhst,bthk->bshk", jax.nn.softmax(s, -1), vh, precision=HI)
    return jnp.einsum("bshk,hkd->bsd", o, _w(p, "wo", g), precision=HI)


def _swiglu(x, wg, wu, wd):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, wg, precision=HI)) \
        * jnp.einsum("bsd,df->bsf", x, wu, precision=HI)
    return jnp.einsum("bsf,fd->bsd", h, wd, precision=HI)


def _moe(p, x, g, k):
    E = p["router"].shape[-1]
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, _w(p, "router", g),
                                      precision=HI), -1)
    top, idx = jax.lax.top_k(probs, k)
    gates = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32) * top[..., None], -2)

    def expert(e, acc):
        w = [p[name][g, e].astype(jnp.float32) for name in ("w_gate", "w_up", "w_down")]
        return acc + _swiglu(x, *w) * gates[..., e, None]

    return jax.lax.fori_loop(0, E, expert, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("mixer", "ffn", "g", "eps", "k"))
def _layer(p, x, *, mixer, ffn, g, eps, k):
    h = _rms(x, p["ln1"]["scale"][g], eps)
    x = x + (_mamba(p["mamba"], h, g, eps) if mixer == "mamba"
             else _attention(p["attn"], h, g))
    h = _rms(x, p["ln2"]["scale"][g], eps)
    if ffn == "moe":
        return x + _moe(p["moe"], h, g, k)
    f = p["ffn"]
    return x + _swiglu(h, *(_w(f, n_, g) for n_ in ("w_gate", "w_up", "w_down")))


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed.astype(jnp.float32), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("first", "eps"))
def _head(params, x, first, eps):
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    h = _rms(x[:, first:], params["final_norm"]["scale"], eps)
    return jnp.einsum("bsd,dv->bsv", h, w.astype(jnp.float32), precision=HI)


def ref_logits(cfg: dict, params: dict, tokens, first: int, weights_fp8=False):
    """Float32 logits (B, S - first, V) of positions ``first`` .. S-1 of
    ``tokens`` (B, S), one layer at a time.  ``weights_fp8``: the float8
    control, each layer's weight matrices rounded (:func:`fp8`) just
    before the layer runs."""
    n = dims(cfg)
    rounded = fp8 if weights_fp8 else (lambda t: t)
    top = rounded({k: v for k, v in params.items() if k != "layers"})
    x = _embed(top["embed"], tokens)
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        x = _layer(rounded(params["layers"][f"l{i % n['P']}"]), x, mixer=mixer,
                   ffn=ffn, g=i // n["P"], eps=n["eps"], k=n["K"])
    return _head(top, x, first, n["eps"])
