"""rwkv6 family: the program's model built from a configuration file, the
weights the benchmark makes from the seed, model FLOPs per token counted
from shapes, and a plain float32 reference of the loss and of the first
AdamW steps.

The reference imports nothing of the program.  It follows the equations of
the program's RWKV-6 (``repro.models.rwkv`` docstring), token by token:

    x^_t = x_t + mu * (x_{t-1} - x_t)                     token shift
    w_t  = exp(max(-exp(w0 + tanh(x^w_t A) B), -25/32))   decay, floored
    y_t  = r_t . (S_{t-1} + diag(u) k_t^T v_t)
    S_t  = diag(w_t) S_{t-1} + k_t^T v_t

with RMSNorm before each mixer, a per-head group norm on y, a SiLU gate,
and the squared-ReLU channel mix.  The program computes the recurrence in
chunks of 32 tokens; the reference steps through time, so the two share no
algebra.  Every contraction runs at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LOG_DECAY_FLOOR = -25.0 / 32.0
TIME_BLOCK = 32          # reference recurrence: remat boundary every 32 tokens

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"L": cfg["num_hidden_layers"], "d": d, "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "D": cfg["head_size"],
            "H": d // cfg["head_size"], "r": cfg["time_decay_extra_dim"],
            "eps": cfg["layer_norm_epsilon"]}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_model(cfg: dict):
    """The program's ``Model`` for this configuration."""
    from repro.models import LayerPattern, Model, ModelConfig
    n = dims(cfg)
    return Model(ModelConfig(
        name=cfg["name"], family="ssm", n_layers=n["L"], d_model=n["d"],
        n_heads=n["H"], n_kv_heads=n["H"], d_head=n["D"], d_ff=n["f"],
        vocab=n["V"], rwkv_head_dim=n["D"], rwkv_decay_lora=n["r"],
        tie_embeddings=cfg["tie_word_embeddings"], norm_eps=n["eps"],
        dtype=cfg["compute_dtype"], pattern=(LayerPattern("rwkv", "rwkv_cm"),)))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


def init_params(cfg: dict, key) -> dict:
    """Float32 weights in the program's layout.  Matrices are normal with
    std 1/sqrt(fan-in); decay base and bonus follow the upstream RWKV-6
    init (a nonzero bonus, decay speeds spread over channels and layers)."""
    n = dims(cfg)
    L, d, f, V, r = n["L"], n["d"], n["f"], n["V"], n["r"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, fan_in, scale=1.0):
        return jax.random.normal(next(ks), shape, jnp.float32) * (scale / math.sqrt(fan_in))

    ch = jnp.arange(d, dtype=jnp.float32) / (d - 1)                 # 0..1 over channels
    depth = (jnp.arange(L, dtype=jnp.float32) / max(L - 1, 1))[:, None]
    decay_base = -6.0 + 5.0 * ch[None] ** (0.7 + 1.3 * depth)
    zigzag = ((jnp.arange(d) + 1) % 3 - 1).astype(jnp.float32) * 0.1
    bonus = depth * (1.0 - ch[None]) + zigzag[None]
    ones = lambda *s: jnp.ones(s, jnp.float32)
    tm = {
        "mu": jax.random.uniform(next(ks), (L, 5, d), jnp.float32),
        "w_r": normal((L, d, d), d), "w_k": normal((L, d, d), d),
        "w_v": normal((L, d, d), d), "w_g": normal((L, d, d), d),
        "w_o": normal((L, d, d), d),
        "decay_base": decay_base,
        "decay_a": normal((L, d, r), d, 0.5),
        "decay_b": normal((L, r, d), r, 0.1),
        "bonus_u": bonus,
        "ln_scale": ones(L, d),
    }
    cm = {
        "mu": jax.random.uniform(next(ks), (L, 2, d), jnp.float32),
        "w_k": normal((L, d, f), d), "w_v": normal((L, f, d), f),
        "w_r": normal((L, d, d), d),
    }
    params = {
        "embed": normal((V, d), V),
        "layers": {"l0": {"ln1": {"scale": ones(L, d)}, "tm": tm,
                          "ln2": {"scale": ones(L, d)}, "cm": cm}},
        "final_norm": {"scale": ones(d)},
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = normal((d, V), d)
    return params


def make_init(cfg: dict, shardings=None):
    """One jitted call, ``init(seed_key(seed))``, that makes the weights on
    the device.  The key is an argument, so every seed runs one program."""
    return jax.jit(lambda key: init_params(cfg, key), out_shardings=shardings)


def change_norms(init):
    """``f(params, key)``: every leaf's norm of ``params`` minus the
    weights ``init(key)`` made."""
    return jax.jit(lambda p, key: leaf_norms(jax.tree.map(jnp.subtract, p, init(key))))


# ---------------------------------------------------------------------------
# model FLOPs per token (forward + backward, no recompute)
# ---------------------------------------------------------------------------

def matmul_params_per_layer(cfg: dict) -> int:
    n = dims(cfg)
    d, f, r = n["d"], n["f"], n["r"]
    time_mix = 5 * d * d + 2 * d * r          # r, k, v, g, out; decay LoRA
    channel_mix = 2 * d * f + d * d           # key, value, receptance
    return time_mix + channel_mix


def flops_per_token(cfg: dict) -> float:
    """6 x matmul parameters (head included, embedding lookup not) plus the
    WKV recurrence: per head and token, k^T v and r . S are D^2 multiply-adds
    each, 3x for forward and backward."""
    n = dims(cfg)
    dense = n["L"] * matmul_params_per_layer(cfg) + n["d"] * n["V"]
    wkv = n["L"] * n["H"] * 2 * (2 * n["D"] * n["D"])
    return 6.0 * dense + 3.0 * wkv


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def _mm(spec, a, b, q):
    if q is not None:
        a, b = q(a), q(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _prev(x):
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _wkv(r, k, v, w, u):
    """Sequential recurrence over time.  r, k, v, w: (B, S, H, D); u: (H, D)."""
    B, S, H, D = r.shape

    def step(st, t):
        rt, kt, vt, wt = t
        kv = kt[..., :, None] * vt[..., None, :]                  # (B, H, D, D)
        y = jnp.einsum("bhi,bhij->bhj", rt, st + u[None, :, :, None] * kv,
                       precision=HI)
        return wt[..., None] * st + kv, y

    @jax.checkpoint
    def block(st, blk):
        return jax.lax.scan(step, st, blk)

    nb = S // TIME_BLOCK if S % TIME_BLOCK == 0 else 1
    tb = S // nb
    to_blocks = lambda t: t.transpose(1, 0, 2, 3).reshape(nb, tb, B, H, D)
    st0 = jnp.zeros((B, H, D, D), jnp.float32)
    _, y = jax.lax.scan(block, st0, tuple(map(to_blocks, (r, k, v, w))))
    return y.reshape(S, B, H, D).transpose(1, 0, 2, 3)


def _layer(x, p, n, q):
    B, S, d = x.shape
    H, D, eps = n["H"], n["D"], n["eps"]
    tm, cm = p["tm"], p["cm"]
    h = _rms(x, p["ln1"]["scale"], eps)
    hp = _prev(h)
    xr, xk, xv, xw, xg = (h + tm["mu"][i] * (hp - h) for i in range(5))
    heads = lambda t: t.reshape(B, S, H, D)
    r = heads(_mm("bsd,de->bse", xr, tm["w_r"], q))
    k = heads(_mm("bsd,de->bse", xk, tm["w_k"], q))
    v = heads(_mm("bsd,de->bse", xv, tm["w_v"], q))
    g = _mm("bsd,de->bse", xg, tm["w_g"], q)
    lora = jnp.tanh(_mm("bsd,dr->bsr", xw, tm["decay_a"], q))
    log_w = -jnp.exp(tm["decay_base"] + _mm("bsr,rd->bsd", lora, tm["decay_b"], q))
    w = heads(jnp.exp(jnp.maximum(log_w, LOG_DECAY_FLOOR)))
    y = _wkv(r, k, v, w, tm["bonus_u"].reshape(H, D))
    mean = y.mean(-1, keepdims=True)
    var = ((y - mean) ** 2).mean(-1, keepdims=True)
    y = (y - mean) * jax.lax.rsqrt(var + eps) * tm["ln_scale"].reshape(H, D)
    y = y.reshape(B, S, d) * jax.nn.silu(g)
    x = x + _mm("bse,ed->bsd", y, tm["w_o"], q)

    h = _rms(x, p["ln2"]["scale"], eps)
    hp = _prev(h)
    xk = h + cm["mu"][0] * (hp - h)
    xr = h + cm["mu"][1] * (hp - h)
    kk = jnp.square(jax.nn.relu(_mm("bsd,df->bsf", xk, cm["w_k"], q)))
    kv = _mm("bsf,fd->bsd", kk, cm["w_v"], q)
    return x + jax.nn.sigmoid(_mm("bsd,de->bse", xr, cm["w_r"], q)) * kv


def ref_loss(params, tokens, targets, cfg: dict, q=None, s_chunk: int = 512):
    """Mean next-token cross entropy over every position."""
    n = dims(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    layer = jax.checkpoint(lambda x, p: _layer(x, p, n, q))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p["l0"]), None), x,
                        params["layers"])
    h = _rms(x, params["final_norm"]["scale"], n["eps"])
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    B, S, d = h.shape
    c = s_chunk if S % s_chunk == 0 else S

    @jax.checkpoint
    def nll(hc, tc):
        logits = _mm("bsd,dv->bsv", hc, head, q)
        tgt = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt)

    hs = h.reshape(B, S // c, c, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, S // c, c).transpose(1, 0, 2)
    total = jax.lax.map(lambda a: nll(*a), (hs, ts)).sum()
    return total / (B * S)


def lr_at(step: int, hyper: dict) -> float:
    """The configured warmup-cosine schedule (floor 0.1 of peak)."""
    peak, warm, total = hyper["peak_lr"], hyper["warmup"], hyper["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def _adamw(params, m, v, g, count, lr, clip, wd):
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
    g = jax.tree.map(lambda x: x * scale, g)
    bc1, bc2 = 1.0 - ADAM_B1 ** count, 1.0 - ADAM_B2 ** count
    m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
    v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + ADAM_EPS)
        - lr * wd * p, params, m, v)
    return params, m, v, g, gn


def leaf_norms(tree) -> dict:
    """{path: L2 norm} of every leaf, as float32 device scalars."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def ref_train(cfg: dict, seed: int, batches: list, hyper: dict, q=None,
              shardings=None, batch_sharding=None) -> dict:
    """The reference's first ``len(batches)`` AdamW steps from the seed's
    weights.  Returns each step's loss, every leaf's norm of the clipped
    first gradient, the first gradient's global norm before clipping, and
    every leaf's norm of the change after the steps."""
    init, key = make_init(cfg, shardings), seed_key(seed)
    params = init(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    m, v = zeros(params), zeros(params)
    grad = jax.jit(jax.value_and_grad(
        lambda p, t, y: ref_loss(p, t, y, cfg, q)))
    upd = jax.jit(_adamw, static_argnums=(6, 7), donate_argnums=(0, 1, 2))
    norms = jax.jit(leaf_norms)
    losses, gnorms = [], None
    for i, (tok, tgt) in enumerate(batches):
        if batch_sharding is not None:
            tok, tgt = jax.device_put((tok, tgt), batch_sharding)
        loss, g = grad(params, tok, tgt)
        losses.append(float(loss))
        params, m, v, gc, gn = upd(params, m, v, g, float(i + 1), lr_at(i, hyper),
                                   hyper["clip_norm"], hyper["weight_decay"])
        if i == 0:
            gnorms = {k: float(x) for k, x in norms(gc).items()}
            global_gn = float(gn)
        del g, gc
    del m, v
    dnorms = {k: float(x) for k, x in change_norms(init)(params, key).items()}
    return {"loss": losses, "grad_norm": gnorms, "change_norm": dnorms,
            "global_grad_norm": global_gn}


def ref_placement(cfg: dict, devices) -> tuple:
    """The reference's own placement where it runs on several chips:
    (weight shardings, batch sharding).  Each weight is split over all the
    chips along its widest axis that they divide, the batch along its
    rows; on one chip, (None, None)."""
    if len(devices) < 2:
        return None, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    n = len(devices)
    mesh = Mesh(np.array(devices), ("all",))

    def split(x):
        axes = [i for i in range(x.ndim) if x.shape[i] % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        a = max(axes, key=lambda i: (x.shape[i], i))
        return NamedSharding(mesh, P(*(("all" if i == a else None)
                                       for i in range(x.ndim))))

    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    return jax.tree.map(split, shapes), NamedSharding(mesh, P("all", None))


def fp8(x):
    """Round to float8 e4m3 and back: the control's precision, one step
    below the configuration's bfloat16 compute."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
