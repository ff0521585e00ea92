"""Jamba at a tiny size on the CPU against the benchmark's plain float32
reference (``chipbench/models/jamba.py``, which imports nothing of the
program): prefill, and decode through the hybrid cache (KV plus conv and
SSM state), match the reference's full forward in logits; the MoE is
dropless and leaves its top-2 gates as the softmax gives them; the Mamba
mixer normalises dt, B and C; and a planted departure fails."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models import jamba as J  # noqa: E402
from repro.models import Model, moe, ssm  # noqa: E402
from repro.models.specs import init_params  # noqa: E402

# one whole 8-layer period, 4 experts top-2, d_state 4, computed in float32
TINY = {"name": "jamba-tiny", "family": "jamba", "hidden_size": 128,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256, "num_experts": 4,
        "num_experts_per_tok": 2, "mamba_expand": 2, "mamba_d_state": 4,
        "mamba_dt_rank": 8, "mamba_d_conv": 4, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "num_hidden_layers": 8, "attn_layer_period": 8, "attn_layer_offset": 4,
        "expert_layer_period": 2, "expert_layer_offset": 1,
        "tie_word_embeddings": False, "compute_dtype": "float32",
        "param_dtype": "float32"}
PROMPT, STEPS = 24, 6
# In float32 the program departs from the reference by the bfloat16 KV
# cache and the chunked scan's reassociation: about 4e-4 of the logits'
# norm.  A planted departure moves them by more than 1e-2.
TOL = 2e-3


@pytest.fixture(scope="module")
def weights():
    params = J.init_params(TINY, J.seed_key(7))
    tokens = jax.random.randint(jax.random.key(1), (2, PROMPT + STEPS), 0,
                                TINY["vocab_size"])
    ref = J.ref_logits(TINY, params, tokens, PROMPT - 1)
    return params, tokens, ref


def _gap(got, ref):
    return float(jnp.sqrt(jnp.sum((got - ref) ** 2) / jnp.sum(ref ** 2)))


def _serve(model, params, tokens):
    """Prefill logits of the last prompt position, then one row per decode
    step: positions PROMPT - 1 .. PROMPT + STEPS - 1."""
    S = tokens.shape[1]
    logits, cache = model.prefill(params, {"tokens": tokens[:, :PROMPT]}, max_len=S)
    out = [logits]
    for t in range(PROMPT, S):
        logits, cache = model.decode_step(params, cache, tokens[:, t:t + 1],
                                          jnp.asarray(t, jnp.int32))
        out.append(logits)
    return jnp.stack(out, 1)


def test_reference_weights_are_the_program_layout():
    model = J.program_model(TINY)
    ab = model.abstract()
    params = J.init_params(TINY, J.seed_key(3))
    assert jax.tree.structure(ab) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(ab), jax.tree.leaves(params)))
    kinds = [(p.mixer, p.ffn) for p in model.cfg.pattern]
    assert kinds == J.layer_kinds(TINY)
    assert kinds[4] == ("attn", "dense") and kinds[1] == ("mamba", "moe")


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_program_matches_reference(weights, phase):
    params, tokens, ref = weights
    got = _serve(J.program_model(TINY), params, tokens)
    rows = slice(0, 1) if phase == "prefill" else slice(1, None)
    assert _gap(got[:, rows], ref[:, rows]) < TOL


@pytest.mark.parametrize("fault", ["renormalised_gates", "no_dt_norm"])
def test_planted_departure_fails(weights, monkeypatch, fault):
    params, tokens, ref = weights
    model = J.program_model(TINY)
    if fault == "renormalised_gates":
        model = Model(dataclasses.replace(model.cfg, norm_topk_prob=True))
    else:
        real = ssm.rms_norm          # the inner norms alone: dt is 8 wide
        monkeypatch.setattr(ssm, "rms_norm", lambda p, x, eps: x
                            if x.shape[-1] == TINY["mamba_dt_rank"]
                            else real(p, x, eps))
    assert _gap(_serve(model, params, tokens), ref) > 5 * TOL


def _moe_cfg(**kw):
    return dataclasses.replace(J.program_model(TINY).cfg, **kw)


def _moe_dense(p, x, cfg, renorm):
    """Every expert on every token, weighted by its gate (0 off the top-k)."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if renorm:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, cfg.n_experts) * top[..., None], -2)
    ys = [(jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) @ p["w_down"][e]
          for e in range(cfg.n_experts)]
    return sum(y * gates[..., e, None] for e, y in enumerate(ys))


@pytest.mark.parametrize("case", ["dropless", "gates_as_given", "capacity_drops"])
def test_moe_routing(case):
    cfg = _moe_cfg()
    p = init_params(moe.moe_specs(cfg), jax.random.key(4))
    # every token's router prefers expert 0: a capacity of 1.25 x fair
    # share must drop most of them, a dropless layer none
    p["router"] = p["router"].at[:, 0].add(5.0)
    x = jax.random.normal(jax.random.key(5), (2, 16, cfg.d_model))
    if case == "capacity_drops":
        cfg = dataclasses.replace(cfg, capacity_factor=1.25)
    out, _ = moe.moe_ffn(p, x, cfg)
    want = _moe_dense(p, x, cfg, renorm=False)
    err = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
    if case == "capacity_drops":
        assert err > 0.1
    elif case == "dropless":
        assert err < 1e-5
    else:
        renormed = _moe_dense(p, x, cfg, renorm=True)
        assert err < 1e-5
        assert float(jnp.max(jnp.abs(out - renormed)) / jnp.max(jnp.abs(want))) > 0.1


def test_mamba_normalises_dt_b_and_c():
    """With RMSNorms on dt, B and C, scaling ``x_proj`` leaves the mixer's
    output as it was; without them, dt, B and C would scale with it."""
    cfg = _moe_cfg(ssm_state=4)
    p = init_params(ssm.mamba_specs(cfg), jax.random.key(6))
    assert {"dt_norm", "b_norm", "c_norm"} <= set(p)
    assert np.allclose(np.exp(np.asarray(p["a_log"][0])), np.arange(1, 5), rtol=1e-6)
    dt = jax.nn.softplus(np.asarray(p["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    x = jax.random.normal(jax.random.key(7), (2, 8, cfg.d_model))
    y = ssm.mamba(p, x, cfg)
    y10 = ssm.mamba(dict(p, x_proj=p["x_proj"] * 10.0), x, cfg)
    assert float(jnp.max(jnp.abs(y10 - y)) / jnp.max(jnp.abs(y))) < 1e-4
