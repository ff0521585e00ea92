"""AI21-Jamba2-Mini [hybrid] — 32L d_model=4096 32H (GQA kv=8, head 128)
d_ff=14336 vocab=65536, MoE 16e top-2 with no renormalisation of the top-2
gates and no dropped tokens — Mamba-1 (d_state 16, conv 4, expand 2, dt_rank
256, RMSNorms on dt, B and C) and attention 7:1 (one attention layer per
8-layer block, at index 4), MoE on odd layers, no positional encoding
(the Mamba layers carry position).  Jamba-v0.1 and Jamba-1.5-Mini have the
same widths and layer equations.

Source: https://huggingface.co/ai21labs/AI21-Jamba2-Mini/blob/main/config.json
Papers: arXiv:2403.19887 (Jamba), arXiv:2408.12570 (Jamba-1.5)."""

import math

from repro.models import ModelConfig, LayerPattern

SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-Mini/blob/main/config.json"


def layer_pattern(attn_period: int, attn_offset: int, expert_period: int,
                  expert_offset: int) -> tuple:
    """One period of the layer stack from the config's period and offset
    keys: attention where ``i % attn_period == attn_offset``, else Mamba;
    MoE where ``i % expert_period == expert_offset``, else a dense MLP."""
    return tuple(
        LayerPattern("attn" if i % attn_period == attn_offset else "mamba",
                     "moe" if i % expert_period == expert_offset else "dense")
        for i in range(math.lcm(attn_period, expert_period)))


CONFIG = ModelConfig(
    name="jamba2-mini",
    family="hybrid",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_head=128,
    d_ff=14336,
    vocab=65536,
    rope_theta=0.0,             # jamba: no explicit positional encoding
    norm_eps=1e-6,
    n_experts=16,
    experts_per_token=2,
    d_ff_expert=14336,
    capacity_factor=None,       # dropless
    norm_topk_prob=False,       # softmax over 16, top-2, gates as they are
    ssm_state=16,
    ssm_conv=4,
    ssm_expand=2,
    tie_embeddings=False,
    pattern=layer_pattern(8, 4, 2, 1),
)
