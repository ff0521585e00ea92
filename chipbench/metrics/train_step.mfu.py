"""Model FLOPs of the window's steps (from the configuration's shapes, no
recompute) per second, as a share of the chips' bf16 peak."""


def read(ctx):
    out = ctx["out"]
    if "train_tokens_per_s" not in out:
        return None
    rate = out["flops_per_token"] * out["train_tokens_per_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops"])
