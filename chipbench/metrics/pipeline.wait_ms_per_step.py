"""Host time per window step spent taking the next batch from the token
pipeline and putting it on the device (the benchmark's span around
``next(pipeline)`` and the batch build)."""


def read(ctx):
    out = ctx["out"]
    if not out.get("steps"):
        return None
    lo = out["window_t0"]
    wait = ctx["spans"].total("pipeline.wait", lo, lo + out["window_s"])
    return 1e3 * wait / out["steps"]
