"""Share of the traced train steps' window in which no operation ran on the
chip (averaged over the chips of the cell)."""


def read(ctx):
    tr = ctx["out"].get("trace")
    if not tr or "steps" not in tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
