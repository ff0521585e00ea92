"""Raw MB per second from ``save()`` returning to its manifest being
durable (``wait()``): precondition, codec and container write."""


def read(ctx):
    blocks = ctx["out"].get("blocks")
    if not blocks:
        return None
    return sum(b["raw"] for b in blocks) / sum(b["host"] for b in blocks) / 1e6
