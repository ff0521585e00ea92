"""Raw MB per second of the program's ``ckpt.read_branch`` spans (container
read, codec, inverse precondition; not the device put)."""


def read(ctx):
    blocks = ctx["out"].get("blocks")
    if not blocks or not sum(b["decode"] for b in blocks):
        return None
    return sum(b["raw"] for b in blocks) / sum(b["decode"] for b in blocks) / 1e6
