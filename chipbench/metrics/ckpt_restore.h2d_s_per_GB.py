"""Seconds of ``restore()`` outside its decode spans (opening the file,
host-to-device puts, until the block is on the device) per GB."""


def read(ctx):
    blocks = ctx["out"].get("blocks")
    if not blocks or not sum(b["decode"] for b in blocks):
        return None
    raw = sum(b["raw"] for b in blocks)
    rest = sum(b["restore"] - b["decode"] for b in blocks)
    return rest / (raw / 1e9)
