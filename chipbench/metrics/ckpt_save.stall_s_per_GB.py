"""Seconds that ``CheckpointManager.save(snapshot=True)`` blocks its caller
(the device-to-host snapshot) per GB of raw state."""


def read(ctx):
    blocks = ctx["out"].get("blocks")
    if not blocks:
        return None
    raw = sum(b["raw"] for b in blocks)
    return sum(b["stall"] for b in blocks) / (raw / 1e9)
