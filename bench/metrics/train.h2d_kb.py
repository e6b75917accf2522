"""Plan + schedule: device bytes each batch's `transfer` span created (its
`bytes` arg: bind arrays, positives and negatives, and static slot arrays
not already on the device), per batch, in KB (1024 bytes)."""


def read(ctx):
    sizes = [ev.get("args", {}).get("bytes") for ev in ctx._spans
             if ev["name"] == "transfer"]
    if not sizes or None in sizes:
        return None
    return sum(sizes) / len(sizes) / 1024
