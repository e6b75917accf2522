"""Pipeline: the scheduler thread's time inside its work spans but off a
CPU (the GIL, a lock, the runtime), per batch, in ms: the sum of `dur`
minus `tdur` (the span's thread CPU time) over its `sample`, `negatives`,
`schedule` and `transfer` spans, over the batches (`schedule` spans)."""

WORK = ("sample", "negatives", "schedule", "transfer")


def read(ctx):
    spans = [ev for ev in ctx._spans if ev["name"] in WORK]
    batches = sum(1 for ev in spans if ev["name"] == "schedule")
    if not batches or any("tdur" not in ev for ev in spans):
        return None
    return 1e-3 * sum(ev["dur"] - ev["tdur"] for ev in spans) / batches
