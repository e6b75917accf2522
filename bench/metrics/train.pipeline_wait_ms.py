"""Pipeline: `pipeline_wait` span seconds per retired step, in ms: time the
dispatch thread starved for a prepared batch."""


def read(ctx):
    waits, steps = ctx.span_seconds("pipeline_wait"), ctx.info.get("steps")
    if not waits or not steps:
        return None
    return 1e3 * sum(waits) / steps
