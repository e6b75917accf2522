"""Plan + schedule: `schedule` plus `transfer` span seconds per batch, in ms
(plan compile, Algorithm-1 scheduling, bind and device puts)."""


def read(ctx):
    sched = ctx.span_seconds("schedule")
    if not sched:
        return None
    return 1e3 * (sum(sched) + sum(ctx.span_seconds("transfer"))) / len(sched)
