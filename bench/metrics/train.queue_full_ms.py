"""Pipeline: seconds of the `prepared_put` span per batch, in ms: the
scheduler thread blocked on a full prepared queue, waiting on the consumer
(dispatch or device)."""


def read(ctx):
    return ctx.mean_ms("prepared_put")
