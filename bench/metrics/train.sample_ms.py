"""Sampling: seconds of the `sample` span per batch, in ms (the pipeline
scheduler draws each batch inline from the online sampler)."""


def read(ctx):
    return ctx.mean_ms("sample")
