"""Sampling: seconds of the `negatives` span per batch, in ms (the pipeline
scheduler draws each batch's negatives in `sampler.to_training_arrays`)."""


def read(ctx):
    return ctx.mean_ms("negatives")
