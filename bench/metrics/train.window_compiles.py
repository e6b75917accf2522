"""Plan + schedule: train-step signatures first seen inside the window (the
trainer's step CompileCache misses over the window)."""


def read(ctx):
    return ctx.info.get("window_compiles")
