"""Fused step: operations counted by `ops_count` for the steps retired in
the window, over the window, the chips and the bf16 peak, in %."""


def read(ctx):
    return ctx.mfu()
