"""Fused step: device busy ms per retired step, from the profiler trace."""


def read(ctx):
    steps = ctx.info.get("steps")
    if ctx.device is None or not steps:
        return None
    return 1e3 * ctx.device["busy_s"] / steps
