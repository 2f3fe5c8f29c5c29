"""Device time of the paged-extend program per call, from the trace.

A prefix hit prefills its uncached suffix through ``jit(extend_step)``;
its calls hold up the decode steps of every other slot."""

EXTEND = "extend_step"


def read(ctx):
    p = ctx.trace.program(EXTEND)
    return 1e3 * p["time_s"] / p["calls"] if p["calls"] else None
