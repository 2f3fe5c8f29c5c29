"""Device time of the paged-decode program per call, from the trace."""

from chipbench.trace import DECODE


def read(ctx):
    p = ctx.trace.program(DECODE)
    return 1e3 * p["time_s"] / p["calls"] if p["calls"] else None
