"""The paged-decode program's share of its roofline.

Least time of each decode step = max(FLOPs / peak FLOP/s, least bytes /
peak bandwidth), where the least bytes are the weights once, each live
slot's K/V up to its position and its new row; summed over the window's
steps and divided by the program's device time in the trace.
"""

from chipbench.trace import DECODE


def read(ctx):
    d, c, pk = ctx.drive, ctx.costs, ctx.peaks
    steps = [r for r in d.steps if d.t0 <= r.start < d.end
             and r.decode_positions]
    least = 0.0
    for r in steps:
        flops, nbytes = c.decode_step_cost(ctx.conf, r.decode_positions)
        least += max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    secs = ctx.trace.program(DECODE)["time_s"]
    return 100.0 * least / secs if least and secs else None
