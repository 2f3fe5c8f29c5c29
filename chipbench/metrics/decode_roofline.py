"""The paged-decode program's share of its roofline.

Least time of each decode step = max(FLOPs / peak FLOP/s, least bytes /
peak bandwidth), where the least bytes are the weights once, each live
slot's K/V up to its position and its new row; summed over the steps of the
traced part and divided by the program's device time there.
"""

from chipbench.trace import DECODE


def read(ctx):
    c, pk = ctx.costs, ctx.peaks
    least = 0.0
    for r in ctx.drive.steps_within(ctx.trace.window_s):
        if r.decode_positions:
            flops, nbytes = c.decode_step_cost(ctx.conf, r.decode_positions)
            least += max(flops / pk["bf16_flops_per_s"],
                         nbytes / pk["hbm_bytes_per_s"])
    secs = ctx.trace.program(DECODE)["time_s"]
    return 100.0 * least / secs if least and secs else None
