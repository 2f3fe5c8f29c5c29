"""The decode attention kernel's share of its roofline.

Device time of the ops under ``ng:fused:fused_attn_decode`` in the
paged-decode program, against the least time of that attention over the
steps of the traced part: each live K/V row read once per KV head, the
query read and the output written.
"""

from chipbench.trace import ATTN_DECODE, DECODE


def read(ctx):
    c, pk = ctx.costs, ctx.peaks
    least = 0.0
    for r in ctx.drive.steps_within(ctx.trace.window_s):
        if r.decode_positions:
            flops, nbytes = c.decode_attention_cost(ctx.conf,
                                                    r.decode_positions)
            least += max(flops / pk["bf16_flops_per_s"],
                         nbytes / pk["hbm_bytes_per_s"])
    secs = ctx.trace.site_time(DECODE, ATTN_DECODE)
    return 100.0 * least / secs if least and secs else None
