"""Model FLOPs of the tokens emitted in the traced part over the chips' peak.

A decoded token counts one forward pass at its depth; a first token counts
its whole prompt. The FLOPs come from the configuration's shapes
(``reference/<name>.py``), the time is the traced part's.
"""


def read(ctx):
    c = ctx.costs
    steps = ctx.drive.steps_within(ctx.trace.window_s)
    flops = sum(c.decode_token_flops(ctx.conf, n) for r in steps
                for n in r.decode_positions)
    flops += sum(c.prompt_flops(ctx.conf, n) for r in steps
                 for n in r.prompt_tokens)
    if not flops or not ctx.trace.window_s:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.trace.window_s * peak)
