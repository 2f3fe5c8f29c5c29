"""Mean share of the engine's slots that decoded a token, over the window's steps.

Read from the program's own counter of decoded tokens after each step.
"""


def read(ctx):
    d = ctx.drive
    steps = [r for r in d.steps if d.t0 <= r.start < d.end]
    if not steps:
        return None
    slots = ctx.conf["engine"]["max_batch"]
    return 100.0 * sum(r.decode_tokens for r in steps) / (len(steps) * slots)
