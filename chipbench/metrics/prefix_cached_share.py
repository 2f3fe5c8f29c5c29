"""Share of the prompt tokens admitted in the traced part that the prefix
cache served, so that only the rest went through prefill or extend.

Read from each request's own count of reused tokens (``Request.cached_len``,
set at admission), over the requests whose first token came in the traced
part. A program whose requests do not count them reports nothing."""


def read(ctx):
    d = ctx.drive
    limit = d.t0 + ctx.trace.window_s
    admitted = [s for s in d.served
                if s.stamps and d.t0 <= s.stamps[0] <= limit]
    if not admitted or not hasattr(admitted[0].handle, "cached_len"):
        return None
    prompt = sum(len(s.req.prompt) for s in admitted)
    return 100.0 * sum(s.handle.cached_len for s in admitted) / prompt
