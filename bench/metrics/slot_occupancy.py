"""Decoder: share (%) of slot-steps that decoded a token: output tokens
over (segments x segment length x slots), for the window's requests."""


def reduce(ctx):
    segs = ctx.counters.get("segments", 0)
    if not segs:
        return None
    serving = ctx.knobs["serving"]
    tokens = sum(r.seen[-1][1] for r in ctx.records if r.seen)
    return 100.0 * tokens / (segs * serving["segment"] * serving["slots"])
