"""Engine: device time of the decode segment program per decode step (ms),
from the trace's program runs."""


def reduce(ctx):
    runs = ctx.runs("segment_fn")
    steps = len(runs) * ctx.knobs["serving"]["segment"]
    if not steps:
        return None
    return 1e3 * sum((ev[2] - ev[1]) / 1e9 for ev, _ in runs) / steps
