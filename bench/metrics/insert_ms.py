"""Engine: device time of one slot insert (ms), from the trace's program
runs."""


def reduce(ctx):
    runs = ctx.runs("insert_fn")
    if not runs:
        return None
    return 1e3 * sum((ev[2] - ev[1]) / 1e9 for ev, _ in runs) / len(runs)
