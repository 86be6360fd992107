"""Host: share (%) of the traced window in which no op ran on the chip
while a program span other than ``sched.wait`` was open on some thread.
Prints the device's idle time split by what the host was doing."""
from bench import spans


def reduce(ctx):
    if not any(s.name != spans.WAIT for s in ctx.spans):
        return None
    work, _, _ = spans.idle_split(ctx, spans.idle(ctx))
    spans.report_idle(ctx)
    return 100.0 * work / ctx.window_s
