"""Load generator: 99th percentile of how late requests were submitted
after their due time (ms).  A starved generator must not read as a fast
server."""
from bench import tails


def reduce(ctx):
    lags = [r.submit_s - r.due_s for r in ctx.records if r.submit_s is not None]
    if not lags:
        return None
    return 1e3 * tails.percentile(lags, 99)
