"""Server, proxy to decoder: per request, its ``decode.first_token`` mark
less the start of its ``proxy.submit`` (ms, median): the program's own
time to first token.  Prints its split into steps."""
import statistics

from bench import spans


def reduce(ctx):
    submit = ctx.first_by_uid("onepiece.proxy.submit")
    first = ctx.first_by_uid("onepiece.decode.first_token")
    got = [(first[u].start - s.start) / 1e6
           for u, s in submit.items() if u in first]
    if not got:
        return None
    spans.report_ttft(ctx)
    return statistics.median(got)
