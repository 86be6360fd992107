"""Proxy / scheduler: per request, the start of its first stage span
(the prefill) less the start of its ``proxy.submit`` (ms, mean)."""
from bench import spans


def reduce(ctx):
    submit = ctx.first_by_uid("onepiece.proxy.submit")
    stage = ctx.first_by_uid("onepiece.stage")
    return spans.mean([(stage[u].start - s.start) / 1e6
                       for u, s in submit.items() if u in stage])
