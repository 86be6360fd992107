"""Transport: per request, from the start of the prefill's ``deliver``
(the ring write of its KV pages) to the end of the decode instance's
``recv`` (the poll and decode of the entry) (ms, mean)."""
from bench import spans


def reduce(ctx):
    deliver = ctx.first_by_uid("onepiece.deliver")
    recv = spans.ring_recv(ctx, deliver)
    return spans.mean([(r.end - deliver[u].start) / 1e6
                       for u, r in recv.items()])
