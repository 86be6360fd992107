"""Decoder: mean self time of a ``decode.tick`` (ms): its duration less
what its ``insert`` and ``segment`` children cover (admission, harvest,
publish and retract)."""
from bench import spans


def reduce(ctx):
    return spans.mean([
        t.ms - sum(c.ms for name in ("onepiece.decode.insert",
                                     "onepiece.decode.segment")
                   for c in ctx.children(t, name))
        for t in ctx.complete("onepiece.decode.tick")])
