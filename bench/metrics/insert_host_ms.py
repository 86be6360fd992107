"""Decoder: mean duration of ``decode.insert``, the decode thread's stall
per slot insert (host side and dispatch of the insert program) (ms)."""
from bench import spans


def reduce(ctx):
    return spans.mean([s.ms for s in ctx.complete("onepiece.decode.insert")])
