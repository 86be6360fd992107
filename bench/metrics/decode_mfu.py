"""Device (whole decode step): model FLOPs of the tokens decoded in the
traced window (attention over each row's actual context), over the window
times the chip's peak, in %.  Beside ``decode_roofline``: it still bounds
a gain where the step's implementation changes."""
from bench import flops


def reduce(ctx):
    work = 0
    for _, call in ctx.runs("segment_fn"):
        if call is not None and "steps" in call:
            for keys in call["steps"]:
                work += sum(flops.token_flops(ctx.sizes, n, True)
                            for n in keys)
    if work <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * work / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
