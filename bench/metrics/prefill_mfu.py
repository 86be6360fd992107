"""Device (whole prefill program): model FLOPs of the real prompt rows
prefilled in the traced window, over the window times the chip's peak, in
%.  Beside the prefill kernels' rooflines: it still bounds a gain where a
kernel leaves the path."""
from bench import flops


def reduce(ctx):
    work = sum(flops.prefill_flops(ctx.sizes, call["real"], call["length"])
               for _, call in ctx.runs("prefill_fn") if call is not None)
    if work <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * work / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
