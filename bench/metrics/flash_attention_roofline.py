"""Kernels: share (%) of its roofline that the prefill flash-attention
kernel reached: the least time the chip needs for its causal FLOPs or
bytes (bench/flops.py, at the rows it was given) over its device time."""
from bench import flops


def reduce(ctx):
    return ctx.prefill_kernel_share("flash_attention", flops.flash_attention)
