"""Kernels: share (%) of its roofline that the prefill WKV6 kernel
reached: the least time the chip needs for its FLOPs or bytes
(bench/flops.py) over its device time."""
from bench import flops


def reduce(ctx):
    return ctx.prefill_kernel_share("wkv6", flops.wkv6)
