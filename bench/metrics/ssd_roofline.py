"""Kernels: share (%) of its roofline that the prefill SSD kernel of the
Mamba2 layers reached: the least time the chip needs for the recurrence's
FLOPs or bytes (bench/flops_hybrid.py, at the rows and length it was
given) over its device time."""
from bench import flops_hybrid


def reduce(ctx):
    return ctx.prefill_kernel_share("ssd_scan", flops_hybrid.ssd)
