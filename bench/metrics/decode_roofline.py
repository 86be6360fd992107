"""Kernels: share (%) of its roofline that the slot decode step reached:
per step, the least time for what the step needs (weights read once, the
cache of each advancing row up to its length; or its FLOPs, if more), over
the segment program's device time.  The count does not depend on how the
step is implemented."""
import sys

from bench import flops


def reduce(ctx):
    need = spent = 0.0
    bounds = set()
    for ev, call in ctx.runs("segment_fn"):
        if call is None or "steps" not in call:
            continue
        for keys in call["steps"]:
            if keys:
                r = flops.roofline(flops.decode_step(ctx.sizes, keys), 1.0,
                                   ctx.peaks)
                need += r["share_pct"] / 100.0
                bounds.add(r["bound"])
        spent += (ev[2] - ev[1]) / 1e9
    if spent <= 0:
        return None
    print(f"decode step: {'/'.join(sorted(bounds))} bound, least time "
          f"{need:.6f}s over {spent:.6f}s of segment time", file=sys.stderr)
    return 100.0 * need / spent
