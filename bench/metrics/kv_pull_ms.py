"""Transport: host time of the prefill's cache pull (device to host and
the per-request slices) per request pulled (ms); prints the rate."""
import sys


def reduce(ctx):
    pulls = ctx.complete("onepiece.handoff.pull")
    n = sum(len(p.uids()) for p in pulls)
    if not n:
        return None
    ns = sum(p.end - p.start for p in pulls)
    gb = sum(int(p.args.get("bytes", 0)) for p in pulls) / 1e9
    print(f"kv_pull: {gb:.6f} GB in {ns / 1e9:.6f}s over {n} requests, "
          f"{gb / max(ns / 1e9, 1e-12):.3f} GB/s", file=sys.stderr)
    return ns / 1e6 / n
