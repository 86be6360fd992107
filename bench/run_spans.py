"""A traced run of one cell that also reads the program's own spans.

    python bench/run_spans.py --workload <cell> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``, and so is its result line, with
the span metrics below added to ``metrics``; the reducers that print
(``kv_pull_ms``, ``first_token_ms``, ``host_stall_share``) add the pull's
rate, each request's time to first token split into steps, and the
device's idle time split by what the host was doing to standard error.
``bench/run.py`` cannot report these yet: its trace reader keeps the
``bench.*`` host spans alone.  So this script hands the harness a context
that holds the ``onepiece.*`` spans too (``bench/spans.py``) and adds the
span metrics to the cell's per-layer ones.  Exits as ``bench/run.py``
does: 4 when a span metric finds nothing to read.
"""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402  (times set-up from its import)

SPAN_METRICS = {
    "queue_wait_ms": "ms", "kv_pull_ms": "ms", "kv_ring_ms": "ms",
    "insert_host_ms": "ms", "tick_host_ms": "ms", "first_token_ms": "ms",
    "host_stall_share": "%",
}


def main(argv=None) -> int:
    from bench import harness, spans, tracing

    plain_context, plain_per_layer = tracing.context, harness.per_layer

    def context(cell, served, peaks, sizes):
        return spans.with_spans(plain_context(cell, served, peaks, sizes),
                                served.trace_dir)

    def per_layer(cell, ctx):
        out = plain_per_layer(cell, ctx)
        print(f"program spans in the window: {len(ctx.spans)}, "
              f"{len(ctx.spans) / ctx.window_s:.1f}/s", file=sys.stderr)
        for name, unit in SPAN_METRICS.items():
            got = cell.metric_reducer(name).reduce(ctx)
            if got is None:
                raise harness.MissingMetric(
                    f"{cell.name}: span metric {name!r} found nothing to "
                    f"read in the traced window (bench/metrics/{name}.py)")
            out[name] = {"value": float(got), "unit": unit}
        return out

    tracing.context, harness.per_layer = context, per_layer
    args = sys.argv[1:] if argv is None else list(argv)
    return run.main([*args, "--trace", "1"])


if __name__ == "__main__":
    raise SystemExit(main())
