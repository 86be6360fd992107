"""Run one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the correctness check
compared, with its limit.  The same numbers end standard error.  Exits 0
when the run completed (``correct`` may still be false), 3 without a
result line when JAX finds no TPU, too few chips or a chip not in
``bench/peaks.json``, 2 when the cell or the program cannot be loaded,
and 4 when a per-layer metric the cell lists found nothing to read.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        import repro  # noqa: F401
        from bench import harness, spec
    except ImportError as e:
        print(f"bench: cannot load the program or the harness: {e}",
              file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(ROOT, args.workload, seed=args.seed,
                               seconds=args.seconds, trace=bool(args.trace),
                               t_process=T_PROCESS)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except harness.MissingMetric as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
