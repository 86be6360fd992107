"""The control of the correctness check, and the program's readings beside
it, for one cell on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, in one process: the cell's weights, a short window of the
cell's own traffic at its own rate through the served path (as a run
makes it), then over the same sample of finished requests that a run
compares (the longest among them):

  * ``program``: the widest gap between a served token's reference logit
    and the reference's best (the number a run compares);
  * ``control``: the same reading for the token that the reference
    computed in float8 e4m3 (one precision below the configurations'
    bfloat16) puts first at each of those positions.

One JSON line per seed.  A limit lies above every program reading and
below every control reading (``bench/cells/<cell>.json``); the control has
to come out as not correct.  Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL = "fp8"


def readings(root, name, seed, seconds, *, require_tpu=True, fault=None):
    """-> dict of the program's and the control's widest gap for one seed."""
    from bench import check, harness

    p = harness.prepare(root, name, seed, require_tpu)
    cell, ref, params, sizes = p.cell, p.ref, p.params, p.sizes
    served = harness.serve(cell, p.cfg, params, seed=seed, seconds=seconds,
                           trace=False, t_process=time.perf_counter(),
                           counter=p.counter, fault=fault)
    gc.collect()
    finished = [r for r in served.records if r.finished]
    sampled = check.sample(finished, check.SAMPLE, seed)
    seqs = [(r.prompt[0], r.tokens[0, r.prompt.shape[1]:]) for r in sampled]
    length = cell.knobs["serving"]["max_len"]
    prog = check.served_gaps(ref, params, sizes, seqs, length=length)
    ctl = check.control_gaps(ref, params, sizes, seqs, length=length,
                             quant=CONTROL)
    return {
        "cell": name, "seed": seed, "due": len(served.records),
        "finished": len(finished), "compared_tokens": int(sum(g.size for g in prog)),
        "program": max(float(g.max()) for g in prog),
        "control": max(float(g.max()) for g in ctl),
        "program_per_request": [round(float(g.max()), 6) for g in prog],
        "control_per_request": [round(float(g.max()), 6) for g in ctl],
        "program_p99": float(np.percentile(np.concatenate(prog), 99)),
        "control_median": float(np.median(np.concatenate(ctl))),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = readings(ROOT, args.workload, seed, args.seconds)
        except harness.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
