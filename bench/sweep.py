"""Knee sweep of one cell: the same set-up, then one window per rate and
traffic seed.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1.0,1.5,2.0 --seeds 11,12,13

``--seed`` makes the weights; each window's schedule comes from one of
``--seeds``.  For each window it prints one JSON line: rate, seed,
requests due, finished, failed, TTFT p50/p90, TPOT mean/p90, output
tokens/s, and the backlog (requests submitted and not yet finished) at the
middle and at the end of the window, and its mean over the window's third
and fourth quarters.  The knee is the highest rate whose backlog does not
grow over the second half (its mean over the fourth quarter exceeds that
over the third by at most ``GROWTH``) on any of the seeds, with no
failures.  Every rate given is swept, so that a rate read as grown by
chance does not hide the ones above it.  A last line gives the
knee and the rate for the cell (``choose``), which is set once from it in
``bench/cells/<cell>.json``.  Not part of a benchmark run: it needs the
chip, like ``run.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def backlog(records, t: float) -> int:
    return sum(1 for r in records if r.submit_s is not None
               and r.submit_s <= t and (r.last_s is None or r.last_s > t))


def summary(records, seconds: float, rate: float, seed: int) -> dict:
    from bench import tails

    ts = np.arange(seconds / 2, seconds + 1e-9, 0.25)
    b = np.array([backlog(records, t) for t in ts], float)
    q3, q4 = b[ts < 0.75 * seconds], b[ts >= 0.75 * seconds]
    return {
        "rate_per_s": rate, "seed": seed, "due": len(records),
        "finished": sum(r.finished for r in records),
        "failed": sum(not r.finished for r in records),
        "ttft_p50_ms": 1e3 * tails.percentile([r.ttft_s() for r in records], 50),
        "ttft_p90_ms": 1e3 * tails.percentile([r.ttft_s() for r in records], 90),
        "tpot_mean_ms": 1e3 * tails.mean([r.tpot_s() for r in records]),
        "tpot_p90_ms": 1e3 * tails.percentile([r.tpot_s() for r in records], 90),
        "output_tokens_per_s": sum(r.tokens_seen_by(seconds)
                                   for r in records) / seconds,
        "backlog_mid": int(b[0]), "backlog_end": int(b[-1]),
        "backlog_q3": float(q3.mean()), "backlog_q4": float(q4.mean()),
    }


# requests: at about 0.2 req/s, where nothing queues, the order of
# arrivals alone moved a quarter's mean by -0.68 to +2.0 (PERF.md)
GROWTH = 2.5
STEADY = 1.2    # largest over smallest TTFT p50 of the seeds at one rate


def holds(rows) -> bool:
    """No failures, and a backlog that does not grow, on every seed."""
    return all(r["failed"] == 0 and r["backlog_q4"] - r["backlog_q3"] <= GROWTH
               for r in rows)


def choose(rows):
    """-> (knee, rate).  The knee: the highest rate that held on every seed
    (None if none did).  The cell's rate: the highest swept rate at or
    below 0.8 x knee whose seeds agree on the median TTFT within
    ``STEADY``, since a median that moves with the order of the requests
    cannot be held to a bound; the lowest swept rate if none qualifies."""
    by: dict = {}
    for r in rows:
        by.setdefault(r["rate_per_s"], []).append(r)
    held = [rate for rate, rs in by.items() if holds(rs)]
    knee = max(held) if held else None

    def steady(rs):
        t = [r["ttft_p50_ms"] for r in rs]
        return max(t) <= STEADY * min(t)
    ok = [rate for rate, rs in by.items() if knee is not None
          and rate <= 0.8 * knee + 1e-9 and steady(rs)]
    return knee, max(ok) if ok else min(by)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    try:
        p = harness.prepare(ROOT, args.workload, args.seed)
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    stand = harness.Stand(p.cell, p.cfg, p.params, trace=False)
    with stand.ws:
        stand.warm(args.seed)
        rows = []
        for rate in (float(r) for r in args.rates.split(",")):
            for seed in (int(s) for s in args.seeds.split(",")):
                recs = stand.records(seed=seed, seconds=args.seconds,
                                     rate=rate)
                stand.client.run(recs, window_s=args.seconds,
                                 drain_s=harness.DRAIN_S,
                                 t0=time.perf_counter())
                rows.append(summary(recs, args.seconds, rate, seed))
                print(json.dumps(rows[-1]), flush=True)
    knee, rate = choose(rows)
    print(json.dumps({"knee": knee, "rate_per_s": rate}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
