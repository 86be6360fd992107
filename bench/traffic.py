"""The one traffic generator: a mix file and a seed give the requests.

A mix file names an arrival process and length distributions.  Sampling is
stratified: a run of ``n`` requests takes the ``n`` mid-quantiles
``(i + 0.5) / n`` of each distribution, and the run's seed shuffles each
of them (gaps, prompt lengths, output lengths) into its own order.  Every
seed thus offers the same sizes and gaps, so a seed changes the schedule
and not the amount of work.

Supported keys (see ``bench/traffic/*.json``):

  arrivals.process   "poisson": exponential gaps at the cell's rate
  prompt_tokens      lognormal {median, sigma}, rounded up to the smallest
                     of ``round_up_to`` that holds it (the largest if none)
  output_tokens      lognormal {median, sigma}, clipped to [min, max]

Every request is greedy: the correctness check compares greedy tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np
from scipy.special import ndtri


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # offset from the start of the window
    prompt_len: int
    output_len: int


def mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(q: np.ndarray, spec: Dict[str, Any]) -> np.ndarray:
    return float(spec["median"]) * np.exp(float(spec["sigma"]) * ndtri(q))


def prompt_lengths(q: np.ndarray, spec: Dict[str, Any]) -> np.ndarray:
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown prompt distribution {spec.get('dist')!r}")
    raw = lognormal(q, spec)
    buckets = np.asarray(sorted(spec["round_up_to"]))
    idx = np.minimum(np.searchsorted(buckets, raw, side="left"),
                     len(buckets) - 1)
    return buckets[idx].astype(np.int64)


def output_lengths(q: np.ndarray, spec: Dict[str, Any]) -> np.ndarray:
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown output distribution {spec.get('dist')!r}")
    raw = np.rint(lognormal(q, spec))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def arrival_offsets(n: int, seconds: float, spec: Dict[str, Any],
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` due times in ``[0, seconds)``, the first at 0."""
    if spec.get("process") != "poisson":
        raise ValueError(f"unknown arrival process {spec.get('process')!r}")
    gaps = rng.permutation(-np.log1p(-mid_quantiles(n)))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def generate(mix: Dict[str, Any], *, rate: float, seconds: float,
             seed: int) -> List[Request]:
    """The requests due in a window of ``seconds`` at ``rate`` per second,
    in order of their due time, in the order ``seed`` gives."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 4])
    due = arrival_offsets(n, seconds, mix["arrivals"], rng)
    q = mid_quantiles(n)
    prompts = rng.permutation(prompt_lengths(q, mix["prompt_tokens"]))
    outputs = rng.permutation(output_lengths(q, mix["output_tokens"]))
    return [Request(i, float(due[i]), int(prompts[i]), int(outputs[i]))
            for i in range(n)]


def buckets(mix: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can send: the shapes to warm up."""
    return sorted(int(b) for b in mix["prompt_tokens"]["round_up_to"])
