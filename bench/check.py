"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the window finished,
drawn from the seed and always holding the longest, is scored by the
configuration's plain float32 reference (``bench/configs/<config>.py``):
each prompt followed by its served tokens is run through the reference
once, and at every served position the reference's logits give

    gap = (best logit - logit of the served token) / RMS of the logits

in units of the row's RMS.  Served greedy tokens of a sound program sit at
or near the reference's best, so the widest gap over the sample is small;
a wrong prefill, handoff, slot insert or decode step puts tokens far below
it.  The number compared is that widest gap, against the cell's limit.

The control (``control_gaps``) puts the reference itself in the program's
place one precision lower, float8 e4m3 against the configuration's
bfloat16, and reads at the same positions the gap of the token that the
lower precision puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import refkit as K

CHUNK = 256   # positions per block of logits
SAMPLE = 6    # finished requests compared per run, the longest among them


def sample(finished: Sequence, k: int, seed: int) -> List:
    """``k`` finished records drawn from ``seed``, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r.tokens.shape[1], -r.index))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def teacher_forced(seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                   length: int, rows: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """(prompt, served) pairs -> inputs [N, length] (prompt then served
    tokens but the last, zero-padded) and targets [N, length]: the served
    token each position predicts, -1 where nothing is compared.  N is
    ``len(seqs)`` rounded up to a multiple of ``rows`` (one program shape
    for every block of rows)."""
    n = -(-len(seqs) // rows) * rows
    toks = np.zeros((n, length), np.int32)
    tgt = np.full((n, length), -1, np.int32)
    for i, (prompt, served) in enumerate(seqs):
        p, m = len(prompt), len(served)
        if p + m - 1 > length:
            raise ValueError(f"sequence of {p + m - 1} > {length} positions")
        toks[i, :p] = prompt
        toks[i, p:p + m - 1] = served[:-1]
        tgt[i, p - 1:p + m - 1] = served
    return toks, tgt


@jax.jit
def _gap_of(h, w, tgt):
    """h [B, C, D] f32, w [D, V], tgt [B, C] -> gap [B, C] (0 where
    tgt < 0)."""
    lg = K.mm("bcd,dv->bcv", h, w)
    best = lg.max(-1)
    rms = jnp.sqrt(jnp.mean(lg * lg, -1))
    at = jnp.take_along_axis(lg, jnp.maximum(tgt, 0)[..., None], -1)[..., 0]
    return jnp.where(tgt >= 0, (best - at) / rms, 0.0)


@functools.partial(jax.jit, static_argnames=("quant",))
def _pick(h, w, quant):
    return jnp.argmax(K.mm("bcd,dv->bcv", h, w, quant), -1).astype(jnp.int32)


def _blocks(ref, params, sizes, toks, rows, quant=""):
    for i in range(0, len(toks), rows):
        yield i, ref.hidden(params, jnp.asarray(toks[i:i + rows]), sizes,
                            quant)


def served_gaps(ref, params, sizes, seqs, *, length: int,
                rows: int = 2) -> List[np.ndarray]:
    """Per sequence, the gap of each served token."""
    toks, tgt = teacher_forced(seqs, length, rows)
    w = ref.unembed(params, sizes)
    out = np.zeros(tgt.shape, np.float32)
    for i, h in _blocks(ref, params, sizes, toks, rows):
        for c in range(0, length, CHUNK):
            t = tgt[i:i + rows, c:c + CHUNK]
            if (t >= 0).any():
                out[i:i + rows, c:c + CHUNK] = np.asarray(
                    _gap_of(h[:, c:c + CHUNK], w, jnp.asarray(t)))
        del h
    return [out[j][tgt[j] >= 0] for j in range(len(seqs))]


def control_gaps(ref, params, sizes, seqs, *, length: int, quant: str,
                 rows: int = 2) -> List[np.ndarray]:
    """Per sequence, the gap of the token the ``quant`` reference puts
    first at each served position (same prompts and served tokens)."""
    toks, tgt = teacher_forced(seqs, length, rows)
    w = ref.unembed(params, sizes)
    picks = np.full(tgt.shape, -1, np.int32)
    for i, h in _blocks(ref, params, sizes, toks, rows, quant):
        for c in range(0, length, CHUNK):
            t = tgt[i:i + rows, c:c + CHUNK]
            if (t >= 0).any():
                p = np.asarray(_pick(h[:, c:c + CHUNK], w, quant))
                picks[i:i + rows, c:c + CHUNK] = np.where(t >= 0, p, -1)
        del h
    ctl = [(s[0], picks[j][tgt[j] >= 0]) for j, s in enumerate(seqs)]
    return served_gaps(ref, params, sizes, ctl, length=length, rows=rows)


def verdict(records, sampled, gaps: List[np.ndarray],
            limit: float) -> Tuple[bool, Dict[str, Tuple[float, float]], List[str]]:
    """-> (correct, {number: (value, limit)}, reasons it is not)."""
    why = []
    unfinished = [r for r in records if not r.finished]
    wrong_len = [r for r in records if r.finished and
                 r.tokens.shape[1] - r.prompt.shape[1] != r.output_len]
    wrong_prompt = [r for r in records if r.finished and not np.array_equal(
        r.tokens[0, :r.prompt.shape[1]], r.prompt[0])]
    if unfinished:
        why.append(f"{len(unfinished)} requests never finished; the first: "
                   f"{unfinished[0].error}")
    if wrong_len:
        why.append(f"{len(wrong_len)} results of the wrong length")
    if wrong_prompt:
        why.append(f"{len(wrong_prompt)} results do not start with their "
                   f"prompt")
    widest = max((float(g.max()) for g in gaps if g.size), default=float("nan"))
    if not sampled:
        why.append("no finished request to compare")
    elif not widest <= limit:
        why.append(f"a served token lies {widest:.4f} RMS below the "
                   f"reference's best (limit {limit})")
    numbers = {
        "max_gap_rms": (widest, limit),
        "unfinished": (float(len(unfinished)), 0.0),
        "wrong_length": (float(len(wrong_len)), 0.0),
        "wrong_prompt": (float(len(wrong_prompt)), 0.0),
    }
    return not why, numbers, why
