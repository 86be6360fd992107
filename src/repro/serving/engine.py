"""Model-level serving engine: batched prefill -> on-device decode loop
for any assigned architecture (the per-stage compute a TaskWorker runs when
a workflow stage is an LM rather than a diffusion model).

The engine is deliberately synchronous-batch (the paper's Collaboration
Mode): ONE jitted prefill over the whole prompt, then the entire decode
generation as ONE jitted ``lax.scan`` — a single host sync per generation
to fetch the sampled tokens, instead of the seed's one blocking dispatch
per prompt token plus one per decode step.

The prefill cache covers exactly the prompt length; decode needs the
preallocated ``max_len`` layout, so the prefill wrapper zero-pads every
cache leaf out to the ``abstract_cache(cfg, B, max_len)`` shape inside the
same jitted call.  Padding is semantics-preserving for every family:
full-length KV caches are masked by ``cur_index``; ring (sliding-window)
caches hold position ``t`` at slot ``t % w`` and a prompt shorter than the
window lays tokens out at ``t`` identically before and after padding;
recurrent states (rwkv/mamba) are already O(1)-sized and pass through.

RNG contract (docs/disaggregation.md)
-------------------------------------
Sampling must be *batch-composition independent*: the token sequence a
request produces may depend only on ``(seed, row, step)``, never on which
other requests share its batch.  Row ``b`` of decode step ``i`` samples
with ``fold_in(fold_in(PRNGKey(seed), b), i)`` through a per-row (vmapped)
categorical — a batched ``categorical(key, [B, V])`` draws Gumbel noise
whose layout depends on B, which is exactly the coupling continuous
batching cannot tolerate.  ``generate``, ``generate_reference``, and the
slot-based continuous decoder all share this derivation, which is what
makes scan-vs-loop parity hold at ``temperature > 0`` and lets a request
entering a half-full slot batch emit tokens bit-identical to a solo run.

Disaggregated serving (docs/disaggregation.md)
----------------------------------------------
``prefill``/``init_slots``/``insert_slot``/``decode_segment``/
``release_slot`` split generation into the two workflow stages of the
``llm_disagg`` DAG: prefill produces a per-request cache (batch axis per
leaf from the ``abstract_cache`` ParamSpec logical names) that ships over
the fabric as KV pages; decode holds a ``max_slots``-wide slot cache where
requests join and leave at segment boundaries with per-slot ``cur_index``
vectors and active masks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.models.param import is_spec


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # [B, prompt + generated]
    prompt_len: int
    steps: int


def _row_base_keys(seed, rows: int):
    """[rows, 2] uint32 — per-row sampling streams for one batch seed."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda r: jax.random.fold_in(base, r))(jnp.arange(rows))


def _sample_rows(logits, step_keys, temperature):
    """Per-row categorical over [B, V] logits.  ``temperature`` is either a
    static float (generate paths) or a per-row [B] f32 vector (slot decode);
    a static t > 0 and a vector entry t compute the same f32 division, so
    the two paths sample bit-identically."""
    greedy = jnp.argmax(logits, axis=-1)
    if isinstance(temperature, (int, float)):
        if temperature <= 0:
            return greedy
        t = jnp.float32(temperature)
        return jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg / t))(step_keys, logits)
    t = jnp.maximum(temperature.astype(jnp.float32), jnp.float32(1e-6))
    sampled = jax.vmap(
        lambda k, lg, tt: jax.random.categorical(k, lg / tt))(
        step_keys, logits, t)
    return jnp.where(temperature > 0, sampled, greedy)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, max_len: int = 256,
                 seed: int = 0, use_pallas: str | None = None):
        if use_pallas is not None:  # per-engine kernel dispatch override
            cfg = dataclasses.replace(cfg, use_pallas=use_pallas)
        self.cfg = cfg
        self.max_len = max_len
        self.params = params if params is not None else registry.init_params(
            jax.random.PRNGKey(seed), cfg)

        cfgs = cfg
        max_len_s = max_len

        # MoE models tally, per decode segment, the routed (row, expert)
        # assignments of the rows that advanced: those landing on experts
        # held here, and those routed to experts held elsewhere.
        self.moe_layers = (cfg.num_layers - cfg.first_dense_layers
                           if cfg.num_experts else 0)
        moe = self.moe_layers > 0
        routed_per_row = self.moe_layers * cfg.top_k

        # Per-leaf batch-axis map: every family's abstract_cache ParamSpec
        # names its batch dim "batch", but at a different position per leaf
        # (stacked-layer leading dims, gemma3 period dims) — this tree is
        # what lets slot insert/extract address any leaf uniformly.
        spec1 = registry.abstract_cache(cfg, 1, max_len)
        self._batch_axes = jax.tree.map(
            lambda s: s.logical.index("batch"), spec1, is_leaf=is_spec)
        # which cache leaves (flatten order) are recurrent state, fixed in
        # size, and which a KV cache that grows along "cache_seq"
        self.state_leaves = [
            "cache_seq" not in s.logical
            for s in jax.tree.leaves(spec1, is_leaf=is_spec)]

        @jax.jit
        def prefill_fn(params, batch):
            logits, cache = registry.prefill(params, batch, cfgs, dropless=True)
            b = batch["tokens"].shape[0]
            spec = registry.abstract_cache(cfgs, b, max_len_s)

            def pad(leaf, s):
                target = tuple(s.shape)
                if tuple(leaf.shape) == target:
                    return leaf
                if any(c > t for c, t in zip(leaf.shape, target)):
                    raise ValueError(
                        f"prefill cache leaf {leaf.shape} exceeds decode "
                        f"layout {target}")
                return jax.lax.pad(leaf, jnp.zeros((), leaf.dtype),
                                   [(0, t - c, 0)
                                    for c, t in zip(leaf.shape, target)])

            return logits, jax.tree.map(pad, cache, spec)

        @jax.jit
        def decode_fn(params, cache, tokens, cur_index):
            return registry.decode_step(
                params, cache, {"tokens": tokens, "cur_index": cur_index},
                cfgs, dropless=True)[:2]

        @functools.partial(jax.jit, static_argnames=("steps", "temperature"))
        def decode_loop_fn(params, cache, logits, start, seed, *, steps,
                           temperature):
            """The whole generation as one on-device scan: sample from the
            carried logits, run one decode step, repeat.  Token i lands at
            position start+i; one host sync fetches the [B, steps] block.
            Row b of step i samples with fold_in(fold_in(key(seed), b), i)
            — see the module RNG contract."""
            row_keys = _row_base_keys(seed, logits.shape[0])

            def body(carry, i):
                logits, cache, idx = carry
                step_keys = jax.vmap(
                    lambda k: jax.random.fold_in(k, i))(row_keys)
                tok = _sample_rows(logits, step_keys, temperature)
                tok = jnp.minimum(tok, cfgs.vocab_size - 1).astype(jnp.int32)
                logits, cache = registry.decode_step(
                    params, cache, {"tokens": tok, "cur_index": idx},
                    cfgs, dropless=True)[:2]
                return (logits, cache, idx + 1), tok

            (logits, cache, _), toks = jax.lax.scan(
                body, (logits, cache, jnp.int32(start)), jnp.arange(steps))
            return jnp.transpose(toks), logits  # [B, steps]

        def insert_fn(state, cache1, logits1, slot, start, seed, rem, temp):
            """Graft one prefilled request into slot ``slot``: overwrite the
            slot's cache row (per-leaf batch axis), seed its sampling stream
            (row 0 of its own seed — identical to a solo B=1 run), and arm
            the per-slot counters."""
            cache = jax.tree.map(
                lambda big, small, ax: jax.lax.dynamic_update_slice_in_dim(
                    big, jnp.asarray(small, big.dtype), slot, axis=ax),
                state["cache"], cache1, self._batch_axes)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
            return dict(
                state, cache=cache,
                logits=state["logits"].at[slot].set(logits1),
                cur_index=state["cur_index"].at[slot].set(start),
                step=state["step"].at[slot].set(0),
                remaining=state["remaining"].at[slot].set(rem),
                keys=state["keys"].at[slot].set(key),
                temp=state["temp"].at[slot].set(temp),
                active=state["active"].at[slot].set(True))

        @functools.partial(jax.jit, static_argnames=("k",))
        def segment_fn(params, state, *, k):
            """k decode steps over the whole slot batch.  Slots advance only
            while active with budget remaining; the rest decode masked-out
            garbage (row-independent math, overwritten at next insert).
            Returns (state', toks [k, N], advanced-mask [k, N]).  An MoE
            model's state carries beside them ``expert_rows``: [held,
            absent] assignments of the advanced rows in this segment."""

            def body(carry, _):
                logits, cache, cur, step, rem, keys, temp, active = carry
                step_keys = jax.vmap(jax.random.fold_in)(keys, step)
                tok = _sample_rows(logits, step_keys, temp)
                tok = jnp.minimum(tok, cfgs.vocab_size - 1).astype(jnp.int32)
                adv = active & (rem > 0)
                out = registry.decode_step(
                    params, cache, {"tokens": tok, "cur_index": cur},
                    cfgs, dropless=True)
                new_logits, cache = out[:2]
                held = jnp.where(adv, out[2], 0.0) if moe else None
                logits = jnp.where(adv[:, None], new_logits, logits)
                ai = adv.astype(jnp.int32)
                carry = (logits, cache, cur + ai, step + ai, rem - ai,
                         keys, temp, active)
                return carry, (tok, adv, held)

            carry = (state["logits"], state["cache"], state["cur_index"],
                     state["step"], state["remaining"], state["keys"],
                     state["temp"], state["active"])
            carry, (toks, adv, held) = jax.lax.scan(body, carry, None,
                                                    length=k)
            logits, cache, cur, step, rem, keys, temp, active = carry
            state = dict(state, logits=logits, cache=cache, cur_index=cur,
                         step=step, remaining=rem)
            if moe:
                n_held = held.sum().astype(jnp.int32)
                n_adv = adv.sum().astype(jnp.int32)
                state["expert_rows"] = jnp.stack(
                    [n_held, n_adv * routed_per_row - n_held])
            return state, toks, adv

        @jax.jit
        def release_fn(state, slot):
            return dict(state, active=state["active"].at[slot].set(False),
                        remaining=state["remaining"].at[slot].set(0))

        self._prefill = prefill_fn
        self._decode = decode_fn
        self._decode_loop = decode_loop_fn
        self._insert = jax.jit(insert_fn)
        self._segment = segment_fn
        self._release = release_fn

    def _fresh_cache(self, batch: int):
        spec = registry.abstract_cache(self.cfg, batch, self.max_len)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
                             spec, is_leaf=is_spec)
        if self.cfg.family == "audio":
            from repro.models.encdec import make_decode_cache

            frames = jnp.zeros(
                (batch, self.cfg.frontend_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
            cache = make_decode_cache(self.params, frames, self.cfg, self.max_len)
        return cache

    def _prefill_batch(self, prompts: np.ndarray) -> Dict[str, jax.Array]:
        batch: Dict[str, jax.Array] = {"tokens": jnp.asarray(prompts)}
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (prompts.shape[0], self.cfg.frontend_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return batch

    # ------------------------------------------------- disaggregated stages
    def prefill(self, prompts: np.ndarray):
        """The prefill *stage*: [B, P] prompts -> (logits [B, V], cache tree
        in the padded max_len decode layout).  The cache's per-leaf batch
        axes (``batch_axes``) are what the KV-ship path slices per request."""
        return self._prefill(self.params, self._prefill_batch(prompts))

    @property
    def batch_axes(self):
        """Tree (matching the cache tree) of each leaf's batch-axis index."""
        return self._batch_axes

    @property
    def shipment_bytes(self) -> int:
        """Page bytes of one prefilled request on the wire: its B=1 cache
        leaves at ``max_len`` plus the f32 last-token logits row."""
        spec1 = registry.abstract_cache(self.cfg, 1, self.max_len)
        cache = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                    for s in jax.tree.leaves(spec1, is_leaf=is_spec))
        return cache + 4 * self.cfg.vocab_padded

    def state_nbytes(self, leaves) -> int:
        """Bytes of the recurrent-state leaves among one request's cache
        ``leaves`` (flatten order); the rest of its bytes are KV."""
        return sum(int(leaf.nbytes)
                   for leaf, st in zip(leaves, self.state_leaves) if st)

    @property
    def handoff_split(self) -> Dict[str, int]:
        """One request's cache at ``max_len``: bytes of recurrent state and
        of KV."""
        spec1 = registry.abstract_cache(self.cfg, 1, self.max_len)
        out = {"state_bytes": 0, "kv_bytes": 0}
        for s, st in zip(jax.tree.leaves(spec1, is_leaf=is_spec),
                         self.state_leaves):
            n = int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
            out["state_bytes" if st else "kv_bytes"] += n
        return out

    def init_slots(self, max_slots: int) -> Dict[str, Any]:
        """Fresh continuous-batching decode state: a ``max_slots``-wide slot
        cache plus per-slot sampling/progress vectors, all inactive."""
        if self.cfg.family == "audio":
            raise NotImplementedError(
                "continuous batching needs the uniform abstract_cache layout; "
                "the audio enc-dec cache is built per request")
        spec = registry.abstract_cache(self.cfg, max_slots, self.max_len)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
                             spec, is_leaf=is_spec)
        n, v = max_slots, self.cfg.vocab_padded
        tally = {"expert_rows": jnp.zeros((2,), jnp.int32)} \
            if self.moe_layers else {}
        return {
            **tally,
            "cache": cache,
            "logits": jnp.zeros((n, v), jnp.float32),
            "cur_index": jnp.zeros((n,), jnp.int32),
            "step": jnp.zeros((n,), jnp.int32),
            "remaining": jnp.zeros((n,), jnp.int32),
            "keys": jnp.zeros((n, 2), jnp.uint32),
            "temp": jnp.zeros((n,), jnp.float32),
            "active": jnp.zeros((n,), bool),
        }

    def insert_slot(self, state, slot: int, cache1, logits1, *, start: int,
                    seed: int, steps: int, temperature: float):
        """Join: land a prefilled request (B=1 cache leaves + last-token
        logits [V]) in slot ``slot`` at a segment boundary."""
        return self._insert(state, cache1, jnp.asarray(logits1),
                            jnp.int32(slot), jnp.int32(start),
                            jnp.int32(seed), jnp.int32(steps),
                            jnp.float32(temperature))

    def decode_segment(self, state, k: int):
        """Run ``k`` lockstep decode steps over the slot batch.  Returns
        (state', tokens [k, N] np.int32, advanced [k, N] np.bool_): column
        s of ``tokens`` holds the next min(k, remaining) tokens of the
        request in slot s, rows where ``advanced`` is set."""
        state, toks, adv = self._segment(self.params, state, k=k)
        return state, np.asarray(toks), np.asarray(adv)

    def release_slot(self, state, slot: int):
        """Leave: free a slot at a segment boundary (cache row stays as
        garbage until the next insert overwrites it)."""
        return self._release(state, jnp.int32(slot))

    # ------------------------------------------------------ monolithic path
    def generate(self, prompts: np.ndarray, *, steps: int = 16,
                 temperature: float = 0.0, seed: int = 0) -> GenerationResult:
        """prompts: [B, P] int32.  One jitted prefill consumes the prompt,
        one jitted scan generates ``steps`` tokens greedily (or with
        temperature); the only host sync is fetching the finished block."""
        b, p = prompts.shape
        assert p + steps <= self.max_len
        logits, cache = self._prefill(self.params, self._prefill_batch(prompts))
        toks, _ = self._decode_loop(
            self.params, cache, logits, jnp.int32(p), jnp.int32(seed),
            steps=steps, temperature=float(temperature))
        tokens = np.concatenate([prompts, np.asarray(toks)], axis=1)
        return GenerationResult(tokens=tokens, prompt_len=p, steps=steps)

    def generate_reference(self, prompts: np.ndarray, *, steps: int = 16,
                           temperature: float = 0.0,
                           seed: int = 0) -> GenerationResult:
        """The seed's token-at-a-time loop (teacher-forced prompt, one host
        sync per decode step).  Kept as the parity/benchmark baseline for
        the scan path — not a serving path.  Shares the (seed, row, step)
        key derivation with ``generate`` so parity holds at temperature > 0."""
        b, p = prompts.shape
        assert p + steps <= self.max_len
        cache = self._fresh_cache(b)
        row_keys = _row_base_keys(seed, b)

        logits = None
        for t in range(p):
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(prompts[:, t]), jnp.int32(t))
        out = [prompts]
        for i in range(steps):
            step_keys = jax.vmap(
                lambda k: jax.random.fold_in(k, i))(row_keys)  # noqa: B023
            cur = _sample_rows(logits, step_keys, float(temperature))
            cur = jnp.minimum(cur, self.cfg.vocab_size - 1).astype(jnp.int32)
            out.append(np.asarray(cur)[:, None])
            logits, cache = self._decode(self.params, cache, cur,
                                         jnp.int32(p + i))
        return GenerationResult(tokens=np.concatenate(out, axis=1),
                                prompt_len=p, steps=steps)

    def teacher_forced_logits(self, prompts: np.ndarray,
                              tokens: np.ndarray) -> np.ndarray:
        """[B, V] f32 logits scoring the token after ``prompts`` [B, P]
        followed by ``tokens`` [B, T] — the same prefill and scalar-index
        decode programs as ``generate``, one step at a time.  The reference
        a served stream is held to where it departs from a solo run."""
        p = prompts.shape[1]
        assert p + tokens.shape[1] <= self.max_len
        logits, cache = self._prefill(self.params, self._prefill_batch(prompts))
        for t in range(tokens.shape[1]):
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(tokens[:, t], jnp.int32),
                                         jnp.int32(p + t))
        return np.asarray(logits)
