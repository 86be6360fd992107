"""Disaggregated prefill/decode LLM serving (docs/disaggregation.md).

Generation is split into the two stages of the ``llm_disagg`` workflow:

  * **prefill** — one jitted ``ServingEngine.prefill`` over the prompt
    (batched under the coalescer when the instance runs ``max_batch > 1``).
    Each request's KV cache leaves are sliced out along their per-leaf
    batch axes (``engine.batch_axes``) on the device and handed downstream
    as :class:`~repro.core.messaging.KVPages` of device arrays: the ring
    carries the meta, the wire ledger the arrays, and the cache never
    leaves the device.

  * **decode** — a :class:`ContinuousDecoder`, a *continuous* stage
    (``repro.core.streaming``): requests join and leave a running slot
    batch at scan-segment boundaries instead of PR 3's static
    ``max_batch`` buckets.  The instance scheduler pumps ``tick()``
    between inbox polls, so admission happens exactly at token
    boundaries; finished requests are delivered under their original
    message identity, in-flight prefixes stream through the database as
    ``partial/<uid>`` (``Proxy.poll_partial``).

Because the engine's RNG contract makes sampling batch-composition
independent, a request decoded in whatever slot mix happens to be resident
emits tokens bit-identical to a solo ``engine.generate`` run with the same
seed — the parity every test and benchmark in this PR pins.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.runtime import make_lock
from repro.cluster.node_manager import StageSpec, WorkflowSpec
from repro.cluster.workflow_set import WorkflowSet
from repro.core.batching import PerRequest
from repro.core import profiling
from repro.core.messaging import KVPages
from repro.core.profiling import profiler, span
from repro.core.streaming import DEFERRED
from repro.serving.engine import ServingEngine

APP_LLM_DISAGG = 7


def meta_wire_bytes(engine: ServingEngine) -> int:
    """Ring bytes one KV handoff entry can take: headers and the JSON meta,
    whose prompt ids (at most ``max_len`` of them, <= 12 characters each)
    dominate.  The pages travel beside the ring."""
    return 4096 + 12 * engine.max_len


def make_prefill_fn(engine: ServingEngine) -> Callable[[Any], Any]:
    """Stage fn for the prefill half.

    Accepts either a raw client payload (``max_batch == 1`` bypass) or the
    coalescer's stacked form — ``steps`` arrives as a plain int in the
    first case and as an ``[N]`` vector in the second (``stack_payloads``
    lifts numeric scalars to vectors) — and returns one ``KVPages`` per
    request: page 0 is the last-token logits row, pages 1.. are the cache
    leaves in ``jax.tree`` flatten order, each the request's B=1 slice
    along that leaf's batch axis.  The pages are device arrays: a batch of
    one hands its cache tree over as it is, a larger batch takes each
    request's slice on the device (one jitted slice per leaf).  A
    ``PerRequest`` wrapper keeps the per-request pages out of
    ``unstack_payload``'s generic row-slicing.
    """

    @jax.jit
    def row(logits, cache, i):
        return logits[i], jax.tree.map(
            lambda leaf, ax: jax.lax.dynamic_slice_in_dim(leaf, i, 1, ax),
            cache, engine.batch_axes)

    def prefill_fn(payload: Dict[str, Any]):
        prompts = np.asarray(payload["prompt"], np.int32)
        stacked = isinstance(payload["steps"], np.ndarray)
        n = prompts.shape[0]
        steps = np.broadcast_to(np.asarray(payload["steps"]), (n,))
        temps = np.broadcast_to(np.asarray(payload.get("temperature", 0.0)), (n,))
        seeds = np.broadcast_to(np.asarray(payload.get("seed", 0)), (n,))
        logits, cache = engine.prefill(prompts)
        logits.block_until_ready()    # waits for the prefill program
        with span("onepiece.handoff.pull", placement="device") as sp:
            if n == 1:
                rows = [[logits[0]] + jax.tree_util.tree_leaves(cache)]
            else:
                rows = []
                for i in range(n):
                    lg, c = row(logits, cache, np.int32(i))
                    rows.append([lg] + jax.tree_util.tree_leaves(c))
            if sp:
                sp.set_metadata(uids=profiling.batch_uids(),
                                bytes=sum(profiling.nbytes_arg(pages)
                                          for pages in rows),
                                state_bytes=sum(engine.state_nbytes(pages[1:])
                                                for pages in rows))
        out = []
        for i, pages in enumerate(rows):
            out.append(KVPages(
                meta={"prompt": prompts[i].tolist(),
                      "start": int(prompts.shape[1]),
                      "steps": int(steps[i]),
                      "temperature": float(temps[i]),
                      "seed": int(seeds[i])},
                pages=pages))
        return PerRequest(out) if stacked else out[0]

    return prefill_fn


class ContinuousDecoder:
    """The decode half: a continuous stage over a slot-based decode batch.

    ``__call__`` only parks the shipped KV pages (returning ``DEFERRED``);
    all real work happens in ``tick()``, on the instance scheduler thread:

      1. admit waiting requests into free slots (``engine.insert_slot`` —
         the KV pages reassemble into the cache tree via the batch-axes
         treedef, so flatten order is the wire order);
      2. run one ``engine.decode_segment`` of ``segment_len`` lockstep
         steps over the whole slot batch;
      3. harvest each slot's advanced rows, publish the growing prefix
         (token-boundary streaming), and return finished requests as
         ``[(uid, tokens [1, P+steps]), ...]``.

    ``abandon()`` releases every slot and reports the orphaned uids so the
    instance can tombstone them — a crash mid-decode accounts every
    absorbed request through the §9 ledger, never stranding a slot.
    """

    continuous = True

    def __init__(self, engine: ServingEngine, *, max_slots: int = 8,
                 segment_len: int = 8,
                 publish: Optional[Callable[[str, np.ndarray], None]] = None,
                 retract: Optional[Callable[[str], None]] = None):
        self.engine = engine
        self.max_slots = max_slots
        self.segment_len = segment_len
        self.publish = publish
        self.retract = retract
        self._treedef = jax.tree_util.tree_structure(engine.batch_axes)
        self._lock = make_lock("ContinuousDecoder._lock")
        # guarded_by: _lock -- slot state + queues below
        self._state = engine.init_slots(max_slots)
        self._waiting: deque = deque()          # (uid, KVPages)
        self._slots: Dict[int, Dict[str, Any]] = {}   # slot -> request entry
        self._free: List[int] = list(range(max_slots - 1, -1, -1))
        self.stats = {"admitted": 0, "completed": 0, "segments": 0,
                      "abandoned": 0, "max_resident": 0, "max_parked": 0}
        if engine.moe_layers:
            self.stats.update(expert_rows=0, expert_rows_absent=0)

    # ------------------------------------------------------------- protocol
    def __call__(self, payload: Any, *, uid: str):
        if not isinstance(payload, KVPages):
            raise TypeError(
                f"decode stage expects KVPages, got {type(payload).__name__}")
        with self._lock:
            self._waiting.append((uid, payload))
            self.stats["max_parked"] = max(self.stats["max_parked"],
                                           len(self._waiting))
        return DEFERRED

    def expert_rows_per_step(self) -> float:
        """Routed rows each held expert of a layer took per decode step,
        on average over the segments run (0 for a model without experts)."""
        layers = self.engine.moe_layers
        steps = self.stats["segments"] * self.segment_len
        if not layers or not steps:
            return 0.0
        return self.stats["expert_rows"] / (
            steps * layers * self.engine.cfg.num_experts)

    def pending(self) -> int:
        with self._lock:
            return len(self._waiting) + len(self._slots)

    def parked(self) -> int:
        """Shipments waiting for a slot, each holding a whole cache.  Read
        without the lock, which a tick holds for a whole decode segment:
        the prefill's gate asks from its own thread and must not wait for
        one (``len`` of a deque is atomic)."""
        return len(self._waiting)

    def tick(self) -> List[Tuple[str, Any]]:
        with span("onepiece.decode.tick", seq=self.stats["segments"]):
            return self._tick()

    def _tick(self) -> List[Tuple[str, Any]]:
        done: List[Tuple[str, np.ndarray]] = []
        partials: List[Tuple[str, np.ndarray]] = []
        prof = profiler()
        with self._lock:
            while self._free and self._waiting:
                uid, kv = self._waiting.popleft()
                slot = self._free.pop()
                cache1 = jax.tree_util.tree_unflatten(self._treedef, kv.pages[1:])
                with span("onepiece.decode.insert", uid=uid, slot=slot,
                          start=kv.meta["start"]) as sp:
                    if sp:
                        sp.set_metadata(bytes=profiling.nbytes_arg([kv]),
                                        state_bytes=self.engine.state_nbytes(
                                            kv.pages[1:]))
                    self._state = self.engine.insert_slot(
                        self._state, slot, cache1, kv.pages[0],
                        start=kv.meta["start"], seed=kv.meta["seed"],
                        steps=kv.meta["steps"],
                        temperature=kv.meta["temperature"])
                if prof.enabled:
                    prof.admit(uid)
                self._slots[slot] = {"uid": uid, "meta": kv.meta, "toks": []}
                self.stats["admitted"] += 1
            if not self._slots:
                return []
            self.stats["max_resident"] = max(self.stats["max_resident"],
                                             len(self._slots))
            with span("onepiece.decode.segment", seq=self.stats["segments"],
                      k=self.segment_len):
                self._state, toks, adv = self.engine.decode_segment(
                    self._state, self.segment_len)
            self.stats["segments"] += 1
            if "expert_rows" in self._state:
                held, absent = np.asarray(self._state["expert_rows"])
                self.stats["expert_rows"] += int(held)
                self.stats["expert_rows_absent"] += int(absent)
            for slot, ent in list(self._slots.items()):
                fresh = toks[adv[:, slot], slot]
                if fresh.size:
                    if not ent["toks"]:
                        profiling.mark("onepiece.decode.first_token",
                                       uid=ent["uid"])
                    ent["toks"].extend(int(t) for t in fresh)
                want = ent["meta"]["steps"]
                if len(ent["toks"]) >= want:
                    tokens = np.asarray(
                        [ent["meta"]["prompt"] + ent["toks"][:want]], np.int32)
                    done.append((ent["uid"], tokens))
                    self._state = self.engine.release_slot(self._state, slot)
                    del self._slots[slot]
                    self._free.append(slot)
                    self.stats["completed"] += 1
                else:
                    partials.append((ent["uid"], np.asarray(
                        [ent["meta"]["prompt"] + ent["toks"]], np.int32)))
        # Hooks run outside the lock: they hit the replicated database,
        # which takes its own locks per replica.
        if self.publish is not None:
            for uid, t in partials:
                self.publish(uid, t)
        if self.retract is not None:
            for uid, _ in done:
                self.retract(uid)
        return done

    def abandon(self) -> List[str]:
        with self._lock:
            uids = [e["uid"] for e in self._slots.values()]
            uids += [u for u, _ in self._waiting]
            for slot in list(self._slots):
                self._state = self.engine.release_slot(self._state, slot)
                self._free.append(slot)
            self._slots.clear()
            self._waiting.clear()
            self.stats["abandoned"] += len(uids)
        if self.retract is not None:
            for uid in uids:
                self.retract(uid)
        return uids


def build_llm_disagg_set(
    engine: ServingEngine,
    *,
    name: str = "llm",
    max_slots: int = 8,
    segment_len: int = 8,
    prefill_batch: int = 1,
    max_wait_s: float = 0.004,
    n_prefill: int = 1,
    n_decode: int = 1,
    inline: bool = True,
    control_loop: bool = False,
    prefill_time_s: float = 0.01,
    decode_time_s: float = 0.05,
) -> Tuple[WorkflowSet, "ContinuousDecoder"]:
    """Wire a two-stage llm_disagg Workflow Set around one engine.

    Both stages run the engine in this process, so each KV cache stays on
    the device from prefill to slot insert; a decode inbox entry is its
    meta alone.  What is on its way or parked waiting for a slot holds a
    whole cache in device memory, so it is bounded at ``in_flight``: a
    full slot batch plus one prefill batch per prefill instance.  Two
    ``StageSpec.room`` gates hold it there without a drop — the decode
    instance reads its inbox only while fewer than ``in_flight`` caches
    are parked, and a prefill instance takes a prompt only while parked
    and travelling caches leave room for one more batch; the excess waits
    as prompts in the prefill inbox.  The decode ring holds ``in_flight``
    entries (the prefill inbox keeps the instance default).  The decoder
    publishes per-segment partials to the set's replicated database and
    purges them on completion.  Returns ``(set, decoder)`` — the decoder
    is shared by every decode instance, so all of them feed one slot
    batch.
    """
    ws = WorkflowSet(name, control_loop=control_loop)
    db = ws.database

    def publish(uid: str, tokens: np.ndarray) -> None:
        db.store(f"partial/{uid}", tokens)

    def retract(uid: str) -> None:
        db.purge(f"partial/{uid}")

    decoder = ContinuousDecoder(engine, max_slots=max_slots,
                                segment_len=segment_len,
                                publish=publish, retract=retract)
    in_flight = max_slots + n_prefill * prefill_batch

    def decode_room() -> int:
        return in_flight - decoder.parked()

    def prefill_room() -> int:
        return max_slots + 1 - decoder.parked() - ws.joins.wire_pages()

    ws.register_workflow(WorkflowSpec(APP_LLM_DISAGG, "llm_disagg", [
        StageSpec("prefill", fn=make_prefill_fn(engine),
                  exec_time_s=prefill_time_s, deps=[], room=prefill_room),
        StageSpec("decode", fn=decoder, exec_time_s=decode_time_s,
                  deps=["prefill"], room=decode_room),
    ]))
    for i in range(n_prefill):
        ws.add_instance(f"prefill{i}", stage="prefill",
                        max_batch=prefill_batch, max_wait_s=max_wait_s,
                        pad_to_full=prefill_batch > 1, inline=inline)
    for i in range(n_decode):
        ws.add_instance(f"decode{i}", stage="decode", max_batch=1,
                        inline=inline, ring_slots=in_flight,
                        ring_bytes=in_flight * meta_wire_bytes(engine))
    ws.add_proxy("p0")
    return ws, decoder
