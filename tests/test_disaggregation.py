"""Disaggregated prefill/decode serving (docs/disaggregation.md):
KV-cache shipping over the fabric, continuous batching at token
boundaries, wire-ledger accounting under fault injection.

Run this file alone with ``scripts/check.sh --disagg``.
"""
from __future__ import annotations

import dataclasses
import random
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier

from repro.cluster import JoinTable
from repro.configs import get_config
from repro.core import SimulatedCrash
from repro.core.messaging import KVPages, WorkflowMessage
from repro.serving import (
    APP_LLM_DISAGG,
    ContinuousDecoder,
    ServingEngine,
    build_llm_disagg_set,
    make_prefill_fn,
)


def _wait_until(pred, timeout_s: float = 10.0, interval_s: float = 0.005):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval_s)
    return pred()


def _quiesce(ws, proxy, uids, timeout_s: float = 30.0):
    """Wait until every UID is stored or terminally accounted; returns
    {uid: tokens} for the stored ones (idiom of test_dag_workflows)."""
    results = {}
    snap = {"state": None, "since": time.monotonic()}

    def settled():
        for u in uids:
            if u not in results:
                v = proxy.poll_result(u)
                if v is not None:
                    results[u] = v
        if set(results) | ws.joins.dropped_uids >= set(uids):
            return True
        state = (len(results), frozenset(ws.joins.pending_uids()),
                 tuple(sorted((n, i.stats.processed, i.stats.dropped)
                              for n, i in ws.instances.items())))
        now = time.monotonic()
        if state != snap["state"]:
            snap["state"], snap["since"] = state, now
            return False
        return now - snap["since"] >= 1.0

    _wait_until(settled, timeout_s=timeout_s, interval_s=0.02)
    return results


FAMILIES = {"qwen3": "qwen3-1.7b", "rwkv6": "rwkv6-7b"}


def _engine(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    return ServingEngine(cfg, max_len=64)


@pytest.fixture(scope="module")
def engine():
    return _engine(FAMILIES["qwen3"])


@pytest.fixture(scope="module")
def engines(engine):
    """``family -> engine``: a KV family (qwen3) and a recurrent-state
    family (rwkv6)."""
    made = {"qwen3": engine}

    def get(family):
        if family not in made:
            made[family] = _engine(FAMILIES[family])
        return made[family]
    return get


def _assert_handoffs(ws):
    """Every KV handoff stayed on the device, and the wire ledger holds
    no pages: no device cache outlives its request."""
    stats = ws.transport_stats()
    assert stats.kv_device_handoffs == stats.kv_pages
    assert ws.joins.wire_pages() == 0


def _payload(engine, i, steps=8, temperature=0.7):
    rng = np.random.default_rng(i)
    prompt = rng.integers(0, engine.cfg.vocab_size, (1, 4)).astype(np.int32)
    return {"prompt": prompt, "steps": steps, "temperature": temperature,
            "seed": 100 + i}


def _solo(engine, payload):
    return engine.generate(payload["prompt"], steps=payload["steps"],
                           temperature=payload["temperature"],
                           seed=payload["seed"]).tokens


# ============================================================ happy path
@pytest.mark.parametrize("family", ["qwen3", "rwkv6"])
def test_disagg_end_to_end_matches_solo_generate(engines, family):
    """Two-stage prefill→decode over the fabric, three requests sharing
    the slot batch: every result is bit-identical to a solo generate, the
    KV caches handed over on the device."""
    engine = engines(family)
    ws, dec = build_llm_disagg_set(engine, name="e2e", max_slots=2,
                                   segment_len=3)
    payloads = [_payload(engine, i) for i in range(3)]
    with ws:
        p = ws.proxies[0]
        uids = [p.submit(APP_LLM_DISAGG, pl) for pl in payloads]
        res = [p.wait_result(u, timeout_s=60) for u in uids]
    for pl, r in zip(payloads, res):
        np.testing.assert_array_equal(r, _solo(engine, pl))
    assert dec.stats["completed"] == 3
    assert dec.stats["max_resident"] == 2   # continuous batching engaged
    assert ws.dead_uids() == set()
    # the KV ship was accounted as KV pages on the transport
    stats = ws.transport_stats()
    assert stats.kv_pages >= 3 and stats.kv_bytes > 0
    assert stats.kv_bytes == stats.kv_pages * engine.shipment_bytes
    _assert_handoffs(ws)


def test_device_handoff_slices_a_prefill_batch_on_the_device(engine):
    """A coalesced prefill batch of two hands each request its own B=1
    device slice: four requests, bit-identical to solo, none copied out."""
    ws, dec = build_llm_disagg_set(engine, name="batch2", max_slots=2,
                                   segment_len=3, prefill_batch=2,
                                   max_wait_s=0.2)
    payloads = [_payload(engine, i, temperature=0.0) for i in range(4)]
    with ws:
        p = ws.proxies[0]
        uids = p.submit_many(APP_LLM_DISAGG, payloads)
        res = [p.wait_result(u, timeout_s=60) for u in uids]
    for pl, r in zip(payloads, res):
        np.testing.assert_array_equal(r, _solo(engine, pl))
    stats = ws.transport_stats()
    assert stats.kv_pages == 4 and stats.kv_device_handoffs == 4
    assert stats.kv_bytes == 4 * engine.shipment_bytes
    assert ws.instances["batch2.prefill0"].stats.batches < 4  # coalesced
    assert ws.joins.wire_pages() == 0


def test_disagg_partial_streaming(engine):
    """poll_partial watches the token prefix grow at segment boundaries
    and goes quiet after completion purges the partial key."""
    ws, _ = build_llm_disagg_set(engine, name="part", max_slots=2,
                                 segment_len=2)
    pl = _payload(engine, 0, steps=12, temperature=0.0)
    with ws:
        p = ws.proxies[0]
        uid = p.submit(APP_LLM_DISAGG, pl)
        lens = []
        final = None
        deadline = time.monotonic() + 60
        while final is None and time.monotonic() < deadline:
            part = p.poll_partial(uid)
            if part is not None and (not lens or part.shape[1] > lens[-1]):
                lens.append(part.shape[1])
            final = p.poll_result(uid)
            time.sleep(0.001)
        assert final is not None
        assert lens, "no partial prefix observed"
        assert lens == sorted(lens)
        assert lens[-1] < final.shape[1]
        assert p.poll_partial(uid) is None  # purged on completion
    np.testing.assert_array_equal(final, _solo(engine, pl))


def test_decode_ring_holds_the_shipments_in_flight(engine):
    """The decode inbox is sized from what travels in it, a handoff's meta
    alone, for every cache that may be on its way or parked — so no
    configuration drops a KV handoff on ring capacity."""
    import jax

    from repro.serving.disagg import meta_wire_bytes

    logits, cache = engine.prefill(np.zeros((1, 4), np.int32))
    pages = [logits[0]] + jax.tree.leaves(cache)
    assert engine.shipment_bytes == sum(p.nbytes for p in pages)
    ws, _ = build_llm_disagg_set(engine, name="ring", max_slots=3,
                                 prefill_batch=2)
    inbox = ws.instances["ring.decode0"].inbox
    msg = WorkflowMessage.new(APP_LLM_DISAGG, payload=KVPages(
        meta={"prompt": list(range(engine.max_len)), "start": 4,
              "steps": 8, "temperature": 0.0, "seed": 0}, pages=pages))
    entry = sum(len(p) for p in msg.pack_parts())
    assert inbox.n_slots == 3 + 2
    assert inbox.buf_size >= (3 + 2) * entry
    assert entry <= meta_wire_bytes(engine) < engine.shipment_bytes


def test_decode_tick_error_fails_the_run(engine, monkeypatch):
    """A decode segment that raises abandons its requests under §9 and
    surfaces as a stage error with its traceback in the run's failures."""
    from repro.launch.serve import run_failures, wait_results

    ws, dec = build_llm_disagg_set(engine, name="tickerr", max_slots=2,
                                   segment_len=2)

    def broken(state, k):
        raise RuntimeError("decode segment refused")

    monkeypatch.setattr(dec.engine, "decode_segment", broken)
    with ws:
        p = ws.proxies[0]
        uid = p.submit(APP_LLM_DISAGG, _payload(engine, 0))
        results, timed_out = wait_results(ws, p, [uid], 30.0)
    assert results == {} and timed_out == 0
    stats = ws.transport_stats()
    assert stats.stage_errors >= 1
    assert "decode segment refused" in stats.first_error
    fails = run_failures(ws)
    assert any("decode segment refused" in f for f in fails)
    assert any("dead" in f for f in fails)


# ==================================================== fault injection
def _entry_write(ws, name):
    """Fault-hook predicate: the write of an entry's bytes (not a ring
    header or slot word) into ``name``'s inbox — there, the KV handoff's
    entry."""
    inbox = ws.instances[name].inbox

    def hits(verb, region, offset):
        return (verb == "write" and region == inbox.region
                and offset >= inbox.buf_off)
    return hits


def test_kv_ship_dropped_mid_writev_is_accounted(engine):
    """The decode-bound KV entry's writev is lost on the wire: the consumer
    sees only a corrupt ring entry, yet the wire ledger keeps the victim
    in dead_uids() — submitted == stored ∪ dead, no decode slot stranded,
    and the victim's device pages freed when the set stops."""
    ws, dec = build_llm_disagg_set(engine, name="wire", max_slots=2,
                                   segment_len=3)
    state = {"armed": False, "dropped": 0}
    kv_entry = _entry_write(ws, "wire.decode0")

    def hook(client, verb, region, offset, n):
        if state["armed"] and kv_entry(verb, region, offset):
            state["armed"] = False
            state["dropped"] += 1
            return False
        return True

    ws.fabric.fault_hook = hook
    with ws:
        p = ws.proxies[0]
        good1 = [_payload(engine, i) for i in range(2)]
        u1 = [p.submit(APP_LLM_DISAGG, pl) for pl in good1]
        for pl, u in zip(good1, u1):
            np.testing.assert_array_equal(p.wait_result(u, timeout_s=60),
                                          _solo(engine, pl))
        state["armed"] = True
        victim = p.submit(APP_LLM_DISAGG, _payload(engine, 7))
        _wait_until(lambda: state["dropped"] == 1)
        good2 = [_payload(engine, i) for i in range(3, 5)]
        u2 = [p.submit(APP_LLM_DISAGG, pl) for pl in good2]
        results = _quiesce(ws, p, u2 + [victim])
    assert state["dropped"] == 1
    assert victim not in results            # never delivered
    assert victim in ws.dead_uids()         # ...but fully accounted
    for pl, u in zip(good2, u2):            # traffic kept flowing
        np.testing.assert_array_equal(results[u], _solo(engine, pl))
    # the wire loss surfaced as a corrupt entry at the decode consumer
    assert sum(b.stats.corrupt for b in ws.buffers.values()) == 1
    # and never occupied (or stranded) a decode slot
    assert dec.pending() == 0
    assert dec.stats["admitted"] == 4
    _assert_handoffs(ws)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_kv_ship_killed_by_simulated_crash_is_accounted(engine):
    """The prefill worker dies mid-writev (SimulatedCrash while appending
    the KV entry): its tracked shipment never settles, so the victim is
    reconciled dead; no decode slot is stranded and no device page kept."""
    ws, dec = build_llm_disagg_set(engine, name="crash", max_slots=2,
                                   segment_len=3, inline=False)
    state = {"armed": False, "fired": 0}
    kv_entry = _entry_write(ws, "crash.decode0")

    def hook(client, verb, region, offset, n):
        if state["armed"] and kv_entry(verb, region, offset):
            state["armed"] = False
            state["fired"] += 1
            raise SimulatedCrash("prefill sender died mid KV writev")
        return True

    ws.fabric.fault_hook = hook
    with ws:
        p = ws.proxies[0]
        pl0 = _payload(engine, 0)
        u0 = p.submit(APP_LLM_DISAGG, pl0)
        np.testing.assert_array_equal(p.wait_result(u0, timeout_s=60),
                                      _solo(engine, pl0))
        state["armed"] = True
        victim = p.submit(APP_LLM_DISAGG, _payload(engine, 9))
        _wait_until(lambda: state["fired"] == 1)
        results = _quiesce(ws, p, [victim], timeout_s=5.0)
    assert state["fired"] == 1
    assert victim not in results
    assert victim in ws.dead_uids()
    assert dec.pending() == 0               # nothing stranded in a slot
    assert dec.stats["admitted"] == 1       # only the pre-crash request
    assert ws.joins.wire_pages() == 0       # the crashed append's pages


def test_drain_abandons_parked_decode_requests(engine):
    """Stopping the set while requests sit in decode slots tombstones
    them through fn.abandon() — parked work is dropped with accounting,
    never silently stranded (§9), and its device pages are freed."""
    ws, dec = build_llm_disagg_set(engine, name="drain", max_slots=2,
                                   segment_len=2)
    pls = [_payload(engine, i, steps=200 + i) for i in range(3)]
    with ws:
        p = ws.proxies[0]
        uids = [p.submit(APP_LLM_DISAGG, pl) for pl in pls]
        _wait_until(lambda: dec.stats["admitted"] >= 2)
        # leave the context: stop() drains terminal state mid-decode
    assert dec.pending() == 0
    dead = ws.dead_uids()
    assert set(uids) <= dead
    assert dec.stats["abandoned"] >= 2
    _assert_handoffs(ws)


def test_parked_shipments_are_bounded(engine):
    """Requests waiting for a slot each hold a whole cache on the device:
    a burst well past the bound (one slot plus one prefill batch) parks
    at most that many, holds the rest upstream as prompts, and — at the
    default retry budget — every request still completes, none dropped."""
    ws, dec = build_llm_disagg_set(engine, name="park", max_slots=1,
                                   segment_len=2)
    bound = 1 + 1
    pls = [_payload(engine, i, steps=24, temperature=0.0) for i in range(8)]
    with ws:
        p = ws.proxies[0]
        uids = [p.submit(APP_LLM_DISAGG, pl) for pl in pls]
        held = []
        deadline = time.monotonic() + 60
        while dec.stats["completed"] < len(pls) and \
                time.monotonic() < deadline:
            held.append(dec.parked() + ws.joins.wire_pages())
            time.sleep(0.002)
        res = [p.wait_result(u, timeout_s=60) for u in uids]
    for pl, r in zip(pls, res):
        np.testing.assert_array_equal(r, _solo(engine, pl))
    assert dec.stats["max_parked"] == bound     # reached, never passed
    assert dec.stats["max_resident"] == 1
    # parked caches plus those on their way: the bound, and at most the
    # one handed over between the decode's unpack and its park
    assert max(held) <= bound + 1
    stats = ws.transport_stats()
    assert stats.dropped == 0
    assert ws.dead_uids() == set()
    _assert_handoffs(ws)


def test_prefill_is_not_held_behind_a_decode_segment(engine, monkeypatch):
    """The prefill's gate reads the decoder while a tick holds its lock
    for a whole segment: a request that arrives mid-segment is prefilled
    at once, not after the segment."""
    import threading

    ws, dec = build_llm_disagg_set(engine, name="overlap", max_slots=2,
                                   segment_len=2)
    segment = dec.engine.decode_segment
    running = threading.Event()

    def slow(state, k):
        running.set()
        time.sleep(2.0)
        return segment(state, k)

    pls = [_payload(engine, i, steps=4, temperature=0.0) for i in range(2)]
    with ws:
        p = ws.proxies[0]
        u0 = p.submit(APP_LLM_DISAGG, pls[0])
        np.testing.assert_array_equal(p.wait_result(u0, timeout_s=60),
                                      _solo(engine, pls[0]))  # compiled
        monkeypatch.setattr(dec.engine, "decode_segment", slow)
        uids = [p.submit(APP_LLM_DISAGG, pls[0])]
        assert running.wait(timeout=30)
        uids.append(p.submit(APP_LLM_DISAGG, pls[1]))
        prefill = ws.instances["overlap.prefill0"]
        assert _wait_until(lambda: prefill.stats.processed == 3,
                           timeout_s=1.5)
        res = [p.wait_result(u, timeout_s=60) for u in uids]
    for pl, r in zip(pls, res):
        np.testing.assert_array_equal(r, _solo(engine, pl))


def test_wire_ledger_ttl_expiry_tombstones():
    """A tracked shipment that never settles is tombstoned (not merely
    forgotten) by the TTL sweep."""
    t = {"now": 0.0}
    jt = JoinTable(None, ttl_s=5.0, clock=lambda: t["now"])
    jt.track_wire("u1")
    assert "u1" in jt.pending_uids()
    t["now"] = 10.0
    jt.mark_dropped("other")  # any locked entry point runs the sweep
    assert "u1" in jt.dropped_uids
    assert jt.stats.expired_shipments == 1
    assert jt.wire_pending() == 0


def test_wire_ledger_holds_a_device_handoff_until_settled_or_dead():
    """The wire ledger's ends of a handoff: the receiver's settle takes
    its pages, a tombstone drops them at once, an expiry at the TTL sweep,
    and a stopping set's release drops what is left unsettled."""
    import jax.numpy as jnp

    t = {"now": 0.0}
    jt = JoinTable(None, ttl_s=5.0, clock=lambda: t["now"])
    pages = {u: [jnp.full(4, i)] for i, u in
             enumerate(("ok", "dead", "lost", "stopped"))}
    for uid in ("ok", "dead", "lost"):
        jt.track_wire(uid, pages[uid])
    assert jt.wire_pages() == 3
    assert jt.settle_wire("ok") is pages["ok"]
    assert jt.settle_wire("ok") is None          # taken once
    jt.mark_dropped("dead")
    jt.track_wire("dead", pages["dead"])          # a dead uid tracks nothing
    assert jt.wire_pages() == 1
    t["now"] = 10.0
    jt.mark_dropped("other")  # runs the sweep
    assert "lost" in jt.dropped_uids and jt.wire_pages() == 0
    assert jt.settle_wire("lost") is None
    jt.track_wire("stopped", pages["stopped"])
    jt.release_wire_pages()
    assert jt.wire_pages() == 0 and jt.wire_pending() == 1


def test_device_handoff_dropped_on_a_full_ring_frees_its_pages(engine):
    """An append that finds the decode ring full through its retries drops
    the handoff (§9): the uid is tombstoned and the ledger lets go of its
    pages."""
    import jax.numpy as jnp

    from repro.core.ring_buffer import RingProducer

    ws, _ = build_llm_disagg_set(engine, name="full", max_slots=1)
    # the set is not started, so nothing reads the decode inbox: fill it
    filler = RingProducer(ws.instances["full.decode0"].inbox, 1)
    while filler.append(b"x"):
        pass
    msg = WorkflowMessage.new(APP_LLM_DISAGG, payload=KVPages(
        meta={"start": 1}, pages=[jnp.ones(3), jnp.zeros((1, 2))]))
    rd = ws.instances["full.prefill0"].rd
    assert rd.deliver_many([msg], "prefill") == 0
    assert msg.uid_hex in ws.joins.dropped_uids
    assert ws.joins.wire_pages() == 0
    stats = ws.transport_stats()
    assert stats.dropped == 1 and stats.kv_device_handoffs == 0


def test_reassignment_hands_a_device_handoff_to_a_peer(engine):
    """A decode instance reassigned with a handoff still in its inbox
    passes it to a peer decode instance: the pages go back into the wire
    ledger with the re-sent entry, and the peer takes the same arrays."""
    import jax.numpy as jnp

    from repro.core.batching import Coalescer

    ws, _ = build_llm_disagg_set(engine, name="move", max_slots=1,
                                 n_decode=2)
    pages = [jnp.ones(3), jnp.zeros((1, 2))]
    msg = WorkflowMessage.new(APP_LLM_DISAGG, payload=KVPages(
        meta={"start": 1}, pages=pages))
    uid = msg.uid_hex
    assert ws.instances["move.prefill0"].rd.deliver_many([msg], "prefill")
    src, dst = (ws.instances[f"move.decode{i}"] for i in range(2))
    if dst.inbox.ready():                     # round robin picked decode1
        src, dst = dst, src
    ws.nm.assign(src.name, "prefill")
    src._poll_assignment()
    src._apply_reassignment(Coalescer(max_batch=1, max_wait_s=0.01))
    assert src.stats.handoffs == 1
    assert ws.joins.wire_pages() == 1         # travelling again
    got = dst._unpack(dst.inbox.poll())
    assert got.uid_hex == uid
    assert all(a is b for a, b in zip(got.payload.pages, pages))
    assert ws.joins.wire_pending() == 0


def test_device_kv_entry_carries_the_meta_alone():
    """A device handoff's ring entry is its meta: it decodes with no
    pages, for the receiver to take from the wire ledger."""
    import jax.numpy as jnp

    pages = [jnp.arange(4.0), jnp.ones((2, 1, 3))]
    meta = {"start": 4, "steps": 2, "seed": 0, "temperature": 0.0,
            "prompt": [1, 2, 3, 4]}
    kv = KVPages(meta=meta, pages=pages)
    assert kv.on_device and kv.nbytes == 4 * 4 + 6 * 4
    wire = WorkflowMessage.new(app_id=1, payload=kv).pack()
    assert len(wire) < 256                    # no page bytes on the ring
    out = WorkflowMessage.unpack(wire).payload
    assert out.meta == meta and out.pages is None


def test_kv_pages_roundtrip_zero_copy():
    """KVPages ride one gather list and decode to views, not copies."""
    pages = [np.arange(16, dtype=np.float32),
             np.ones((2, 1, 3, 4), np.float32)]
    msg = WorkflowMessage.new(app_id=1, payload=KVPages(
        meta={"start": 4, "steps": 2, "seed": 0, "temperature": 0.0,
              "prompt": [1, 2, 3, 4]}, pages=pages))
    parts = msg.pack_parts()
    assert len(parts) >= 2 + 2 * len(pages)   # header+meta+len/page pairs
    out = WorkflowMessage.unpack(msg.pack()).payload
    assert isinstance(out, KVPages)
    assert out.meta["steps"] == 2
    for a, b in zip(pages, out.pages):
        np.testing.assert_array_equal(a, b)
        assert b.base is not None             # view over the wire buffer


# ================================================ continuous batching
@pytest.mark.parametrize("family", ["qwen3", "rwkv6"])
def test_continuous_batching_random_join_leave_property(engines, family):
    """Property: any random join/leave schedule over the slot batch
    produces, per request, exactly the solo run's tokens.  Requests with
    different lengths/seeds/temperatures enter whenever a slot frees,
    their caches handed over on the device."""
    engine = engines(family)
    rng = random.Random(0)
    dec = ContinuousDecoder(engine, max_slots=3, segment_len=2)
    prefill = make_prefill_fn(engine)
    reqs = []
    for i in range(8):
        pl = _payload(engine, i, steps=rng.randint(3, 12),
                      temperature=rng.choice([0.0, 0.7, 1.3]))
        reqs.append(pl)
    expected = {f"u{i}": _solo(engine, pl) for i, pl in enumerate(reqs)}
    ships = {f"u{i}": prefill(pl) for i, pl in enumerate(reqs)}
    assert all(kv.on_device for kv in ships.values())

    pending = list(enumerate(reqs))
    rng.shuffle(pending)
    got = {}
    while len(got) < len(reqs):
        # random admission trickle: sometimes offer 0, 1, or 2 requests
        for _ in range(rng.randint(0, 2)):
            if pending:
                i, pl = pending.pop()
                dec(ships.pop(f"u{i}"), uid=f"u{i}")
        for uid, toks in dec.tick():
            got[uid] = toks
        if not pending and dec.pending() == 0 and len(got) < len(reqs):
            raise AssertionError("decoder went idle with requests missing")
    for uid, toks in got.items():
        np.testing.assert_array_equal(toks, expected[uid])
    assert dec.stats["max_resident"] <= 3
