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


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    return ServingEngine(cfg, max_len=64)


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
def test_disagg_end_to_end_matches_solo_generate(engine):
    """Two-stage prefill→decode over the fabric, three requests sharing
    the slot batch: every result is bit-identical to a solo generate."""
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
    """The decode inbox is sized from the engine's per-request shipment,
    so no configuration drops every KV ship on ring capacity."""
    import jax

    from repro.serving.disagg import shipment_wire_bytes

    logits, cache = engine.prefill(np.zeros((1, 4), np.int32))
    pages = [np.asarray(logits)[0]] + [np.asarray(x)
                                       for x in jax.tree.leaves(cache)]
    assert engine.shipment_bytes == sum(p.nbytes for p in pages)
    ws, _ = build_llm_disagg_set(engine, name="ring", max_slots=3,
                                 prefill_batch=2)
    ring = ws.instances["ring.decode0"].inbox.buf_size
    assert ring >= (3 + 2) * engine.shipment_bytes
    msg = WorkflowMessage.new(APP_LLM_DISAGG, payload=KVPages(
        meta={"prompt": list(range(engine.max_len)), "start": 4,
              "steps": 8, "temperature": 0.0, "seed": 0}, pages=pages))
    assert sum(len(p) for p in msg.pack_parts()) <= shipment_wire_bytes(engine)


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
def test_kv_ship_dropped_mid_writev_is_accounted(engine):
    """The decode-bound KV-page writev is lost on the wire: the consumer
    sees only a corrupt ring entry, yet the wire ledger keeps the victim
    in dead_uids() — submitted == stored ∪ dead, no decode slot stranded."""
    ws, dec = build_llm_disagg_set(engine, name="wire", max_slots=2,
                                   segment_len=3)
    state = {"armed": False, "dropped": 0}

    def hook(client, verb, region, offset, n):
        if (state["armed"] and verb == "write" and n > 4096
                and region == "wire.decode0.inbox"):
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


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_kv_ship_killed_by_simulated_crash_is_accounted(engine):
    """The prefill worker dies mid-writev (SimulatedCrash while appending
    KV pages): its tracked shipment never settles, so the victim is
    reconciled dead; no decode slot is stranded."""
    ws, dec = build_llm_disagg_set(engine, name="crash", max_slots=2,
                                   segment_len=3, inline=False)
    state = {"armed": False, "fired": 0}

    def hook(client, verb, region, offset, n):
        if (state["armed"] and verb == "write" and n > 4096
                and region == "crash.decode0.inbox"):
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


def test_drain_abandons_parked_decode_requests(engine):
    """Stopping the set while requests sit in decode slots tombstones
    them through fn.abandon() — parked work is dropped with accounting,
    never silently stranded (§9)."""
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
def test_continuous_batching_random_join_leave_property(engine):
    """Property: any random join/leave schedule over the slot batch
    produces, per request, exactly the solo run's tokens.  Requests with
    different lengths/seeds/temperatures enter whenever a slot frees."""
    rng = random.Random(0)
    dec = ContinuousDecoder(engine, max_slots=3, segment_len=2)
    reqs = []
    for i in range(8):
        pl = _payload(engine, i, steps=rng.randint(3, 12),
                      temperature=rng.choice([0.0, 0.7, 1.3]))
        reqs.append(pl)
    expected = {f"u{i}": _solo(engine, pl) for i, pl in enumerate(reqs)}

    logits_cache = {}
    for i, pl in enumerate(reqs):
        logits, cache = engine.prefill(pl["prompt"])
        logits_cache[f"u{i}"] = (np.asarray(logits), cache)

    import jax

    def ship(uid, pl):
        logits, cache = logits_cache[uid]
        leaves = jax.tree_util.tree_leaves(cache)
        axes = jax.tree_util.tree_leaves(engine.batch_axes)
        pages = [logits[0]] + [np.take(np.asarray(leaf), [0], axis=int(ax))
                               for leaf, ax in zip(leaves, axes)]
        return KVPages(meta={"prompt": pl["prompt"][0].tolist(),
                             "start": pl["prompt"].shape[1],
                             "steps": pl["steps"],
                             "temperature": pl["temperature"],
                             "seed": pl["seed"]}, pages=pages)

    pending = list(enumerate(reqs))
    rng.shuffle(pending)
    got = {}
    while len(got) < len(reqs):
        # random admission trickle: sometimes offer 0, 1, or 2 requests
        for _ in range(rng.randint(0, 2)):
            if pending:
                i, pl = pending.pop()
                dec(ship(f"u{i}", pl), uid=f"u{i}")
        for uid, toks in dec.tick():
            got[uid] = toks
        if not pending and dec.pending() == 0 and len(got) < len(reqs):
            raise AssertionError("decoder went idle with requests missing")
    for uid, toks in got.items():
        np.testing.assert_array_equal(toks, expected[uid])
    assert dec.stats["max_resident"] <= 3
