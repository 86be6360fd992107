"""Event-driven request path + per-request latency profiler (docs/perf.md).

Covers the PR-7 hot-path overhaul: doorbell-notify wakeups (a producer's
append wakes the target scheduler instead of it sleep-polling), the
adaptive partial-bucket flush, the per-(uid, stage) span profiler, and
byte-parity between the event-driven and classic polling schedulers.
The §6.1 protocol checker runs over a notify-enabled ring to confirm the
doorbell adds no ring-protocol event.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro.analysis.ring_checker import RingProtocolChecker
from repro.cluster import StageSpec, WorkflowSet, WorkflowSpec
from repro.core import DoubleRingBuffer, RdmaFabric, RingProducer
from repro.core.batching import Coalescer
from repro.core.profiling import EVENTS, PHASES, LatencyProfiler, profiler
from repro.core.streaming import DEFERRED

APP = 1


def _simple_ws(name, fns, *, event_driven=True, **inst_kw):
    ws = WorkflowSet(name, control_loop=False)
    stages = [StageSpec(s, fn=f, exec_time_s=1e-3) for s, f in fns]
    ws.register_workflow(WorkflowSpec(APP, name, stages))
    for s, _ in fns:
        ws.add_instance(f"{s}_0", stage=s, event_driven=event_driven,
                        **inst_kw)
    return ws, ws.add_proxy("p0")


# ------------------------------------------------------------ doorbell wakeup
def test_doorbell_append_wakes_idle_scheduler_fast():
    """With a poll interval far above any acceptable latency, an idle
    event-driven scheduler must still pick up a fresh append immediately:
    the producer's doorbell (fired strictly after the ring lock release)
    is what wakes it, not the poll timer."""
    fns = [("mul", lambda p: {"x": np.asarray(p["x"]) * 2.0}),
           ("store", lambda p: np.asarray(p["x"]) + 1.0)]
    ws, proxy = _simple_ws("wake", fns, poll_interval_s=0.5)
    for inst in ws.instances.values():
        assert inst.inbox.notify_hook is not None
    with ws:
        for i in range(3):
            time.sleep(0.01)  # let the schedulers go idle between requests
            t0 = time.monotonic()
            uid = proxy.submit(APP, {"x": np.float32(i)})
            out = proxy.wait_result(uid, timeout_s=5)
            lat = time.monotonic() - t0
            assert out == np.float32(i) * 2.0 + 1.0
            # two hops + store: with 0.5 s sleep-polling this would take
            # >= ~1 s; the doorbell path must land well under one poll
            assert lat < 0.25, f"wakeup latency {lat:.3f}s (req {i})"


def test_polling_mode_has_no_notify_hook():
    fns = [("id", lambda p: p)]
    ws, _ = _simple_ws("nopoll", fns, event_driven=False)
    for inst in ws.instances.values():
        assert inst.inbox.notify_hook is None


# ----------------------------------------------------- event vs polling parity
def _run_chain(name, *, event_driven):
    def enc(p):
        return {"x": np.asarray(p["x"], np.float32) * 3.0}

    def dec(p):
        return np.asarray(p["x"]) - 1.0

    ws, proxy = _simple_ws(name, [("enc", enc), ("dec", dec)],
                           event_driven=event_driven)
    reqs = [{"x": np.full((1, 4), float(i), np.float32)} for i in range(6)]
    with ws:
        uids = [proxy.submit(APP, r) for r in reqs]
        outs = [proxy.wait_result(u, timeout_s=10) for u in uids]
    return [np.asarray(o).tobytes() for o in outs]


def test_event_driven_chain_bit_identical_to_polling():
    assert _run_chain("evt", event_driven=True) == \
        _run_chain("poll", event_driven=False)


def test_inline_execution_bit_identical_to_worker_thread():
    """Opt-in inline mode (stage fn on the scheduler thread) is a pure
    scheduling change too."""
    def enc(p):
        return {"x": np.asarray(p["x"], np.float32) * 3.0}

    def dec(p):
        return np.asarray(p["x"]) - 1.0

    ws, proxy = _simple_ws("inl", [("enc", enc), ("dec", dec)], inline=True)
    for inst in ws.instances.values():
        assert inst._inline
    reqs = [{"x": np.full((1, 4), float(i), np.float32)} for i in range(6)]
    with ws:
        uids = [proxy.submit(APP, r) for r in reqs]
        outs = [proxy.wait_result(u, timeout_s=10) for u in uids]
    assert [np.asarray(o).tobytes() for o in outs] == \
        _run_chain("inlref", event_driven=False)


# --------------------------------------------------- ring checker over notify
def test_notify_enabled_ring_passes_protocol_checker():
    """The doorbell is NOT a §6.1 protocol action: a notify-enabled ring
    driven through singles, batches and polls must produce exactly the
    same (clean) event stream the checker validated before the hook
    existed — and the hook must actually fire, once per append and once
    per append_many batch."""
    fab = RdmaFabric()
    rb = DoubleRingBuffer(fab, "nring", n_slots=32, buf_size=2048)
    rb.checker = RingProtocolChecker("nring")
    rings = []
    rb.set_notify(lambda: rings.append(1))
    p = RingProducer(rb, 1)
    for i in range(5):
        assert p.append(bytes([i]) * 10)
    assert len(rings) == 5
    assert p.append_many([b"a" * 8, b"b" * 8, b"c" * 8]) == 3
    assert len(rings) == 6  # one doorbell for the whole batch
    got = []
    while True:
        item = rb.poll()
        if item is None:
            break
        got.append(item)
    assert len(got) == 8
    rb.checker.assert_clean()


# ----------------------------------------------------------- adaptive flush
def test_pop_idle_flushes_after_grace():
    t = [0.0]
    c = Coalescer(max_batch=8, max_wait_s=10.0, clock=lambda: t[0])
    c.add("k", "a")
    c.add("k", "b")
    # first sighting: marked, not flushed; next_deadline = now + grace
    flushed, due = c.pop_idle(0.005)
    assert flushed == [] and due == pytest.approx(0.005)
    # growth resets the grace window
    c.add("k", "c")
    flushed, due = c.pop_idle(0.005)
    assert flushed == []
    t[0] = 0.004
    flushed, _ = c.pop_idle(0.005)
    assert flushed == []  # grace not elapsed since the re-mark
    t[0] = 0.02
    flushed, due = c.pop_idle(0.005)
    assert flushed == [("k", ["a", "b", "c"])] and due is None
    assert len(c) == 0


def test_pop_expired_clears_idle_marks():
    t = [0.0]
    c = Coalescer(max_batch=8, max_wait_s=0.01, clock=lambda: t[0])
    c.add("k", "a")
    c.pop_idle(1.0)  # mark with a huge grace
    t[0] = 0.02
    assert c.pop_expired() == [("k", ["a"])]  # deadline still wins
    assert c._idle_marks == {}


def test_adaptive_flush_batched_not_slower_than_unbatched():
    """The BENCH_PR5 regression: a trailing partial bucket used to wait
    out max_wait_s.  With the idle flush, a batched set on a sleep-stage
    workload must beat (or at worst match) the unbatched one even when
    the bucket never fills and max_wait_s is pathological."""
    d = 0.02

    def sleeper(p):
        time.sleep(d)  # one nap per *invocation* — batching amortizes it
        return p

    def run(name, max_batch):
        ws, proxy = _simple_ws(name, [("nap", sleeper)],
                               max_batch=max_batch, max_wait_s=0.5)
        reqs = [{"x": np.full((1, 2), float(i), np.float32)}
                for i in range(6)]
        t0 = time.perf_counter()
        with ws:
            uids = proxy.submit_many(APP, reqs)
            for u in uids:
                proxy.wait_result(u, timeout_s=10)
        return time.perf_counter() - t0

    unbatched = run("nap1", 1)     # 6 sequential naps ≈ 6d
    batched = run("nap8", 8)       # never fills: idle flush ≈ 1 nap + grace
    assert batched <= unbatched, \
        f"batched {batched:.3f}s slower than unbatched {unbatched:.3f}s"


# ---------------------------------------------------------------- profiler
def test_profiler_span_folding_and_percentiles():
    prof = LatencyProfiler()
    prof.enable()
    t = 100.0
    for i, ev in enumerate(EVENTS):
        prof.stamp("u1", 0, ev, label="enc", t=t + i * 0.001)
    assert prof.folded == 1 and prof.open_spans() == 0
    snap = prof.snapshot()
    assert set(snap) == {"enc"}
    for name, _a, _b in PHASES:
        assert snap["enc"][name]["p50_us"] == pytest.approx(1000.0, rel=0.01)
        assert snap["enc"][name]["n"] == 1.0
    line = prof.timeline_compact()
    assert line.startswith("enc[") and "stage_fn=" in line


def test_profiler_first_stamp_wins_and_disabled_is_noop():
    prof = LatencyProfiler()
    prof.stamp("u", 0, "enqueue")  # disabled: must not open a span
    assert prof.open_spans() == 0
    prof.enable()
    prof.stamp("u", 0, "enqueue", t=1.0)
    prof.stamp("u", 0, "enqueue", t=5.0)  # duplicate (fan-out edge): ignored
    for ev in EVENTS[1:]:
        prof.stamp("u", 0, ev, label="s", t=2.0)
    ring = prof.snapshot()["s"]["ring"]
    assert ring["p50_us"] == pytest.approx(1e6)  # 2.0 - 1.0, not 2.0 - 5.0


def test_profiler_surfaces_in_transport_stats():
    fns = [("sq", lambda p: {"x": np.asarray(p["x"]) ** 2}),
           ("fin", lambda p: np.asarray(p["x"]))]
    ws, proxy = _simple_ws("profstats", fns)
    prof = profiler()
    prof.reset()
    prof.enable()
    try:
        with ws:
            uids = [proxy.submit(APP, {"x": np.float32(i)})
                    for i in range(4)]
            for u in uids:
                proxy.wait_result(u, timeout_s=10)
        stats = ws.transport_stats()
    finally:
        prof.disable()
        prof.reset()
    assert set(stats.latency) == {"sq", "fin"}
    for phases in stats.latency.values():
        assert "stage_fn" in phases and "ring" in phases
        assert phases["stage_fn"]["n"] >= 4


class _OneSlot:
    """A continuous stage with one slot, in which a request stays for
    three ticks of ``TICK_S``: the second of two requests waits for it."""

    continuous = True
    TICK_S = 0.05

    def __init__(self):
        self.lock = threading.Lock()
        self.waiting = []
        self.held = None      # [uid, ticks left]

    def __call__(self, payload, *, uid):
        with self.lock:
            self.waiting.append(uid)
        return DEFERRED

    def pending(self):
        with self.lock:
            return len(self.waiting) + (self.held is not None)

    def tick(self):
        with self.lock:
            if self.held is None and self.waiting:
                self.held = [self.waiting.pop(0), 3]
                profiler().admit(self.held[0])
            if self.held is None:
                return []
        time.sleep(self.TICK_S)
        with self.lock:
            self.held[1] -= 1
            if self.held[1]:
                return []
            uid, self.held = self.held[0], None
        return [(uid, np.float32(1.0))]

    def abandon(self):
        with self.lock:
            uids = self.waiting + ([self.held[0]] if self.held else [])
            self.waiting, self.held = [], None
        return uids


def test_profiler_phases_of_a_continuous_stage():
    """For a stage that parks requests and finishes them ticks later,
    ``sched`` is the wait for a slot, ``stage_fn`` the residence from
    admission to the tick that finished it, ``deliver`` the delivery."""
    ws, proxy = _simple_ws("contprof", [("slot", _OneSlot())])
    prof = profiler()
    prof.reset()
    prof.enable()
    try:
        with ws:
            uids = [proxy.submit(APP, {"x": np.float32(i)}) for i in range(2)]
            for u in uids:
                proxy.wait_result(u, timeout_s=10)
        phases = ws.transport_stats().latency["slot"]
    finally:
        prof.disable()
        prof.reset()
    residence = 3 * _OneSlot.TICK_S
    assert phases["stage_fn"]["n"] == 2
    assert phases["stage_fn"]["max_us"] < 3 * residence * 1e6
    assert phases["stage_fn"]["p50_us"] >= 0.9 * residence * 1e6
    # the second request waited out the first one's residence
    assert phases["sched"]["max_us"] >= 0.9 * residence * 1e6
    assert phases["deliver"]["max_us"] < phases["stage_fn"]["p50_us"] / 2


# ------------------------------------------------- Wan I2V parity (slow tier)
@pytest.mark.slow
def test_wan_chain_event_driven_parity():
    """Bit-parity on the real pipeline: the event-driven path must be a
    pure scheduling change — byte-identical frames to the polling path."""
    from repro.models.aigc import WanI2VPipeline, build_stage_fns

    pipe = WanI2VPipeline(seed=0)
    fns = build_stage_fns(pipe)
    stages = ("text_encode", "vae_encode", "diffusion", "vae_decode")

    def run(name, event_driven):
        ws = WorkflowSet(name, control_loop=False)
        ws.register_workflow(WorkflowSpec(APP, name, [
            StageSpec(s, fn=fns[s], exec_time_s=0.01) for s in stages
        ]))
        for s in stages:
            ws.add_instance(f"{s}_0", stage=s, event_driven=event_driven)
        proxy = ws.add_proxy("p0")
        reqs = []
        for i in range(2):
            rng = np.random.default_rng(i)
            cfg = pipe.cfg
            reqs.append({
                "tokens": rng.integers(0, cfg.text_vocab,
                                       (1, cfg.text_len)).astype(np.int32),
                "image": (rng.standard_normal(
                    (1, cfg.image_size, cfg.image_size, 3))
                    * 0.1).astype(np.float32),
                "seed": i,
            })
        with ws:
            uids = [proxy.submit(APP, r) for r in reqs]
            outs = [proxy.wait_result(u, timeout_s=120) for u in uids]
        return [np.asarray(o).tobytes() for o in outs]

    assert run("wanevt", True) == run("wanpoll", False)


@pytest.mark.slow
def test_wan_dag_event_driven_parity():
    """Same parity bar over the branch-parallel Wan DAG: fan-out, join
    assembly and the single-successor in-place restamp all under the
    event-driven scheduler, byte-identical to polling."""
    from repro.models.aigc import DAG_DEPS, WanI2VPipeline, build_dag_stage_fns

    pipe = WanI2VPipeline(seed=0)
    fns = build_dag_stage_fns(pipe)

    def run(name, event_driven):
        ws = WorkflowSet(name, control_loop=False)
        ws.register_workflow(WorkflowSpec(APP, name, [
            StageSpec(s, fn=fns[s], exec_time_s=0.01, deps=DAG_DEPS[s])
            for s in DAG_DEPS
        ]))
        for s in DAG_DEPS:
            ws.add_instance(f"{s}_0", stage=s, event_driven=event_driven)
        proxy = ws.add_proxy("p0")
        cfg = pipe.cfg
        reqs = []
        for i in range(2):
            rng = np.random.default_rng(i)
            reqs.append({
                "tokens": rng.integers(0, cfg.text_vocab,
                                       (1, cfg.text_len)).astype(np.int32),
                "image": (rng.standard_normal(
                    (1, cfg.image_size, cfg.image_size, 3))
                    * 0.1).astype(np.float32),
                "seed": i,
            })
        with ws:
            uids = [proxy.submit(APP, r) for r in reqs]
            outs = [proxy.wait_result(u, timeout_s=120) for u in uids]
        assert ws.joins.stats.completed == len(reqs)
        assert ws.dead_uids() == set()
        return [np.asarray(o).tobytes() for o in outs]

    assert run("dagevt", True) == run("dagpoll", False)
