"""Trace spans inside the served LLM path (repro.core.profiling.span), on a
tiny engine through ``build_llm_disagg_set``, and the names of the engine's
programs that the benchmark's trace reducers match."""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import profiling
from repro.serving import APP_LLM_DISAGG, ServingEngine, build_llm_disagg_set

#: every span the served path records, with the arguments it carries
SPANS = {
    "onepiece.proxy.submit": {"uid"},
    "onepiece.sched.wait": {"instance"},
    "onepiece.recv": {"instance", "uid", "bytes"},
    "onepiece.stage": {"instance", "stage", "uids"},
    "onepiece.handoff.pull": {"uids", "bytes", "state_bytes", "placement"},
    "onepiece.deliver": {"instance", "stage", "uids", "bytes"},
    "onepiece.decode.tick": {"seq"},
    "onepiece.decode.insert": {"uid", "slot", "start", "bytes",
                               "state_bytes"},
    "onepiece.decode.segment": {"seq", "k"},
    "onepiece.decode.first_token": {"uid"},
}


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    return ServingEngine(cfg, max_len=64)


def _payload(engine, i, steps):
    rng = np.random.default_rng(i)
    prompt = rng.integers(0, engine.cfg.vocab_size, (1, 8)).astype(np.int32)
    return {"prompt": prompt, "steps": steps, "temperature": 0.0, "seed": i}


def _serve(engine, steps, trace_dir=None):
    """Warm the set with one request, then serve one request per entry of
    ``steps``, under a profiler trace when ``trace_dir`` is given."""
    ws, _ = build_llm_disagg_set(engine, max_slots=2, segment_len=4)
    with ws:
        proxy = ws.proxies[0]
        proxy.wait_result(proxy.submit(APP_LLM_DISAGG,
                                       _payload(engine, 99, 4)), timeout_s=120)
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        try:
            uids = [proxy.submit(APP_LLM_DISAGG, _payload(engine, i, s))
                    for i, s in enumerate(steps)]
            for u in uids:
                proxy.wait_result(u, timeout_s=120)
            time.sleep(0.1)       # the schedulers park again
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    return uids


def _recorded(trace_dir: pathlib.Path):
    """(name, start_ns, end_ns, args, host line) of every onepiece span."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.rglob("*.xplane.pb")
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats), i)
                   for p in ProfileData.from_file(str(path)).planes
                   if p.name == "/host:CPU"
                   for i, line in enumerate(p.lines) for e in line.events
                   if e.name.startswith("onepiece.")), key=lambda x: x[1])


def test_served_path_records_every_span_linked_by_uid(engine, tmp_path):
    # 3 steps finish inside the first segment of 4; 6 take two segments;
    # three requests for two slots, so one waits for a slot
    uids = _serve(engine, [3, 6, 6], tmp_path)
    got = _recorded(tmp_path)
    for name, args in SPANS.items():
        seen = [a for n, _, _, a, _ in got if n == name]
        assert seen, f"no {name} span"
        assert all(set(a) == args for a in seen), (name, seen[:2])

    ticks = [s for s in got if s[0] == "onepiece.decode.tick"]
    for name in ("onepiece.decode.insert", "onepiece.decode.segment"):
        for _, s, e, _, line in (x for x in got if x[0] == name):
            assert any(t[4] == line and t[1] <= s and e <= t[2]
                       for t in ticks), f"{name} outside every tick"

    def of(name, u):
        return [a for n, _, _, a, _ in got if n == name
                and u in (a.get("uid"), *str(a.get("uids", "")).split(","))]
    for u in uids:
        assert len(of("onepiece.decode.first_token", u)) == 1
        assert len(of("onepiece.proxy.submit", u)) == 1
        # the prefill's stage, its pull and its ring write; the decode
        # stage's park and the result's delivery
        assert [a["stage"] for a in of("onepiece.stage", u)] == [
            "prefill", "decode"]
        assert len(of("onepiece.handoff.pull", u)) == 1
        assert [a["stage"] for a in of("onepiece.deliver", u)] == [
            "prefill", "decode"]
        assert [a["instance"] for a in of("onepiece.recv", u)] == [
            "llm.prefill0", "llm.decode0"]
        assert len(of("onepiece.decode.insert", u)) == 1
    insert_bytes = {a["bytes"] for a in (x[3] for x in got)
                    if "slot" in a}
    assert insert_bytes == {engine.shipment_bytes}


def test_no_span_argument_is_built_with_the_profiler_off(engine, tmp_path,
                                                         monkeypatch):
    calls = collections.Counter()

    def counted(name, fn):
        def wrapped(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        monkeypatch.setattr(profiling, name, wrapped)
    for name in ("uids_arg", "nbytes_arg", "batch_uids"):
        counted(name, getattr(profiling, name))

    _serve(engine, [3, 6])
    assert sum(calls.values()) == 0, calls
    _serve(engine, [3], tmp_path)
    assert set(calls) == {"uids_arg", "nbytes_arg", "batch_uids"}, calls


@pytest.mark.parametrize("program", ["prefill_fn", "segment_fn", "insert_fn",
                                     "release_fn"])
def test_engine_programs_keep_the_names_the_trace_reducers_match(
        engine, program):
    """The benchmark finds each program's runs in a device trace by its
    module name ``jit_<fn>``: a rename would drop its metrics on the chip."""
    state = engine.init_slots(2)
    params = engine.params
    i32 = np.int32(0)
    lowered = {
        "prefill_fn": lambda: engine._prefill.lower(
            params, {"tokens": np.zeros((1, 8), np.int32)}),
        "segment_fn": lambda: engine._segment.lower(params, state, k=4),
        "insert_fn": lambda: engine._insert.lower(
            state, jax.tree.map(lambda x, ax: x.take(np.arange(1), axis=ax),
                                state["cache"], engine.batch_axes),
            state["logits"][0], i32, i32, i32, i32, np.float32(0)),
        "release_fn": lambda: engine._release.lower(state, i32),
    }[program]()
    assert f"module @jit_{program} " in lowered.as_text()
