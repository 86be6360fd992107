"""Each per-layer reducer on a small recorded trace, against numbers worked
out by hand in the comments (sizes: 2 layers, d 64, 4/2 heads of 16,
d_ff 128, vocab 512; peaks 1e9 FLOP/s and 1e7 B/s)."""
from __future__ import annotations

import json
import pathlib
import types

import pytest

from bench import spec, tracing

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json"
BENCH = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def ctx():
    d = json.loads(DATA.read_text())
    tr = tracing.Trace.from_json(d["trace"])
    recs = [types.SimpleNamespace(**r) for r in d["records"]]
    busy = tracing.union_ns((s, e) for _, s, e in tr.ops) / 1e9
    return tracing.Context(tr, {int(k): v for k, v in d["calls"].items()},
                           d["counters"], recs, d["sizes"], d["knobs"],
                           d["peaks"], tr.window_s, busy)


def reduce(name, ctx):
    return spec.load_module(BENCH / "metrics" / f"{name}.py",
                            f"m_{name}").reduce(ctx)


@pytest.mark.parametrize("name,want", [
    # segment runs 160 + 160 ms over 2 runs x 2 steps
    ("decode_step_ms", 80.0),
    # one insert run of 10 ms
    ("insert_ms", 10.0),
    # 470979584 bytes over 2 KVPages
    ("kv_mb_per_request", 235.489792),
    # 16 tokens seen over 4 segments x 2 steps x 4 slots
    ("slot_occupancy", 50.0),
    # lags 1 and 3 ms: 1 + 2 * 0.99
    ("gen_lag_ms", 2.98),
    # busy union 100 + 10 + 160 + 160 + 5 = 435 ms of 1000
    ("device_idle_share", 56.5),
    # flash over L=2, B=1, S=128: bytes 2*128*16*2*(2*4+2*2) = 98304 ->
    # 9.8304 ms at 1e7 B/s (FLOPs 4227072 -> 4.227 ms); kernel time 20 ms
    ("flash_attention_roofline", 49.152),
    # weights 213760 B per step, KV 256 B per key: steps of 129, 130,
    # 131+10 and 132 keys -> 24.6784 + 24.704 + 24.9856 + 24.7552 ms of
    # least time over 320 ms of segment time
    ("decode_roofline", 30.976),
    # prefill 1 x 128: 23166976 FLOPs over 1 s x 1e9
    ("prefill_mfu", 2.3166976),
    # decode tokens 279040 + 279552 + 280064 + 218112 + 280576 = 1337344
    # FLOPs over 1 s x 1e9
    ("decode_mfu", 0.1337344),
])
def test_reducer_gives_known_number(ctx, name, want):
    assert reduce(name, ctx) == pytest.approx(want, rel=1e-9)


def test_reducer_without_its_source_reports_nothing(ctx):
    # no WKV6 kernel ran in this trace: the metric is left out, never 0
    assert reduce("wkv6_roofline", ctx) is None


@pytest.mark.parametrize("renamed,lost", [
    ("jit_prefill_fn", ["flash_attention_roofline", "prefill_mfu"]),
    ("jit_segment_fn", ["decode_step_ms", "decode_roofline", "decode_mfu"]),
    ("jit_insert_fn", ["insert_ms"]),
])
def test_a_listed_metric_whose_program_was_renamed_fails_the_run(
        renamed, lost):
    """A trace in which a program the reducers read runs under another
    name: the cell's run fails naming a metric, it does not drop it."""
    from bench import harness

    d = json.loads(DATA.read_text())
    for ev in d["trace"]["modules"]:
        ev[0] = ev[0].replace(renamed, "jit_renamed_fn")
    tr = tracing.Trace.from_json(d["trace"])
    busy = tracing.union_ns((s, e) for _, s, e in tr.ops) / 1e9
    c = tracing.Context(tr, {int(k): v for k, v in d["calls"].items()},
                        d["counters"],
                        [types.SimpleNamespace(**r) for r in d["records"]],
                        d["sizes"], d["knobs"], d["peaks"], tr.window_s, busy)
    per_layer = json.loads((BENCH.parent / "BENCHMARK.json").read_text())[
        "per_layer"]
    cell = types.SimpleNamespace(
        name="qwen3-chat",
        metrics=lambda trace: [m for m in per_layer
                               if "qwen3-chat" in m["workloads"]],
        metric_reducer=lambda n: spec.load_module(
            BENCH / "metrics" / f"{n}.py", f"m_{n}"))
    assert {m["name"] for m in cell.metrics(True)} >= set(lost)
    with pytest.raises(harness.MissingMetric) as e:
        harness.per_layer(cell, c)
    assert any(repr(n) in str(e.value) for n in lost)


def test_runs_pair_each_program_run_with_the_call_that_dispatched_it(ctx):
    runs = ctx.runs("segment_fn")
    assert [c["steps"] for _, c in runs] == [[[129], [130]],
                                            [[131, 10], [132]]]
    assert ctx.runs("prefill_fn")[0][1]["length"] == 128


def test_breakdown_names_device_ops_by_self_time_and_gaps_by_host_span(ctx):
    b = ctx.breakdown()
    ops = dict(b["device_ops"])
    # fusion: (100 - 20) + 100 + 100 ms of its own; while: 2 x (160 - 100)
    assert ops == pytest.approx({"fusion": 0.28, "while": 0.12,
                                 "flash_attention_bhsd": 0.02, "copy": 0.015})
    assert [n for n, _ in b["device_ops"]][:2] == ["fusion", "while"]
    gaps = b["idle_gaps"]
    assert gaps[0] == ["none @0.705s", pytest.approx(0.295)]
    assert gaps[1] == ["bench.prefill @0.000s", pytest.approx(0.1)]
    assert ["bench.release+bench.segment @0.660s",
            pytest.approx(0.04)] in gaps


def test_every_per_layer_metric_has_a_reducer_and_moves_an_end_to_end():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e


def test_traced_calls_are_spans_on_the_host_and_a_cpu_trace_is_refused(
        tmp_path):
    """The wrapped engine calls and the window land in the profiler's
    trace as ``bench.*`` spans with their sequence numbers; a trace with
    no TPU plane (a CPU run) gives no device metric."""
    import time

    import numpy as np
    from jax.profiler import ProfileData

    class Engine:
        def prefill(self, prompts):
            return prompts.sum()

        def insert_slot(self, state, slot, cache1, logits1, *, start, **kw):
            return state

        def decode_segment(self, state, k):
            return state, np.zeros((k, 2), np.int32), np.ones((k, 2), bool)

        def release_slot(self, state, slot):
            return state

    eng = Engine()
    log = tracing.instrument(eng)
    prof = tracing.Profiler(tmp_path / "trace")
    prof.start_async()
    prof._threads[0].join()
    eng.prefill(np.array([[1, 2], [1, 2]]))
    eng.insert_slot(None, 1, None, None, start=5)
    eng.decode_segment(None, 2)
    eng.release_slot(None, 1)
    time.sleep(0.01)
    prof.stop_async()
    prof.join()
    # two rows, one of them the coalescer's repeat; row 1 advanced twice
    assert log.calls[0] == {"kind": "prefill", "rows": 2, "real": 1,
                            "length": 2}
    assert log.calls[2]["steps"] == [[1, 6], [2, 7]]
    files = list((tmp_path / "trace").rglob("*.xplane.pb"))
    names = [(e.name, dict(e.stats).get("seq"))
             for p in ProfileData.from_file(str(files[0])).planes
             for line in p.lines for e in line.events
             if e.name.startswith("bench.")]
    assert ("bench.window", None) in names
    assert [n for n in names if n[0] != "bench.window"] == [
        ("bench.prefill", 0), ("bench.insert", 1), ("bench.segment", 2),
        ("bench.release", 3)]
    with pytest.raises(ValueError, match="no TPU device plane"):
        tracing.read(tmp_path / "trace")
