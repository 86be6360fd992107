"""The span metrics on a small hand-made trace, against numbers worked out
by hand in the comments (times in ms of a 1000 ms window; the device busy
100-150, 470-480, 500-600, 566-578, 700-705, 720-880: 325 ms)."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import spans, spec, tracing

DATA = pathlib.Path(__file__).parent / "data" / "trace_spans.json"
BENCH = pathlib.Path(__file__).resolve().parents[1]
METRICS = ["queue_wait_ms", "kv_pull_ms", "kv_ring_ms", "insert_host_ms",
           "tick_host_ms", "first_token_ms", "host_stall_share"]


def make_ctx(keep=lambda s: True) -> spans.SpanContext:
    d = json.loads(DATA.read_text())
    tr = tracing.Trace.from_json(d["trace"])
    busy = tracing.union_ns((s, e) for _, s, e in tr.ops) / 1e9
    return spans.SpanContext(
        tr, {}, {}, [], {}, {}, {}, tr.window_s, busy,
        spans=[s for s in (spans.Span(*x) for x in d["spans"]) if keep(s)])


def reduce(name, ctx):
    return spec.load_module(BENCH / "metrics" / f"{name}.py",
                            f"m_{name}").reduce(ctx)


@pytest.mark.parametrize("name,want", [
    # prefill stage start - submit: a 55 - 50, b 565 - 557; c's submit
    # began before the window
    ("queue_wait_ms", 6.5),
    # pulls of 40 and 15 ms, one request each
    ("kv_pull_ms", 27.5),
    # decode recv end - prefill deliver start: a 330 - 200, b 640 - 600
    # (the prefill instance's recv of each came before its deliver)
    ("kv_ring_ms", 85.0),
    # inserts of 85 and 50 ms
    ("insert_host_ms", 67.5),
    # tick self time: 230 - 85 - 120 and 250 - 50 - 180
    ("tick_host_ms", 22.5),
    # first token - submit: a 612 - 50, b 895 - 557; median of two
    ("first_token_ms", 450.0),
    # idle under some span but sched.wait: 48 in 0-100, 221 in 150-470,
    # 20 in 480-500, 86 in 600-700, 15 in 705-720, 25 in 880-1000
    ("host_stall_share", 41.5),
])
def test_span_metric_gives_known_number(name, want):
    assert reduce(name, make_ctx()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", METRICS)
def test_span_metric_without_its_spans_reports_nothing(name):
    # a program that records no onepiece.* span (or a trace of the
    # benchmark's own spans alone): every span metric is left out
    assert reduce(name, make_ctx(keep=lambda s: False)) is None


def test_ttft_split_telescopes_to_first_token():
    split = spans.ttft_split(make_ctx())
    assert split[("a" * 32)] == pytest.approx({
        "queue": 5, "prefill_stage": 145, "ring": 130, "tick_wait": 65,
        "insert": 85, "first_segment": 132})
    assert sum(split["b" * 32].values()) == pytest.approx(895 - 557)
    assert set(split) == {"a" * 32, "b" * 32}


def test_device_idle_split_three_ways(capsys):
    ctx = make_ctx()
    work, wait, none = spans.idle_split(ctx, spans.idle(ctx))
    # idle 675 ms: 415 under work spans; sched.wait covers up to 960 ms
    assert (work, wait, none) == pytest.approx((0.415, 0.22, 0.04))
    spans.report_idle(ctx)
    err = capsys.readouterr().err
    # the longest gap, 150-470 ms: 221 ms under stage, pull, deliver, recv,
    # tick and insert; 99 ms under sched.wait alone
    assert ("idle gap @0.150s 0.320000s: work 0.221000s (decode.insert+"
            "decode.tick+deliver+handoff.pull+recv+stage), sched.wait "
            "0.099000s, none 0.000000s") in err


def test_spans_are_read_from_a_profiler_trace_with_args_and_threads(
        tmp_path):
    """``read`` keeps the program's spans of the window from a recorded
    trace, one host line per thread, and leaves the rest."""
    import threading

    import jax

    from repro.core.profiling import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("onepiece.proxy.submit", uid="u1"):
            pass

        def other():
            with span("onepiece.decode.tick", seq=3):
                with span("onepiece.decode.segment", seq=3, k=8):
                    pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        with jax.profiler.TraceAnnotation("bench.segment", seq=0):
            pass
    finally:
        jax.profiler.stop_trace()
    got = spans.read(tmp_path, (0, float("inf")))
    assert [(s.name, s.args) for s in got] == [
        ("onepiece.proxy.submit", {"uid": "u1"}),
        ("onepiece.decode.tick", {"seq": "3"}),
        ("onepiece.decode.segment", {"seq": "3", "k": "8"})]
    assert got[0].thread != got[1].thread == got[2].thread
