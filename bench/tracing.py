"""Traced runs: host spans around the engine's calls, the profiler, and the
reduction of its trace to what the per-layer reducers read.

In a traced run only, ``instrument`` wraps the engine instance's
``prefill``, ``insert_slot``, ``decode_segment`` and ``release_slot`` in
``jax.profiler.TraceAnnotation`` spans named ``bench.<call>`` with a
sequence number, and logs what each call was given (prompt rows and
length; per decode step, the cached length of every row that advanced).
The wrapping lives here and not in the program.

The trace of a TPU run has, on the plane ``/device:TPU:<n>``, a line
``XLA Modules`` (one event per program run, named ``jit_<fn>(<id>)``) and
a line ``XLA Ops`` (one event per op, named by its HLO text, nested for
loops); host threads are lines of ``/host:CPU``.  Device and host events
share one clock.  ``read`` keeps the events inside the traced window,
which is the span ``bench.window`` opened after the profiler starts and
closed before it stops.
"""
from __future__ import annotations

import dataclasses
import glob
import itertools
import pathlib
import re
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE = re.compile(r"^jit_(\w+?)\(")
OP = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")

Event = Tuple[str, float, float]    # (name, start_ns, end_ns)


TRACE_FROM, TRACE_TO = 0.1, 0.5    # the traced stretch, as window shares


def span(seconds: float) -> Tuple[float, float]:
    """(offset into the window, length) of the traced stretch: long enough
    at a cell's rate to hold prefills as well as decode segments."""
    return TRACE_FROM * seconds, (TRACE_TO - TRACE_FROM) * seconds


# ------------------------------------------------------------- host side
class CallLog:
    """What each wrapped engine call was given, by sequence number."""

    def __init__(self):
        self.calls: Dict[int, Dict[str, Any]] = {}
        self._seq = itertools.count()
        self._ctx: Dict[int, int] = {}     # slot -> cached length

    def add(self, kind: str, **info) -> int:
        seq = next(self._seq)
        self.calls[seq] = dict(kind=kind, **info)
        return seq


def instrument(engine) -> CallLog:
    import jax

    log = CallLog()
    ann = jax.profiler.TraceAnnotation
    prefill, insert = engine.prefill, engine.insert_slot
    segment, release = engine.decode_segment, engine.release_slot

    def w_prefill(prompts):
        p = np.asarray(prompts)
        # rows the coalescer padded with are repeats of a real row
        real = len({row.tobytes() for row in p})
        seq = log.add("prefill", rows=p.shape[0], real=real, length=p.shape[1])
        with ann("bench.prefill", seq=seq):
            return prefill(prompts)

    def w_insert(state, slot, *a, start, **kw):
        seq = log.add("insert", slot=int(slot), start=int(start))
        with ann("bench.insert", seq=seq):
            out = insert(state, slot, *a, start=start, **kw)
        log._ctx[int(slot)] = int(start)
        return out

    def w_segment(state, k):
        seq = log.add("segment", k=int(k))
        with ann("bench.segment", seq=seq):
            state, toks, adv = segment(state, k)
        steps = []
        for i in range(adv.shape[0]):
            keys = []
            for s in np.flatnonzero(adv[i]):
                keys.append(log._ctx.get(int(s), 0) + 1)
                log._ctx[int(s)] = keys[-1]
            steps.append(keys)
        log.calls[seq]["steps"] = steps
        return state, toks, adv

    def w_release(state, slot):
        seq = log.add("release", slot=int(slot))
        with ann("bench.release", seq=seq):
            return release(state, slot)

    engine.prefill, engine.insert_slot = w_prefill, w_insert
    engine.decode_segment, engine.release_slot = w_segment, w_release
    return log


class Profiler:
    """Starts and stops the profiler from a thread of its own, so that the
    load generator is not held while the profiler starts."""

    def __init__(self, out_dir: pathlib.Path):
        self.dir = out_dir
        self._threads: List[threading.Thread] = []
        self._window = None
        self.error: Optional[BaseException] = None

    def _run(self, fn):
        def body():
            try:
                fn()
            except BaseException as e:  # reported by join()
                self.error = e
        t = threading.Thread(target=body, name="bench-profiler")
        t.start()
        self._threads.append(t)

    def _start(self):
        import jax

        jax.profiler.start_trace(str(self.dir))
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def _stop(self):
        import jax

        for t in self._threads[:1]:
            t.join()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def start_async(self):
        self._run(self._start)

    def stop_async(self):
        self._run(self._stop)

    def join(self):
        for t in self._threads:
            t.join()
        if self.error is not None:
            raise self.error


# ------------------------------------------------------------ the trace
@dataclasses.dataclass
class Trace:
    """Events of the traced window, all in ns on one clock."""
    window: Tuple[float, float]
    modules: List[Event]                  # program runs on the device
    ops: List[Event]                      # ops on the device (nested)
    host: List[Tuple[str, float, float, Dict[str, str]]]   # bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "Trace":
        return Trace(tuple(d["window"]), [tuple(e) for e in d["modules"]],
                     [tuple(e) for e in d["ops"]],
                     [(n, s, e, dict(st)) for n, s, e, st in d["host"]])


def read(trace_dir: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[0])
    modules, ops, host, devices = [], [], [], 0
    window = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.end_ns,
                                     {k: str(v) for k, v in e.stats}))
    if devices == 0:
        raise ValueError(f"the trace in {trace_dir} has no TPU device plane")
    if window is None:
        raise ValueError(f"the trace in {trace_dir} has no bench.window span")
    lo, hi = window

    def inside(evs):
        return sorted((e for e in evs if e[1] >= lo and e[2] <= hi),
                      key=lambda e: e[1])
    return Trace(window, inside(modules), inside(ops),
                 sorted((h for h in host if h[1] <= hi and h[2] >= lo),
                        key=lambda h: h[1]))


# ------------------------------------------------------- for reducers
def union_ns(intervals) -> float:
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def self_times(ops: List[Event]) -> Dict[str, float]:
    """Seconds of each op name, less the time of ops nested inside it."""
    out: Dict[str, float] = {}
    stack: List[list] = []      # [name, end, child_ns, dur]
    def close(fr):
        out[fr[0]] = out.get(fr[0], 0.0) + (fr[3] - fr[2]) / 1e9
    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def op_name(hlo: str) -> str:
    m = OP.match(hlo)
    return m.group(1) if m else hlo.split(" ")[0]


def program(module_name: str) -> str:
    m = MODULE.match(module_name)
    return m.group(1) if m else module_name


@dataclasses.dataclass
class Context:
    """What a per-layer reducer reads: the trace, the calls made in it,
    the run's counters and client records, sizes and peaks."""
    trace: Trace
    calls: Dict[int, Dict[str, Any]]
    counters: Dict[str, float]
    records: List[Any]
    sizes: Dict[str, Any]
    knobs: Dict[str, Any]
    peaks: Dict[str, float]
    window_s: float             # the traced window
    busy_s: float

    def runs(self, fn: str) -> List[Tuple[Event, Optional[Dict[str, Any]]]]:
        """Device runs of the program ``fn`` in the window, each with the
        logged call that dispatched it: the latest ``bench.<call>`` span
        that began before the run did (calls of one kind are made from one
        thread, one after another)."""
        kind = {"prefill_fn": "prefill", "segment_fn": "segment",
                "insert_fn": "insert", "release_fn": "release"}.get(fn)
        spans = [(s, int(st["seq"])) for n, s, e, st in self.trace.host
                 if kind and n == f"bench.{kind}" and "seq" in st]
        out = []
        for ev in self.trace.modules:
            if program(ev[0]) != fn:
                continue
            call = None
            before = [seq for s, seq in spans if s <= ev[1]]
            if before:
                call = self.calls.get(before[-1])
            out.append((ev, call))
        return out

    def kernel_time_in(self, run: Event, kernel: str) -> float:
        """Device seconds of the ops named ``kernel`` inside ``run``."""
        return sum(e - s for n, s, e in self.trace.ops
                   if s >= run[1] and e <= run[2]
                   and op_name(n).startswith(kernel)) / 1e9

    def prefill_kernel_share(self, kernel: str, work_fn) -> Optional[float]:
        """Share (%) of its roofline that ``kernel`` reached over the
        prefill runs of the window: the least time for the work
        ``work_fn(sizes, rows, length)`` of each run, over the kernel's
        device time in those runs.  Prints which bound applies."""
        from bench import flops

        need = spent = 0.0
        bounds = set()
        for ev, call in self.runs("prefill_fn"):
            t = self.kernel_time_in(ev, kernel)
            if call is None or t <= 0:
                continue
            r = flops.roofline(work_fn(self.sizes, call["rows"],
                                       call["length"]), 1.0, self.peaks)
            need += r["share_pct"] / 100.0
            spent += t
            bounds.add(r["bound"])
        if spent <= 0:
            return None
        print(f"{kernel}: {'/'.join(sorted(bounds))} bound, least time "
              f"{need:.6f}s over {spent:.6f}s of kernel time", file=sys.stderr)
        return 100.0 * need / spent

    def breakdown(self) -> Dict[str, List[List[Any]]]:
        merged = {}
        for n, t in self_times(self.trace.ops).items():
            merged[op_name(n)] = merged.get(op_name(n), 0.0) + t
        tops = sorted(merged.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, t] for n, t in tops],
                "idle_gaps": [[n, t] for n, t in self.idle_gaps()[:10]]}

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Gaps between busy device intervals, longest first, each named
        by the host spans that overlap it (``none`` if no bench span)."""
        lo, hi = self.trace.window
        busy = []
        for _, s, e in sorted(self.trace.ops, key=lambda x: x[1]):
            if busy and s <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], e)
            else:
                busy.append([s, e])
        edges = [lo] + [x for b in busy for x in b] + [hi]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            over = sorted({n for n, hs, he, _ in self.trace.host
                           if hs < e and he > s})
            gaps.append((f"{'+'.join(over) or 'none'} @{(s - lo) / 1e9:.3f}s",
                         (e - s) / 1e9))
        return sorted(gaps, key=lambda g: -g[1])


def context(cell, served, peaks, sizes) -> Context:
    tr = read(served.trace_dir)
    busy = union_ns((s, e) for _, s, e in tr.ops) / 1e9
    return Context(tr, served.calls.calls if served.calls else {},
                   served.counters, served.records, sizes, cell.knobs,
                   peaks or {}, tr.window_s, busy)
