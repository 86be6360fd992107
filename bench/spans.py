"""The program's own spans in a traced run, and what the span metrics
share.

The served path records ``onepiece.*`` spans (``repro.core.profiling.span``)
on the host plane of the same profiler session as the device's ops, so
they share the device trace's clock.  ``read`` keeps those that overlap
the traced window, with their arguments and the host line (one per
thread) they were recorded on; ``SpanContext`` is the reducers' ``Context``
with them added.  Spans of one request share its ``uid`` (a batch span
lists its ``uids``); spans of one thread nest.

``report_ttft`` and ``report_idle`` print to standard error what a single
number cannot hold: each request's time to first token split into its
steps, and the device's idle time split by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import pathlib
import statistics
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from bench import tracing

PREFIX = "onepiece."
WAIT = "onepiece.sched.wait"     # a scheduler parked for traffic


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                 # ns, the trace's clock
    end: float
    args: Dict[str, str]
    thread: int                  # host line of the trace

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6

    def uids(self) -> List[str]:
        """The requests the span belongs to: its ``uid`` or ``uids``."""
        if "uid" in self.args:
            return [self.args["uid"]]
        return [u for u in self.args.get("uids", "").split(",") if u]


@dataclasses.dataclass
class SpanContext(tracing.Context):
    spans: List[Span] = dataclasses.field(default_factory=list)

    def complete(self, name: str) -> List[Span]:
        """Spans named ``name`` that lie wholly inside the window."""
        lo, hi = self.trace.window
        return [s for s in self.spans
                if s.name == name and s.start >= lo and s.end <= hi]

    def first_by_uid(self, name: str) -> Dict[str, Span]:
        """Per request, its earliest complete span named ``name``."""
        out: Dict[str, Span] = {}
        for s in self.complete(name):
            for u in s.uids():
                if u not in out or s.start < out[u].start:
                    out[u] = s
        return out

    def children(self, parent: Span, name: str) -> List[Span]:
        """Spans named ``name`` nested in ``parent`` on its thread."""
        return [s for s in self.spans
                if s.name == name and s.thread == parent.thread
                and s.start >= parent.start and s.end <= parent.end]


def read(trace_dir: pathlib.Path,
         window: Tuple[float, float]) -> List[Span]:
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    lo, hi = window
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if (e.name.startswith(PREFIX) and e.start_ns <= hi
                        and e.end_ns >= lo):
                    out.append(Span(e.name, e.start_ns, e.end_ns,
                                    {k: str(v) for k, v in e.stats}, i))
    return sorted(out, key=lambda s: s.start)


def with_spans(ctx: tracing.Context, trace_dir) -> SpanContext:
    """``ctx`` with the program's spans of its window added."""
    fields = {f.name: getattr(ctx, f.name)
              for f in dataclasses.fields(tracing.Context)}
    return SpanContext(**fields, spans=read(trace_dir, ctx.trace.window))


# ---------------------------------------------------------- intervals
def merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def overlap_ns(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clipped(ctx: SpanContext, spans: Iterable[Span]) -> List[List[float]]:
    lo, hi = ctx.trace.window
    return merged((max(s.start, lo), min(s.end, hi)) for s in spans)


def idle(ctx: SpanContext) -> List[List[float]]:
    """The window's intervals in which no op ran on the device."""
    lo, hi = ctx.trace.window
    edges = [lo]
    for s, e in merged((s, e) for _, s, e in ctx.trace.ops):
        edges += [max(s, lo), min(e, hi)]
    edges.append(hi)
    return [[s, e] for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def idle_split(ctx: SpanContext,
               gaps: List[List[float]]) -> Tuple[float, float, float]:
    """Seconds of ``gaps`` under a work span (any ``onepiece.*`` span but
    ``sched.wait``, on any thread), under ``sched.wait`` alone, and under
    no span."""
    work = clipped(ctx, (s for s in ctx.spans if s.name != WAIT))
    wait = clipped(ctx, (s for s in ctx.spans if s.name == WAIT))
    under_work = overlap_ns(gaps, work)
    under_any = overlap_ns(gaps, merged(
        [tuple(x) for x in work] + [tuple(x) for x in wait]))
    total = sum(e - s for s, e in gaps)
    return (under_work / 1e9, (under_any - under_work) / 1e9,
            (total - under_any) / 1e9)


# ------------------------------------------------------------- report
SPLIT = ("queue", "prefill_stage", "ring", "tick_wait", "insert",
         "first_segment")


def ttft_split(ctx: SpanContext) -> Dict[str, Dict[str, float]]:
    """Per request whose path lies in the window, the steps (ms) from the
    proxy's submit to its first token: they add up to ``first_token_ms``."""
    submit = ctx.first_by_uid("onepiece.proxy.submit")
    stage = ctx.first_by_uid("onepiece.stage")
    deliver = ctx.first_by_uid("onepiece.deliver")
    insert = ctx.first_by_uid("onepiece.decode.insert")
    first = ctx.first_by_uid("onepiece.decode.first_token")
    recv = ring_recv(ctx, deliver)
    out = {}
    for u, sub in submit.items():
        if not all(u in d for d in (stage, deliver, recv, insert, first)):
            continue
        marks = [sub.start, stage[u].start, deliver[u].start, recv[u].end,
                 insert[u].start, insert[u].end, first[u].start]
        out[u] = {k: (b - a) / 1e6
                  for k, a, b in zip(SPLIT, marks, marks[1:])}
    return out


def ring_recv(ctx: SpanContext, deliver: Dict[str, Span]) -> Dict[str, Span]:
    """Per request, the ``recv`` that took it off the ring after its first
    ``deliver`` (the next hop's)."""
    out: Dict[str, Span] = {}
    for s in ctx.complete("onepiece.recv"):
        u = s.args.get("uid")
        if u in deliver and s.start >= deliver[u].start and u not in out:
            out[u] = s
    return out


def report_ttft(ctx: SpanContext) -> None:
    """Each request's split, longest first, with the parts inside two of
    its steps: the pull in the prefill stage, and the ring write (the
    prefill's ``deliver``) and read (the decode's ``recv``) in the ring."""
    split = ttft_split(ctx)
    deliver = ctx.first_by_uid("onepiece.deliver")
    recv = ring_recv(ctx, deliver)
    pull = ctx.first_by_uid("onepiece.handoff.pull")
    for u, parts in sorted(split.items(), key=lambda kv: -sum(
            kv[1].values())):
        inner = (f" [pull={pull[u].ms:.3f}]" if u in pull else "",
                 f" [write={deliver[u].ms:.3f} read={recv[u].ms:.3f}]")
        print(f"ttft split {u[:8]}: queue={parts['queue']:.3f} "
              f"prefill_stage={parts['prefill_stage']:.3f}{inner[0]} "
              f"ring={parts['ring']:.3f}{inner[1]} " + " ".join(
                  f"{k}={parts[k]:.3f}" for k in SPLIT[3:])
              + f" total={sum(parts.values()):.3f} ms", file=sys.stderr)
    if split:
        med = {k: statistics.median(p[k] for p in split.values())
               for k in SPLIT}
        print("ttft split median ms: " + " ".join(
            f"{k}={v:.3f}" for k, v in med.items()), file=sys.stderr)


def report_idle(ctx: SpanContext, top: int = 3) -> None:
    gaps = idle(ctx)
    work, wait, none = idle_split(ctx, gaps)
    print(f"device idle {work + wait + none:.6f}s of {ctx.window_s:.6f}s: "
          f"under work spans {work:.6f}s, under sched.wait only "
          f"{wait:.6f}s, under no span {none:.6f}s", file=sys.stderr)
    lo = ctx.trace.window[0]
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        w, t, n = idle_split(ctx, [[s, e]])
        names = sorted({sp.name[len(PREFIX):] for sp in ctx.spans
                        if sp.name != WAIT and sp.start < e and sp.end > s})
        print(f"idle gap @{(s - lo) / 1e9:.3f}s {(e - s) / 1e9:.6f}s: "
              f"work {w:.6f}s ({'+'.join(names) or '-'}), sched.wait "
              f"{t:.6f}s, none {n:.6f}s", file=sys.stderr)


def mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
