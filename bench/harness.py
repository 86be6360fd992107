"""One run of one cell: set-up, the open-loop window, the drain, the
metrics and the comparison that decides ``correct``.

Set-up makes the weights on the device from the seed, builds the
``llm_disagg`` Workflow Set around them (Proxy -> prefill instance ->
KVPages over the ring -> ContinuousDecoder -> result store), and sends
one request per prompt bucket through that same path, which compiles (or
loads from the cache) every program the window uses: the prefill of each
bucket at the cell's prefill batch, the slot insert, the decode segment
and the release.  The window then offers the mix's requests at their due
times; requests due in it are waited for up to ``DRAIN_S`` past its
close.  With ``trace`` the profiler records a stretch of the window, and
the per-layer reducers read it.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import check, client, spec, tails, traffic

DRAIN_S = 60.0
WARM_TIMEOUT_S = 1200.0
GREEDY = 0.0      # the check compares greedy tokens


class NoDevice(Exception):
    """No chip, too few chips, or a chip the peaks table does not know."""


class MissingMetric(Exception):
    """A per-layer metric the cell lists found nothing to read."""


def seed32(seed: int, stream: int) -> int:
    """A 31-bit seed for JAX's PRNG from any non-negative seed."""
    return int(np.random.default_rng([seed, stream]).integers(0, 2 ** 31))


def device_guard(chips: int, peaks: Dict[str, Any], require_tpu: bool):
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu:
        if d.platform != "tpu":
            raise NoDevice(f"no TPU: JAX found {d.platform} ({d.device_kind});"
                           f" this benchmark never falls back to it")
        if len(devs) < chips:
            raise NoDevice(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
        if d.device_kind not in peaks["devices"]:
            raise NoDevice(f"device kind {d.device_kind!r} is not in "
                           f"bench/peaks.json")
    return devs


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at one fixed path in the checkout, whatever
    the environment says, and for every program however fast it compiled."""
    import jax

    path = str(root / "bench" / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's compile-path events (tracing, lowering, backend
    compile) so that set-up can be checked to have warmed every shape."""

    def __init__(self):
        import jax

        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration


def make_params(ref, sizes: Dict[str, Any], cfg, seed: int):
    """The configuration's weights, made on the device in one jitted call,
    and checked against the tree the program expects."""
    import jax

    from repro.models import registry
    from repro.models.param import is_spec

    init = jax.jit(lambda key: ref.init(sizes, key))
    key = jax.random.PRNGKey(seed32(seed, 1))
    want = jax.tree.map(lambda s: (tuple(s.shape), np.dtype(s.dtype)),
                        registry.abstract_params(cfg), is_leaf=is_spec)
    got = jax.tree.map(lambda a: (tuple(a.shape), np.dtype(a.dtype)),
                       jax.eval_shape(init, key))
    if want != got:
        raise spec.SpecError(f"{cfg.name}: the weights maker's tree differs "
                             f"from the program's parameter tree")
    return jax.block_until_ready(init(key))


@dataclasses.dataclass
class Served:
    records: List[client.Record]
    window_s: float
    setup_s: float
    compiles_in_window: int
    counters: Dict[str, float]
    memory_peak_bytes: Optional[int]
    trace_dir: Optional[pathlib.Path]
    calls: Any


def _prompt_ids(lengths, vocab: int, seed: int, stream: int):
    rng = np.random.default_rng([seed, stream])
    return [rng.integers(0, vocab, (1, p)).astype(np.int32) for p in lengths]


class Stand:
    """The served path of one cell around the given weights: the engine,
    the ``llm_disagg`` set, its decoder and the open-loop client."""

    def __init__(self, cell: spec.Cell, cfg, params, *, trace: bool,
                 fault=None):
        from repro.serving import (APP_LLM_DISAGG, ServingEngine,
                                   build_llm_disagg_set)

        from bench import tracing

        self.cell, self.cfg = cell, cfg
        k = self.knobs = cell.knobs["serving"]
        self.engine = ServingEngine(cfg, params=params, max_len=k["max_len"])
        self.ws, self.decoder = build_llm_disagg_set(
            self.engine, name="llm", max_slots=k["slots"],
            segment_len=k["segment"], prefill_batch=k["prefill_batch"])
        if fault is not None:
            fault(self.engine, self.decoder)
        self.calls = tracing.instrument(self.engine) if trace else None

        def payload(r):
            return {"prompt": r.prompt, "steps": r.output_len,
                    "temperature": GREEDY, "seed": r.index & 0x7FFFFFFF}

        self.client = client.OpenLoopClient(
            self.ws.proxies[0], self.ws.database, APP_LLM_DISAGG, payload,
            self.ws.joins.dropped_snapshot)

    def records(self, *, seed: int, seconds: float,
                rate: float) -> List[client.Record]:
        reqs = traffic.generate(self.cell.mix, rate=rate, seconds=seconds,
                                seed=seed)
        prompts = _prompt_ids([r.prompt_len for r in reqs],
                              self.cfg.vocab_size, seed, 2)
        return [client.Record(r.index, r.due_s, prompts[r.index],
                              r.output_len) for r in reqs]

    def warm(self, seed: int) -> None:
        """One request per prompt bucket through the whole path: every
        program the window runs is compiled or loaded here."""
        buckets = traffic.buckets(self.cell.mix)
        warm = [client.Record(-1 - i, 0.0, p, 2 * self.knobs["segment"])
                for i, p in enumerate(_prompt_ids(
                    buckets, self.cfg.vocab_size, seed, 3))]
        self.client.run(warm, window_s=0.0, drain_s=WARM_TIMEOUT_S,
                        t0=time.perf_counter())
        bad = [r for r in warm if not r.finished]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].error}; "
                               + "; ".join(_failures(self.ws)))

    def counters(self) -> Dict[str, float]:
        st = self.ws.transport_stats()
        d = self.decoder.stats
        return {"kv_pages": st.kv_pages, "kv_bytes": st.kv_bytes,
                "segments": d["segments"], "admitted": d["admitted"],
                "completed": d["completed"]}


def serve(cell: spec.Cell, cfg, params, *, seed: int, seconds: float,
          trace: bool, t_process: float, counter: CompileCounter,
          fault=None) -> Served:
    """Set up the served path, warm it, run the window and the drain."""
    import jax

    from bench import tracing

    stand = Stand(cell, cfg, params, trace=trace, fault=fault)
    records = stand.records(seed=seed, seconds=seconds,
                            rate=float(cell.knobs["rate_per_s"]))
    tdir = cell.root / "bench" / ".trace" / f"{cell.name}-{seed}"
    prof = tracing.Profiler(tdir) if trace else None
    with stand.ws:
        stand.warm(seed)
        gc.collect()
        base = stand.counters()
        compiles0 = counter.events
        hooks = []
        if prof is not None:
            start, length = tracing.span(seconds)
            hooks = [(start, prof.start_async),
                     (start + length, prof.stop_async)]
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        stand.client.run(records, window_s=seconds, drain_s=DRAIN_S, t0=t0,
                         at=hooks)
        if prof is not None:
            prof.join()
        compiles = counter.events - compiles0
        counters = {key: v - base[key]
                    for key, v in stand.counters().items()}
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    failures = _failures(stand.ws)
    if failures:
        print("set failures: " + "; ".join(failures), file=sys.stderr)
    return Served(records, seconds, setup_s, compiles, counters,
                  peak, tdir if trace else None, stand.calls)


def _failures(ws) -> List[str]:
    from repro.launch.serve import run_failures

    return run_failures(ws)


def end_to_end(served: Served) -> Dict[str, float]:
    recs = served.records
    return {
        "ttft_p50_ms": 1e3 * tails.percentile(
            [r.ttft_s() for r in recs], 50),
        "tpot_mean_ms": 1e3 * tails.mean([r.tpot_s() for r in recs]),
        "setup_s": served.setup_s,
    }


def per_layer(cell: spec.Cell, ctx) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric the cell lists, from its reducer.  A reducer
    that finds nothing to read returns None; a metric the cell lists must
    be there, so that a renamed program or kernel cannot drop it unseen."""
    out = {}
    for m in cell.metrics(trace=True):
        got = cell.metric_reducer(m["name"]).reduce(ctx)
        if got is None:
            raise MissingMetric(
                f"{cell.name}: per-layer metric {m['name']!r} found nothing "
                f"to read in the traced window (bench/metrics/{m['name']}.py"
                f" names the program, kernel or counter it reads)")
        out[m["name"]] = {"value": float(got), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Prepared:
    cell: spec.Cell
    peaks: Dict[str, Any]
    devices: list
    counter: CompileCounter
    cfg: Any
    sizes: Dict[str, Any]
    ref: Any
    params: Any


def prepare(root: pathlib.Path, name: str, seed: int,
            require_tpu: bool = True) -> Prepared:
    """Load the cell, look for the chip, point the compile cache into the
    checkout, and make the weights: what every entry point does first."""
    cell = spec.load_cell(root, name)
    peaks = spec.read_json(root / "bench" / "peaks.json")
    devs = device_guard(cell.chips, peaks, require_tpu)
    use_compile_cache(root)
    counter = CompileCounter()
    cfg = spec.program_config(cell.config)
    sizes = cell.config["sizes"]
    ref = cell.reference()
    return Prepared(cell, peaks, devs, counter, cfg, sizes, ref,
                    make_params(ref, sizes, cfg, seed))


def run_cell(root: pathlib.Path, name: str, *, seed: int, seconds: float,
             trace: bool, t_process: float, require_tpu: bool = True,
             fault=None) -> Dict[str, Any]:
    """Everything but the printing: returns the result line's object."""
    p = prepare(root, name, seed, require_tpu)
    cell, peaks, sizes, ref, params = p.cell, p.peaks, p.sizes, p.ref, p.params
    cfg, counter = p.cfg, p.counter
    served = serve(cell, cfg, params, seed=seed, seconds=seconds, trace=trace,
                   t_process=t_process, counter=counter, fault=fault)
    print(f"compile events inside the window: {served.compiles_in_window}; "
          f"set-up {served.setup_s:.3f}s (compile {counter.seconds:.3f}s)",
          file=sys.stderr)
    print("requests [due_s, prompt, output, ttft_ms, tpot_ms]: " + json.dumps(
        [[round(r.due_s, 3), r.prompt.shape[1], r.output_len,
          None if r.ttft_s() is None else round(1e3 * r.ttft_s(), 3),
          None if r.tpot_s() is None else round(1e3 * r.tpot_s(), 3)]
         for r in served.records]), file=sys.stderr)
    gc.collect()   # the engine, the set and the slot state are gone now

    devs = p.devices
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": served.memory_peak_bytes}
    out: Dict[str, Any] = {}
    if trace:
        from bench import tracing

        t = time.perf_counter()
        ctx = tracing.context(cell, served, peaks.get("devices", {}).get(
            dev.device_kind), sizes)
        shutil.rmtree(served.trace_dir, ignore_errors=True)
        print(f"trace of {ctx.window_s:.3f}s read in "
              f"{time.perf_counter() - t:.3f}s", file=sys.stderr)
        device["busy_s"], device["window_s"] = ctx.busy_s, ctx.window_s
        metrics = per_layer(cell, ctx)
        out["breakdown"] = ctx.breakdown()
    else:
        e2e = end_to_end(served)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in cell.metrics(trace=False)}

    finished = [r for r in served.records if r.finished]
    sampled = check.sample(finished, check.SAMPLE, seed)
    seqs = [(r.prompt[0], r.tokens[0, r.prompt.shape[1]:]) for r in sampled]
    t = time.perf_counter()
    gaps = check.served_gaps(ref, params, sizes, seqs,
                             length=cell.knobs["serving"]["max_len"])
    limit = float(cell.knobs["check"]["limit_gap_rms"])
    correct, numbers, why = check.verdict(served.records, sampled, gaps,
                                          limit)
    print(f"reference over {len(sampled)} requests, "
          f"{sum(g.size for g in gaps)} served tokens, "
          f"{time.perf_counter() - t:.3f}s; widest gap per request "
          f"{[round(float(g.max()), 6) for g in gaps if g.size]}",
          file=sys.stderr)
    for w in why:
        print(f"not correct: {w}", file=sys.stderr)
    failed = sum(not r.finished for r in served.records)
    out = {"correct": correct, "attempted": len(served.records),
           "failed": failed, "metrics": metrics, "device": device, **out}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    return out
