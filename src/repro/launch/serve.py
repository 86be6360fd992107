"""Serving launcher: stand up a complete OnePiece Workflow Set around the
Wan-style I2V pipeline and push batched requests through it.

    PYTHONPATH=src python -m repro.launch.serve --requests 8
    PYTHONPATH=src python -m repro.launch.serve --workflow dag
    PYTHONPATH=src python -m repro.launch.serve --workflow a2v
    PYTHONPATH=src python -m repro.launch.serve --workflow llm \
        --llm-size published --llm-max-len 256 --llm-prompt-len 128 \
        --llm-steps 64 --llm-segment 8 --max-batch 4

This is the paper's deployment in miniature: proxies with fast-reject,
Theorem-1-planned per-stage instance counts, one-sided-RDMA ring-buffer
transport between stages, NodeManager elastic reassignment, transient
replicated result storage.

Workflows (docs/workflows.md):
  * chain — the linear 4-stage pipeline (text -> vae -> dit -> decode);
  * dag   — the paper's real Wan2.1 topology: text encoder ∥ image/VAE
            encoder as independent branches joining into the DiT
            (bit-identical output, critical-path latency);
  * a2v   — audio-to-video: asr -> (llm -> text_encode) ∥ image_encode
            -> diffusion -> vae_decode, a nested two-branch DAG;
  * llm   — disaggregated prefill/decode LLM serving
            (docs/disaggregation.md): jitted prefill hands KV caches as
            KVPages (kept on the device: both stages run one engine) to a
            continuous-batching decode stage.  ``--llm-size reduced`` (float32, CPU-sized) checks the
            tokens bit-identical to solo generate; ``published`` (the
            config's own widths and dtype) holds them to solo generate
            within the bf16 tolerance of ``check_llm_tokens``.

Any stage error, drop, dead request, solo fallback, timeout or control-loop
error makes the run exit non-zero, printing the first traceback.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.cluster import StageSpec, WorkflowSet, WorkflowSpec
from repro.cluster.proxy import Rejected
from repro.core import RequestMonitor, critical_path, plan_dag, profiler
from repro.models.aigc import (
    DAG_DEPS,
    WanI2VPipeline,
    build_dag_stage_fns,
    build_stage_fns,
)
from repro.models.aigc.pipeline import measure_stage_times

APP_I2V = 1
STAGES = ("text_encode", "vae_encode", "diffusion", "vae_decode")
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

#: A bf16 logit may sit this many RMS-of-its-row from its float32 value.
#: Measured 0.16-0.21 for seeded qwen3 cut to 28 layers at d_model 512-768
#: (bf16 vs f32 prefill on the host CPU); a wrong kernel puts the whole
#: row off by several RMS.
BF16_LOGIT_TOL = 0.5


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, wins (JAX reads it itself
    and nothing is set here); otherwise the cache lives at
    ``<checkout>/.jax_cache``, a path that never moves, so a later run of
    the same checkout hits it.  Called by entry points, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_a2v_stage_fns(pipe: WanI2VPipeline):
    """Toy ASR/LLM front stages (deterministic numpy transforms standing in
    for Whisper and a prompt-rewriting LLM) feeding the real Wan DAG."""
    cfg = pipe.cfg
    dag = build_dag_stage_fns(pipe)

    def stage_asr(p):
        audio = np.asarray(p["audio"])  # [B, n] waveform
        toks = (np.abs(audio[:, :cfg.text_len]) * 997.0).astype(np.int64)
        return {"tokens": (toks % cfg.text_vocab).astype(np.int32),
                "image": p["image"], "seed": p["seed"]}

    def stage_llm(p):
        # image/seed ride along: the downstream text_encode wraps the
        # chain stage fn, whose payload contract includes them
        toks = np.asarray(p["tokens"]).astype(np.int64)
        return {"tokens": ((toks * 31 + 7) % cfg.text_vocab).astype(np.int32),
                "image": p["image"], "seed": p["seed"]}

    return {
        "asr": stage_asr,
        "llm": stage_llm,
        "text_encode": dag["text_encode"],
        "image_encode": dag["image_encode"],
        "diffusion": dag["diffusion"],
        "vae_decode": dag["vae_decode"],
    }


A2V_DEPS = {
    "asr": [],
    "llm": ["asr"],
    "text_encode": ["llm"],
    "image_encode": ["asr"],
    "diffusion": ["text_encode", "image_encode"],
    "vae_decode": ["diffusion"],
}


def workflow_spec(workflow: str, pipe: WanI2VPipeline):
    """-> (WorkflowSpec, stage_times dict) for a named scenario."""
    times = measure_stage_times(pipe)
    if workflow == "chain":
        fns = build_stage_fns(pipe)
        spec = WorkflowSpec(APP_I2V, "wan-i2v", [
            StageSpec(s, fn=fns[s], exec_time_s=times[s]) for s in STAGES
        ])
        return spec, {s: times[s] for s in STAGES}
    if workflow == "dag":
        fns = build_dag_stage_fns(pipe)
        dag_times = {"text_encode": times["text_encode"],
                     "image_encode": times["vae_encode"],
                     "diffusion": times["diffusion"],
                     "vae_decode": times["vae_decode"]}
        spec = WorkflowSpec(APP_I2V, "wan-i2v-dag", [
            StageSpec(s, fn=fns[s], exec_time_s=dag_times[s],
                      deps=DAG_DEPS[s])
            for s in DAG_DEPS
        ])
        return spec, dag_times
    if workflow == "a2v":
        fns = build_a2v_stage_fns(pipe)
        # The toy asr/llm are near-free; planning them at their real
        # (~µs) cost would make them the pacing entrance and blow the
        # per-path Theorem-1 counts up to T_dit/T_asr instances.  Budget
        # them like light encoder stages instead.
        a2v_times = {"asr": times["text_encode"], "llm": times["text_encode"],
                     "text_encode": times["text_encode"],
                     "image_encode": times["vae_encode"],
                     "diffusion": times["diffusion"],
                     "vae_decode": times["vae_decode"]}
        spec = WorkflowSpec(APP_I2V, "audio2video", [
            StageSpec(s, fn=fns[s], exec_time_s=a2v_times[s],
                      deps=A2V_DEPS[s])
            for s in A2V_DEPS
        ])
        return spec, a2v_times
    raise ValueError(f"unknown workflow {workflow!r}")


def make_request(workflow: str, cfg, rng, i: int):
    req = {
        "tokens": rng.integers(0, cfg.text_vocab,
                               (1, cfg.text_len)).astype(np.int32),
        "image": (rng.standard_normal(
            (1, cfg.image_size, cfg.image_size, 3)) * 0.1).astype(np.float32),
        "seed": i,
    }
    if workflow == "a2v":
        del req["tokens"]
        req["audio"] = rng.standard_normal(
            (1, cfg.text_len * 2)).astype(np.float32)
    return req


def build_set(spec: WorkflowSpec, *, counts, admit_rate: float,
              name: str = "ws0", max_batch: int = 1,
              max_wait_s: float = 0.02, elastic: bool = True,
              spares: int = 0) -> WorkflowSet:
    ws = WorkflowSet(name, control_loop=elastic)
    ws.register_workflow(spec)
    # Without the elastic loop nothing reassigns instances mid-run, so the
    # stage fn can run inline on the scheduler thread (docs/perf.md); with
    # it, keep the worker thread so drain-and-handoff stays preemptive.
    inline = not elastic
    for stage, n in counts.items():
        for i in range(n):
            ws.add_instance(f"{stage}_{i}", stage=stage, max_batch=max_batch,
                            max_wait_s=max_wait_s, pad_to_full=max_batch > 1,
                            inline=inline)
    for i in range(spares):
        ws.add_instance(f"spare_{i}", max_batch=max_batch,
                        max_wait_s=max_wait_s, pad_to_full=max_batch > 1,
                        inline=inline)
    # nm_managed: the live control loop keeps (T_X, K) tracking the actual
    # entrance-stage instance count as it rebalances (§5)
    mon = RequestMonitor(t_entrance_s=1.0 / max(admit_rate, 1e-9), k_entrance=1,
                         window_s=2.0, nm_managed=elastic)
    ws.add_proxy("p0", monitor=mon)
    return ws


# ---------------------------------------------------------------- outcomes
def wait_results(ws: WorkflowSet, proxy, uids: Sequence[str],
                 timeout_s: float) -> Tuple[Dict[str, Any], int]:
    """Collect each admitted request's result.  A request the set has
    tombstoned stops being waited for at once (``run_failures`` reports
    it); the rest share one deadline.  Returns ``({uid: result},
    timed_out)``."""
    results: Dict[str, Any] = {}
    pending = list(uids)
    deadline = time.monotonic() + timeout_s
    while pending:
        dead = ws.joins.dropped_snapshot()
        for u in list(pending):
            v = proxy.poll_result(u)
            if v is not None:
                results[u] = v
            if v is not None or u in dead:
                pending.remove(u)
        if pending:
            if time.monotonic() >= deadline:
                break
            ws.database.wait_store(0.05)
    return results, len(pending)


def run_failures(ws: WorkflowSet, *, rejected: int = 0,
                 timed_out: int = 0) -> List[str]:
    """Everything that fails a run, read once the set has stopped: stage
    errors (with the first traceback), drops, dead requests, solo
    fallbacks, rejected or timed-out requests, control-loop errors.  §9
    drops stay drops — the run just refuses to call them success."""
    stats = ws.transport_stats()
    out = []
    if stats.stage_errors:
        out.append(f"{stats.stage_errors} stage errors; the first:\n"
                   f"{stats.first_error}")
    dropped = stats.dropped + sum(i.stats.dropped
                                  for i in ws.instances.values())
    if dropped:
        out.append(f"{dropped} messages dropped")
    dead = ws.dead_uids()
    if dead:
        out.append(f"{len(dead)} requests dead (dropped or stranded)")
    solo = sum(i.stats.solo_fallbacks for i in ws.instances.values())
    if solo:
        out.append(f"{solo} batches fell back to per-request execution")
    if rejected:
        out.append(f"{rejected} requests not admitted")
    if timed_out:
        out.append(f"{timed_out} requests timed out")
    if ws.control is not None and ws.control.errors:
        out.append(f"control loop errors: {ws.control.errors}")
    return out


def print_latency() -> None:
    prof = profiler()
    prof.disable()
    print("per-stage latency (p50 ms by phase):")
    for stage, phases in prof.timeline():
        inner = " ".join(f"{ph}={v:.2f}" for ph, v in phases.items())
        print(f"  {stage:>14}: {inner}")


def report(failures: List[str]) -> int:
    for f in failures:
        print(f"FAILED: {f}")
    return 1 if failures else 0


# --------------------------------------------------------------------- llm
def llm_config(arch: str, size: str):
    """``published``: the config's own widths and dtype; ``reduced``: the
    CPU-sized float32 preset of the tests."""
    from repro.configs import get_config

    cfg = get_config(arch)
    if size == "reduced":
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
    return cfg


def build_llm(cfg, *, max_len: int, slots: int, segment: int,
              prefill_batch: int, seed: int = 0):
    """-> (engine, set, decoder): seeded weights behind the llm_disagg
    Workflow Set."""
    from repro.serving import ServingEngine, build_llm_disagg_set

    engine = ServingEngine(cfg, max_len=max_len, seed=seed)
    ws, decoder = build_llm_disagg_set(
        engine, name="llm", max_slots=slots, segment_len=segment,
        prefill_batch=prefill_batch)
    return engine, ws, decoder


def llm_requests(cfg, *, n: int, prompt_len: int, steps: int,
                 seed: int) -> List[Dict[str, Any]]:
    """Greedy requests (temperature 0), the case the bf16 check can judge:
    a departure from the reference is then a near-tie of its top logits."""
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n, prompt_len)).astype(np.int32)
    return [{"prompt": prompts[i:i + 1], "steps": steps,
             "temperature": 0.0, "seed": i} for i in range(n)]


def serve_llm(ws: WorkflowSet, reqs: List[Dict[str, Any]], *,
              timeout_s: float = 300.0):
    """Push ``reqs`` through the set's proxy in one burst.  Returns
    (per-request tokens or None, failures, wall seconds)."""
    from repro.serving import APP_LLM_DISAGG

    t0 = time.time()
    with ws:
        proxy = ws.proxies[0]
        uids = proxy.submit_many(APP_LLM_DISAGG, reqs)
        results, timed_out = wait_results(ws, proxy, uids, timeout_s)
    wall = time.time() - t0
    outs = [results.get(u) for u in uids]
    outs += [None] * (len(reqs) - len(uids))
    return outs, run_failures(ws, rejected=len(reqs) - len(uids),
                              timed_out=timed_out), wall


def check_llm_tokens(engine, reqs, outs, *, exact: bool) -> List[str]:
    """Hold every served stream to a solo ``engine.generate`` of the same
    request.  ``exact`` (float32 presets): bit-identical.  Otherwise (bf16,
    temperature 0): equal up to the first divergence, where the served
    token's reference logit must lie within ``BF16_LOGIT_TOL`` RMS of the
    reference's top logit — a near-tie that bf16 rounding may flip."""
    fails: List[str] = []
    gaps: List[float] = []
    v = engine.cfg.vocab_size
    for i, (r, out) in enumerate(zip(reqs, outs)):
        if out is None:
            continue  # unserved: already a run failure
        gold = engine.generate(r["prompt"], steps=r["steps"],
                               temperature=r["temperature"],
                               seed=r["seed"]).tokens
        if out.shape == gold.shape and np.array_equal(out, gold):
            continue
        if exact or r["temperature"] != 0 or out.shape != gold.shape:
            fails.append(f"request {i}: tokens differ from solo generate")
            continue
        p = r["prompt"].shape[1]
        j = int(np.flatnonzero(out[0] != gold[0])[0])
        ref = engine.teacher_forced_logits(r["prompt"], gold[:, p:j])[0]
        # sampled ids >= vocab_size - 1 are clamped to vocab_size - 1
        ref = np.concatenate([ref[:v - 1], ref[v - 1:].max(keepdims=True)])
        gap = float((ref[gold[0, j]] - ref[out[0, j]])
                    / np.sqrt(np.mean(ref * ref)))
        gaps.append(gap)
        if gap > BF16_LOGIT_TOL:
            fails.append(f"request {i}: diverges at token {j - p} where the "
                         f"reference prefers its token by {gap:.4f} RMS "
                         f"(> {BF16_LOGIT_TOL})")
    if not exact:
        print(f"token check: {len(gaps)}/{len(reqs)} streams depart from "
              f"solo generate; reference top-vs-served gaps at departure "
              f"(RMS units, limit {BF16_LOGIT_TOL}): "
              f"{[round(g, 6) for g in gaps]}")
    return fails


def check_prefill_logits(engine, prompts: np.ndarray,
                         batch: int) -> Tuple[float, List[str]]:
    """Last-token prefill logits of the engine's dtype against a float32
    forward of the same weights on the same device, ``batch`` prompts at a
    time.  Returns (worst max-abs error in RMS-of-row units, failures)."""
    import jax
    import jax.numpy as jnp

    from repro.models import registry

    cfg32 = dataclasses.replace(engine.cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), engine.params)
    fwd32 = jax.jit(lambda p, t: registry.prefill(
        p, {"tokens": t}, cfg32, dropless=True)[0])
    worst = 0.0
    for i in range(0, len(prompts), batch):
        t = prompts[i:i + batch]
        lo = np.asarray(engine.prefill(t)[0], np.float32)
        hi = np.asarray(fwd32(params32, jnp.asarray(t)))
        err = np.abs(lo - hi).max(-1) / np.sqrt(np.mean(hi * hi, -1))
        worst = max(worst, float(err.max()))
    del params32
    fails = [] if worst <= BF16_LOGIT_TOL else [
        f"prefill logits off the float32 forward by {worst:.4f} RMS "
        f"(> {BF16_LOGIT_TOL})"]
    return worst, fails


def device_share(stats) -> str:
    """``kv_device_handoffs / kv_pages`` of a set's transport stats."""
    return f"{stats.kv_device_handoffs}/{stats.kv_pages}"


def run_llm(args) -> int:
    """--workflow llm: the two-stage llm_disagg DAG end-to-end.

    Prefill coalesces requests, ships per-request KV caches as KVPages
    over the fabric; decode continuous-batches them through slot-based
    ``lax.scan`` segments.  Every emitted stream is then held to a solo
    ``ServingEngine.generate`` (``check_llm_tokens``), and a model not in
    float32 also to a float32 forward (``check_prefill_logits``)."""
    cfg = llm_config(args.llm_arch, args.llm_size)
    engine, ws, decoder = build_llm(
        cfg, max_len=args.llm_max_len, slots=args.llm_slots,
        segment=args.llm_segment, prefill_batch=args.max_batch,
        seed=args.seed)
    reqs = llm_requests(cfg, n=args.requests, prompt_len=args.llm_prompt_len,
                        steps=args.llm_steps, seed=args.seed)
    if args.profile_latency:
        profiler().reset()
        profiler().enable()
    outs, fails, wall = serve_llm(ws, reqs)
    stats = ws.transport_stats()
    exact = cfg.dtype == "float32"
    fails += check_llm_tokens(engine, reqs, outs, exact=exact)
    if not exact:
        prompts = np.concatenate([r["prompt"] for r in reqs])
        worst, f32_fails = check_prefill_logits(engine, prompts,
                                                args.max_batch)
        print(f"prefill logits vs float32 forward: max |error| "
              f"{worst:.6f} RMS (limit {BF16_LOGIT_TOL})")
        fails += f32_fails
    served = sum(o is not None for o in outs)
    print(f"{served} requests x {args.llm_steps} tokens in {wall:.2f}s "
          f"({served/wall:.2f} req/s), {cfg.name} {cfg.dtype}, tokens "
          f"{'bit-identical to' if exact else 'checked against'} solo")
    print(f"decode slots: admitted={decoder.stats['admitted']} "
          f"segments={decoder.stats['segments']} "
          f"max_resident={decoder.stats['max_resident']}/{args.llm_slots}")
    print(f"kv shipping: {stats.kv_pages} KVPages messages, "
          f"{stats.kv_bytes/1e6:.1f} MB of cache, "
          f"{device_share(stats)} of handoffs kept on the device")
    if args.profile_latency:
        print_latency()
    return report(fails)


# --------------------------------------------------------------------- wan
@dataclasses.dataclass
class WanRun:
    videos: List[np.ndarray]
    failures: List[str]
    wall_s: float
    ws: WorkflowSet


def serve_wan(workflow: str, *, requests: int, seed: int = 0,
              max_batch: int = 1, batch_wait_ms: float = 20.0,
              elastic: bool = True, spares: int = 0,
              timeout_s: float = 120.0) -> WanRun:
    """Plan (Theorem 1 per path), build and drive one Wan I2V scenario."""
    pipe = WanI2VPipeline(seed=seed)
    cfg = pipe.cfg
    spec, times = workflow_spec(workflow, pipe)
    print("stage times (s):", {k: round(v, 4) for k, v in times.items()})

    # Theorem 1 per path: instance counts that rate-match the entrance
    deps = spec.resolved_deps()
    counts = plan_dag(times, deps, k_entrance=1)
    print("Theorem-1 plan:", counts)
    cp_latency, cp = critical_path(times, deps)
    print(f"critical path: {' -> '.join(cp)} = {cp_latency:.4f}s "
          f"(serialized sum {sum(times.values()):.4f}s)")

    entrance_t = max(times[s] for s in spec.entrance_stages())
    ws = build_set(spec, counts=counts, admit_rate=1.0 / entrance_t,
                   max_batch=max_batch, max_wait_s=batch_wait_ms / 1e3,
                   elastic=elastic, spares=spares)
    proxy = ws.proxies[0]

    rng = np.random.default_rng(seed)
    t0 = time.time()
    uids: List[str] = []
    with ws:
        reqs = [make_request(workflow, cfg, rng, i) for i in range(requests)]
        if max_batch > 1:
            uids = proxy.submit_many(APP_I2V, reqs)  # one doorbell-batched burst
        else:
            for r in reqs:
                while True:
                    try:
                        uids.append(proxy.submit(APP_I2V, r))
                        break
                    except Rejected:
                        time.sleep(0.05)  # fast-rejected: retry (client behavior)
        # §9: the data plane may drop under pressure and never
        # retransmits — a production client resubmits; here it fails the run.
        results, timed_out = wait_results(ws, proxy, uids, timeout_s)
    wall = time.time() - t0
    videos = [results[u] for u in uids if u in results]
    fails = run_failures(ws, rejected=len(reqs) - len(uids),
                         timed_out=timed_out)
    if not all(np.isfinite(v).all() for v in videos):
        fails.append("non-finite video")
    return WanRun(videos, fails, wall, ws)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--profile", default="small", choices=["small"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workflow", default="chain",
                    choices=["chain", "dag", "a2v", "llm"],
                    help="stage topology: linear chain, the branch-parallel "
                         "Wan DAG, the nested audio-to-video DAG, or the "
                         "disaggregated prefill/decode LLM split")
    ap.add_argument("--llm-arch", default="qwen3-1.7b",
                    help="--workflow llm: model config")
    ap.add_argument("--llm-size", default="reduced",
                    choices=["reduced", "published"],
                    help="--workflow llm: the CPU-sized float32 preset, or "
                         "the config's published widths in its own dtype")
    ap.add_argument("--llm-max-len", type=int, default=64,
                    help="--workflow llm: cache positions per request")
    ap.add_argument("--llm-prompt-len", type=int, default=4,
                    help="--workflow llm: prompt tokens per request")
    ap.add_argument("--llm-steps", type=int, default=16,
                    help="--workflow llm: decode tokens per request")
    ap.add_argument("--llm-slots", type=int, default=8,
                    help="--workflow llm: continuous-batching decode slots")
    ap.add_argument("--llm-segment", type=int, default=4,
                    help="--workflow llm: tokens per decode segment "
                         "(join/leave granularity)")
    ap.add_argument("--max-batch", type=int, default=1,
                    help="stage-level microbatch size (1 = per-request)")
    ap.add_argument("--batch-wait-ms", type=float, default=20.0,
                    help="partial-batch flush deadline")
    ap.add_argument("--no-elastic", action="store_true",
                    help="disable the live NM control loop (§8.2)")
    ap.add_argument("--spare-instances", type=int, default=0,
                    help="extra idle-pool instances the control loop may "
                         "pull onto a hot stage")
    ap.add_argument("--profile-latency", action="store_true",
                    help="record per-request latency spans and print the "
                         "per-stage phase breakdown (docs/perf.md)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.workflow == "llm":
        return run_llm(args)

    if args.profile_latency:
        profiler().reset()
        profiler().enable()
    run = serve_wan(args.workflow, requests=args.requests, seed=args.seed,
                    max_batch=args.max_batch,
                    batch_wait_ms=args.batch_wait_ms,
                    elastic=not args.no_elastic,
                    spares=args.spare_instances)
    ws, videos = run.ws, run.videos
    per_stage = {n: i.stats.processed for n, i in ws.instances.items()}
    if videos:
        print(f"{len(videos)} videos of shape {videos[0].shape} in "
              f"{run.wall_s:.2f}s ({len(videos)/run.wall_s:.2f} req/s)")
    print("per-instance processed:", per_stage)
    js = ws.joins.stats
    if js.offered:
        print(f"joins: {js.completed} assembled from {js.offered} partials, "
              f"{js.aborted_joins} aborted, pending={ws.joins.pending_joins()}")
    if ws.control is not None:
        print(f"control loop: {ws.control.steps} ticks, "
              f"moves={ws.control.moves}, evicted={ws.control.evicted}, "
              f"capacity_pushes={ws.control.capacity_pushes}")
    fabric = ws.fabric.stats
    print(f"fabric: {fabric.total_ops} one-sided ops, "
          f"{fabric.total_bytes/1e6:.1f} MB moved, "
          f"modeled wire time {fabric.modeled_time_s*1e3:.2f} ms")
    print(f"ring buffers: corrupt={sum(b.stats.corrupt for b in ws.buffers.values())} "
          f"takeovers={sum(b.stats.lock_takeovers for b in ws.buffers.values())}")
    if args.profile_latency:
        print_latency()
    return report(run.failures)


if __name__ == "__main__":
    raise SystemExit(main())
