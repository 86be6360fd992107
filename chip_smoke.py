"""Smoke run of the served path on one TPU chip: proof that the system
starts there and answers correctly.  It is not a benchmark and claims no
speed; the times it prints include compilation.

    python chip_smoke.py [--arch qwen3-1.7b | granite-4.0-h-small]

Runs in one process (the chip belongs to one process), through the same
builders ``python -m repro.launch.serve`` uses:

  1. the ``--arch`` model (qwen3-1.7b by default) at its published widths
     in bfloat16 — for granite-4.0-h-small the one-chip share of its
     deployment, 20 of 40 layers and 9 of 72 experts a layer — seeded
     random weights: 8 requests of 128-token prompts and 64 new tokens
     through Proxy -> WorkflowSet -> prefill -> KVPages handed over on the
     device -> ContinuousDecoder -> result store (8 slots, max_len 256,
     segment 8, prefill batch 4).
     Each stream is held to a solo ``engine.generate`` and, where a
     float32 copy of the weights fits beside them, the prefill logits to a
     float32 forward of the same weights (serve's ``check_llm_tokens`` /
     ``check_prefill_logits``).  It prints what a handoff carries (state
     against KV bytes) and, for a model with experts, the routed rows per
     held expert per decode step.
  2. The Wan I2V ``dag`` workflow, small profile, 4 requests; every video
     must be finite.

Exits non-zero, without the result line, when JAX finds no TPU, on any
stage error, drop, dead request, solo fallback or timeout, when the
kernels run in interpret mode, or when a dispatch entry fell back to its
reference where a kernel exists.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Dispatch entries allowed to record "reference" on the served path, and why.
KNOWN_FALLBACKS = {
    "attention_decode": "slot decode passes a per-row cur_index, which no "
                        "kernel takes yet (ROADMAP queue 1, item 4)",
}
# the widths each arch is served at here (granite: the one-chip share)
PUBLISHED = {
    "qwen3-1.7b": dict(num_layers=28, d_model=2048, num_heads=16,
                       num_kv_heads=8, head_dim=128, d_ff=6144,
                       vocab_size=151_936, dtype="bfloat16"),
    "granite-4.0-h-small": dict(
        num_layers=20, d_model=4096, num_heads=32, num_kv_heads=8,
        head_dim=128, d_ff=768, vocab_size=100_352, ssm_state=128,
        ssm_head_dim=64, shared_ff=1536, num_experts=9,
        resolved_router_experts=72, top_k=10, dtype="bfloat16"),
}
# a float32 copy of the weights for the prefill-logits check must fit
# beside them on one 16 GB chip
F32_CHECK_MAX_PARAMS = 2.5e9


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def dispatch_failures(snapshot, known) -> list:
    fails = []
    for entry, path in sorted(snapshot.items()):
        if path == "pallas":
            continue
        if entry in known:
            say(f"  known fallback: {entry} -> {path}: {known[entry]}")
        else:
            fails.append(f"dispatch {entry} fell back to {path}")
    return fails


def phase_llm(serve, layers, compile_s, arch) -> list:
    cfg = serve.llm_config(arch, "published")
    widths = {k: getattr(cfg, k) for k in PUBLISHED[arch]}
    if widths != PUBLISHED[arch]:
        return [f"{arch} config is not at published widths: {widths}"]
    c0, t0 = compile_s(), time.time()
    engine, ws, decoder = serve.build_llm(cfg, max_len=256, slots=8,
                                          segment=8, prefill_batch=4, seed=0)
    say(f"phase 1: {cfg.name} {cfg.dtype}, {cfg.param_count()/1e9:.3f}B "
        f"params, {widths}")
    reqs = serve.llm_requests(cfg, n=8, prompt_len=128, steps=64, seed=0)
    outs, fails, wall = serve.serve_llm(ws, reqs)
    served = layers.last_dispatch()
    stats = ws.transport_stats()
    n_ok = sum(o is not None for o in outs)
    say(f"phase 1 served {n_ok}/{len(reqs)} requests, "
        f"{n_ok * 64} new tokens, wall {wall:.3f}s (compile included)")
    say(f"phase 1 decode slots: admitted={decoder.stats['admitted']} "
        f"segments={decoder.stats['segments']} "
        f"max_resident={decoder.stats['max_resident']}/8; kv shipping "
        f"{stats.kv_pages} KVPages, {stats.kv_bytes/1e6:.1f} MB, "
        f"{serve.device_share(stats)} of handoffs kept on the device")
    say(f"phase 1 dispatch while serving: {served}")
    fails += dispatch_failures(served, KNOWN_FALLBACKS)
    for line in serve.handoff_lines(engine, decoder):
        say(f"phase 1 {line}")
    fails += serve.check_llm_tokens(engine, reqs, outs, exact=False)
    n = cfg.param_count()
    if n <= F32_CHECK_MAX_PARAMS:
        worst, f32_fails = serve.check_prefill_logits(
            engine, np.concatenate([r["prompt"] for r in reqs]), 4)
        say(f"phase 1 prefill logits vs float32 forward: max |error| "
            f"{worst:.6f} RMS of the row (limit {serve.BF16_LOGIT_TOL})")
        fails += f32_fails
    else:
        say(f"phase 1 prefill logits vs float32 forward: not run, a float32 "
            f"copy of {n/1e9:.2f} B parameters does not fit beside them; "
            f"bench/run.py checks this model against its float32 reference")
    after = layers.last_dispatch()
    say(f"phase 1 dispatch of the references: {after}")
    fails += dispatch_failures(after, {})
    say(f"phase 1 trace+compile {compile_s() - c0:.3f}s, "
        f"phase wall {time.time() - t0:.3f}s")
    return fails


def phase_wan(serve, layers, compile_s) -> list:
    c0, t0 = compile_s(), time.time()
    run = serve.serve_wan("dag", requests=4, seed=0)
    fails = list(run.failures)
    if len(run.videos) != 4:
        fails.append(f"{len(run.videos)}/4 videos")
    shape = run.videos[0].shape if run.videos else None
    say(f"phase 2 wan-i2v dag (small): {len(run.videos)} finite videos of "
        f"shape {shape}, serving wall {run.wall_s:.3f}s")
    snap = layers.last_dispatch()
    say(f"phase 2 dispatch: {snap}")
    fails += dispatch_failures(snap, {})
    for entry in ("attention_full", "ddim_update"):
        if snap.get(entry) != "pallas":
            fails.append(f"phase 2 never dispatched {entry} to its kernel")
    say(f"phase 2 trace+compile {compile_s() - c0:.3f}s, "
        f"phase wall {time.time() - t0:.3f}s")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen3-1.7b", choices=sorted(PUBLISHED),
                    help="the model of phase 1")
    args = ap.parse_args(argv)
    try:
        from repro.kernels import kernel_mode
        from repro.launch import serve
        from repro.models import layers
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {dev.platform} "
              f"({dev.device_kind}); this smoke never falls back to it",
              file=sys.stderr)
        return 2
    cache = serve.use_compile_cache()
    spent = [0.0]

    def on_duration(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            spent[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    say("chip smoke run: the served path starts and answers correctly; "
        "not a benchmark")
    say(f"device {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"kernels {kernel_mode()}, compile cache {cache}")
    fails = []
    if kernel_mode() != "compiled":
        fails.append(f"kernels in {kernel_mode()} mode")
    t0 = time.time()
    fails += phase_llm(serve, layers, lambda: spent[0], args.arch)
    fails += phase_wan(serve, layers, lambda: spent[0])
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    say(f"total wall {time.time() - t0:.3f}s, trace+compile "
        f"{spent[0]:.3f}s, peak device memory {peak} bytes")
    if fails:
        for f in fails:
            print(f"FAILED: {f}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
