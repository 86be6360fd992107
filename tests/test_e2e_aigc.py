"""Integration: the Wan-style I2V pipeline served through a complete
OnePiece workflow set must produce bit-identical results to the monolithic
path — tensors crossing the simulated RDMA fabric, Theorem-1 planning,
round-robin scheduling and replicated storage all in the loop.
"""
from __future__ import annotations

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

from repro.cluster import StageSpec, WorkflowSet, WorkflowSpec
from repro.core import plan_chain
from repro.models.aigc import (
    DAG_DEPS,
    WanI2VPipeline,
    build_dag_stage_fns,
    build_stage_fns,
)
from repro.models.aigc.pipeline import measure_stage_times

APP = 1
STAGES = ("text_encode", "vae_encode", "diffusion", "vae_decode")


@pytest.fixture(scope="module")
def pipe():
    return WanI2VPipeline(seed=0)


def make_request(pipe, i):
    cfg = pipe.cfg
    rng = np.random.default_rng(i)
    return {
        "tokens": rng.integers(0, cfg.text_vocab, (1, cfg.text_len)).astype(np.int32),
        "image": (rng.standard_normal((1, cfg.image_size, cfg.image_size, 3))
                  * 0.1).astype(np.float32),
        "seed": i,
    }


def test_staged_pipeline_matches_monolithic(pipe):
    fns = build_stage_fns(pipe)
    req = make_request(pipe, 3)
    mono = pipe.generate(req["tokens"], req["image"], seed=3)
    p = dict(req)
    for s in STAGES:
        p = fns[s](p)
    np.testing.assert_allclose(p, mono, atol=1e-5)


def test_workflow_set_serves_aigc_requests(pipe):
    fns = build_stage_fns(pipe)
    ws = WorkflowSet("aigc")
    ws.register_workflow(WorkflowSpec(APP, "i2v", [
        StageSpec(s, fn=fns[s], exec_time_s=0.01) for s in STAGES
    ]))
    for s in STAGES:
        ws.add_instance(f"{s}_0", stage=s)
    ws.add_instance("diffusion_1", stage="diffusion")  # scale the dominant stage
    proxy = ws.add_proxy("p0")

    reqs = [make_request(pipe, i) for i in range(4)]
    monos = [pipe.generate(r["tokens"], r["image"], seed=r["seed"]) for r in reqs]
    with ws:
        uids = [proxy.submit(APP, r) for r in reqs]
        outs = [proxy.wait_result(u, timeout_s=120) for u in uids]
    for out, mono in zip(outs, monos):
        np.testing.assert_allclose(out, mono, atol=1e-5)
    # the dominant stage was actually load-balanced
    d0 = ws.instances["aigc.diffusion_0"].stats.processed
    d1 = ws.instances["aigc.diffusion_1"].stats.processed
    assert d0 + d1 == 4 and d0 > 0 and d1 > 0


def test_batched_workflow_set_matches_monolithic(pipe):
    """Microbatched execution (max_batch=4): requests coalesce into one
    stacked jitted call per stage, yet every request's output must match
    its own per-request monolithic run — randomness is derived per seed,
    so batch composition can't leak between requests."""
    fns = build_stage_fns(pipe)
    ws = WorkflowSet("aigc_mb")
    ws.register_workflow(WorkflowSpec(APP, "i2v", [
        StageSpec(s, fn=fns[s], exec_time_s=0.01) for s in STAGES
    ]))
    for s in STAGES:
        # generous deadline: the submit_many burst fills max_batch at once,
        # so the wait only matters if the box stalls mid-poll — a short
        # deadline would then flush a partial batch and flake the
        # batches==1 assertion below.
        ws.add_instance(f"{s}_0", stage=s, max_batch=4, max_wait_s=2.0)
    proxy = ws.add_proxy("p0")

    reqs = [make_request(pipe, i) for i in range(4)]
    monos = [pipe.generate(r["tokens"], r["image"], seed=r["seed"]) for r in reqs]
    with ws:
        uids = proxy.submit_many(APP, reqs)
        outs = [proxy.wait_result(u, timeout_s=120) for u in uids]
    for out, mono in zip(outs, monos):
        assert out.shape == mono.shape
        np.testing.assert_allclose(out, mono, atol=1e-5)
    inst = ws.instances["aigc_mb.diffusion_0"]
    assert inst.stats.processed == 4
    assert inst.stats.batches == 1  # one stacked invocation, not four


def test_wan_dag_bit_identical_to_chain(pipe):
    """The acceptance bar (docs/workflows.md): Wan I2V expressed as the
    DAG it really is — text encoder ∥ image encoder joining into the DiT —
    must produce byte-identical frames to the linear-chain baseline, with
    both encoder branches genuinely running on their own instances."""
    chain_fns = build_stage_fns(pipe)
    dag_fns = build_dag_stage_fns(pipe)
    reqs = [make_request(pipe, i) for i in range(3)]

    def serve(name, stages):
        ws = WorkflowSet(name, control_loop=False)
        ws.register_workflow(WorkflowSpec(APP, name, stages))
        for s in [st.name for st in stages]:
            ws.add_instance(f"{s}_0", stage=s)
        proxy = ws.add_proxy("p0")
        with ws:
            uids = [proxy.submit(APP, r) for r in reqs]
            outs = [proxy.wait_result(u, timeout_s=120) for u in uids]
        return ws, outs

    _, chain_outs = serve("wchain", [
        StageSpec(s, fn=chain_fns[s], exec_time_s=0.01) for s in STAGES
    ])
    dag_ws, dag_outs = serve("wdag", [
        StageSpec(s, fn=dag_fns[s], exec_time_s=0.01, deps=DAG_DEPS[s])
        for s in DAG_DEPS
    ])
    for a, b in zip(chain_outs, dag_outs):
        assert a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the branches really ran in parallel stages, assembled by the join
    assert dag_ws.instances["wdag.text_encode_0"].stats.processed == 3
    assert dag_ws.instances["wdag.image_encode_0"].stats.processed == 3
    assert dag_ws.joins.stats.completed == 3
    assert dag_ws.dead_uids() == set()


def test_a2v_nested_dag_serves_end_to_end(pipe):
    """The second DAG scenario (audio → video, nested branch): asr →
    (llm → text_encode) ∥ image_encode → diffusion → vae_decode."""
    from repro.launch.serve import make_request, workflow_spec

    spec, _ = workflow_spec("a2v", pipe)
    ws = WorkflowSet("a2v", control_loop=False)
    ws.register_workflow(WorkflowSpec(APP, "a2v", spec.stages))
    for s in spec.stage_names():
        ws.add_instance(f"{s}_0", stage=s)
    proxy = ws.add_proxy("p0")
    rng = np.random.default_rng(0)
    reqs = [make_request("a2v", pipe.cfg, rng, i) for i in range(2)]
    with ws:
        uids = [proxy.submit(APP, r) for r in reqs]
        outs = [proxy.wait_result(u, timeout_s=120) for u in uids]
    for out in outs:
        assert np.isfinite(out).all()
    assert ws.joins.stats.completed == 2 and ws.dead_uids() == set()
    assert ws.instances["a2v.llm_0"].stats.processed == 2
    assert ws.instances["a2v.image_encode_0"].stats.processed == 2


def test_serve_exits_nonzero_with_traceback_on_stage_error(monkeypatch,
                                                          capsys):
    """A failing stage anywhere on the served path fails the launcher
    run, printing the traceback — never a quiet timeout and exit 0."""
    from repro.launch import serve

    real = serve.build_dag_stage_fns

    def with_refused_diffusion(pipe):
        fns = real(pipe)

        def refused(payload):
            raise RuntimeError("diffusion kernel refused")

        return dict(fns, diffusion=refused)

    monkeypatch.setattr(serve, "build_dag_stage_fns", with_refused_diffusion)
    rc = serve.main(["--workflow", "dag", "--requests", "2", "--no-elastic"])
    out = capsys.readouterr().out
    assert rc != 0
    assert "stage errors" in out and "diffusion kernel refused" in out
    assert "Traceback" in out


def test_theorem1_plan_for_measured_stage_times(pipe):
    times = measure_stage_times(pipe)
    chain = [times[s] for s in STAGES]
    plan = plan_chain(chain, 1)
    # diffusion dominates -> gets the most instances
    assert plan[2] == max(plan)
    assert plan[0] == 1
