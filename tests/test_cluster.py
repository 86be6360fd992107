"""Cluster-layer tests: NodeManager elastic assignment, Paxos safety,
database TTL/replication, proxy fast-reject, instance sharing, multi-set
fault isolation, end-to-end workflow execution over the RDMA fabric.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro.cluster import (
    DatabaseInstance,
    MultiSetFrontend,
    NMCluster,
    NodeManager,
    Rejected,
    ReplicatedDatabase,
    StageSpec,
    WorkflowSet,
    WorkflowSpec,
    elect_primary,
)
from repro.core import RequestMonitor


# ------------------------------------------------------------------- paxos
def test_paxos_single_winner_no_loss():
    decided = elect_primary([0, 1, 2, 3, 4])
    assert decided and len(set(decided)) == 1


@pytest.mark.parametrize("drop", [0.1, 0.3])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_paxos_safety_under_message_loss(drop, seed):
    """Concurrent proposers + lossy network: every decided value agrees."""
    decided = elect_primary([0, 1, 2], drop=drop, seed=seed)
    assert len(set(decided)) <= 1


def test_nm_cluster_failover_elects_new_primary():
    c = NMCluster(n_replicas=3)
    assert c.primary_id == 0
    c.fail(0)
    winner = c.maybe_elect(seed=42)
    assert winner in (1, 2)
    assert c.primary is c.replicas[winner]


# ------------------------------------------------------------ node manager
def _nm_with_stages():
    nm = NodeManager()
    nm.register_workflow(WorkflowSpec(1, "wf", [
        StageSpec("prep", exec_time_s=1.0),
        StageSpec("diffusion", exec_time_s=12.0),
        StageSpec("decode", exec_time_s=2.0),
    ]))
    for i in range(3):
        nm.register_instance(f"prep{i}")
        nm.assign(f"prep{i}", "prep")
    for i in range(3):
        nm.register_instance(f"diff{i}")
        nm.assign(f"diff{i}", "diffusion")
    nm.register_instance("idle0")  # idle pool
    return nm


def test_elastic_scaling_uses_idle_pool_first():
    nm = _nm_with_stages()
    for i in range(3):
        nm.report_utilization(f"diff{i}", 0.99)
        nm.report_utilization(f"prep{i}", 0.40)
    moved = nm.rebalance()
    assert moved == ("idle0", "diffusion")
    assert "idle0" in nm.stage_instances("diffusion")


def test_elastic_scaling_steals_from_underutilized_stage():
    nm = _nm_with_stages()
    nm.assign("idle0", "decode")  # no idle pool left
    for i in range(3):
        nm.report_utilization(f"diff{i}", 0.95)
        nm.report_utilization(f"prep{i}", 0.30)  # underutilized donor (Fig 10)
    nm.report_utilization("idle0", 0.5)
    inst, stage = nm.rebalance()
    assert stage == "diffusion" and inst.startswith("prep")
    assert len(nm.stage_instances("prep")) == 2  # donor not emptied


def test_no_rebalance_below_threshold():
    nm = _nm_with_stages()
    for i in range(3):
        nm.report_utilization(f"diff{i}", 0.5)
        nm.report_utilization(f"prep{i}", 0.5)
    assert nm.rebalance() is None


def test_theorem1_plan_from_nm():
    nm = _nm_with_stages()
    plan = nm.plan_stage_instances(1, k_entrance=2)
    assert plan == {"prep": 2, "diffusion": 24, "decode": 4}


# -------------------------------------------------------------- database
def test_database_ttl_and_purge_on_fetch():
    clock = [0.0]
    db = DatabaseInstance("d", default_ttl_s=10.0, clock=lambda: clock[0])
    db.store("u1", b"v1")
    assert db.fetch("u1") == b"v1"
    assert db.fetch("u1") is None  # purged on fetch
    db.store("u2", b"v2")
    clock[0] += 11.0
    assert db.fetch("u2") is None  # TTL expired


def test_replicated_database_failover():
    a, b = DatabaseInstance("a"), DatabaseInstance("b")
    rd = ReplicatedDatabase([a, b])
    rd.store("u", 42)
    a.alive = False
    assert rd.fetch("u") == 42  # falls through to replica b


def test_replicated_database_all_down():
    a = DatabaseInstance("a")
    a.alive = False
    with pytest.raises(ConnectionError):
        ReplicatedDatabase([a]).store("u", 1)


# ---------------------------------------------------------- end-to-end WS
def make_simple_ws(name="ws", reject_rate=None):
    ws = WorkflowSet(name)
    ws.register_workflow(WorkflowSpec(1, "mul-add", [
        StageSpec("mul", fn=lambda p: p * 2.0, exec_time_s=0.001),
        StageSpec("add", fn=lambda p: p + 1.0, exec_time_s=0.001),
    ]))
    ws.add_instance("m0", stage="mul")
    ws.add_instance("a0", stage="add")
    mon = None
    if reject_rate is not None:
        mon = RequestMonitor(t_entrance_s=1.0, k_entrance=reject_rate)
    ws.add_proxy("p0", monitor=mon)
    return ws


def test_end_to_end_workflow_tensor_payload():
    ws = make_simple_ws()
    with ws:
        p = ws.proxies[0]
        uid = p.submit(1, np.arange(6, dtype=np.float32).reshape(2, 3))
        res = p.wait_result(uid, timeout_s=5)
    np.testing.assert_allclose(res, np.arange(6, dtype=np.float32).reshape(2, 3) * 2 + 1)


def test_uid_tracks_request_through_lifecycle():
    ws = make_simple_ws()
    with ws:
        p = ws.proxies[0]
        uids = [p.submit(1, np.float32(i)) for i in range(8)]
        assert len(set(uids)) == 8  # unique per request
        results = {u: p.wait_result(u, timeout_s=5) for u in uids}
    for i, u in enumerate(uids):
        assert results[u] == np.float32(i * 2 + 1)


def test_stage_error_counted_with_traceback():
    """A stage fn that raises still drops its request under §9, but the
    set counts the exception and keeps its traceback."""
    def refused(payload):
        raise RuntimeError("kernel refused by the compiler")

    ws = WorkflowSet("err", control_loop=False)
    ws.register_workflow(WorkflowSpec(1, "wf", [StageSpec("s", fn=refused)]))
    ws.add_instance("s0", stage="s")
    p = ws.add_proxy("p0")
    with ws:
        uid = p.submit(1, {"x": np.zeros(2, np.float32)})
        deadline = time.monotonic() + 10
        while uid not in ws.joins.dropped_snapshot() and \
                time.monotonic() < deadline:
            time.sleep(0.005)
    stats = ws.transport_stats()
    assert stats.stage_errors == 1
    assert "Traceback" in stats.first_error
    assert "kernel refused by the compiler" in stats.first_error
    assert uid in ws.dead_uids()


@pytest.mark.parametrize("inline", [True, False])
def test_stage_room_gate_holds_requests_upstream(inline):
    """While a stage's ``room`` gate reads 0 its instance takes nothing,
    whether it runs the stage inline or on a worker: the request waits in
    the inbox and runs once the gate opens."""
    gate = {"room": 0}
    ws = WorkflowSet("gate", control_loop=False)
    ws.register_workflow(WorkflowSpec(1, "wf", [
        StageSpec("s", fn=lambda x: x + 1.0, room=lambda: gate["room"])]))
    ws.add_instance("s0", stage="s", inline=inline)
    p = ws.add_proxy("p0")
    with ws:
        uid = p.submit(1, np.float32(1.0))
        time.sleep(0.2)
        assert p.poll_result(uid) is None
        assert ws.instances["gate.s0"].stats.processed == 0
        gate["room"] = 1
        assert p.wait_result(uid, timeout_s=5) == 2.0


def test_instance_sharing_across_workflows():
    """§8.3: two apps share the 'mul' stage instances, diverge afterwards."""
    ws = WorkflowSet("share")
    ws.register_workflow(WorkflowSpec(1, "a", [
        StageSpec("mul", fn=lambda p: p * 2.0, exec_time_s=0.001),
        StageSpec("add", fn=lambda p: p + 1.0, exec_time_s=0.001),
    ]))
    ws.register_workflow(WorkflowSpec(2, "b", [
        StageSpec("mul", fn=lambda p: p * 2.0, exec_time_s=0.001),
        StageSpec("sub", fn=lambda p: p - 5.0, exec_time_s=0.001),
    ]))
    ws.add_instance("m0", stage="mul")   # shared by app 1 and app 2
    ws.add_instance("a0", stage="add")
    ws.add_instance("s0", stage="sub")
    p = ws.add_proxy("p0")
    with ws:
        u1 = p.submit(1, np.float32(10.0))
        u2 = p.submit(2, np.float32(10.0))
        assert p.wait_result(u1, timeout_s=5) == 21.0
        assert p.wait_result(u2, timeout_s=5) == 15.0
    assert ws.instances["share.m0"].stats.processed == 2


def test_proxy_fast_reject_and_multiset_retry():
    ws1 = make_simple_ws("s1", reject_rate=0)   # admits nothing
    ws2 = make_simple_ws("s2")                  # unbounded
    with ws1, ws2:
        front = MultiSetFrontend([ws1, ws2], seed=3)
        got_ws, uid = front.submit(1, np.float32(1.0))
        assert got_ws is ws2  # rejected by s1, landed on s2
        assert got_ws.proxies[0].wait_result(uid, timeout_s=5) == 3.0
    assert ws1.proxies[0].monitor.stats.rejected >= 0


def test_nm_reassignment_repurposes_instance_live():
    """An idle instance assigned mid-run starts taking work (§8.2)."""
    ws = make_simple_ws()
    idle = ws.add_instance("extra")  # no stage: idle pool
    with ws:
        p = ws.proxies[0]
        uid = p.submit(1, np.float32(2.0))
        assert p.wait_result(uid, timeout_s=5) == 5.0
        assert ws.nm.get_assignment("ws.extra")[0] is None
        ws.nm.assign("ws.extra", "mul")
        time.sleep(0.05)  # manager loop picks up the new version
        uids = [p.submit(1, np.float32(i)) for i in range(12)]
        for i, u in enumerate(uids):
            assert p.wait_result(u, timeout_s=5) == np.float32(i * 2 + 1)
    assert ws.instances["ws.extra"].stats.processed > 0


def test_collaboration_mode_all_workers_one_request():
    ws = WorkflowSet("cm")
    import numpy as _np

    def cm_stage(p, worker_idx=0, n_workers=1):
        # each worker computes a shard of the output (TP-style)
        return _np.full((2,), float(worker_idx), dtype=_np.float32)

    ws.register_workflow(WorkflowSpec(1, "cm", [
        StageSpec("shard", fn=cm_stage, exec_time_s=0.001, mode="CM"),
    ]))
    ws.add_instance("c0", stage="shard", n_workers=3, mode="CM")
    p = ws.add_proxy("p0")
    with ws:
        uid = p.submit(1, np.float32(0.0))
        res = p.wait_result(uid, timeout_s=5)
    np.testing.assert_allclose(res, [0, 0, 1, 1, 2, 2])  # aggregated shards
