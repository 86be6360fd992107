"""Workflow message codec + pipeline planner + request monitor tests."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    RequestMonitor,
    WorkflowMessage,
    offered_rate,
    plan_chain,
    required_instances,
    simulate_pipeline,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


# ----------------------------------------------------------------- messaging
def test_roundtrip_bytes():
    m = WorkflowMessage.new(app_id=3, payload=b"\x00\x01binary\xff")
    m2 = WorkflowMessage.unpack(m.pack())
    assert m2.payload == m.payload and m2.app_id == 3 and m2.uid == m.uid


@pytest.mark.parametrize("dtype", ["float32", "float16", "int32", "uint8", "bool"])
def test_roundtrip_tensor_dtypes(dtype):
    x = (np.arange(24).reshape(2, 3, 4) % 2).astype(dtype)
    m2 = WorkflowMessage.unpack(WorkflowMessage.new(1, payload=x).pack())
    np.testing.assert_array_equal(m2.payload, x)


_WIRE_DTYPES = ["bfloat16", "int8", "float8_e4m3fn"]


def _wire_array(dtype, shape=(1, 8, 5, 16)):
    import ml_dtypes  # noqa: F401 -- registers the extension dtype names

    return (np.arange(np.prod(shape)) % 251 - 125).reshape(shape).astype(dtype)


@pytest.mark.parametrize("dtype", _WIRE_DTYPES)
def test_roundtrip_tensor_dtype_bytes_intact(dtype):
    """Model dtypes cross the fabric with dtype, shape and bytes intact —
    bfloat16 has no buffer-protocol format and a void type string."""
    x = _wire_array(dtype)
    out = WorkflowMessage.unpack(
        b"".join(bytes(p) for p in WorkflowMessage.new(1, payload=x).pack_parts())
    ).payload
    assert out.dtype == x.dtype and out.shape == x.shape
    assert out.tobytes() == x.tobytes()
    tree = WorkflowMessage.unpack(
        WorkflowMessage.new(1, payload={"x": [x]}).pack()).payload
    assert tree["x"][0].dtype == x.dtype
    assert tree["x"][0].tobytes() == x.tobytes()


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_roundtrip_kvpages_cache_dtypes(dtype):
    from repro.core.messaging import KVPages

    logits = np.linspace(-3, 3, 64, dtype=np.float32)
    pages = [logits, _wire_array(dtype), _wire_array(dtype, (1, 8, 5))]
    kv = KVPages(meta={"start": 5, "seed": 1}, pages=pages)
    out = WorkflowMessage.unpack(
        b"".join(bytes(p) for p in WorkflowMessage.new(7, payload=kv).pack_parts())
    ).payload
    assert out.meta == kv.meta and out.nbytes == kv.nbytes
    for got, want in zip(out.pages, pages):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def test_roundtrip_pytree():
    payload = {
        "latents": np.random.randn(2, 4, 8).astype(np.float32),
        "text_emb": np.random.randn(1, 16).astype(np.float16),
        "meta": {"steps": 50, "cfg": 7.5, "prompt": "a cat"},
        "frames": [np.zeros((3, 3), np.uint8), np.ones((2, 2), np.uint8)],
        "none": None,
    }
    m2 = WorkflowMessage.unpack(WorkflowMessage.new(9, payload=payload).pack())
    np.testing.assert_allclose(m2.payload["latents"], payload["latents"])
    np.testing.assert_allclose(m2.payload["text_emb"], payload["text_emb"])
    assert m2.payload["meta"] == payload["meta"]
    np.testing.assert_array_equal(m2.payload["frames"][1], payload["frames"][1])
    assert m2.payload["none"] is None


def test_dynamic_sizes_vary_per_message():
    """The L2 motivation: consecutive messages of different byte sizes."""
    sizes = set()
    for n in (0, 1, 7, 1000):
        m = WorkflowMessage.new(1, payload=np.zeros(n, np.float32))
        sizes.add(len(m.pack()))
    assert len(sizes) == 4


def test_next_stage_preserves_identity():
    m = WorkflowMessage.new(5, payload=b"x", stage=2)
    n = m.next_stage(b"y")
    assert n.uid == m.uid and n.timestamp == m.timestamp and n.stage == 3


if HAVE_HYPOTHESIS:

    @settings(max_examples=50, deadline=None)
    @given(st.binary(max_size=2000), st.integers(0, 2**31 - 1), st.integers(0, 100))
    def test_property_codec_roundtrip(blob, app_id, stage):
        m = WorkflowMessage.new(app_id, payload=blob, stage=stage)
        m2 = WorkflowMessage.unpack(m.pack())
        assert m2.payload == blob and m2.app_id == app_id and m2.stage == stage


# ------------------------------------------------------------------ Theorem 1
def test_theorem1_paper_example_fig5():
    """T_X=4, T_Y=12, K=1 -> M=3; output every 4 s (Figure 5)."""
    assert required_instances(4.0, 1, 12.0) == 3
    res = simulate_pipeline([4.0, 12.0], [1, 3], n_requests=30, arrival_period=4.0)
    assert res.rate_matched
    assert res.max_queue_depth == 0  # "no request is delayed within instances"
    assert max(res.latencies) == pytest.approx(16.0)  # T_X + T_Y


def test_theorem1_paper_example_fig6():
    """K=2 workers in X, M=6 instances in Y -> output every 2 s (Figure 6)."""
    assert required_instances(4.0, 2, 12.0) == 6
    res = simulate_pipeline([4.0, 12.0], [2, 6], n_requests=40, arrival_period=2.0)
    assert res.rate_matched
    assert res.output_rate == pytest.approx(0.5, rel=0.05)


def test_underprovisioned_stage_queues():
    res = simulate_pipeline([4.0, 12.0], [1, 2], n_requests=40, arrival_period=4.0)
    assert not res.rate_matched or res.max_queue_depth > 0
    assert max(res.latencies) > 16.0  # queueing delay appears


def test_plan_chain_multistage():
    # WAN-style chain: encode 1s, diffusion 12s, decode 2s
    plan = plan_chain([1.0, 12.0, 2.0], k_entrance=2)
    assert plan == [2, 24, 4]
    res = simulate_pipeline([1.0, 12.0, 2.0], plan, n_requests=60, arrival_period=0.5)
    assert res.rate_matched and res.max_queue_depth == 0


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        tx=st.floats(0.5, 10.0),
        ty=st.floats(0.5, 50.0),
        k=st.integers(1, 4),
    )
    def test_property_theorem1_rate_matching(tx, ty, k):
        """For any (T_X, T_Y, K), M = ceil(K*T_Y/T_X) keeps queues empty."""
        m = required_instances(tx, k, ty)
        res = simulate_pipeline([tx, ty], [k, m], n_requests=50, arrival_period=tx / k)
        assert res.max_queue_depth == 0
        assert max(res.latencies) == pytest.approx(tx + ty, rel=1e-6)


# ------------------------------------------------------------ request monitor
def test_fast_reject_over_rate():
    clock = [0.0]
    mon = RequestMonitor(t_entrance_s=1.0, k_entrance=2, window_s=1.0, clock=lambda: clock[0])
    # admissible rate = 2/s; hammer 10 requests at t=0
    admitted = sum(mon.try_admit() for _ in range(10))
    assert admitted == 2
    assert mon.stats.rejected == 8
    clock[0] += 1.01  # window slides
    assert mon.try_admit()


def test_monitor_capacity_update_from_nm():
    clock = [0.0]
    mon = RequestMonitor(1.0, 1, window_s=1.0, clock=lambda: clock[0])
    assert mon.try_admit() and not mon.try_admit()
    mon.update_capacity(1.0, 4)  # NM scaled the entrance stage up
    assert sum(mon.try_admit() for _ in range(5)) == 3  # 4 total in window
