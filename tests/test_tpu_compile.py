"""Every Pallas kernel the dispatch layer can reach, compiled for a described
(not attached) TPU v5e chip at real widths.

Nothing runs: the TPU compiler is installed with JAX, so each compile
refuses here exactly what the chip's compiler would refuse (block shapes
off the (8, 128) tiling, too much VMEM) at no chip time.  The topology is
described inside a module-scoped fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

from repro.configs import get_config
from repro.configs.wan_i2v import FULL as WAN_FULL
from repro.kernels import (
    ddim_step,
    decode_attention_cache,
    decode_attention_int8_cache,
    flash_attention,
    ssd_scan,
    wkv6,
)

QWEN3 = get_config("qwen3-1.7b")
RWKV6 = get_config("rwkv6-7b")
GRANITE = get_config("granite-4.0-h-small")


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent compile
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _op_names(compiled):
    """The ops of the compiled program by name, as the device trace names
    them (``%<name>.<n> = ...``): the benchmark's kernel reducers match the
    prefixes ``flash_attention``, ``wkv6`` and ``ssd_scan``."""
    return set(re.findall(r"^\s*%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ",
                          compiled.as_text(), re.M))


@pytest.mark.parametrize("seq", [128, 4096])
def test_flash_attention_compiles_at_qwen3_prefill_widths(one_chip, seq):
    h, kv, d = QWEN3.num_heads, QWEN3.resolved_kv_heads, QWEN3.resolved_head_dim
    bf = jnp.bfloat16
    compiled = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                        interpret=False),
                        one_chip, ((4, seq, h, d), bf), ((4, seq, kv, d), bf),
                        ((4, seq, kv, d), bf))
    assert any(n.startswith("flash_attention") for n in _op_names(compiled))


@pytest.mark.parametrize("cache", ["bfloat16", "int8"])
def test_decode_cache_kernel_compiles_at_8_slots_x_4096(one_chip, cache):
    h, kv, d = QWEN3.num_heads, QWEN3.resolved_kv_heads, QWEN3.resolved_head_dim
    slots, s = 8, 4096
    q = ((slots, h, d), jnp.bfloat16)
    idx = ((), jnp.int32)
    if cache == "int8":
        kq = ((slots, kv, s, d), jnp.int8)
        sc = ((slots, kv, s), jnp.float32)
        _compile(lambda q, k, v, ks, vs, i: decode_attention_int8_cache(
            q, k, v, ks, vs, i, interpret=False), one_chip, q, kq, kq, sc, sc,
            idx)
    else:
        kc = ((slots, kv, s, d), jnp.bfloat16)
        _compile(lambda q, k, v, i: decode_attention_cache(
            q, k, v, i, interpret=False), one_chip, q, kc, kc, idx)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ddim_step_compiles_at_wan_full_latent(one_chip, dtype):
    p = WAN_FULL.patch
    latent = (1, WAN_FULL.video_tokens, p * p * WAN_FULL.vae_latent_ch)
    _compile(lambda x, e, a, b: ddim_step(x, e, a, b, interpret=False),
             one_chip, (latent, dtype), (latent, dtype), ((), jnp.float32),
             ((), jnp.float32))


def test_wkv6_compiles_at_rwkv6_7b_widths(one_chip):
    h, kk = RWKV6.num_heads, RWKV6.resolved_head_dim
    seq = ((1, 1024, h, kk), jnp.bfloat16)
    compiled = _compile(
        lambda r, k, v, w, u, s: wkv6(r, k, v, w, u, s, interpret=False),
        one_chip, seq, seq, seq, seq, ((h, kk), jnp.bfloat16),
        ((1, h, kk, kk), jnp.float32))
    assert any(n.startswith("wkv6") for n in _op_names(compiled))


@pytest.mark.parametrize("seq", [256, 1536])
def test_ssd_scan_compiles_at_granite_widths(one_chip, seq):
    p, n = GRANITE.ssm_head_dim, GRANITE.ssm_state
    h = GRANITE.d_inner // p
    f32 = jnp.float32
    compiled = _compile(
        lambda x, dt, la, b, c, s: ssd_scan(x, dt, la, b, c, s,
                                            interpret=False),
        one_chip, ((1, seq, h, p), f32), ((1, seq, h), f32),
        ((1, seq, h), f32), ((1, seq, n), f32), ((1, seq, n), f32),
        ((1, h, p, n), f32))
    assert any(n.startswith("ssd_scan") for n in _op_names(compiled))
