"""§Perf variant flags must be *exact* (causal skip, ZeRO-3 gather) or
*boundedly approximate* (int8 cache) versus the paper-faithful baseline.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

from repro.configs import get_config
from repro.models import layers as L
from repro.models import registry as R
from repro.models.param import is_spec


def rand(i, shape):
    return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)


# ----------------------------------------------------- causal skip exactness
@pytest.mark.parametrize("s,h,kv,d", [(4096, 4, 2, 64), (2560, 2, 1, 32)])
def test_causal_skip_matches_baseline_blockwise(s, h, kv, d):
    b = 1
    q, k, v = rand(0, (b, s, h, d)), rand(1, (b, s, kv, d)), rand(2, (b, s, kv, d))
    base = L.attention_blockwise(q, k, v, causal=True, causal_skip=False)
    skip = L.attention_blockwise(q, k, v, causal=True, causal_skip=True)
    np.testing.assert_allclose(np.asarray(skip), np.asarray(base),
                               atol=3e-5, rtol=3e-5)


def test_causal_skip_gradients_match():
    b, s, h, d = 1, 2560, 2, 32
    q, k, v = rand(3, (b, s, h, d)), rand(4, (b, s, h, d)), rand(5, (b, s, h, d))

    def loss(fn_skip):
        def f(q_, k_, v_):
            return L.attention_blockwise(q_, k_, v_, causal=True,
                                         causal_skip=fn_skip).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g0, g1 = loss(False), loss(True)
    for a, b_ in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=5e-4, rtol=5e-4)


# ------------------------------------------------- zero-3 gather exactness
def test_fsdp_weight_gather_is_numerically_identical():
    cfg0 = dataclasses.replace(get_config("qwen3-1.7b").reduced(), dtype="float32")
    cfg1 = dataclasses.replace(cfg0, fsdp_weight_gather=True)
    params = R.init_params(jax.random.PRNGKey(0), cfg0)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg0.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg0.vocab_size),
    }
    l0, _ = R.loss_fn(params, batch, cfg0)
    l1, _ = R.loss_fn(params, batch, cfg1)
    # without an ambient partitioner constrain() is a no-op -> identical
    assert float(l0) == float(l1)


# --------------------------------------------------------- int8 cache decode
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-moe-16b"])
def test_int8_cache_decode_close_to_f32(arch):
    cfg8 = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                               cache_dtype="int8")
    cfgf = dataclasses.replace(cfg8, cache_dtype="float32")
    params = R.init_params(jax.random.PRNGKey(0), cfg8)
    b = 2
    toks = np.random.default_rng(0).integers(0, cfg8.vocab_size, (b, 8)).astype(np.int32)

    def run(cfg):
        spec = R.abstract_cache(cfg, b, 16)
        c = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
                         spec, is_leaf=is_spec)
        logits = None
        for t in range(8):
            logits, c = R.decode_step(
                params, c, {"tokens": jnp.asarray(toks[:, t]),
                            "cur_index": jnp.int32(t)}, cfg, dropless=True)[:2]
        return np.asarray(logits)

    l8, lf = run(cfg8), run(cfgf)
    # greedy decode must agree; probabilities close
    assert (l8.argmax(-1) == lf.argmax(-1)).all()
    p8 = np.asarray(jax.nn.softmax(l8))
    pf = np.asarray(jax.nn.softmax(lf))
    assert np.abs(p8 - pf).max() < 0.05


def test_int8_cache_spec_is_quarter_the_bytes():
    import math

    cfg8 = dataclasses.replace(get_config("qwen3-1.7b"), cache_dtype="int8")
    cfgf = dataclasses.replace(cfg8, cache_dtype="float32")

    def total(cfg):
        spec = R.abstract_cache(cfg, 8, 1024)
        by = 0
        for s in jax.tree.leaves(spec, is_leaf=is_spec):
            by += math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
        return by

    assert total(cfg8) < 0.30 * total(cfgf)  # int8 + scales vs f32
