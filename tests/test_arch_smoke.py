"""Per-architecture smoke tests (assignment deliverable f).

Every assigned architecture is instantiated as a REDUCED variant of the same
family (2 layers, d_model<=256, <=4 experts — same GQA ratio, qk_norm,
sliding pattern, shared experts, hybrid period) and runs one forward/train
step on CPU asserting output shapes + finiteness.  A prefill<->decode
consistency check guards the KV-cache / recurrent-state plumbing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

from repro.configs import ARCH_IDS, get_config
from repro.models import registry as R
from repro.models.param import is_spec
from repro.training import adamw_init, make_train_step

jax.config.update("jax_enable_x64", False)


def reduced(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    # ensure pattern/hybrid period actually occurs at smoke depth
    if cfg.local_global_pattern != (0, 0):
        cfg = dataclasses.replace(cfg, num_layers=8)      # 1 period + tail
    if cfg.hybrid_attn_every:
        cfg = dataclasses.replace(cfg, num_layers=5, hybrid_attn_every=2)
    return cfg


def make_batch(cfg, b=2, s=16, key=0):
    k = jax.random.PRNGKey(key)
    batch = {
        "tokens": jax.random.randint(k, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(k, (b, s), 0, cfg.vocab_size),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = 0.01 * jax.random.normal(
            k, (b, min(cfg.frontend_tokens, s), cfg.d_model), jnp.float32
        )
    if cfg.family == "audio":
        batch["frames"] = 0.01 * jax.random.normal(
            k, (b, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    return batch


def zeros_cache(cfg, b, max_len):
    spec = R.abstract_cache(cfg, b, max_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
                        spec, is_leaf=is_spec)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch):
    cfg = reduced(arch)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    step = make_train_step(cfg, dropless=True)
    opt = adamw_init(params)
    p2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    moved = jax.tree.map(lambda a, b_: float(jnp.abs(a - b_).max()), params, p2)
    assert max(jax.tree.leaves(moved)) > 0
    # shapes preserved
    same = jax.tree.map(lambda a, b_: a.shape == b_.shape, params, p2)
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_loss_decreases(arch):
    """A few steps on a fixed tiny batch must reduce the loss."""
    cfg = reduced(arch)
    params = R.init_params(jax.random.PRNGKey(1), cfg)
    batch = make_batch(cfg, b=2, s=8)
    step = jax.jit(make_train_step(cfg, lr=5e-3, dropless=True))
    opt = adamw_init(params)
    first = last = None
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        first = first if first is not None else float(m["ce"])
        last = float(m["ce"])
    assert last < first, (first, last)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_and_decode_shapes(arch):
    cfg = reduced(arch)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 2, 16
    batch = make_batch(cfg, b, s)
    logits, cache = R.prefill(params, batch, cfg, dropless=True)
    assert logits.shape == (b, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits)).all()
    c = zeros_cache(cfg, b, 32)
    for t in range(3):
        logits, c = R.decode_step(
            params, c,
            {"tokens": jnp.full((b,), 3, jnp.int32), "cur_index": jnp.int32(t)},
            cfg, dropless=True,
        )[:2]
        assert logits.shape == (b, cfg.vocab_padded)
        assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_prefill(arch):
    """Teacher-forced decode through the cache must reproduce prefill logits
    for the same prefix — catches cache-update / position bugs."""
    cfg = reduced(arch)
    params = R.init_params(jax.random.PRNGKey(2), cfg)
    b, s = 2, 8
    batch = make_batch(cfg, b, s, key=7)
    tokens = batch["tokens"]

    if cfg.family == "audio":  # cross K/V must come from the encoder output
        from repro.models.encdec import make_decode_cache

        c = make_decode_cache(params, batch["frames"], cfg, 16)
    else:
        c = zeros_cache(cfg, b, 16)
    got = []
    for t in range(s):
        step_batch = {"tokens": tokens[:, t], "cur_index": jnp.int32(t)}
        logits, c = R.decode_step(params, c, step_batch, cfg, dropless=True)[:2]
        got.append(np.asarray(logits))

    for t in (0, s // 2, s - 1):
        pre_batch = dict(batch, tokens=tokens[:, : t + 1])
        if cfg.family == "vlm":
            pre_batch["patch_embeds"] = batch["patch_embeds"][:, : t + 1]
        want, _ = R.prefill(params, pre_batch, cfg, dropless=True)
        if cfg.family == "vlm" and t < batch["patch_embeds"].shape[1]:
            continue  # decode path has no patch injection for prompt positions
        np.testing.assert_allclose(got[t], np.asarray(want), rtol=2e-3, atol=2e-3)


def test_vocab_padding_multiple_of_round():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.vocab_padded % cfg.vocab_round == 0
        assert cfg.vocab_padded >= cfg.vocab_size


def test_active_params_less_than_total_for_moe():
    for arch in ("granite-moe-3b-a800m", "deepseek-moe-16b"):
        cfg = get_config(arch)
        assert R.count_active_params(cfg) < R.count_params(cfg)
