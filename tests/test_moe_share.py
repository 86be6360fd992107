"""The MoE layer's expert share and its served dropless path, at a small
size on the CPU: the served path against the dense oracle, shares that add
up to the uncut layer, compiled FLOPs that do not grow with the experts
held, and the per-token tally of held-expert rows."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.moe import (_shared_mlp, held_experts,
                              moe_ffn_dense_fallback, moe_ffn_served,
                              moe_param_specs)
from repro.models.param import init_tree

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

ROUTER = 8


def _cfg(held=ROUTER, offset=0, top_k=3, router=ROUTER, shared=48):
    base = get_config("granite-4.0-h-small")
    return dataclasses.replace(
        base, d_model=32, d_ff=24, num_experts=held, router_experts=router,
        expert_offset=offset, top_k=top_k, shared_ff=shared, dtype="float32")


def _layer(cfg, key=0):
    """One layer's MoE weights for ``cfg``: router and shared MLP from the
    uncut layer, experts ``expert_offset ..`` of the uncut stack."""
    full = dataclasses.replace(cfg, num_experts=cfg.resolved_router_experts,
                               expert_offset=0)
    lp = jax.tree.map(lambda a: a[0], init_tree(
        jax.random.PRNGKey(key), moe_param_specs(full, 1, "float32")))
    lo, hi = cfg.expert_offset, cfg.expert_offset + cfg.num_experts
    return {k: (v[lo:hi] if k.startswith("we_") else v)
            for k, v in lp.items()}


def _x(t=12, d=32, key=1):
    return jax.random.normal(jax.random.PRNGKey(key), (2, t, d), jnp.float32)


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 0), (4, 4), (3, 2),
                                         (1, 7)])
def test_served_moe_matches_the_dense_oracle(held, offset):
    """Routed (token, held expert) rows only, against every token through
    every held expert gated by its routing; assignments to experts held
    elsewhere add nothing in either."""
    cfg = _cfg(held, offset)
    lp, x = _layer(cfg), _x()
    want, _ = moe_ffn_dense_fallback(x, lp, cfg)
    got, held_n = moe_ffn_served(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the tally: each token's assignments that landed on experts held here
    logits = jnp.einsum("bsd,de->bse", x, lp["router"])
    _, top_i = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    _, mask = held_experts(top_i, cfg)
    np.testing.assert_array_equal(np.asarray(held_n),
                                  np.asarray(mask.sum(-1), np.float32))
    if held < ROUTER:
        assert float(held_n.sum()) < x.shape[0] * x.shape[1] * cfg.top_k


@pytest.mark.parametrize("held", [4, 2])
def test_expert_shares_add_up_to_the_uncut_layer(held):
    """Over all shares of a layer, the held parts, with the shared MLP
    (which every chip computes alike) counted once, give the uncut layer."""
    uncut = _cfg()
    x = _x()
    want, _ = moe_ffn_dense_fallback(x, _layer(uncut), uncut)
    shared = np.asarray(_shared_mlp(x.reshape(-1, x.shape[-1]),
                                    _layer(uncut))).reshape(x.shape)
    total = shared
    for offset in range(0, ROUTER, held):
        cfg = _cfg(held, offset)
        part, _ = moe_ffn_served(x, _layer(cfg), cfg)
        total = total + (np.asarray(part) - shared)
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5, rtol=1e-5)


def _flops(fn, cfg, x):
    lp = jax.eval_shape(lambda: _layer(cfg))
    compiled = jax.jit(lambda x, lp: fn(x, lp, cfg)[0]).lower(x, lp).compile()
    return compiled.cost_analysis()["flops"]


def test_served_moe_flops_do_not_grow_with_the_experts_held():
    """The routing held fixed (32 experts routed over, top-2), the served
    layer's compiled FLOPs at 4 and at 16 held experts: the same, since it
    computes routed rows only; the dense oracle's grow with the count."""
    x = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    served = [_flops(moe_ffn_served, _cfg(h, 0, top_k=2, router=32, shared=0),
                     x) for h in (4, 16)]
    dense = [_flops(moe_ffn_dense_fallback,
                    _cfg(h, 0, top_k=2, router=32, shared=0), x)
             for h in (4, 16)]
    assert served[1] <= served[0] * 1.01, served
    assert dense[1] > 3 * dense[0], dense
    assert served[0] < dense[0]


def test_published_size_serves_one_chips_share():
    """``serve --llm-size published`` is the config at its published widths,
    cut to one chip's share where the whole model needs more chips: granite
    keeps 20 of 40 layers and 9 of the 72 experts its router spans; qwen3
    fits whole."""
    from repro.configs.granite_4h_small import CONFIG, ONE_CHIP
    from repro.launch.serve import llm_config

    g = llm_config("granite-4.0-h-small", "published")
    assert g == ONE_CHIP
    assert (g.num_layers, g.num_experts, g.resolved_router_experts) == (20, 9, 72)
    assert dataclasses.replace(g, num_layers=40, num_experts=72) == CONFIG
    assert llm_config("qwen3-1.7b", "published") == get_config("qwen3-1.7b")
    assert llm_config("qwen3-1.7b", "reduced").dtype == "float32"
