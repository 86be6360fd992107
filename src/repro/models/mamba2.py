"""Mamba2 (SSD) blocks and the two hybrid stacks built from them.

Mamba2 block: in-proj -> (z, xBC, dt); depthwise causal conv over xBC;
selective state-space recurrence
    S_t = exp(dt_t * A) S_{t-1} + (dt_t x_t) B_t^T ,   y_t = S_t C_t + D x_t
with per-head scalar A; gated RMSNorm; out-proj.  The prefill recurrence
runs the ``repro.kernels.ssd_scan`` Pallas kernel where the dispatch layer
says so (``models/layers.py``); decode is the single-step update.

Zamba2 (arXiv:2411.15242): a stack of Mamba2 layers with ONE shared
transformer block (attention + SwiGLU, weights reused) applied after every
``hybrid_attn_every`` SSM layers — scan over periods with the shared block
closed over.  Decode state: per-layer (conv_state [B,conv_dim,3],
ssd_state [B,H,hd,d_state]) + a KV cache per shared-block application.

Layer pattern (granite-4.0-h, ``attn_layer_period`` > 0): periods of
``attn_layer_period`` layers, each a Mamba2 mixer but the attention mixer
(``transformer._attention``) at ``attn_layer_offset``; after every mixer
the MoE of ``models/moe.py`` plus its shared MLP.  Both residual branches
are scaled by ``residual_multiplier``, embeddings by
``embedding_multiplier``, logits divided by ``logits_scaling``.  Scanned
over periods as ``transformer.py`` scans gemma3's.  Decode state: the
Mamba2 states of every Mamba2 layer and a KV cache per attention layer.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.param import ParamSpec, constrain

Tree = Dict[str, Any]
CONV_WIDTH = 4
N_GROUPS = 1


def _dims(cfg: ModelConfig):
    d_inner = cfg.d_inner
    n_heads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * N_GROUPS * cfg.ssm_state
    d_in_proj = 2 * d_inner + 2 * N_GROUPS * cfg.ssm_state + n_heads
    return d_inner, n_heads, conv_dim, d_in_proj


def mamba_param_specs(cfg: ModelConfig, nl: int) -> Tree:
    dt = cfg.dtype
    d = cfg.d_model
    d_inner, n_heads, conv_dim, d_in_proj = _dims(cfg)
    return {
        "norm": ParamSpec((nl, d), ("layers", "embed"), dt, "zeros"),
        "w_in": ParamSpec((nl, d, d_in_proj), ("layers", "embed", "ssm_inner"), dt),
        "conv_w": ParamSpec((nl, conv_dim, CONV_WIDTH), ("layers", "ssm_inner", None), dt),
        "conv_b": ParamSpec((nl, conv_dim), ("layers", "ssm_inner"), dt, "zeros"),
        "dt_bias": ParamSpec((nl, n_heads), ("layers", "ssm_heads"), "float32", "zeros"),
        "a_log": ParamSpec((nl, n_heads), ("layers", "ssm_heads"), "float32", "zeros"),
        "d_skip": ParamSpec((nl, n_heads), ("layers", "ssm_heads"), "float32", "ones"),
        "gn_w": ParamSpec((nl, d_inner), ("layers", "ssm_inner"), dt, "zeros"),
        "w_out": ParamSpec((nl, d_inner, d), ("layers", "ssm_inner", "embed"), dt),
    }


# ----------------------------------------------------------------- ssd core
def ssd_scan(x, dt, la, B, C, state, chunk: int = 256, use_pallas=None):
    """x: [B,T,H,P]; dt/la: [B,T,H] (step size, log decay); B/C: [B,T,N];
    state: [B,H,P,N].  Returns (y [B,T,H,P], final state).

    ``use_pallas`` routes to the ``repro.kernels.ssd_scan`` Pallas kernel
    (forward-only — the train path forces this scan, whose checkpointed
    chunks the backward needs); the scan below is its oracle.  Chunked +
    checkpointed so the backward saves state per chunk, not per step (cf.
    rwkv6.wkv6_scan)."""
    if L.resolve_use_pallas(use_pallas):
        from repro.kernels.ssd_scan.ops import ssd_scan as ssd_kernel

        L._record("ssd_scan", "pallas")
        return ssd_kernel(x, dt, la, B, C, state.astype(jnp.float32))
    L._record("ssd_scan", "reference")

    def step(s, xs):
        xt, dtt, lat, bt, ct = xs
        s = jnp.exp(lat)[..., None, None] * s + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt
        )
        y = jnp.einsum("bhpn,bn->bhp", s, ct)
        return s, y

    bsz, t = x.shape[0], x.shape[1]
    chunk = min(chunk, t)
    while t % chunk:
        chunk //= 2
    nc = t // chunk
    xs = jax.tree.map(
        lambda v: v.reshape(v.shape[0], nc, chunk, *v.shape[2:]).swapaxes(0, 1),
        (x, dt, la, B, C),
    )

    @jax.checkpoint
    def chunk_body(s, xs_c):
        xs_t = jax.tree.map(lambda v: v.swapaxes(0, 1), xs_c)
        s, ys = jax.lax.scan(step, s, xs_t)
        return s, ys.swapaxes(0, 1)

    state, ys = jax.lax.scan(chunk_body, state, xs)
    ys = ys.swapaxes(0, 1).reshape(bsz, t, *ys.shape[3:])
    return ys, state


def ssd_step(x, dt, la, B, C, state):
    """Single token: x [B,H,P], dt/la [B,H], B/C [B,N].  The float32 state
    is read at float32 precision, as the prefill kernel reads it."""
    hi = jax.lax.Precision.HIGHEST
    state = jnp.exp(la)[..., None, None] * state + jnp.einsum(
        "bhp,bn->bhpn", x * dt[..., None], B, precision=hi
    )
    y = jnp.einsum("bhpn,bn->bhp", state, C, precision=hi)
    return y, state


def _causal_conv_seq(x, w, b):
    """Depthwise causal conv, x: [B,T,C], w: [C,W]."""
    pads = [jnp.pad(x, ((0, 0), (CONV_WIDTH - 1 - i, i), (0, 0)))[:, : x.shape[1]]
            for i in range(CONV_WIDTH)]
    out = sum(p * w[None, None, :, i] for i, p in enumerate(pads))
    return out + b[None, None]


def _gated_norm(y, z, w, eps):
    return L.rms_norm(y * jax.nn.silu(z), w, eps)


def mamba_layer(x, lp, cfg: ModelConfig, cache, seq_mode: bool,
                use_pallas=None):
    """cache: (conv_state [B,conv_dim,W-1], ssd_state [B,H,P,N]).
    Returns (residual delta, new cache)."""
    bsz, t, d = x.shape
    d_inner, n_heads, conv_dim, _ = _dims(cfg)
    hd, ns = cfg.ssm_head_dim, cfg.ssm_state
    conv_state, ssd_state = cache

    xn = L.rms_norm(x, lp["norm"], cfg.norm_eps)
    zxbcdt = xn @ lp["w_in"]
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : d_inner + conv_dim]
    dt_raw = zxbcdt[..., d_inner + conv_dim :]  # [B,T,H]

    if seq_mode:
        xBC_conv = jax.nn.silu(_causal_conv_seq(xBC, lp["conv_w"], lp["conv_b"]))
        # keep last W-1 inputs for decode continuation
        new_conv = xBC[:, -(CONV_WIDTH - 1) :].swapaxes(1, 2) if t >= CONV_WIDTH - 1 \
            else jnp.concatenate([conv_state, xBC.swapaxes(1, 2)], -1)[..., -(CONV_WIDTH - 1):]
    else:
        hist = jnp.concatenate([conv_state, xBC.swapaxes(1, 2)], axis=-1)  # [B,C,W]
        out = (hist * lp["conv_w"][None]).sum(-1) + lp["conv_b"][None]
        xBC_conv = jax.nn.silu(out)[:, None]
        new_conv = hist[..., 1:]

    xs = xBC_conv[..., :d_inner].reshape(bsz, t, n_heads, hd)
    Bm = xBC_conv[..., d_inner : d_inner + ns]
    Cm = xBC_conv[..., d_inner + ns :]
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
    la = -jnp.exp(lp["a_log"]) * dtv  # [B,T,H] log decay
    xs32 = xs.astype(jnp.float32)
    if seq_mode:
        y, new_ssd = ssd_scan(xs32, dtv, la, Bm.astype(jnp.float32),
                              Cm.astype(jnp.float32), ssd_state,
                              use_pallas=use_pallas)
    else:
        y, new_ssd = ssd_step(xs32[:, 0], dtv[:, 0], la[:, 0],
                              Bm.astype(jnp.float32)[:, 0],
                              Cm.astype(jnp.float32)[:, 0], ssd_state)
        y = y[:, None]
    y = y + lp["d_skip"][None, None, :, None] * xs32
    y = y.reshape(bsz, t, d_inner).astype(x.dtype)
    out = _gated_norm(y, z, lp["gn_w"], cfg.norm_eps) @ lp["w_out"]
    return out, (new_conv, new_ssd)


# ------------------------------------------------------- zamba2 shared block
def shared_block_specs(cfg: ModelConfig) -> Tree:
    from repro.models.transformer import _attn_specs, _mlp_specs

    p = _attn_specs(cfg, 1, cfg.dtype)
    p.update(_mlp_specs(cfg, 1, cfg.dtype))
    return jax.tree.map(
        lambda s: ParamSpec(s.shape[1:], s.logical[1:], s.dtype, s.init),
        p, is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _shared_block(x, sp, cfg: ModelConfig, mode, cache, cur_index):
    from repro.models.transformer import _attention, _sincos

    sincos = _sincos(cfg, _positions(x, mode, cur_index))
    delta, new_cache = _attention(x, sp, cfg, mode, sincos, 0, cache, cur_index)
    x = x + delta
    h = L.rms_norm(x, sp["mlp_norm"], cfg.norm_eps)
    x = x + L.swiglu(h, sp["w_gate"], sp["w_up"], sp["w_down"])
    return constrain(x, "batch", "seq_res", "act_embed"), new_cache


# ------------------------------------------------------------------ zamba2
def _zamba_params(cfg: ModelConfig) -> Tree:
    dt = cfg.dtype
    p: Tree = {
        "embedding": ParamSpec((cfg.vocab_padded, cfg.d_model), ("vocab", "embed"), dt, "small"),
        "final_norm": ParamSpec((cfg.d_model,), ("embed",), dt, "zeros"),
        "unembed": ParamSpec((cfg.d_model, cfg.vocab_padded), ("embed", "vocab"), dt, "small"),
        "layers": mamba_param_specs(cfg, cfg.num_layers),
    }
    if cfg.hybrid_attn_every:
        p["shared"] = shared_block_specs(cfg)
    return p


def _periods(cfg: ModelConfig) -> Tuple[int, int, int]:
    every = cfg.hybrid_attn_every or cfg.num_layers
    return cfg.num_layers // every, every, cfg.num_layers % every


def _zero_mamba_cache(cfg: ModelConfig, batch: int, nl: int):
    d_inner, n_heads, conv_dim, _ = _dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    return (
        jnp.zeros((nl, batch, conv_dim, CONV_WIDTH - 1), dt),
        jnp.zeros((nl, batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
    )


def _stack(params, x, cfg: ModelConfig, mode, cache, cur_index, remat):
    n_p, every, tail = _periods(cfg)
    seq_mode = mode != "decode"
    up = "off" if mode == "train" else cfg.use_pallas   # kernels: forward only
    n_main = n_p * every
    shared = params.get("shared")

    mcache = cache["mamba"] if cache else _zero_mamba_cache(cfg, x.shape[0], cfg.num_layers)
    main_c = jax.tree.map(lambda a: a[:n_main].reshape((n_p, every) + a.shape[1:]), mcache)
    tail_c = jax.tree.map(lambda a: a[n_main:], mcache)
    main_p = jax.tree.map(lambda a: a[:n_main].reshape((n_p, every) + a.shape[1:]),
                          params["layers"])
    tail_p = jax.tree.map(lambda a: a[n_main:], params["layers"])
    attn_c = cache.get("attn") if (cache and shared is not None) else None

    def period(carry, xs):
        xx = carry
        lp_p, mc_p, ac = xs

        def inner(c2, xs2):
            lp, mc = xs2
            delta, nmc = mamba_layer(c2, lp, cfg, mc, seq_mode, up)
            return constrain(c2 + delta, "batch", "seq_res", "act_embed"), nmc

        xx, nmc = jax.lax.scan(inner, xx, (lp_p, mc_p))
        nac = None
        if shared is not None:
            xx, nac = _shared_block(xx, shared, cfg, mode, ac, cur_index)
        return xx, (nmc, nac)

    if remat:
        period = jax.checkpoint(period, policy=jax.checkpoint_policies.nothing_saveable)

    new_cache: Tree = {}
    if n_p:
        x, (nmc_main, nac) = jax.lax.scan(period, x, (main_p, main_c, attn_c))
    else:
        nmc_main, nac = None, None

    ntail = []
    for i in range(tail):
        lp = jax.tree.map(lambda a: a[i], tail_p)
        mc = jax.tree.map(lambda a: a[i], tail_c)
        delta, nmc = mamba_layer(x, lp, cfg, mc, seq_mode, up)
        x = constrain(x + delta, "batch", "seq_res", "act_embed")
        ntail.append(nmc)

    if mode != "train":
        parts = []
        if nmc_main is not None:
            parts.append(jax.tree.map(
                lambda a: a.reshape((n_main,) + a.shape[2:]), nmc_main))
        if ntail:
            parts.append(jax.tree.map(lambda *xs: jnp.stack(xs), *ntail))
        new_cache["mamba"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, 0), *parts
        ) if len(parts) > 1 else parts[0]
        if nac is not None:
            new_cache["attn"] = nac
    return x, (new_cache or None)


def _zamba_loss(params: Tree, batch: Tree, cfg: ModelConfig, **_):
    x = jnp.take(params["embedding"], batch["tokens"], axis=0)
    x = constrain(x, "batch", "seq_res", "act_embed")
    x, _ = _stack(params, x, cfg, "train", None, None, remat=True)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    ce = L.chunked_cross_entropy(x, params["unembed"], batch["labels"])
    return ce, {"ce": ce, "aux": 0.0}


def _zamba_prefill(params: Tree, batch: Tree, cfg: ModelConfig, **_):
    x = jnp.take(params["embedding"], batch["tokens"], axis=0)
    x, cache = _stack(params, x, cfg, "prefill", None, None, remat=False)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x[:, -1] @ params["unembed"]).astype(jnp.float32), cache


def _zamba_decode(params: Tree, cache: Tree, batch: Tree, cfg: ModelConfig, **_):
    x = jnp.take(params["embedding"], batch["tokens"][:, None], axis=0)
    x, ncache = _stack(params, x, cfg, "decode", cache, batch["cur_index"], remat=False)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x[:, 0] @ params["unembed"]).astype(jnp.float32), ncache


# ------------------------------------------------ layer pattern (granite-4.0-h)
def hybrid_pattern(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_periods, period, attention offset) of a layer-pattern stack."""
    period = cfg.attn_layer_period
    if cfg.num_layers % period:
        raise ValueError(f"{cfg.name}: {cfg.num_layers} layers are not whole "
                         f"periods of {period}")
    return cfg.num_layers // period, period, cfg.attn_layer_offset


EXPERTS = ("we_gate", "we_up", "we_down")


def _pattern_params(cfg: ModelConfig) -> Tree:
    from repro.models.moe import moe_param_specs
    from repro.models.transformer import _attn_specs

    n_p, period, _ = hybrid_pattern(cfg)
    dt, v, d = cfg.dtype, cfg.vocab_padded, cfg.d_model
    p: Tree = {
        "embedding": ParamSpec((v, d), ("vocab", "embed"), dt, "small"),
        "final_norm": ParamSpec((d,), ("embed",), dt, "zeros"),
        "mamba": mamba_param_specs(cfg, n_p * (period - 1)),
        "attn": _attn_specs(cfg, n_p, dt),
        "moe": moe_param_specs(cfg, cfg.num_layers, dt),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = ParamSpec((d, v), ("embed", "vocab"), dt, "small")
    return p


def _positions(x, mode, cur_index):
    if mode == "decode":
        if jnp.ndim(cur_index) > 0:  # per-row positions [B] -> [B,1]
            return jnp.asarray(cur_index, jnp.int32)[:, None]
        return jnp.full((x.shape[0], 1), cur_index, jnp.int32)
    return jnp.arange(x.shape[1])[None, :].repeat(x.shape[0], 0)


def _take(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _put(tree, new, i):
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(
            a, n.astype(a.dtype), i, 0), tree, new)


def _pattern_stack(params, x, cfg: ModelConfig, mode, cache, cur_index,
                   remat, dropless):
    """-> (hidden, new cache, aux): aux is the routers' balance loss, or on
    the served MoE path each token's held-expert rows over all layers.

    The caches of every layer ride through the layer loops as one carried
    buffer per leaf, each layer reading and writing its own row, so a
    decode step updates the slot state in place rather than stacking a
    fresh copy of it."""
    from repro.models.transformer import _attention, _ffn, _sincos, served_moe

    n_p, period, off = hybrid_pattern(cfg)
    nm = period - 1
    seq_mode = mode != "decode"
    up = "off" if mode == "train" else cfg.use_pallas   # kernels: forward only
    served = served_moe(mode, dropless)
    rm = jnp.asarray(cfg.residual_multiplier, x.dtype)
    sincos = _sincos(cfg, _positions(x, mode, cur_index))
    b, t = x.shape[:2]

    def periods(a, n):
        return a.reshape((n_p, n) + a.shape[1:])

    mc = cache["mamba"] if cache else _zero_mamba_cache(cfg, b, n_p * nm)
    if cache:
        kv = cache["attn"]
    else:   # prefill: each attention layer's keys and values of the prompt
        shape = (n_p, b, cfg.resolved_kv_heads, t, cfg.resolved_head_dim)
        cd = jnp.dtype(cfg.resolved_cache_dtype)
        kv = (jnp.zeros(shape, cd), jnp.zeros(shape, cd))
    mp = jax.tree.map(lambda a: periods(a, nm), params["mamba"])
    experts = {k: params["moe"][k] for k in EXPERTS}
    moe_p = jax.tree.map(lambda a: periods(a, period),
                         {k: a for k, a in params["moe"].items()
                          if k not in EXPERTS})

    def moe_block(xx, lp, layer):
        if served:   # the loop reads this layer's experts in place
            return _ffn(xx, dict(lp, **experts), cfg, True, dropless, mode,
                        layer=layer)
        return _ffn(xx, dict(lp, **{k: w[layer] for k, w in experts.items()}),
                    cfg, True, dropless, mode)

    def residual(xx, aux, delta, moe_lp, layer):
        xx = xx + rm * delta
        ff, a = moe_block(xx, moe_lp, layer)
        return constrain(xx + rm * ff, "batch", "seq_res", "act_embed"), aux + a

    def mamba_run(carry, lps, moe_lps, first, layers):
        """Mamba2 layers ``first ..`` of the stack (global layer indices
        ``layers``), each followed by its MoE."""
        if layers.shape[0] == 0:
            return carry

        def body(carry, xs):
            xx, aux, mc = carry
            lp, moe_lp, j, layer = xs
            delta, nc = mamba_layer(xx, lp, cfg, _take(mc, j), seq_mode, up)
            xx, aux = residual(xx, aux, delta, moe_lp, layer)
            return (xx, aux, _put(mc, nc, j)), None

        js = first + jnp.arange(layers.shape[0])
        carry, _ = jax.lax.scan(body, carry, (lps, moe_lps, js, layers))
        return carry

    def period_body(carry, xs):
        xx, aux, mc, kv = carry
        i, lp_m, lp_a, lp_moe = xs
        layers = i * period + jnp.arange(period)

        def part(tree, lo, hi):
            return jax.tree.map(lambda a: a[lo:hi], tree)

        xx, aux, mc = mamba_run((xx, aux, mc), part(lp_m, 0, off),
                                part(lp_moe, 0, off), i * nm, layers[:off])
        delta, nkv = _attention(xx, lp_a, cfg, mode, sincos, 0,
                                _take(kv, i) if cache else None, cur_index)
        xx, aux = residual(xx, aux, delta,
                           jax.tree.map(lambda a: a[off], lp_moe),
                           layers[off])
        if nkv is not None:   # train keeps no cache
            kv = _put(kv, nkv, i)
        xx, aux, mc = mamba_run((xx, aux, mc), part(lp_m, off, nm),
                                part(lp_moe, off + 1, period), i * nm + off,
                                layers[off + 1:])
        return (xx, aux, mc, kv), None

    if remat:
        period_body = jax.checkpoint(
            period_body, policy=jax.checkpoint_policies.nothing_saveable)
    aux0 = jnp.zeros(x.shape[:2] if served else (), jnp.float32)
    (x, aux, mc, kv), _ = jax.lax.scan(
        period_body, (x, aux0, mc, kv),
        (jnp.arange(n_p), mp, params["attn"], moe_p))
    new_cache = None if mode == "train" else {"mamba": mc, "attn": kv}
    return x, new_cache, aux


def _pattern_embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embedding"], tokens, axis=0)
    x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return constrain(x, "batch", "seq_res", "act_embed")


def _unembed(params, cfg: ModelConfig):
    return params["embedding"].T if cfg.tie_embeddings else params["unembed"]


def _pattern_logits(params, x, cfg: ModelConfig):
    return (x @ _unembed(params, cfg)).astype(jnp.float32) / cfg.logits_scaling


# ---------------------------------------------------------------- public API
def abstract_params(cfg: ModelConfig) -> Tree:
    if cfg.attn_layer_period:
        return _pattern_params(cfg)
    return _zamba_params(cfg)


def loss_fn(params: Tree, batch: Tree, cfg: ModelConfig, *,
            dropless: bool = False, **_):
    if not cfg.attn_layer_period:
        return _zamba_loss(params, batch, cfg)
    x = _pattern_embed(params, batch["tokens"], cfg)
    x, _, aux = _pattern_stack(params, x, cfg, "train", None, None,
                               remat=True, dropless=dropless)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = _unembed(params, cfg) / jnp.asarray(cfg.logits_scaling, x.dtype)
    ce = L.chunked_cross_entropy(x, w, batch["labels"])
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


def prefill(params: Tree, batch: Tree, cfg: ModelConfig, *,
            dropless: bool = False, **_):
    if not cfg.attn_layer_period:
        return _zamba_prefill(params, batch, cfg)
    x = _pattern_embed(params, batch["tokens"], cfg)
    x, cache, _ = _pattern_stack(params, x, cfg, "prefill", None, None,
                                 remat=False, dropless=dropless)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _pattern_logits(params, x[:, -1], cfg), cache


def decode_step(params: Tree, cache: Tree, batch: Tree, cfg: ModelConfig, *,
                dropless: bool = False, **_):
    """Returns (logits [B,V], new cache); the served MoE of a layer-pattern
    stack (``dropless``) adds each row's assignments to held experts over
    all layers [B]."""
    if not cfg.attn_layer_period:
        return _zamba_decode(params, cache, batch, cfg)
    x = _pattern_embed(params, batch["tokens"][:, None], cfg)
    x, ncache, aux = _pattern_stack(params, x, cfg, "decode", cache,
                                    batch["cur_index"], remat=False,
                                    dropless=dropless)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _pattern_logits(params, x[:, 0], cfg)
    if dropless:
        return logits, ncache, aux[:, 0]
    return logits, ncache


def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Tree:
    d_inner, n_heads, conv_dim, _ = _dims(cfg)
    if cfg.attn_layer_period:
        n_p, period, _ = hybrid_pattern(cfg)
        nl, n_attn = n_p * (period - 1), n_p
    else:
        n_p, every, tail = _periods(cfg)
        nl, n_attn = cfg.num_layers, n_p if cfg.hybrid_attn_every else 0
    c: Tree = {
        "mamba": (
            ParamSpec((nl, batch, conv_dim, CONV_WIDTH - 1),
                      ("layers", "batch", "ssm_inner", None), cfg.dtype, "zeros"),
            ParamSpec((nl, batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      ("layers", "batch", "ssm_heads", None, None), "float32", "zeros"),
        )
    }
    if n_attn:
        kv, hd = cfg.resolved_kv_heads, cfg.resolved_head_dim
        shape = (n_attn, batch, kv, seq_len, hd)
        logical = ("layers", "batch", "cache_kv_heads", "cache_seq", None)
        cd = cfg.resolved_cache_dtype
        c["attn"] = (ParamSpec(shape, logical, cd, "zeros"),
                     ParamSpec(shape, logical, cd, "zeros"))
    return c
