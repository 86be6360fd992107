"""Decoder-only transformer covering the dense / moe / vlm families.

Design notes
  * Parameters are stacked over layers ([L, ...]) and the stack is applied
    with `lax.scan` — keeps HLO size O(1) in depth (deepseek-67b is 95L).
  * gemma3's 5:1 local:global pattern is applied as a scan over *periods*
    (params reshaped [n_periods, period, ...]) with the 6 layers of a period
    unrolled — no `lax.cond` in the hot path, so cost_analysis stays honest.
  * Local (sliding-window) layers use a ring KV cache of size `window`;
    global layers use a full-length cache (context-parallel shardable).
  * MoE layers swap the SwiGLU for `moe_ffn`; leading dense layers
    (deepseek-moe) are unrolled separately before the scanned MoE stack.
  * VLM (internvl2): patch embeddings from the stubbed vision frontend are
    pasted over the first `frontend_tokens` embedding positions.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import (moe_ffn, moe_ffn_dense_fallback, moe_ffn_served,
                              moe_param_specs)
from repro.models.param import ParamSpec, constrain

Tree = Dict[str, Any]


# ---------------------------------------------------------------- param spec
def _attn_specs(cfg: ModelConfig, n: int, dtype: str) -> Tree:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.resolved_kv_heads, cfg.resolved_head_dim
    p = {
        "attn_norm": ParamSpec((n, d), ("layers", "embed"), dtype, "zeros"),
        "wq": ParamSpec((n, d, h, hd), ("layers", "embed", "heads", "head_dim"), dtype),
        "wk": ParamSpec((n, d, kv, hd), ("layers", "embed", "kv_heads", "head_dim"), dtype),
        "wv": ParamSpec((n, d, kv, hd), ("layers", "embed", "kv_heads", "head_dim"), dtype),
        "wo": ParamSpec((n, h, hd, d), ("layers", "heads", "head_dim", "embed"), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = ParamSpec((n, hd), ("layers", "head_dim"), dtype, "zeros")
        p["k_norm"] = ParamSpec((n, hd), ("layers", "head_dim"), dtype, "zeros")
    return p


def _mlp_specs(cfg: ModelConfig, n: int, dtype: str, ff: int = 0) -> Tree:
    d, f = cfg.d_model, ff or cfg.d_ff
    return {
        "mlp_norm": ParamSpec((n, d), ("layers", "embed"), dtype, "zeros"),
        "w_gate": ParamSpec((n, d, f), ("layers", "embed", "mlp"), dtype),
        "w_up": ParamSpec((n, d, f), ("layers", "embed", "mlp"), dtype),
        "w_down": ParamSpec((n, f, d), ("layers", "mlp", "embed"), dtype),
    }


def _layer_specs(cfg: ModelConfig, n: int, dtype: str, moe: bool) -> Tree:
    p = _attn_specs(cfg, n, dtype)
    p.update(moe_param_specs(cfg, n, dtype) if moe else _mlp_specs(cfg, n, dtype))
    return p


def abstract_params(cfg: ModelConfig) -> Tree:
    dt = cfg.dtype
    v, d = cfg.vocab_padded, cfg.d_model
    is_moe = cfg.num_experts > 0
    n_moe = cfg.num_layers - cfg.first_dense_layers
    p: Tree = {
        "embedding": ParamSpec((v, d), ("vocab", "embed"), dt, "small"),
        "final_norm": ParamSpec((d,), ("embed",), dt, "zeros"),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = ParamSpec((d, v), ("embed", "vocab"), dt, "small")
    if cfg.first_dense_layers:  # leading dense layers (deepseek-moe)
        p["dense0"] = _layer_specs(
            dataclass_ff(cfg), cfg.first_dense_layers, dt, moe=False
        )
    p["layers"] = _layer_specs(cfg, n_moe if is_moe else cfg.num_layers, dt, moe=is_moe)
    return p


def dataclass_ff(cfg: ModelConfig) -> ModelConfig:
    """cfg with d_ff swapped for the leading-dense-layer width."""
    import dataclasses

    return dataclasses.replace(cfg, d_ff=cfg.dense_ff or cfg.d_ff)


# ------------------------------------------------------------------ pattern
def layer_pattern(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_periods, period, tail): gemma3 (10, 6, 2); uniform -> (0,0,L)."""
    loc, glob = cfg.local_global_pattern
    if not (loc or glob):
        return 0, 0, cfg.num_layers
    period = loc + glob
    return cfg.num_layers // period, period, cfg.num_layers % period


def _is_local(cfg: ModelConfig, idx_in_period: int) -> bool:
    loc, _ = cfg.local_global_pattern
    return idx_in_period < loc


# ------------------------------------------------------------------- layer
def _gathered(w, cfg: ModelConfig, *logical):
    """ZeRO-3 weight gather: re-constrain an FSDP-sharded weight so its
    contraction dim is whole before the dot.  Without this XLA all-reduces
    the (much larger) activations — 1.8 TB/chip/step for deepseek-67b
    train_4k (§Perf pair B)."""
    from repro.models.param import constrain

    if not cfg.fsdp_weight_gather:
        return w
    return constrain(w, *logical)


def _attention(x, lp, cfg: ModelConfig, mode, sincos, window, cache, cur_index):
    """One attention sub-block. cache: (k,v) for this layer or None.
    Returns (residual_delta, new_cache)."""
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    wq = _gathered(lp["wq"], cfg, None, "heads", None)
    wk = _gathered(lp["wk"], cfg, None, "kv_heads", None)
    wv = _gathered(lp["wv"], cfg, None, "kv_heads", None)
    q = jnp.einsum("bsd,dhk->bshk", h, wq)
    k = jnp.einsum("bsd,dhk->bshk", h, wk)
    v = jnp.einsum("bsd,dhk->bshk", h, wv)
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.rope:
        sin, cos = sincos
        rd = cfg.resolved_head_dim // 2 if cfg.rope_2d else cfg.resolved_head_dim
        q = L.apply_rope(q, sin, cos, rd)
        k = L.apply_rope(k, sin, cos, rd)
    if cfg.attention_multiplier:
        # the kernels scale scores by 1/sqrt(head_dim); fold the rest into q
        q = q * jnp.asarray(cfg.attention_multiplier
                            * cfg.resolved_head_dim ** 0.5, q.dtype)
    q = constrain(q, "batch", "seq", "act_heads", None)

    new_cache = None
    int8_cache = cfg.resolved_cache_dtype == "int8"
    cd = jnp.dtype(jnp.int8 if int8_cache else cfg.resolved_cache_dtype)
    # the Pallas kernels are forward-only; train always runs the reference
    up = "off" if mode == "train" else cfg.use_pallas
    if mode == "decode":
        # cache layout [B, KV, S, hd]: GEMM-ready per head, no relayout.
        # cur_index may be a scalar (lockstep batch) or a [B] vector —
        # per-slot positions for continuous-batching serving; vector slots
        # scatter one token per row instead of one shared column.
        slot = (cur_index % window) if window else cur_index
        vec = jnp.ndim(cur_index) > 0

        def put(cache_leaf, token_leaf):
            # token_leaf [B,KV,1,...]; write row b at column slot[b] (vec)
            # or every row at the shared scalar column.
            if vec:
                bi = jnp.arange(cache_leaf.shape[0])
                return cache_leaf.at[bi, :, slot].set(token_leaf[:, :, 0])
            return jax.lax.dynamic_update_slice_in_dim(
                cache_leaf, token_leaf, slot, 2)

        if int8_cache:
            ck, cv, ks, vs = cache
            k1, ksc = L.quantize_token_kv(k[:, 0][:, :, None])
            v1, vsc = L.quantize_token_kv(v[:, 0][:, :, None])
            ck, cv = put(ck, k1), put(cv, v1)
            ks, vs = put(ks, ksc), put(vs, vsc)
            assert not window, "int8 ring cache not implemented"
            att = L.attention_decode_int8(q[:, 0], ck, cv, ks, vs, cur_index,
                                          use_pallas=up)[:, None]
            new_cache = (ck, cv, ks, vs)
        else:
            ck, cv = cache
            k1 = k[:, 0][:, :, None].astype(cd)  # [B,KV,1,hd]
            v1 = v[:, 0][:, :, None].astype(cd)
            ck, cv = put(ck, k1), put(cv, v1)
            if window:
                att = L.attention_decode_ring(q[:, 0], ck, cv, cur_index)[:, None]
            else:
                att = L.attention_decode(q[:, 0], ck, cv, cur_index,
                                         use_pallas=up)[:, None]
            new_cache = (ck, cv)
    else:
        s = x.shape[1]
        if s > 2048:
            att = L.attention_blockwise(q, k, v, causal=True, window=window,
                                        causal_skip=cfg.attn_causal_skip,
                                        use_pallas=up)
        else:
            att = L.attention_full(q, k, v, causal=True, window=window,
                                   use_pallas=up)
        if mode == "prefill":
            if window:
                w = min(window, s)
                kc = jnp.roll(k[:, s - w :], s % w, axis=1)
                vc = jnp.roll(v[:, s - w :], s % w, axis=1)
            else:
                kc, vc = k, v
            kc = kc.transpose(0, 2, 1, 3)
            vc = vc.transpose(0, 2, 1, 3)
            if int8_cache:
                kq, ksc = L.quantize_token_kv(kc)
                vq, vsc = L.quantize_token_kv(vc)
                new_cache = (kq, vq, ksc, vsc)
            else:
                new_cache = (kc.astype(cd), vc.astype(cd))
    att = constrain(att, "batch", "seq", "act_heads", None)
    wo = _gathered(lp["wo"], cfg, "heads", None, None)
    return jnp.einsum("bshk,hkd->bsd", att, wo), new_cache


def served_moe(mode: str, dropless: bool) -> bool:
    """Dropless serving (prefill and decode) takes the served MoE path,
    whose second output is each token's count of held-expert rows."""
    return dropless and mode != "train"


def _ffn(x, lp, cfg: ModelConfig, moe: bool, dropless: bool, mode: str,
         layer=None):
    """-> (delta, aux): aux is the router's balance loss, or with the
    served path (``served_moe``) each token's held-expert rows [B,S]."""
    h = L.rms_norm(x, lp["mlp_norm" if not moe else "moe_norm"], cfg.norm_eps)
    if not moe:
        return L.swiglu(h,
                        _gathered(lp["w_gate"], cfg, None, "mlp"),
                        _gathered(lp["w_up"], cfg, None, "mlp"),
                        _gathered(lp["w_down"], cfg, "mlp", None)), 0.0
    if served_moe(mode, dropless):
        return moe_ffn_served(h, lp, cfg, layer)
    fn = moe_ffn_dense_fallback if dropless else moe_ffn
    return fn(h, lp, cfg)


def _layer(x, lp, cfg, mode, sincos, window, cache, cur_index, moe, dropless):
    delta, new_cache = _attention(x, lp, cfg, mode, sincos, window, cache, cur_index)
    x = x + delta
    ff, aux = _ffn(x, lp, cfg, moe, dropless, mode)
    x = x + ff
    x = constrain(x, "batch", "seq_res", "act_embed")
    return x, new_cache, aux


# ----------------------------------------------------------------- forward
def _sincos(cfg: ModelConfig, positions: jax.Array):
    if not cfg.rope:
        return None
    rd = cfg.resolved_head_dim // 2 if cfg.rope_2d else cfg.resolved_head_dim
    return L.rope_freqs(positions, cfg.resolved_head_dim, cfg.rope_theta, rd)


def _stack_forward(
    params: Tree,
    x: jax.Array,
    cfg: ModelConfig,
    mode: str,
    cache: Optional[Tree],
    cur_index,
    *,
    remat: bool = False,
    dropless: bool = False,
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Apply the full layer stack. Returns (hidden, new_cache, aux_sum)."""
    s = x.shape[1]
    if mode == "decode":
        if jnp.ndim(cur_index) > 0:  # per-row positions [B] -> [B,1]
            positions = jnp.asarray(cur_index, jnp.int32)[:, None]
        else:
            positions = jnp.full((x.shape[0], 1), cur_index, jnp.int32)
    else:
        positions = jnp.arange(s)[None, :].repeat(x.shape[0], 0)
    sincos = _sincos(cfg, positions)
    moe = cfg.num_experts > 0
    n_periods, period, tail = layer_pattern(cfg)

    # served MoE: per-token held-expert rows, summed over the layers
    aux_total = jnp.zeros(x.shape[:2] if moe and served_moe(mode, dropless)
                          else (), jnp.float32)
    new_cache: Dict[str, Any] = {}

    # ---- leading dense layers (deepseek-moe) --------------------------------
    if cfg.first_dense_layers:
        dcfg = dataclass_ff(cfg)
        dck = cache.get("dense0") if cache else None
        outs = []
        for i in range(cfg.first_dense_layers):
            lp = jax.tree.map(lambda a: a[i], params["dense0"])
            c = jax.tree.map(lambda a: a[i], dck) if dck is not None else None
            x, nc, aux = _layer(x, lp, dcfg, mode, sincos, 0, c, cur_index, False, dropless)
            aux_total += aux
            outs.append(nc)
        if outs[0] is not None:
            new_cache["dense0"] = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    lps = params["layers"]

    if period == 0:
        # ---- homogeneous scan ------------------------------------------------
        def body(carry, xs):
            xx, aux = carry
            lp, c = xs
            xx, nc, a = _layer(xx, lp, cfg, mode, sincos, 0, c, cur_index, moe, dropless)
            return (xx, aux + a), nc

        if remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        cs = cache.get("layers") if cache else None
        xs = (lps, cs)
        # decode_unroll > 1 flattens the while loop: XLA:CPU otherwise keeps
        # hoisted f32 mirrors of the whole while-carried KV cache stack.
        unroll = cfg.decode_unroll if mode == "decode" else 1
        (x, aux_total), ncs = jax.lax.scan(body, (x, aux_total), xs, unroll=unroll)
        if ncs is not None and mode != "train":
            new_cache["layers"] = ncs
        return x, (new_cache or None), aux_total

    # ---- period scan (gemma3 local:global) ----------------------------------
    loc, _glob = cfg.local_global_pattern
    w = cfg.sliding_window
    n_main = n_periods * period

    def reshape_main(a):
        return a[:n_main].reshape((n_periods, period) + a.shape[1:])

    main = jax.tree.map(reshape_main, lps) if n_periods else None
    tail_p = jax.tree.map(lambda a: a[n_main:], lps)

    def period_body(carry, xs):
        xx, aux = carry
        lp_p, c_loc, c_glob = xs
        ncl_k, ncl_v = [], []
        ncg = None
        for j in range(period):
            lp = jax.tree.map(lambda a: a[j], lp_p)
            local = _is_local(cfg, j)
            if local:
                c = jax.tree.map(lambda a: a[j], c_loc) if c_loc is not None else None
            else:
                c = c_glob
            xx, nc, a = _layer(
                xx, lp, cfg, mode, sincos, w if local else 0, c, cur_index, moe, dropless
            )
            aux = aux + a
            if nc is not None:
                if local:
                    ncl_k.append(nc[0])
                    ncl_v.append(nc[1])
                else:
                    ncg = nc
        ys = None
        if ncl_k:
            ys = ((jnp.stack(ncl_k), jnp.stack(ncl_v)), ncg)
        return (xx, aux), ys

    if remat:
        period_body = jax.checkpoint(
            period_body, policy=jax.checkpoint_policies.nothing_saveable
        )
    if n_periods:
        c_loc = cache.get("local") if cache else None      # [P, loc, B, w, KV, hd] x2
        c_glob = cache.get("global") if cache else None    # [P, B, S, KV, hd] x2
        (x, aux_total), ys = jax.lax.scan(
            period_body, (x, aux_total), (main, c_loc, c_glob)
        )
        if ys is not None and mode != "train":
            new_cache["local"], new_cache["global"] = ys

    # tail layers (all local by construction)
    tails = []
    c_tail = cache.get("tail") if cache else None
    for i in range(tail):
        lp = jax.tree.map(lambda a: a[i], tail_p)
        c = jax.tree.map(lambda a: a[i], c_tail) if c_tail is not None else None
        x, nc, a = _layer(x, lp, cfg, mode, sincos, w, c, cur_index, moe, dropless)
        aux_total += a
        tails.append(nc)
    if tail and tails[0] is not None and mode != "train":
        new_cache["tail"] = jax.tree.map(lambda *xs: jnp.stack(xs), *tails)
    return x, (new_cache or None), aux_total


# --------------------------------------------------------------- embeddings
def _embed(params, cfg: ModelConfig, tokens: jax.Array, batch: Optional[Tree]) -> jax.Array:
    x = jnp.take(params["embedding"], tokens, axis=0)
    x = x * jnp.asarray(cfg.d_model, x.dtype) ** 0.5 if cfg.name.startswith("gemma") else x
    if cfg.family == "vlm" and batch is not None and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    return constrain(x, "batch", "seq_res", "act_embed")


def _unembed_matrix(params, cfg: ModelConfig):
    return params["embedding"].T if cfg.tie_embeddings else params["unembed"]


# ----------------------------------------------------------------- public API
def loss_fn(params: Tree, batch: Tree, cfg: ModelConfig, *, dropless: bool = False):
    """batch: tokens [B,S], labels [B,S] (+ patch_embeds for vlm)."""
    x = _embed(params, cfg, batch["tokens"], batch)
    x, _, aux = _stack_forward(params, x, cfg, "train", None, None, remat=True,
                               dropless=dropless)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    ce = L.chunked_cross_entropy(x, _unembed_matrix(params, cfg), batch["labels"])
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


def prefill(params: Tree, batch: Tree, cfg: ModelConfig, *, dropless: bool = False):
    """Returns (last-token logits [B,V], cache)."""
    x = _embed(params, cfg, batch["tokens"], batch)
    x, cache, _ = _stack_forward(params, x, cfg, "prefill", None, None,
                                 remat=False, dropless=dropless)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, -1] @ _unembed_matrix(params, cfg)).astype(jnp.float32)
    return logits, cache


def decode_step(params: Tree, cache: Tree, batch: Tree, cfg: ModelConfig, *,
                dropless: bool = False):
    """batch: tokens [B] (new token ids), cur_index scalar int32.
    Returns (logits [B,V], new_cache); the served MoE (``dropless``) adds
    each row's assignments to held experts over all layers [B]."""
    tokens = batch["tokens"][:, None]
    x = _embed(params, cfg, tokens, None)
    x, new_cache, aux = _stack_forward(
        params, x, cfg, "decode", cache, batch["cur_index"], remat=False,
        dropless=dropless,
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ _unembed_matrix(params, cfg)).astype(jnp.float32)
    if cfg.num_experts and served_moe("decode", dropless):
        return logits, new_cache, aux[:, 0]
    return logits, new_cache


# -------------------------------------------------------------------- cache
def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Tree:
    """ParamSpec tree for the decode cache (dry-run shardable stand-ins)."""
    kv, hd = cfg.resolved_kv_heads, cfg.resolved_head_dim
    dt = cfg.resolved_cache_dtype
    n_periods, period, tail = layer_pattern(cfg)

    def kvspec(lead: Tuple[int, ...], s: int):
        shape = lead + (batch, kv, s, hd)
        logical = ("layers",) * len(lead) + ("batch", "cache_kv_heads", "cache_seq", None)
        if dt == "int8":
            sshape = lead + (batch, kv, s)
            slog = logical[:-1]
            return (ParamSpec(shape, logical, "int8", "zeros"),
                    ParamSpec(shape, logical, "int8", "zeros"),
                    ParamSpec(sshape, slog, "float32", "zeros"),
                    ParamSpec(sshape, slog, "float32", "zeros"))
        return (ParamSpec(shape, logical, dt, "zeros"),
                ParamSpec(shape, logical, dt, "zeros"))

    c: Tree = {}
    if cfg.first_dense_layers:
        c["dense0"] = kvspec((cfg.first_dense_layers,), seq_len)
    if period == 0:
        n = cfg.num_layers - cfg.first_dense_layers
        c["layers"] = kvspec((n,), seq_len)
        return c
    loc, _ = cfg.local_global_pattern
    w = min(cfg.sliding_window, seq_len)
    if n_periods:
        c["local"] = kvspec((n_periods, loc), w)
        c["global"] = kvspec((n_periods,), seq_len)
    if tail:
        c["tail"] = kvspec((tail,), w)
    return c
