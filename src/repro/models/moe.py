"""Mixture-of-Experts FFN: shard-local sorted dispatch + expert tensor
parallelism over d_ff.

Distribution design (see DESIGN.md §5):
  * tokens stay batch-sharded over (pod, data) — dispatch (argsort, capacity
    packing, scatter) happens entirely within each data shard via shard_map,
    so no global sort and no replicated [T*k, D] buffers (a naive jit
    dispatch replicated them: 380-550 GB/chip at train_4k scale);
  * expert weights are sharded over `model` on the per-expert FFN dim
    (d_ff), NOT on the expert count — so granite's 40 experts and
    deepseek-moe's 64 both work on a 16-way axis; each model shard computes
    a d_ff slice of EVERY expert and the down-projection partials are
    psum'ed (exactly dense-MLP tensor parallelism, applied per expert);
  * capacity is static per shard: cap = T_loc * top_k / E * capacity_factor
    (overflow dropped -> active FLOPs stay 6*N_active*D for the roofline).

Shared experts (deepseek-moe) ride along inside the same shard_map with the
same d_ff sharding and the same single psum.

Expert share: a layer holds ``num_experts`` experts, from
``expert_offset`` on, of the ``router_experts`` its router spans (all of
them unless a config says otherwise).  It routes over all of them and
computes only its own experts' part of the result; assignments to experts
held elsewhere add nothing here.  On one chip nothing stands in for the
absent share.

Serving (``moe_ffn_served``) is dropless and computes only routed (token,
held expert) rows: assignments sorted by expert, then a grouped matmul
that walks tiles of one expert's rows, as many as were routed.
``moe_ffn_dense_fallback`` (every token through every held expert, gated)
is its test oracle and the training path when drops are not allowed.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.param import ParamSpec, current_partitioner

Tree = Dict[str, Any]


def moe_param_specs(cfg: ModelConfig, n_layers: int, dtype: str):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    r = cfg.resolved_router_experts
    # expert dim replicated; d_ff ("mlp") carries the model-axis sharding
    p = {
        "moe_norm": ParamSpec((n_layers, d), ("layers", "embed"), dtype, "zeros"),
        "router": ParamSpec((n_layers, d, r), ("layers", "embed", None), "float32"),
        "we_gate": ParamSpec((n_layers, e, d, f), ("layers", None, "embed", "mlp"), dtype),
        "we_up": ParamSpec((n_layers, e, d, f), ("layers", None, "embed", "mlp"), dtype),
        "we_down": ParamSpec((n_layers, e, f, d), ("layers", None, "mlp", "embed"), dtype),
    }
    fs = cfg.resolved_shared_ff
    if fs:
        p["ws_gate"] = ParamSpec((n_layers, d, fs), ("layers", "embed", "mlp"), dtype)
        p["ws_up"] = ParamSpec((n_layers, d, fs), ("layers", "embed", "mlp"), dtype)
        p["ws_down"] = ParamSpec((n_layers, fs, d), ("layers", "mlp", "embed"), dtype)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cap = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(8, (cap + 7) // 8 * 8)


def _router(x: jax.Array, lp, cfg: ModelConfig):
    """Dense routing (outside shard_map). x: [B,S,D] ->
    (top_w [B,S,k] f32, top_i [B,S,k] i32, aux loss)."""
    b, s, d = x.shape
    # the router's float32 weights at float32 precision: a one-pass bf16
    # product moves near-tied experts across the top-k cut
    logits = jnp.matmul(x.astype(jnp.float32), lp["router"],
                        precision=jax.lax.Precision.HIGHEST)   # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    e = cfg.resolved_router_experts
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (
        b * s * cfg.top_k
    )
    aux = e * jnp.sum(me * ce)
    return top_w, top_i, aux


def _dispatch_compute(x, top_w, top_i, we_gate, we_up, we_down, shared, cfg):
    """Per-shard MoE: x [B_loc,S,D] (full D), weights [E,D,F_loc]/[E,F_loc,D].
    Returns the (F-partial) output [B_loc,S,D]."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k
    xf = x.reshape(t, d)
    cap = _capacity(t, cfg)

    flat_e = top_i.reshape(-1)                        # [T*k] local
    order = jnp.argsort(flat_e)
    seg = flat_e[order]
    src_tok = order // k
    starts = jnp.searchsorted(seg, jnp.arange(e))
    pos_in_seg = jnp.arange(t * k) - starts[seg]
    keep = pos_in_seg < cap
    slot = jnp.where(keep, seg * cap + pos_in_seg, e * cap)

    gathered = jnp.take(xf, src_tok, axis=0)          # [T*k, D]
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(gathered)
    h = buf[: e * cap].reshape(e, cap, d)

    g = jnp.einsum("ecd,edf->ecf", h, we_gate)
    u = jnp.einsum("ecd,edf->ecf", h, we_up)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, we_down)  # F-partial

    # combine: weight each sorted assignment and scatter-add straight into
    # the token output — one pass instead of gather->unsort-scatter->sum
    # (§Perf iteration A2: saves a full [T*k, D] scatter + reduction)
    yflat = jnp.concatenate([y.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], 0)
    per_assign = jnp.take(yflat, slot, axis=0)                # [T*k, D] sorted
    w_sorted = top_w.reshape(t * k)[order].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[src_tok].add(per_assign * w_sorted[:, None])

    if shared is not None:
        ws_gate, ws_up, ws_down = shared
        sh = jax.nn.silu(xf @ ws_gate) * (xf @ ws_up)
        out = out + sh @ ws_down
    return out.reshape(b, s, d)


def _shared_mlp(xf, lp):
    sh = jax.nn.silu(xf @ lp["ws_gate"]) * (xf @ lp["ws_up"])
    return sh @ lp["ws_down"]


def held_experts(top_i: jax.Array, cfg: ModelConfig):
    """Routed expert ids -> (local index among the experts held here,
    ``num_experts`` where the expert is held elsewhere; held mask)."""
    loc = top_i - cfg.expert_offset
    held = (loc >= 0) & (loc < cfg.num_experts)
    return jnp.where(held, loc, cfg.num_experts), held


def moe_ffn(x: jax.Array, lp, cfg: ModelConfig):
    """x: [B,S,D] -> ([B,S,D], aux). Sharded when a Partitioner is ambient."""
    if cfg.resolved_router_experts != cfg.num_experts:
        raise NotImplementedError(
            "the capacity path holds every expert; an expert share is served "
            "by moe_ffn_served")
    top_w, top_i, aux = _router(x, lp, cfg)
    shared = (lp["ws_gate"], lp["ws_up"], lp["ws_down"]) \
        if cfg.resolved_shared_ff else None
    part = current_partitioner()
    if part is None:  # single-device path (smoke tests)
        return _dispatch_compute(x, top_w, top_i, lp["we_gate"], lp["we_up"],
                                 lp["we_down"], shared, cfg), aux

    mesh = part.mesh
    P = jax.sharding.PartitionSpec
    bd = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bd = bd if len(bd) > 1 else (bd[0] if bd else None)
    tok = P(bd, None, None)
    w_spec = (P(None, None, "model"), P(None, None, "model"), P(None, "model", None))
    sh_spec = (P(None, "model"), P(None, "model"), P("model", None)) \
        if shared is not None else None

    def local(xl, twl, til, wg, wu, wd, *sh):
        # chunk the local tokens so dispatch buffers stay ~8k tokens per
        # step (a single 65k-token dispatch held 8 GB of transient buffers)
        b_loc, s_loc, d_loc = xl.shape
        n_chunk = 1
        for cand in range(max(1, (b_loc * s_loc) // 8192), 0, -1):
            if s_loc % cand == 0:
                n_chunk = cand
                break
        sc = s_loc // n_chunk

        def to_chunks(a):
            return a.reshape(a.shape[0], n_chunk, sc, *a.shape[2:]).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_body(_, xs):
            xc, twc, tic = xs
            out_c = _dispatch_compute(xc, twc, tic, wg, wu, wd, sh or None, cfg)
            return None, out_c

        _, outs = jax.lax.scan(chunk_body, None,
                               (to_chunks(xl), to_chunks(twl), to_chunks(til)))
        out = outs.swapaxes(0, 1).reshape(b_loc, s_loc, d_loc)
        return jax.lax.psum(out, "model")  # combine d_ff partials

    args = [x, top_w, top_i, lp["we_gate"], lp["we_up"], lp["we_down"]]
    in_specs = [tok, tok, tok, *w_spec]
    if shared is not None:
        args += list(shared)
        in_specs += list(sh_spec)
    out = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=tok, check_vma=False)(*args)
    return out, aux


def moe_ffn_dense_fallback(x: jax.Array, lp, cfg: ModelConfig):
    """Dropless oracle: every token through every held expert via one-hot
    einsum, gated by its routing (zero for experts it was not routed to or
    that are held elsewhere).  O(E/k) more FLOPs — smoke-scale tests and
    training only."""
    b, s, d = x.shape
    t = b * s
    e = cfg.num_experts
    xf = x.reshape(t, d)
    top_w, top_i, aux = _router(x, lp, cfg)
    loc, _ = held_experts(top_i.reshape(t, cfg.top_k), cfg)
    gate = jnp.zeros((t, e + 1), jnp.float32)
    gate = gate.at[jnp.arange(t)[:, None], loc].set(
        top_w.reshape(t, cfg.top_k))[:, :e]                  # [T,E]
    g = jnp.einsum("td,edf->tef", xf, lp["we_gate"])
    u = jnp.einsum("td,edf->tef", xf, lp["we_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, lp["we_down"])
    out = jnp.einsum("te,ted->td", gate.astype(x.dtype), y)
    if cfg.resolved_shared_ff:
        out = out + _shared_mlp(xf, lp)
    return out.reshape(b, s, d), aux


def _tile_rows(rows_per_expert: float) -> int:
    """Rows of one grouped-matmul tile: the routed rows an expert expects,
    as a power of two in [8, 128]."""
    tm = 8
    while tm < 128 and tm < rows_per_expert:
        tm *= 2
    return tm


def moe_ffn_served(x: jax.Array, lp, cfg: ModelConfig, layer=None):
    """Dropless serving MoE: x [B,S,D] -> (out [B,S,D], held [B,S]), where
    ``held`` counts each token's assignments to experts held here.

    The router spans every expert; only (token, held expert) rows are
    computed.  Assignments are sorted by local expert (those held elsewhere
    last, never computed); a while loop walks tiles of ``tm`` rows, each
    tile one expert's, as many tiles as the routed rows fill, reading each
    used expert's weights once per tile.  With ``layer`` the expert weights
    in ``lp`` carry a leading layer axis and that layer's are read in the
    loop, so no per-layer copy of them is made."""
    b, s, d = x.shape
    t, k, e = b * s, cfg.top_k, cfg.num_experts
    xf = x.reshape(t, d)
    top_w, top_i, _ = _router(x, lp, cfg)
    loc, held = held_experts(top_i.reshape(t * k), cfg)
    rows = t * min(k, e)            # held assignments a batch can have
    order = jnp.argsort(loc, stable=True)[:rows]
    tok = order // k
    sizes = jnp.zeros((e + 1,), jnp.int32).at[loc].add(1)[:e]
    first_row = jnp.cumsum(sizes) - sizes
    tm = _tile_rows(t * k / cfg.resolved_router_experts)
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    xs = jnp.pad(jnp.take(xf, tok, axis=0), ((0, tm), (0, 0)))

    def weights(name, ex):
        w = lp[name]
        return w[ex] if layer is None else w[layer, ex]

    def tile(i, y):
        ex = jnp.searchsorted(tile_end, i, side="right")
        start = first_row[ex] + (i - tile_end[ex] + tiles[ex]) * tm
        valid = jnp.arange(tm) < first_row[ex] + sizes[ex] - start
        xt = jax.lax.dynamic_slice_in_dim(xs, start, tm, 0)
        h = jax.nn.silu(xt @ weights("we_gate", ex)) * (xt @ weights("we_up", ex))
        yt = jnp.where(valid[:, None], h @ weights("we_down", ex), 0)
        return jax.lax.dynamic_update_slice_in_dim(y, yt.astype(y.dtype),
                                                   start, 0)

    y = jax.lax.fori_loop(0, tile_end[-1], tile,
                          jnp.zeros((rows + tm, d), x.dtype))[:rows]
    gate = jnp.where(jnp.arange(rows) < sizes.sum(),
                     top_w.reshape(t * k)[order], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(
        y.astype(jnp.float32) * gate[:, None])
    if cfg.resolved_shared_ff:
        out = out + _shared_mlp(xf, lp).astype(jnp.float32)
    held_n = held.reshape(b, s, k).sum(-1).astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, d), held_n
