"""granite-4.0-h-small (IBM Granite 4.0-H Small, ``GraniteMoeHybrid``):
weights from the seed, and the plain float32 reference.

A stack of periods of ``attn_layer_period`` layers, each a Mamba2 mixer but
a GQA attention mixer at ``attn_layer_offset``; after every mixer a routed
MoE plus a shared SwiGLU MLP.  Per layer, with m = residual_multiplier:

    h += m * mixer(norm(h))
    h += m * (moe(norm(h)) + shared_mlp(norm(h)))

Mamba2 mixer: in-projection to (z, xBC, dt); causal depthwise conv of
width 4 with bias over xBC, then SiLU; split x, B, C (one group);
dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD recurrence, one
step at a time,

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T ,   y_t = S_t C_t + D x_t

then the gated RMSNorm of y * silu(z) and the out-projection.  Attention:
no positional encoding, softmax(q k^T * attention_multiplier) v over the
causal past, 8 KV heads shared by 32 query heads.  MoE: the router's
logits over all ``router_experts`` experts, the top ``top_k``, gates the
softmax over those top logits; the experts held here (``expert_offset``
on, ``num_experts`` of them) each compute SwiGLU(h) for every token and
add it with its gate, zero where the token was not routed to it; what the
experts held elsewhere would add is left out, as in the program.  The
shared MLP is counted once.  Embeddings times ``embedding_multiplier``,
tied output matrix, logits divided by ``logits_scaling``.

Written from those equations, to the conventions of the program's
parameter layout, which departs from the published model in stated ways:
every RMSNorm, the gated one included, scales by ``1 + w`` (the published
ones by ``w``: the same family of functions under another offset of the
weights); the router's weights are kept in float32; the in-projection's
outputs are ordered z, xBC, dt as in the published Mamba2 code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import refkit as K

CONV = 4
EMBED_RMS = 0.01   # of the scaled embeddings, against ~1 for a block's output


def dims(s):
    d_inner = s["ssm_expand"] * s["d_model"]
    heads = d_inner // s["ssm_head_dim"]
    conv_dim = d_inner + 2 * s["ssm_state"]
    return d_inner, heads, conv_dim, d_inner + conv_dim + heads


def layout(s):
    """-> (periods, period, attention offset, Mamba2 layers)."""
    period, off = s["attn_layer_period"], s["attn_layer_offset"]
    n_p = s["num_layers"] // period
    return n_p, period, off, n_p * (period - 1)


def vocab_padded(s) -> int:
    return K.round_up(s["vocab_size"], s["vocab_round"])


def init(s, key):
    """The parameter tree in bfloat16 (router and the per-head SSM scalars
    in float32), from one key."""
    L, d, f, fs = s["num_layers"], s["d_model"], s["d_ff"], s["shared_ff"]
    n_p, _, _, nm = layout(s)
    d_inner, heads, conv_dim, d_proj = dims(s)
    h, kv, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    e, r = s["num_experts"], s["router_experts"]
    vp, bf, f32 = vocab_padded(s), jnp.bfloat16, K.F32
    ks = iter(jax.random.split(key, 32))
    # Mamba2's published initialisation: dt log-uniform in [1e-3, 1e-1]
    # (dt_bias its inverse softplus), A uniform in [1, 16]
    dt0 = jnp.exp(jax.random.uniform(next(ks), (nm, heads), f32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    mamba = {
        "norm": K.normal(next(ks), (nm, d), 0.1, bf),
        "w_in": K.normal(next(ks), (nm, d, d_proj), d ** -0.5, bf),
        "conv_w": K.normal(next(ks), (nm, conv_dim, CONV), 0.5, bf),
        "conv_b": K.normal(next(ks), (nm, conv_dim), 0.1, bf),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "a_log": jnp.log(jax.random.uniform(next(ks), (nm, heads), f32,
                                            1.0, 16.0)),
        "d_skip": K.uniform(next(ks), (nm, heads), 0.5, 1.5, f32),
        "gn_w": K.normal(next(ks), (nm, d_inner), 0.1, bf),
        "w_out": K.normal(next(ks), (nm, d_inner, d), d_inner ** -0.5, bf),
    }
    attn = {
        "attn_norm": K.normal(next(ks), (n_p, d), 0.1, bf),
        "wq": K.normal(next(ks), (n_p, d, h, hd), 2 * d ** -0.5, bf),
        "wk": K.normal(next(ks), (n_p, d, kv, hd), 2 * d ** -0.5, bf),
        "wv": K.normal(next(ks), (n_p, d, kv, hd), d ** -0.5, bf),
        "wo": K.normal(next(ks), (n_p, h, hd, d), (h * hd) ** -0.5, bf),
    }
    moe = {
        "moe_norm": K.normal(next(ks), (L, d), 0.1, bf),
        "router": K.normal(next(ks), (L, d, r), d ** -0.5, f32),
        "we_gate": K.normal(next(ks), (L, e, d, f), d ** -0.5, bf),
        "we_up": K.normal(next(ks), (L, e, d, f), d ** -0.5, bf),
        "we_down": K.normal(next(ks), (L, e, f, d), f ** -0.5, bf),
        "ws_gate": K.normal(next(ks), (L, d, fs), d ** -0.5, bf),
        "ws_up": K.normal(next(ks), (L, d, fs), d ** -0.5, bf),
        "ws_down": K.normal(next(ks), (L, fs, d), fs ** -0.5, bf),
    }
    # the tied output matrix scores each token by its embedding: a residual
    # stream that still held its input's embedding would score that token
    # far above the rest.  Embeddings small beside what the layers add
    # keep the seeded model from copying its input.
    emb = K.normal(next(ks), (vp, d), EMBED_RMS / s["embedding_multiplier"],
                   bf, stacked=False)
    emb = emb.at[s["vocab_size"]:].set(0)
    return {"embedding": emb,
            "final_norm": K.normal(next(ks), (d,), 0.1, bf, stacked=False),
            "mamba": mamba, "attn": attn, "moe": moe}


def _ssd(x, dt, la, B, C):
    """The recurrence, one step at a time.  x [B,T,H,P], dt/la [B,T,H],
    B/C [B,T,N] -> y [B,T,H,P]."""
    b, _, h, p = x.shape

    def step(S, xs):
        xt, dtt, lat, bt, ct = xs
        S = jnp.exp(lat)[..., None, None] * S + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt, precision=K.HIGHEST)
        return S, jnp.einsum("bhpn,bn->bhp", S, ct, precision=K.HIGHEST)

    S0 = jnp.zeros((b, h, p, B.shape[-1]), K.F32)
    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, la, B, C))
    _, ys = jax.lax.scan(step, S0, xs)
    return jnp.swapaxes(ys, 0, 1)


def _mamba(x, p, s, quant):
    d_inner, heads, conv_dim, _ = dims(s)
    hp, n, eps = s["ssm_head_dim"], s["ssm_state"], s["norm_eps"]
    b, t, _ = x.shape
    xn = K.rms_norm(x, p["norm"], eps)
    zxbcdt = K.mm("btd,de->bte", xn, p["w_in"], quant)
    z, xbc = zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    # causal depthwise conv: tap i reads the input CONV-1-i steps back
    xp = jnp.pad(xbc, ((0, 0), (CONV - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + t] * p["conv_w"][:, i] for i in range(CONV))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :d_inner].reshape(b, t, heads, hp)
    Bm, Cm = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    la = -jnp.exp(p["a_log"]) * dt
    y = _ssd(xs, dt, la, Bm, Cm) + p["d_skip"][:, None] * xs
    y = y.reshape(b, t, d_inner) * jax.nn.silu(z)
    return K.mm("bte,ed->btd", K.rms_norm(y, p["gn_w"], eps), p["w_out"],
                quant)


def _attention(x, p, s, quant):
    h, kv, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    b, t, _ = x.shape
    xn = K.rms_norm(x, p["attn_norm"], s["norm_eps"])
    q = K.mm("btd,dhk->bthk", xn, p["wq"], quant)
    k = K.mm("btd,dhk->bthk", xn, p["wk"], quant)
    v = K.mm("btd,dhk->bthk", xn, p["wv"], quant)
    q = q.reshape(b, t, kv, h // kv, hd)
    sc = K.mm("bsngk,btnk->bngst", q, k, quant) * s["attention_multiplier"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    att = K.mm("bngst,btnk->bsngk", pr, v, quant).reshape(b, t, h, hd)
    return K.mm("bshk,hkd->bsd", att, p["wo"], quant)


def _moe(x, p, s, quant):
    xn = K.rms_norm(x, p["moe_norm"], s["norm_eps"])
    logits = K.mm("btd,de->bte", xn, p["router"], quant)
    top, idx = jax.lax.top_k(logits, s["top_k"])
    gates = jax.nn.softmax(top, axis=-1)
    held = idx - s["expert_offset"]                      # [B,T,k]
    onehot = held[..., None] == jnp.arange(s["num_experts"])
    gate = jnp.sum(jnp.where(onehot, gates[..., None], 0.0), -2)  # [B,T,E]
    g = K.mm("btd,edf->btef", xn, p["we_gate"], quant)
    u = K.mm("btd,edf->btef", xn, p["we_up"], quant)
    y = K.mm("btef,efd->bted", jax.nn.silu(g) * u, p["we_down"], quant)
    out = jnp.einsum("bte,bted->btd", gate, y, precision=K.HIGHEST)
    sh = jax.nn.silu(K.mm("btd,df->btf", xn, p["ws_gate"], quant)) * K.mm(
        "btd,df->btf", xn, p["ws_up"], quant)
    return out + K.mm("btf,fd->btd", sh, p["ws_down"], quant)


@functools.lru_cache(maxsize=None)
def _layer_fn(s_items, quant, attention):
    s = dict(s_items)
    m = s["residual_multiplier"]
    mixer = _attention if attention else _mamba

    @jax.jit
    def layer(x, mixers, moes, j, i):
        x = x + m * mixer(x, K.layer_slice(mixers, j), s, quant)
        return x + m * _moe(x, K.layer_slice(moes, i), s, quant)

    return layer


def hidden(params, tokens, s, quant=""):
    """Final normed hidden states [B, T, D] f32 of ``tokens`` [B, T]."""
    items = tuple(sorted(s.items()))
    _, period, off, _ = layout(s)
    x = jnp.take(params["embedding"], tokens, axis=0).astype(K.F32)
    x = x * s["embedding_multiplier"]
    for i in range(s["num_layers"]):
        q, r = divmod(i, period)
        if r == off:
            x = _layer_fn(items, quant, True)(x, params["attn"],
                                              params["moe"], q, i)
        else:
            j = q * (period - 1) + (r if r < off else r - 1)
            x = _layer_fn(items, quant, False)(x, params["mamba"],
                                               params["moe"], j, i)
    return K.rms_norm(x, params["final_norm"], s["norm_eps"])


def unembed(params, s):
    """[D, vocab_size] output matrix: the tied embedding over the logits
    scale (a power of two, exact in bfloat16)."""
    return params["embedding"][: s["vocab_size"]].T / s["logits_scaling"]
