"""qwen3-1.7b: weights from the seed, and the plain float32 reference.

Qwen3 decoder layer (hf:Qwen/Qwen3-1.7B): RMSNorm, grouped-query attention
with RMSNorm on each query and key head before RoPE, SwiGLU feed-forward,
input embedding tied to the output.  Written from the architecture, not
from the program, to the conventions of the program's parameter layout:

  * a norm scales by ``1 + w`` (so a zero weight is the identity);
  * RoPE rotates the interleaved pairs ``(2i, 2i + 1)`` of a head, where
    the published model rotates its two halves.  With seeded weights the
    two are the same model up to a fixed permutation of ``wq``/``wk``.

``init`` makes the parameter tree the program serves; ``hidden`` runs the
reference one layer at a time, so that only one layer is ever held in
float32 beside the bfloat16 weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import refkit as K


def vocab_padded(s) -> int:
    return K.round_up(s["vocab_size"], s["vocab_round"])


def init(s, key):
    """The parameter tree in bfloat16, from one key."""
    L, d, f = s["num_layers"], s["d_model"], s["d_ff"]
    h, kv, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    vp, bf = vocab_padded(s), jnp.bfloat16
    out_std = 1.0 / (2.0 * L) ** 0.5   # residual branches add up over depth
    ks = iter(jax.random.split(key, 16))
    emb = K.normal(next(ks), (vp, d), 0.02, bf, stacked=False)
    emb = jnp.where(jnp.arange(vp)[:, None] < s["vocab_size"], emb,
                    jnp.zeros((), bf))
    layers = {
        "attn_norm": K.normal(next(ks), (L, d), 0.1, bf),
        "wq": K.normal(next(ks), (L, d, h, hd), d ** -0.5, bf),
        "wk": K.normal(next(ks), (L, d, kv, hd), d ** -0.5, bf),
        "wv": K.normal(next(ks), (L, d, kv, hd), d ** -0.5, bf),
        "wo": K.normal(next(ks), (L, h, hd, d), (h * hd) ** -0.5 * out_std, bf),
        "q_norm": K.normal(next(ks), (L, hd), 0.1, bf),
        "k_norm": K.normal(next(ks), (L, hd), 0.1, bf),
        "mlp_norm": K.normal(next(ks), (L, d), 0.1, bf),
        "w_gate": K.normal(next(ks), (L, d, f), d ** -0.5, bf),
        "w_up": K.normal(next(ks), (L, d, f), d ** -0.5, bf),
        "w_down": K.normal(next(ks), (L, f, d), f ** -0.5 * out_std, bf),
    }
    return {"embedding": emb,
            "final_norm": K.normal(next(ks), (d,), 0.1, bf, stacked=False),
            "layers": layers}


def _rope(x, pos, theta):
    """x [B, T, H, hd]; rotate pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=K.F32) / hd)
    ang = pos[None, :, None, None].astype(K.F32) * inv
    c, s_ = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s_, x2 * c + x1 * s_], -1).reshape(x.shape)


@functools.lru_cache(maxsize=None)
def _layer_fn(s_items, quant):
    s = dict(s_items)
    h, kv, eps = s["num_heads"], s["num_kv_heads"], s["norm_eps"]

    @jax.jit
    def layer(x, stacked, i):
        p = K.layer_slice(stacked, i)
        b, t, _ = x.shape
        pos = jnp.arange(t)
        y = K.rms_norm(x, p["attn_norm"], eps)
        q = K.mm("btd,dhk->bthk", y, p["wq"], quant)
        k = K.mm("btd,dhk->bthk", y, p["wk"], quant)
        v = K.mm("btd,dhk->bthk", y, p["wv"], quant)
        q = _rope(K.rms_norm(q, p["q_norm"], eps), pos, s["rope_theta"])
        k = _rope(K.rms_norm(k, p["k_norm"], eps), pos, s["rope_theta"])
        hd = q.shape[-1]
        q = q.reshape(b, t, kv, h // kv, hd) * hd ** -0.5
        sc = K.mm("btngk,bsnk->bngts", q, k, quant)
        causal = pos[:, None] >= pos[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        att = K.mm("bngts,bsnk->btngk", pr, v, quant).reshape(b, t, h, hd)
        x = x + K.mm("bthk,hkd->btd", att, p["wo"], quant)
        y = K.rms_norm(x, p["mlp_norm"], eps)
        gate = K.mm("btd,df->btf", y, p["w_gate"], quant)
        up = K.mm("btd,df->btf", y, p["w_up"], quant)
        return x + K.mm("btf,fd->btd", jax.nn.silu(gate) * up, p["w_down"],
                        quant)

    return layer


def hidden(params, tokens, s, quant=""):
    """Final normed hidden states [B, T, D] f32 of ``tokens`` [B, T]."""
    layer = _layer_fn(tuple(sorted(s.items())), quant)
    x = jnp.take(params["embedding"], tokens, axis=0).astype(K.F32)
    for i in range(s["num_layers"]):
        x = layer(x, params["layers"], i)
    return K.rms_norm(x, params["final_norm"], s["norm_eps"])


def unembed(params, s):
    """[D, vocab_size] output matrix (the tied embedding, real rows)."""
    return params["embedding"][: s["vocab_size"]].T
