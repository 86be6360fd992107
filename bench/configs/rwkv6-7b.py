"""rwkv6-7b (Finch, arXiv:2404.05892): weights from the seed, and the
plain float32 reference.

Per layer, a time-mix block (data-dependent token shift through a LoRA,
the WKV6 recurrence with data-dependent per-channel decay ``w_t`` and
bonus ``u``, a per-head group norm, an output gate) and a channel-mix
block (token shift, squared-ReLU feed-forward, receptance gate):

    y_t = r_t S_{t-1} + (u * k_t . r_t) v_t
    S_t = diag(w_t) S_{t-1} + k_t^T v_t,   w_t = exp(-exp(w0 + lora(x_t)))

Written from the paper, to the conventions of the program's parameter
layout, which departs from the paper in two stated ways: RMSNorm (scaling
by ``1 + w``) where the paper has LayerNorm, and one rank-32 LoRA shared
by the five token-shift mixes, with a B matrix per mix.  The token shift
of a block mixes the normed input with the previous position's normed
input (zero before the first).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import refkit as K

LORA_MIX = 32
LORA_DECAY = 64
GROUP_NORM_EPS = 64e-5


def vocab_padded(s) -> int:
    return K.round_up(s["vocab_size"], s["vocab_round"])


def init(s, key):
    """The parameter tree in bfloat16, from one key."""
    L, d, f = s["num_layers"], s["d_model"], s["d_ff"]
    h, hd = s["num_heads"], s["head_dim"]
    vp, bf = vocab_padded(s), jnp.bfloat16
    out_std = 1.0 / (2.0 * L) ** 0.5
    ks = iter(jax.random.split(key, 32))
    # decay bias per channel: -6 .. -1 across the width, as the published
    # initialisation spreads it (decay per step exp(-exp(w0)) 0.9975 .. 0.69)
    ramp = jnp.linspace(0.0, 1.0, d) ** 0.7
    w0 = (-6.0 + 5.0 * ramp)[None, :] + 0.3 * jax.random.normal(
        next(ks), (L, d), K.F32)
    layers = {
        "ln_att": K.normal(next(ks), (L, d), 0.1, bf),
        "ln_ffn": K.normal(next(ks), (L, d), 0.1, bf),
        "mu_x": K.uniform(next(ks), (L, d), 0.0, 1.0, bf),
        "mu_rkvwg": K.uniform(next(ks), (L, 5, d), 0.0, 1.0, bf),
        "lora_a": K.normal(next(ks), (L, d, 5 * LORA_MIX), d ** -0.5, bf),
        "lora_b": K.normal(next(ks), (L, 5, LORA_MIX, d), 0.05, bf),
        "w0": w0.astype(bf),
        "wa": K.normal(next(ks), (L, d, LORA_DECAY), d ** -0.5, bf),
        "wb": K.normal(next(ks), (L, LORA_DECAY, d), 0.05, bf),
        "bonus_u": K.normal(next(ks), (L, h, hd), 0.3, bf),
        "w_r": K.normal(next(ks), (L, d, d), d ** -0.5, bf),
        "w_k": K.normal(next(ks), (L, d, d), d ** -0.5, bf),
        "w_v": K.normal(next(ks), (L, d, d), d ** -0.5, bf),
        "w_g": K.normal(next(ks), (L, d, d), d ** -0.5, bf),
        "w_o": K.normal(next(ks), (L, d, d), d ** -0.5 * out_std, bf),
        "gn_w": K.normal(next(ks), (L, d), 0.1, bf),
        "mu_k2": K.uniform(next(ks), (L, d), 0.0, 1.0, bf),
        "mu_r2": K.uniform(next(ks), (L, d), 0.0, 1.0, bf),
        "w_k2": K.normal(next(ks), (L, d, f), d ** -0.5, bf),
        "w_v2": K.normal(next(ks), (L, f, d), f ** -0.5 * out_std, bf),
        "w_r2": K.normal(next(ks), (L, d, d), d ** -0.5, bf),
    }
    return {"embedding": K.normal(next(ks), (vp, d), 1.0, bf, stacked=False),
            "final_norm": K.normal(next(ks), (d,), 0.1, bf, stacked=False),
            "unembed": K.normal(next(ks), (d, vp), d ** -0.5, bf,
                                stacked=False),
            "layers": layers}


def _shift(x):
    """x [B, T, D] -> the previous position's x, zero before the first."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _wkv(r, k, v, w, u):
    """The recurrence, one step at a time.  r/k/v/w [B, T, H, K], u [H, K]
    -> y [B, T, H, V]."""
    b, _, h, kd = r.shape

    def step(S, xs):
        rt, kt, vt, wt = xs                                  # [B, H, K]
        y = jnp.einsum("bhk,bhkv->bhv", rt, S, precision=K.HIGHEST)
        y = y + jnp.sum(u * kt * rt, -1, keepdims=True) * vt
        S = wt[..., None] * S + kt[..., None] * vt[:, :, None, :]
        return S, y

    S0 = jnp.zeros((b, h, kd, v.shape[-1]), K.F32)
    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, S0, xs)
    return jnp.swapaxes(ys, 0, 1)


@functools.lru_cache(maxsize=None)
def _layer_fn(s_items, quant):
    s = dict(s_items)
    h, hd, eps = s["num_heads"], s["head_dim"], s["norm_eps"]

    @jax.jit
    def layer(x, stacked, i):
        p = K.layer_slice(stacked, i)
        b, t, d = x.shape
        # time mix
        xn = K.rms_norm(x, p["ln_att"], eps)
        delta = _shift(xn) - xn
        base = xn + delta * p["mu_x"]
        lora = jnp.tanh(K.mm("btd,dr->btr", base, p["lora_a"], quant))
        lora = lora.reshape(b, t, 5, LORA_MIX)
        mix = p["mu_rkvwg"] + K.mm("btcr,crd->btcd", lora, p["lora_b"], quant)
        xr, xk, xv, xw, xg = [xn + delta * mix[:, :, c] for c in range(5)]
        r = K.mm("btd,de->bte", xr, p["w_r"], quant).reshape(b, t, h, hd)
        k = K.mm("btd,de->bte", xk, p["w_k"], quant).reshape(b, t, h, hd)
        v = K.mm("btd,de->bte", xv, p["w_v"], quant).reshape(b, t, h, hd)
        g = jax.nn.silu(K.mm("btd,de->bte", xg, p["w_g"], quant))
        dec = p["w0"] + K.mm("btr,rd->btd",
                             jnp.tanh(K.mm("btd,dr->btr", xw, p["wa"], quant)),
                             p["wb"], quant)
        w = jnp.exp(-jnp.exp(dec)).reshape(b, t, h, hd)
        y = _wkv(r, k, v, w, p["bonus_u"])
        mu = y.mean(-1, keepdims=True)
        var = ((y - mu) ** 2).mean(-1, keepdims=True)
        y = ((y - mu) * jax.lax.rsqrt(var + GROUP_NORM_EPS)).reshape(b, t, d)
        y = y * (1.0 + p["gn_w"])
        x = x + K.mm("btd,de->bte", y * g, p["w_o"], quant)
        # channel mix
        xn = K.rms_norm(x, p["ln_ffn"], eps)
        delta = _shift(xn) - xn
        xk = xn + delta * p["mu_k2"]
        xr = xn + delta * p["mu_r2"]
        kk = jnp.square(jax.nn.relu(K.mm("btd,df->btf", xk, p["w_k2"], quant)))
        gate = jax.nn.sigmoid(K.mm("btd,de->bte", xr, p["w_r2"], quant))
        return x + gate * K.mm("btf,fd->btd", kk, p["w_v2"], quant)

    return layer


def hidden(params, tokens, s, quant=""):
    """Final normed hidden states [B, T, D] f32 of ``tokens`` [B, T]."""
    layer = _layer_fn(tuple(sorted(s.items())), quant)
    x = jnp.take(params["embedding"], tokens, axis=0).astype(K.F32)
    for i in range(s["num_layers"]):
        x = layer(x, params["layers"], i)
    return K.rms_norm(x, params["final_norm"], s["norm_eps"])


def unembed(params, s):
    """[D, vocab_size] output matrix."""
    return params["unembed"][:, : s["vocab_size"]]
