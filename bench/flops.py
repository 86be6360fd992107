"""Operations and bytes of each call, worked out from shapes.

``s`` is a configuration's ``sizes`` (``bench/configs/<config>.json``).
FLOPs count a multiply-add as two.  Only what the algorithm needs is
counted: causal attention over the keys each query may see, a decode
step's reads of the weights once and of each active row's cache up to
its length.  Two families: ``attention_free`` configurations are RWKV6,
the rest dense decoder transformers.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2
F32 = 4
RWKV_LORA_MIX, RWKV_LORA_DECAY = 32, 64


def vocab_padded(s: Dict) -> int:
    m = s.get("vocab_round", 256)
    return (s["vocab_size"] + m - 1) // m * m


def layer_matmul_params(s: Dict) -> int:
    """Weights of one layer that every token multiplies through."""
    d, f = s["d_model"], s["d_ff"]
    if s.get("attention_free"):
        return (5 * d * d                                   # r k v g o
                + d * 5 * RWKV_LORA_MIX + 5 * RWKV_LORA_MIX * d
                + 2 * d * RWKV_LORA_DECAY                    # decay lora
                + 2 * d * f + d * d)                         # channel mix
    h, kv, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def weight_bytes(s: Dict) -> int:
    """Bytes of the weights one decode step has to read: every layer's
    matrices and vectors, and the output projection (which is the
    embedding table itself where it is tied)."""
    d, L, vp = s["d_model"], s["num_layers"], vocab_padded(s)
    if s.get("attention_free"):
        h, k = s["num_heads"], s["head_dim"]
        vectors = 9 * d + 5 * d + h * k    # norms, mixes, w0, gn, u
    else:
        vectors = 2 * d + 2 * s["head_dim"]
    return BF16 * (L * (layer_matmul_params(s) + vectors) + vp * d + d)


def attention_flops(s: Dict, keys: int) -> int:
    """One query token's attention over ``keys`` positions, all layers
    (QK^T and PV).  Zero for attention-free models."""
    if s.get("attention_free"):
        return 0
    return 4 * s["num_layers"] * s["num_heads"] * s["head_dim"] * keys


def wkv_flops_per_token(s: Dict) -> int:
    """The WKV6 recurrence of one token in one layer: r^T S (2KV), the
    decayed update of S (3KV), the bonus term (3K + 2V), per head."""
    h, k = s["num_heads"], s["head_dim"]
    return h * (5 * k * k + 3 * k + 2 * k)


def token_flops(s: Dict, keys: int, logits: bool) -> int:
    """Model FLOPs of one token that attends ``keys`` positions."""
    L, d = s["num_layers"], s["d_model"]
    f = 2 * L * layer_matmul_params(s) + attention_flops(s, keys)
    if s.get("attention_free"):
        f += L * wkv_flops_per_token(s)
    if logits:
        f += 2 * d * s["vocab_size"]
    return f


def prefill_flops(s: Dict, rows: int, length: int) -> int:
    """Model FLOPs of prefilling ``rows`` prompts of ``length`` tokens:
    every token through every layer with causal attention, logits of the
    last token only."""
    per_row = (length * 2 * s["num_layers"] * layer_matmul_params(s)
               + 2 * s["d_model"] * s["vocab_size"])
    if s.get("attention_free"):
        per_row += length * s["num_layers"] * wkv_flops_per_token(s)
    else:
        per_row += attention_flops(s, 1) * length * (length + 1) // 2
    return rows * per_row


def row_cache_bytes(s: Dict, length: int) -> int:
    """Cache a decode step reads for one row at ``length`` positions (KV
    read and the new position written), or the recurrent state it reads
    and writes back."""
    L = s["num_layers"]
    if s.get("attention_free"):
        h, k, d = s["num_heads"], s["head_dim"], s["d_model"]
        return 2 * L * (h * k * k * F32 + 2 * d * BF16)
    return 2 * L * s["num_kv_heads"] * s["head_dim"] * BF16 * (length + 1)


def decode_step(s: Dict, keys: Iterable[int]) -> Dict[str, int]:
    """One decode step over the rows that advance, each attending
    ``keys[i]`` positions (its cached length plus the new token)."""
    keys = list(keys)
    if not keys:
        return {"flops": 0, "bytes": 0}
    return {"flops": sum(token_flops(s, n, True) for n in keys),
            "bytes": weight_bytes(s) + sum(row_cache_bytes(s, n - 1)
                                           for n in keys)}


def flash_attention(s: Dict, batch: int, length: int) -> Dict[str, int]:
    """The causal flash-attention kernel over all layers of one prefill
    call, at the rows it is given (padding rows included: the kernel
    computes them)."""
    h, kv, hd, L = (s["num_heads"], s["num_kv_heads"], s["head_dim"],
                    s["num_layers"])
    return {"flops": L * batch * 2 * h * hd * length * (length + 1),
            "bytes": L * batch * length * hd * BF16 * (2 * h + 2 * kv)}


def wkv6(s: Dict, batch: int, length: int) -> Dict[str, int]:
    """The WKV6 kernel over all layers of one prefill call.  The kernel is
    handed r, k, v, w in float32 and writes y in float32; the state is
    read and written once per row, in float32."""
    h, k, L = s["num_heads"], s["head_dim"], s["num_layers"]
    return {"flops": L * batch * length * wkv_flops_per_token(s),
            "bytes": L * batch * (5 * length * h * k * F32
                                  + 2 * h * k * k * F32)}


def roofline(work: Dict[str, int], seconds: float,
             peaks: Dict[str, float]) -> Dict[str, float]:
    """Share (%) of the least time the chip could take over ``seconds``,
    and which bound sets that time."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return {"share_pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": bound}
