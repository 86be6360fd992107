"""Operations and bytes of the hybrid (Mamba2 + attention + MoE)
configurations' kernels, worked out from shapes.

``s`` is a configuration's ``sizes`` (``bench/configs/<config>.json``).
FLOPs count a multiply-add as two.  Only what the algorithm needs is
counted, whatever implements it.
"""
from __future__ import annotations

from typing import Dict

F32 = 4


def mamba_layers(s: Dict) -> int:
    """Mamba2 layers of the stack: all but one layer of every period."""
    period = s["attn_layer_period"]
    return s["num_layers"] // period * (period - 1)


def ssd_flops_per_token(s: Dict) -> int:
    """The SSD recurrence of one token in one layer, per head: dt x (P),
    the decayed update of S, exp(dt A) S + (dt x) B^T (3PN), and the read
    y = S C (2PN)."""
    p, n = s["ssm_head_dim"], s["ssm_state"]
    heads = s["ssm_expand"] * s["d_model"] // p
    return heads * (5 * p * n + p)


def ssd(s: Dict, batch: int, length: int) -> Dict[str, int]:
    """The SSD kernel over all Mamba2 layers of one prefill call, at the
    rows and length it was given.  In float32: x, dt, B and C read and y
    written once per token, the state read and written once per row."""
    p, n = s["ssm_head_dim"], s["ssm_state"]
    heads = s["ssm_expand"] * s["d_model"] // p
    per_token = (2 * heads * p + heads + 2 * n) * F32
    return {"flops": mamba_layers(s) * batch * length * ssd_flops_per_token(s),
            "bytes": mamba_layers(s) * batch * (length * per_token
                                                + 2 * heads * p * n * F32)}
