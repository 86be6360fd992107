"""Pallas TPU kernels for the serving path's compute hot-spots.

Each kernel package has:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (auto interpret=True off-accelerator)
  ref.py    — pure-jnp oracle the kernel is validated against

The paper itself has no kernel-level contribution (it is a serving system);
these cover the stages it schedules: prefill attention (LM + DiT), long-KV
decode attention (full-precision and int8-quantized cache), the fused DDIM
sampling step, the RWKV6 recurrence for the attention-free assigned
arch, and the Mamba2 SSD recurrence for the hybrid ones.  The model-side entry points in ``repro.models.layers`` route here
through the ``use_pallas`` dispatch layer (docs/kernels.md).
"""
from __future__ import annotations

import jax

#: Backends the Mosaic/Triton lowering actually targets.  Everywhere else
#: (cpu, METAL, ...) the kernels run in interpret mode — correct but slow,
#: which is exactly what the parity suites want on a CPU test box.
COMPILED_BACKENDS = ("tpu", "gpu")


def auto_interpret() -> bool:
    """True when the kernels should run in interpret mode.

    The seed version of this check was ``backend != "tpu"`` which silently
    put GPU boxes in interpret mode; the fix is to interpret only on
    backends the Pallas lowering does not target at all.
    """
    return jax.default_backend() not in COMPILED_BACKENDS


def kernel_mode(interpret=None) -> str:
    """'interpret' | 'compiled' — surfaced in bench derived fields."""
    interp = auto_interpret() if interpret is None else interpret
    return "interpret" if interp else "compiled"


from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.decode_attention.ops import (  # noqa: E402
    decode_attention_cache,
    decode_attention_int8_cache,
    quantize_kv,
)
from repro.kernels.rwkv6_wkv.ops import wkv6  # noqa: E402
from repro.kernels.ssd_scan.ops import ssd_scan  # noqa: E402
from repro.kernels.ddim_step.ops import ddim_step  # noqa: E402

__all__ = [
    "COMPILED_BACKENDS",
    "auto_interpret",
    "kernel_mode",
    "flash_attention",
    "decode_attention_cache",
    "decode_attention_int8_cache",
    "quantize_kv",
    "wkv6",
    "ssd_scan",
    "ddim_step",
]
