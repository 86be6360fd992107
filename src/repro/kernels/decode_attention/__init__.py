from repro.kernels.decode_attention.ops import (
    decode_attention_cache,
    decode_attention_int8_cache,
    quantize_kv,
)

__all__ = [
    "decode_attention_cache",
    "decode_attention_int8_cache",
    "quantize_kv",
]
