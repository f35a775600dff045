"""TPU kernel library: attention (flash/ring/ulysses/paged), MoE dispatch
+ fused FFN, grouped-matmul autotuning, and int8 weight-only / KV
quantized matmuls.

This is the package's public surface — serving, bench and the chip
lanes import kernel entry points from here; module paths stay available
for the internals (partial-state kernels, autotune caches) that tests
reach into directly.
"""
from .flash_attention import flash_attention  # noqa: F401
from .gmm_autotune import (candidate_tilings, get_tilings,  # noqa: F401
                           heuristic_tilings)
from .moe_fused import fused_moe_ffn, gather_gmm  # noqa: F401
from .paged_attention import (PagedKVCache, paged_append,  # noqa: F401
                              paged_append_blocks, paged_append_token,
                              paged_attention, paged_cache_init,
                              paged_decode_attention,
                              ragged_decode_partial, ragged_paged_decode)
from .quant_matmul import (attn_pv, attn_qk, dequantize_kv,  # noqa: F401
                           quantize_kv, weight_only_matmul)

__all__ = [
    # paged / ragged decode attention (r4/r12)
    "PagedKVCache", "paged_cache_init", "paged_append",
    "paged_attention", "paged_append_token", "paged_append_blocks",
    "paged_decode_attention", "ragged_decode_partial",
    "ragged_paged_decode",
    # flash attention
    "flash_attention",
    # MoE fused FFN + grouped matmul autotuning
    "fused_moe_ffn", "gather_gmm",
    "heuristic_tilings", "get_tilings", "candidate_tilings",
    # int8 weight-only / KV quantized matmuls
    "weight_only_matmul", "quantize_kv", "dequantize_kv",
    "attn_qk", "attn_pv",
]
