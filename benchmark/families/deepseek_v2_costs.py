"""Operations and bytes that DeepSeek-V2's algorithm needs, from shapes and
from the counts the program's spans carry. As in ``benchmark/costs.py``
they count what the mathematics requires and nothing a program adds: a
cached token is one latent row of ``kv_lora_rank + qk_rope_head_dim``
entries a layer (1,152 B in bf16) read once, whatever padding a pool row
carries; decode attention is the absorbed form (each head scores the
shared row and weighs its latent part); prefill attention is the expanded
form over the causal pairs at q/k ``nope + rope`` and v ``v_head_dim``;
every matmul weight that is not a routed expert's is read once a step; a
routed expert's weights are read only if it had a row, and its FLOPs are
those of the rows it had. ``model`` is the configuration file's dict.

The five functions of the interface take the spans' own counts as keyword
arguments (``expert_rows``, ``experts_hit``: token-expert pairs computed
here and held experts with a row, summed over the expert layers and the
steps or waves; ``starts``: what of each prefill row was cached already);
``readers/moe_trace_roofline.py`` passes them.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple


def _dims(m: Dict):
    return (m["hidden_size"], m["num_attention_heads"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["q_lora_rank"],
            m["kv_lora_rank"])


def attention_params(m: Dict) -> int:
    """W_DQ, W_UQ, W_DKV, W_UKV, W_O of one layer."""
    h, H, dn, dr, dv, qr, r = _dims(m)
    return (h * qr + qr * H * (dn + dr) + h * (r + dr) + r * H * (dn + dv)
            + H * dv * h)


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_width(m: Dict) -> int:
    return int(m.get("router_width", m["n_routed_experts"]))


def dense_layers(m: Dict) -> int:
    return min(m["first_k_dense_replace"], m["num_hidden_layers"])


def expert_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def fixed_params(m: Dict) -> int:
    """Matmul parameters every token passes whatever it is routed to: the
    layers' attention, the dense layers' FFN, the shared experts, the
    routers, and the head (the embedding is a row gather)."""
    h = m["hidden_size"]
    per_moe = (attention_params(m) + m["n_shared_experts"] * expert_params(m)
               + h * router_width(m))
    per_dense = attention_params(m) + 3 * h * m["intermediate_size"]
    return (expert_layers(m) * per_moe + dense_layers(m) * per_dense
            + h * m["vocab_size"])


def latent_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """All layers' rows of one cached token, unpadded."""
    return (m["num_hidden_layers"] * (m["kv_lora_rank"]
                                      + m["qk_rope_head_dim"]) * itemsize)


def expert_gmm_cost(m: Dict, expert_rows: float, experts_hit: float,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' grouped matmuls: three
    matmuls a row, an expert's weights once where it had a row."""
    return (2.0 * expert_params(m) * expert_rows,
            float(expert_params(m)) * itemsize * experts_hit)


def decode_attention_cost(m: Dict, slots: float, live_tokens: float,
                          itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode attention alone (the latent walk), in
    the absorbed form: per cached token-layer each head does one dot over
    the row and one weighted sum of its latent part."""
    _h, H, _dn, dr, _dv, _qr, r = _dims(m)
    flops = (2.0 * m["num_hidden_layers"] * H * ((r + dr) + r)
             * live_tokens)
    return flops, latent_bytes_per_token(m, itemsize) * live_tokens


def decode_step_cost(m: Dict, slots: float, live_tokens: float,
                     itemsize: int = 2, expert_rows: float = 0.0,
                     experts_hit: float = 0.0) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: the fixed weights once, the hit
    experts' weights once, the live latent rows once, one new row a
    slot."""
    af, ab = decode_attention_cost(m, slots, live_tokens, itemsize)
    ef, eb = expert_gmm_cost(m, expert_rows, experts_hit, itemsize)
    flops = 2.0 * fixed_params(m) * slots + af + ef
    nbytes = (fixed_params(m) * itemsize + eb + ab
              + latent_bytes_per_token(m, itemsize) * slots)
    return flops, nbytes


def attn_flops_causal(m: Dict, q_tokens: int, kv_start: int = 0) -> float:
    """Forward FLOPs of the expanded causal attention for ``q_tokens``
    queries whose first sees ``kv_start`` earlier positions: QK^T at
    ``nope + rope``, PV at ``v_head_dim``."""
    _h, H, dn, dr, dv, _qr, _r = _dims(m)
    pairs = q_tokens * kv_start + q_tokens * (q_tokens + 1) / 2
    return m["num_hidden_layers"] * 2.0 * H * (dn + dr + dv) * pairs


def prefill_flops(m: Dict, prompt_tokens: int, start: int = 0,
                  expert_rows: float = 0.0, final: bool = True) -> float:
    """Forward FLOPs to prefill ``prompt_tokens`` real tokens of a row of
    which ``start`` are cached already: the fixed matmuls (the head on the
    last position only, and only where the piece ends the prompt), the
    causal attention over [cached ; piece], and the routed experts' rows."""
    h = m["hidden_size"]
    body = 2.0 * (fixed_params(m) - h * m["vocab_size"])
    return (body * prompt_tokens + attn_flops_causal(m, prompt_tokens, start)
            + (2.0 * h * m["vocab_size"] if final else 0.0)
            + expert_gmm_cost(m, expert_rows, 0.0)[0])


def flash_cost(m: Dict, tokens_by_row: Sequence[int], itemsize: int = 2,
               backward: bool = False, starts: Sequence[int] = ()
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the prefill attention over rows of the given real
    lengths, each after ``starts[i]`` cached tokens: the expanded form's
    causal pairs; bytes: each piece token's queries read and output
    written once, the latent rows of [cached ; piece] read once."""
    if backward:
        raise ValueError("the deepseek_v2 family is not trained here")
    starts = list(starts) or [0] * len(tokens_by_row)
    _h, H, dn, dr, dv, _qr, r = _dims(m)
    flops = sum(attn_flops_causal(m, int(t), int(s))
                for t, s in zip(tokens_by_row, starts))
    per_q = H * (dn + dr + dv) * itemsize * m["num_hidden_layers"]
    nbytes = sum(per_q * t + latent_bytes_per_token(m, itemsize) * (t + s)
                 for t, s in zip(tokens_by_row, starts))
    return flops, float(nbytes)


def train_flops_per_token(m: Dict, seq: int) -> float:
    raise ValueError("the deepseek_v2 family is not trained here")
