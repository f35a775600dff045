"""Operations and bytes that Ling-3.0-flash's algorithm needs, from shapes
and from the counts the program's spans carry. As in ``benchmark/costs.py``
they count what the mathematics requires and nothing a program adds.

- A KDA layer keeps no per-token entry. Its memory a slot is one ``d x d``
  float32 matrix a head (``H d d 4`` = 2,097,152 B a layer at 32 heads of
  128) and the last three inputs of the q/k/v convolutions (``3 x 3 H d``
  in bf16, 73,728 B). A decode step reads and writes both once a live
  slot.
- The recurrence a token a head: the decay of ``S`` (``d^2`` multiplies),
  ``S'^T k`` (``2 d^2``), the rank-one write (``2 d^2``) and ``S^T q`` (``2
  d^2``): ``7 d^2`` = 114,688 FLOPs. That is the count of BOTH kernels'
  work, however they are implemented: the chunk kernel's triangular
  solves and its products at ``highest`` precision are its own way of
  doing these operations, not work the algorithm asks for. Its bytes a
  token a head: q, k, v in and o out at the activations' width, the
  log-decays in float32, beta; a piece's state in and out once a layer.
- The MLA layer's cached token is one latent row of ``kv_lora_rank +
  qk_rope_head_dim`` entries (1,152 B in bf16, whatever padding a pool row
  carries); decode attention in the absorbed form, prefill attention in
  the expanded form, as ``deepseek_v2_costs.py`` counts them, over the MLA
  layers only.
- Every matmul weight that is not a routed expert's is read once a step; a
  routed expert's weights (5,898,240 parameters, 11.8 MB) only if it had a
  row, and its FLOPs are those of the rows it had.

``model`` is the configuration file's dict. The functions of the interface
take the spans' own counts as keyword arguments (``expert_rows``,
``experts_hit``, ``starts``); ``readers/moe_trace_roofline.py`` and
``readers/state_trace_roofline.py`` pass them.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple


def _layers(m: Dict):
    first = int(m.get("first_layer", 0))
    return range(first, first + m["num_hidden_layers"])


def mla_layers(m: Dict) -> int:
    return sum((L + 1) % m["layer_group_size"] == 0 for L in _layers(m))


def kda_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - mla_layers(m)


def dense_layers(m: Dict) -> int:
    return sum(L < m["first_k_dense_replace"] for L in _layers(m))


def expert_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def router_width(m: Dict) -> int:
    return int(m.get("router_width", m["n_routed_experts"]))


def _hd(m: Dict) -> int:
    return m["num_attention_heads"] * m["head_dim"]


def kda_params(m: Dict) -> int:
    """W_q, W_k, W_v, W_f, W_o (h x H d each) and the two head-wise gates
    of one KDA layer."""
    h = m["hidden_size"]
    return 5 * h * _hd(m) + 2 * h * m["num_attention_heads"]


def mla_params(m: Dict) -> int:
    """W_q (uncompressed), W_dkv, W_ukv, W_o and the head-wise gate of one
    MLA layer."""
    h, H = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    r = m["kv_lora_rank"]
    return (h * H * (dn + dr) + h * (r + dr) + r * H * (dn + dv)
            + H * dv * h + h * H)


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_params(m: Dict) -> int:
    """Matmul parameters every token passes whatever it is routed to: the
    layers' token mixing, the dense layers' FFN, the shared experts, the
    routers, and the head (the embedding is a row gather)."""
    h = m["hidden_size"]
    shared = 3 * h * m["moe_shared_expert_intermediate_size"] \
        * m["num_shared_experts"]
    return (kda_layers(m) * kda_params(m) + mla_layers(m) * mla_params(m)
            + dense_layers(m) * 3 * h * m["intermediate_size"]
            + expert_layers(m) * (shared + h * router_width(m))
            + h * m["vocab_size"])


def latent_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """The MLA layers' rows of one cached token, unpadded."""
    return (mla_layers(m) * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * itemsize)


kv_bytes_per_token = latent_bytes_per_token


def matrix_bytes_per_slot(m: Dict) -> int:
    """One KDA layer's float32 matrices of one slot."""
    return m["num_attention_heads"] * m["head_dim"] ** 2 * 4


def conv_bytes_per_slot(m: Dict, itemsize: int = 2) -> int:
    """One KDA layer's last three inputs of the q/k/v convolutions."""
    return (m["short_conv_kernel_size"] - 1) * 3 * _hd(m) * itemsize


def state_bytes_per_slot(m: Dict, itemsize: int = 2) -> int:
    return kda_layers(m) * (matrix_bytes_per_slot(m)
                            + conv_bytes_per_slot(m, itemsize))


def recurrence_flops_per_token(m: Dict) -> float:
    """``7 d^2`` a head over the KDA layers (the module's docstring)."""
    return (7.0 * m["head_dim"] ** 2 * m["num_attention_heads"]
            * kda_layers(m))


def kda_step_cost(m: Dict, slots: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the one-token state update of one decode step:
    every live slot's matrices read and written once a layer, the step's
    vectors (q, k, v, the log-decays and o in float32, beta) beside
    them."""
    vectors = (5 * m["head_dim"] * 4 + 4) * m["num_attention_heads"]
    return (recurrence_flops_per_token(m) * slots,
            kda_layers(m) * (2.0 * matrix_bytes_per_slot(m) + vectors)
            * slots)


def kda_chunk_cost(m: Dict, scan_tokens_by_row: Sequence[int],
                   itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the chunked scan over pieces, each given as the
    ``scan_tokens`` its span counts (real tokens x the KDA layers the scan
    advanced): the recurrence's FLOPs a real token a layer; q, k, v in and
    o out at the activations' width, the log-decays in float32 and beta a
    token a head; a piece's matrices in and out once a layer."""
    toks = float(sum(scan_tokens_by_row))
    H, d = m["num_attention_heads"], m["head_dim"]
    per_tok = H * (d * (4 * itemsize + 4) + 4)
    return (7.0 * d * d * H * toks,
            per_tok * toks + 2.0 * kda_layers(m) * matrix_bytes_per_slot(m)
            * len(scan_tokens_by_row))


def expert_gmm_cost(m: Dict, expert_rows: float, experts_hit: float,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' grouped matmuls: three
    matmuls a row, an expert's weights once where it had a row."""
    return (2.0 * expert_params(m) * expert_rows,
            float(expert_params(m)) * itemsize * experts_hit)


def decode_attention_cost(m: Dict, slots: float, live_tokens: float,
                          itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode attention alone (the latent walk of
    the MLA layers), in the absorbed form: per cached token-layer each head
    does one dot over the row and one weighted sum of its latent part."""
    r, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    flops = (2.0 * mla_layers(m) * m["num_attention_heads"]
             * ((r + dr) + r) * live_tokens)
    return flops, latent_bytes_per_token(m, itemsize) * live_tokens


def decode_step_cost(m: Dict, slots: float, live_tokens: float,
                     itemsize: int = 2, expert_rows: float = 0.0,
                     experts_hit: float = 0.0) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: the fixed weights once, the hit
    experts' weights once, the live latent rows once and one new row a
    slot, every live slot's state read and written once."""
    af, ab = decode_attention_cost(m, slots, live_tokens, itemsize)
    ef, eb = expert_gmm_cost(m, expert_rows, experts_hit, itemsize)
    flops = (2.0 * fixed_params(m) * slots + af + ef
             + recurrence_flops_per_token(m) * slots)
    nbytes = (fixed_params(m) * itemsize + eb + ab
              + (latent_bytes_per_token(m, itemsize)
                 + 2.0 * state_bytes_per_slot(m, itemsize)) * slots)
    return flops, nbytes


def attn_flops_causal(m: Dict, q_tokens: int, kv_start: int = 0) -> float:
    """Forward FLOPs of the MLA layers' expanded causal attention for
    ``q_tokens`` queries whose first sees ``kv_start`` earlier positions."""
    pairs = q_tokens * kv_start + q_tokens * (q_tokens + 1) / 2
    return (mla_layers(m) * 2.0 * m["num_attention_heads"]
            * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
               + m["v_head_dim"]) * pairs)


def prefill_flops(m: Dict, prompt_tokens: int, start: int = 0,
                  expert_rows: float = 0.0, final: bool = True) -> float:
    """Forward FLOPs to prefill ``prompt_tokens`` real tokens of a row of
    which ``start`` are cached already: the fixed matmuls (the head on the
    last position only, and only where the piece ends the prompt), the
    recurrence, the causal attention over [cached ; piece], and the routed
    experts' rows."""
    h = m["hidden_size"]
    body = 2.0 * (fixed_params(m) - h * m["vocab_size"])
    return ((body + recurrence_flops_per_token(m)) * prompt_tokens
            + attn_flops_causal(m, prompt_tokens, start)
            + (2.0 * h * m["vocab_size"] if final else 0.0)
            + expert_gmm_cost(m, expert_rows, 0.0)[0])


def flash_cost(m: Dict, tokens_by_row: Sequence[int], itemsize: int = 2,
               backward: bool = False, starts: Sequence[int] = ()
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the MLA layers' prefill attention over rows of the
    given real lengths, each after ``starts[i]`` cached tokens: the
    expanded form's causal pairs; bytes: each piece token's queries read
    and output written once, the latent rows of [cached ; piece] read
    once."""
    if backward:
        raise ValueError("the ling_hybrid family is not trained here")
    starts = list(starts) or [0] * len(tokens_by_row)
    flops = sum(attn_flops_causal(m, int(t), int(s))
                for t, s in zip(tokens_by_row, starts))
    per_q = (m["num_attention_heads"]
             * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                + m["v_head_dim"]) * itemsize * mla_layers(m))
    nbytes = sum(per_q * t + latent_bytes_per_token(m, itemsize) * (t + s)
                 for t, s in zip(tokens_by_row, starts))
    return flops, float(nbytes)


def train_flops_per_token(m: Dict, seq: int) -> float:
    raise ValueError("the ling_hybrid family is not trained here")
