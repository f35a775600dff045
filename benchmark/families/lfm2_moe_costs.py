"""Operations and bytes that LFM2-8B-A1B's algorithm needs, from shapes and
from the counts the program's spans carry. As in ``benchmark/costs.py``
they count what the mathematics requires and nothing a program adds: a
cached token is one K and one V row of ``num_key_value_heads x head_dim``
entries in each ATTENTION layer (2,048 B a token-layer in bf16, whatever
packing or padding a pool row carries) and nothing in a convolution layer;
a convolution layer's memory is two inputs of ``hidden_size`` a slot;
attention is 4 x head_dim FLOPs a causal pair a head (QK^T and PV); every
matmul weight that is not a routed expert's is read once a step; a routed
expert's weights are read only if it had a row, and its FLOPs are those of
the rows it had. ``model`` is the configuration file's dict.

The five functions of the interface take the spans' own counts as keyword
arguments (``expert_rows``, ``experts_hit``, ``starts``), as
``families/deepseek_v2_costs.py`` does; ``readers/moe_trace_roofline.py``
passes them.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple


def head_dim(m: Dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def layer_types(m: Dict):
    """The types of the layers that are run: a configuration cut in depth
    keeps the published list whole and runs its first
    ``num_hidden_layers`` entries."""
    return list(m["layer_types"])[:m["num_hidden_layers"]]


def attention_layers(m: Dict) -> int:
    return sum(t != "conv" for t in layer_types(m))


def conv_layers(m: Dict) -> int:
    return sum(t == "conv" for t in layer_types(m))


def dense_layers(m: Dict) -> int:
    return min(m["num_dense_layers"], m["num_hidden_layers"])


def expert_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def conv_params(m: Dict) -> int:
    """W_in, the three taps and W_out of one convolution layer."""
    h = m["hidden_size"]
    return h * 3 * h + 3 * h + h * h


def attention_params(m: Dict) -> int:
    """W_q, W_k, W_v, W_o of one attention layer."""
    h, d = m["hidden_size"], head_dim(m)
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return 2 * h * H * d + 2 * h * Hkv * d


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_params(m: Dict) -> int:
    """Matmul parameters every token passes whatever it is routed to: the
    layers' operators, the dense layers' FFN, the routers, and the head
    (tied: the embedding matrix read as the head; the embedding itself is
    a row gather)."""
    h = m["hidden_size"]
    return (conv_layers(m) * conv_params(m)
            + attention_layers(m) * attention_params(m)
            + dense_layers(m) * 3 * h * m["intermediate_size"]
            + expert_layers(m) * h * m["num_experts"]
            + h * m["vocab_size"])


def kv_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """K and V rows of one cached token over the attention layers."""
    return (attention_layers(m) * 2 * m["num_key_value_heads"] * head_dim(m)
            * itemsize)


def state_bytes_per_slot(m: Dict, itemsize: int = 2) -> int:
    """The convolutions' memory of one slot: two inputs a layer."""
    return conv_layers(m) * 2 * m["hidden_size"] * itemsize


def expert_gmm_cost(m: Dict, expert_rows: float, experts_hit: float,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' grouped matmuls: three
    matmuls a row, an expert's weights once where it had a row."""
    return (2.0 * expert_params(m) * expert_rows,
            float(expert_params(m)) * itemsize * experts_hit)


def decode_attention_cost(m: Dict, slots: float, live_tokens: float,
                          itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode attention alone (the ragged walk): per
    cached token-layer each query head does one dot with its key and one
    weighted sum of its value (8,192 FLOPs at 32 heads of 64) against one
    K and one V row (2,048 B)."""
    flops = (attention_layers(m) * 4.0 * m["num_attention_heads"]
             * head_dim(m) * live_tokens)
    return flops, float(kv_bytes_per_token(m, itemsize)) * live_tokens


def decode_step_cost(m: Dict, slots: float, live_tokens: float,
                     itemsize: int = 2, expert_rows: float = 0.0,
                     experts_hit: float = 0.0) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: the fixed weights once, the hit
    experts' weights once, the live cache rows once, one new row and the
    convolutions' state a slot."""
    af, ab = decode_attention_cost(m, slots, live_tokens, itemsize)
    ef, eb = expert_gmm_cost(m, expert_rows, experts_hit, itemsize)
    flops = 2.0 * fixed_params(m) * slots + af + ef
    nbytes = (fixed_params(m) * itemsize + eb + ab
              + (kv_bytes_per_token(m, itemsize)
                 + state_bytes_per_slot(m, itemsize)) * slots)
    return flops, nbytes


def attn_flops_causal(m: Dict, q_tokens: int, kv_start: int = 0) -> float:
    """Forward FLOPs of the causal attention for ``q_tokens`` queries whose
    first sees ``kv_start`` earlier positions."""
    pairs = q_tokens * kv_start + q_tokens * (q_tokens + 1) / 2
    return (attention_layers(m) * 4.0 * m["num_attention_heads"]
            * head_dim(m) * pairs)


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
    lengths, each after ``starts[i]`` cached tokens: the causal pairs;
    bytes: each piece token's queries read and output written once, the K
    and V rows of [cached ; piece] read once."""
    if backward:
        raise ValueError("the lfm2_moe family is not trained here")
    starts = list(starts) or [0] * len(tokens_by_row)
    flops = sum(attn_flops_causal(m, int(t), int(s))
                for t, s in zip(tokens_by_row, starts))
    per_q = (attention_layers(m) * 2 * m["num_attention_heads"] * head_dim(m)
             * itemsize)
    nbytes = sum(per_q * t + kv_bytes_per_token(m, itemsize) * (t + s)
                 for t, s in zip(tokens_by_row, starts))
    return flops, float(nbytes)


def train_flops_per_token(m: Dict, seq: int) -> float:
    raise ValueError("the lfm2_moe family is not trained here")
