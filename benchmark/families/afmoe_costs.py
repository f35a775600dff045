"""Operations and bytes that Trinity's (``afmoe``) algorithm needs, from
shapes and from the counts the program's spans carry. As in
``benchmark/costs.py`` they count what the mathematics requires and nothing
a program adds: a cached token is one K and one V row of
``num_key_value_heads x head_dim`` entries a layer (4,096 B in bf16), and a
layer reads of them what its mask lets a query SEE: a ``full_attention``
layer every earlier token, a ``sliding_attention`` layer the last
``sliding_window`` (the query's own among them). A walk or a history that
reads behind the window therefore shows a LOW share of its roofline, not
more work. Attention is 4 x head_dim FLOPs a visible pair a QUERY head (QK^T
and PV against its own KV head's columns: whatever a cache row makes the
MXU multiply besides is the program's, not the algorithm's). Every matmul
weight that is not a routed expert's is read once a step: the attention's
FIVE projections (the gate's among them), the dense layers' FFN, each
expert layer's router (at its published width) and shared expert, the head
over this chip's slice; a routed expert's weights are read only if it is
HELD here and had a row, and its FLOPs are those of the rows it had. The
gate's multiply and the four norms a layer are counted too
(``elementwise_flops``: a thousandth of a layer's matmuls). ``model`` is
the configuration file's dict.

The functions of the interface take the spans' own counts as keyword
arguments (``expert_rows``, ``experts_hit``, ``starts``), as
``families/mellum_costs.py`` does; ``window_tokens`` (the visible tokens of
the window layers, summed over the slots: ``sum_i min(context_i, window)``)
is what ``readers/window_walk.py`` adds, since a mean context does not give
it.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple


def layer_types(m: Dict):
    """The types of the layers that are run: a configuration cut in depth
    keeps the published list whole and names the published indices it runs
    (``layers_run``)."""
    run = m.get("layers_run") or range(m["num_hidden_layers"])
    return [m["layer_types"][i] for i in run]


def router_width(m: Dict) -> int:
    return int(m.get("router_width", m["n_routed_experts"]))


def full_layers(m: Dict) -> int:
    return sum(t == "full_attention" for t in layer_types(m))


def window_layers(m: Dict) -> int:
    return sum(t == "sliding_attention" for t in layer_types(m))


def expert_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - m["num_dense_layers"]


def attention_params(m: Dict) -> int:
    """W_q, W_g, W_o and W_k, W_v of one layer."""
    h, d = m["hidden_size"], m["head_dim"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return 3 * h * H * d + 2 * h * Hkv * d


def expert_params(m: Dict) -> int:
    """One routed expert's three matrices (the shared expert's too)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_params(m: Dict) -> int:
    """Matmul parameters every token passes whatever it is routed to: the
    layers' attention, the dense layers' FFN, the expert layers' routers
    and shared experts, and the head (untied; the embedding is a row
    gather)."""
    h, L = m["hidden_size"], m["num_hidden_layers"]
    return (L * attention_params(m)
            + m["num_dense_layers"] * 3 * h * m["intermediate_size"]
            + expert_layers(m) * (h * router_width(m)
                                  + m["num_shared_experts"]
                                  * expert_params(m))
            + h * m["vocab_size"])


def elementwise_flops(m: Dict) -> float:
    """A token's FLOPs outside the matmuls that this model adds to a plain
    decoder: the gate's sigmoid and multiply over ``heads x head_dim``
    columns and four norms (square, mean, scale, weight) a layer."""
    return float(m["num_hidden_layers"] * (
        2 * m["num_attention_heads"] * m["head_dim"]
        + 4 * 4 * m["hidden_size"]))

def row_bytes(m: Dict, itemsize: int = 2) -> int:
    """One cached token's K and V rows in one layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def kv_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """The per-token entries that grow with the context: the full
    layers'."""
    return full_layers(m) * row_bytes(m, itemsize)


def window_bytes_per_slot(m: Dict, block: int, itemsize: int = 2) -> int:
    """What a slot holds at most of the window kind: a ring of
    ``ceil(window / block) + 1`` blocks a window layer."""
    ring = -(-m["sliding_window"] // block) + 1
    return window_layers(m) * ring * block * row_bytes(m, itemsize)


def window_tokens_bound(m: Dict, slots: float, live_tokens: float) -> float:
    """Visible tokens of a window layer summed over the slots when only
    the mean context is known: an upper bound (min is concave)."""
    if not slots:
        return 0.0
    return slots * min(live_tokens / slots, m["sliding_window"])


def expert_gmm_cost(m: Dict, expert_rows: float, experts_hit: float,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' grouped matmuls: three
    matmuls a row, an expert's weights once where it had a row."""
    return (2.0 * expert_params(m) * expert_rows,
            float(expert_params(m)) * itemsize * experts_hit)


def walk_cost(m: Dict, kind: str, tokens: float, itemsize: int = 2
              ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode walks of one kind of layer
    (``"full"`` | ``"window"``) over ``tokens`` visible cached tokens
    (summed over the slots): per token-layer each query head does one dot
    with its key and one weighted sum of its value (24,576 FLOPs at 48
    heads of 128) against one K and one V row."""
    layers = full_layers(m) if kind == "full" else window_layers(m)
    return (layers * 4.0 * m["num_attention_heads"] * m["head_dim"] * tokens,
            float(layers * row_bytes(m, itemsize)) * tokens)


def decode_attention_cost(m: Dict, slots: float, live_tokens: float,
                          itemsize: int = 2,
                          window_tokens: Optional[float] = None
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode attention alone, both kinds' walks."""
    if window_tokens is None:
        window_tokens = window_tokens_bound(m, slots, live_tokens)
    ff, fb = walk_cost(m, "full", live_tokens, itemsize)
    wf, wb = walk_cost(m, "window", window_tokens, itemsize)
    return ff + wf, fb + wb


def decode_step_cost(m: Dict, slots: float, live_tokens: float,
                     itemsize: int = 2, expert_rows: float = 0.0,
                     experts_hit: float = 0.0,
                     window_tokens: Optional[float] = None
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: the fixed weights once, the hit
    experts' weights once, the visible cache rows once, one new row a
    layer a slot."""
    af, ab = decode_attention_cost(m, slots, live_tokens, itemsize,
                                   window_tokens)
    ef, eb = expert_gmm_cost(m, expert_rows, experts_hit, itemsize)
    flops = (2.0 * fixed_params(m) + elementwise_flops(m)) * slots + af + ef
    nbytes = (fixed_params(m) * itemsize + eb + ab
              + m["num_hidden_layers"] * row_bytes(m, itemsize) * slots)
    return flops, nbytes


def visible_pairs(m: Dict, kind: str, q_tokens: int, kv_start: int = 0
                  ) -> float:
    """Pairs (query, key) inside the mask of one kind of layer for
    ``q_tokens`` queries whose first sees ``kv_start`` earlier positions:
    query i sees ``kv_start + i + 1`` keys, a window layer at most
    ``sliding_window`` of them."""
    t, s = int(q_tokens), int(kv_start)
    if kind == "full":
        return t * s + t * (t + 1) / 2.0
    W = m["sliding_window"]
    ramp = max(0, min(t, W - s - 1))       # queries that still see all
    return (ramp * s + ramp * (ramp + 1) / 2.0) + (t - ramp) * float(W)


def attn_flops_causal(m: Dict, q_tokens: int, kv_start: int = 0) -> float:
    """Forward FLOPs of the attention, both kinds, for ``q_tokens``
    queries after ``kv_start`` cached positions."""
    per_pair = 4.0 * m["num_attention_heads"] * m["head_dim"]
    return per_pair * (
        full_layers(m) * visible_pairs(m, "full", q_tokens, kv_start)
        + window_layers(m) * visible_pairs(m, "window", q_tokens, kv_start))


def prefill_flops(m: Dict, prompt_tokens: int, start: int = 0,
                  expert_rows: float = 0.0, final: bool = True) -> float:
    """Forward FLOPs to prefill ``prompt_tokens`` real tokens of a row of
    which ``start`` are cached already: the fixed matmuls (the head on the
    last position only, and only where the piece ends the prompt), the
    attention inside each kind's mask, and the routed experts' rows."""
    h = m["hidden_size"]
    body = 2.0 * (fixed_params(m) - h * m["vocab_size"])
    return ((body + elementwise_flops(m)) * prompt_tokens
            + attn_flops_causal(m, prompt_tokens, start)
            + (2.0 * h * m["vocab_size"] if final else 0.0)
            + expert_gmm_cost(m, expert_rows, 0.0)[0])


def flash_cost(m: Dict, tokens_by_row: Sequence[int], itemsize: int = 2,
               backward: bool = False, starts: Sequence[int] = ()
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the prefill attention over rows of the given real
    lengths, each after ``starts[i]`` cached tokens: the pairs inside each
    kind's mask; bytes: each piece token's queries read and output written
    once a layer, and the K and V rows a layer may see read once: [cached
    ; piece] in a full layer, the piece and the last ``window - 1`` cached
    in a window layer."""
    if backward:
        raise ValueError("the afmoe family is not trained here")
    starts = list(starts) or [0] * len(tokens_by_row)
    L, W = m["num_hidden_layers"], m["sliding_window"]
    per_q = 2 * m["num_attention_heads"] * m["head_dim"] * itemsize
    row = row_bytes(m, itemsize)
    flops = nbytes = 0.0
    for t, s in zip(tokens_by_row, starts):
        t, s = int(t), int(s)
        flops += attn_flops_causal(m, t, s)
        nbytes += (L * per_q * t + full_layers(m) * row * (t + s)
                   + window_layers(m) * row * (t + min(s, W - 1)))
    return flops, nbytes


def train_flops_per_token(m: Dict, seq: int) -> float:
    raise ValueError("the afmoe family is not trained here")
