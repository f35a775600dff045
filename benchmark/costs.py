"""Operations and bytes that the algorithm needs, computed from shapes.

These are the numerators of every roofline share and of ``mfu``. They count
what the mathematics requires and nothing a particular program adds:
weights are read once a step, only the live KV of active slots is read,
causal attention does half of the square, recomputation under remat and
padding to a bucket do not count. So a share can reach 100% only when the
program does no more than the algorithm, and none can pass it by
overcounting. ``model`` is the configuration file's dict (HF key names).

The training count follows ``paddle_tpu/models/llama.py`` ``flops_per_token``
(PaLM appendix B: 6 per matmul parameter per token, fwd+bwd) with two
departures, both downward: the embedding table is a gather and not a
matmul, so it is left out of N, and attention counts the causal half
(6*L*h*S and not 12*L*h*S).
"""
from __future__ import annotations

from typing import Dict, Tuple


def layer_matmul_params(m: Dict) -> int:
    h, f = m["hidden_size"], m["intermediate_size"]
    d = m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return h * nq * d + 2 * h * nkv * d + nq * d * h + 3 * h * f


def matmul_params(m: Dict) -> int:
    """Parameters that take part in a matmul per token: the layers and the
    output head (the embedding is a row gather)."""
    return (m["num_hidden_layers"] * layer_matmul_params(m)
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: Dict) -> int:
    h = m["hidden_size"]
    embed = m["vocab_size"] * h * (1 if m.get("tie_word_embeddings") else 2)
    return (m["num_hidden_layers"] * (layer_matmul_params(m) + 2 * h)
            + embed + h)


def attn_flops_causal(m: Dict, q_tokens: int, kv_start: int = 0) -> float:
    """Forward FLOPs of causal attention for ``q_tokens`` queries whose
    first query sees ``kv_start`` earlier positions: QK^T and PV, 2 FLOPs a
    multiply-add, over the keys each query may see."""
    pairs = q_tokens * kv_start + q_tokens * (q_tokens + 1) / 2
    width = m["num_attention_heads"] * m["head_dim"]
    return m["num_hidden_layers"] * 4.0 * width * pairs


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward+backward model FLOPs per trained token at sequence ``seq``."""
    attn = 3.0 * attn_flops_causal(m, seq) / seq
    return 6.0 * matmul_params(m) + attn


def prefill_flops(m: Dict, prompt_tokens: int) -> float:
    """Forward FLOPs to prefill one prompt of ``prompt_tokens`` real tokens
    (the head runs on the last position only)."""
    body = 2.0 * m["num_hidden_layers"] * layer_matmul_params(m)
    return (body * prompt_tokens + attn_flops_causal(m, prompt_tokens)
            + 2.0 * m["hidden_size"] * m["vocab_size"])


def kv_bytes_per_token(m: Dict, kv_itemsize: int = 2) -> int:
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * kv_itemsize)


def decode_step_cost(m: Dict, slots: float, live_tokens: float,
                     itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over ``slots`` active slots whose
    contexts hold ``live_tokens`` tokens together: every matmul weight is
    read once, the live KV once, one new KV entry is written per slot."""
    flops = (2.0 * matmul_params(m) * slots
             + 4.0 * m["num_hidden_layers"] * m["num_attention_heads"]
             * m["head_dim"] * live_tokens)
    nbytes = (matmul_params(m) * itemsize
              + kv_bytes_per_token(m, itemsize) * (live_tokens + slots))
    return flops, nbytes


def decode_attention_cost(m: Dict, slots: float, live_tokens: float,
                          itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the decode attention alone (the ragged walk):
    the live KV read once, QK^T and PV over it."""
    flops = (4.0 * m["num_hidden_layers"] * m["num_attention_heads"]
             * m["head_dim"] * live_tokens)
    return flops, kv_bytes_per_token(m, itemsize) * live_tokens


def flash_cost(m: Dict, tokens_by_row, itemsize: int = 2,
               backward: bool = False) -> Tuple[float, float]:
    """(FLOPs, bytes) of causal flash attention over rows of the given real
    lengths: forward, or forward + backward (the backward's four matmuls
    are twice the forward's two; the scores it recomputes do not count)
    when ``backward``. Bytes: Q, K, V read and O
    written once (twice more with the backward pass)."""
    flops = sum(attn_flops_causal(m, int(t)) for t in tokens_by_row)
    toks = float(sum(tokens_by_row))
    per_tok = ((2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
               * m["head_dim"] * itemsize * m["num_hidden_layers"])
    nbytes = per_tok * toks
    if backward:
        return 3.0 * flops, 3.0 * nbytes
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float, peak
                   ) -> Tuple[float, str]:
    """Least time the chip could take over the time it took, in percent,
    and which peak bounds it."""
    t_flops, t_bytes = flops / peak.flops, nbytes / peak.hbm_bw
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
