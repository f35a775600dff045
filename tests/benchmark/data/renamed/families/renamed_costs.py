"""Costs of the ``renamed`` test family: the counts of ``benchmark/costs.py``
for Llama-shaped layers, read from the renamed keys."""


def _matmul_params(m):
    h, d = m["d_model"], m["d_head"]
    per_layer = (2 * h * m["n_heads"] * d + 2 * h * m["n_kv_heads"] * d
                 + 3 * h * m["d_ff"])
    return m["num_hidden_layers"] * per_layer + h * m["vocab_size"]


def _kv_bytes(m, itemsize):
    return (2 * m["num_hidden_layers"] * m["n_kv_heads"] * m["d_head"]
            * itemsize)


def _attn_flops(m, q_tokens):
    return (m["num_hidden_layers"] * 4.0 * m["n_heads"] * m["d_head"]
            * q_tokens * (q_tokens + 1) / 2)


def decode_attention_cost(m, slots, live_tokens, itemsize=2):
    flops = (4.0 * m["num_hidden_layers"] * m["n_heads"] * m["d_head"]
             * live_tokens)
    return flops, _kv_bytes(m, itemsize) * live_tokens


def decode_step_cost(m, slots, live_tokens, itemsize=2):
    flops, nbytes = decode_attention_cost(m, slots, live_tokens, itemsize)
    return (flops + 2.0 * _matmul_params(m) * slots,
            nbytes + _matmul_params(m) * itemsize
            + _kv_bytes(m, itemsize) * slots)


def prefill_flops(m, prompt_tokens):
    body = 2.0 * (_matmul_params(m) - m["d_model"] * m["vocab_size"])
    return (body * prompt_tokens + _attn_flops(m, prompt_tokens)
            + 2.0 * m["d_model"] * m["vocab_size"])


def flash_cost(m, tokens_by_row, itemsize=2, backward=False):
    flops = sum(_attn_flops(m, int(t)) for t in tokens_by_row)
    nbytes = ((2 * m["n_heads"] + 2 * m["n_kv_heads"]) * m["d_head"]
              * itemsize * m["num_hidden_layers"] * float(sum(tokens_by_row)))
    return (3.0 * flops, 3.0 * nbytes) if backward else (flops, nbytes)


def train_flops_per_token(m, seq):
    return 6.0 * _matmul_params(m) + 3.0 * _attn_flops(m, seq) / seq
