"""Llama text generation with the fused decode loop.

`generate_fused` runs prefill + the whole decode loop as ONE compiled
program (on-device sampling, EOS early exit) — no host dispatch per
token, as the python loop pays. Weights here
are random (no checkpoint download in this environment); point
`--load` at a `paddle.save`d params file to decode a trained model.

Run:  python examples/llama_generate.py [--max-new 64] [--temperature 0.8]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402

_common.setup()

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import llama


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny", choices=["tiny", "740m"])
    ap.add_argument("--load", default=None,
                    help="optional paddle.save'd params pytree")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--eos", type=int, default=None,
                    help="eos token id: rows stop early once all emit it")
    args = ap.parse_args()

    if args.size == "tiny":
        cfg = llama.tiny_llama(vocab=512, hidden=128, layers=4, heads=4,
                               kv_heads=2, seq=256, ffn=256)
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32768, hidden_size=2048, intermediate_size=6144,
            num_layers=12, num_heads=16, num_kv_heads=8, head_dim=128,
            max_seq_len=2048, remat=False, dtype=jnp.bfloat16)

    if args.load:
        import paddle_tpu as paddle
        params = paddle.load(args.load)
        params = jax.tree_util.tree_map(
            lambda v: v._value if hasattr(v, "_value") else jnp.asarray(v),
            params)
    else:
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        if cfg.dtype == jnp.bfloat16:
            # optional: store weights bf16 (halves HBM; forward casts
            # per-use either way)
            params = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16), params)

    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    out = llama.generate_fused(
        params, prompt, cfg, max_new_tokens=args.max_new,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        eos_token_id=args.eos, key=jax.random.PRNGKey(7))
    np.asarray(out)  # sync (compile included)

    t0 = time.perf_counter()
    out = llama.generate_fused(
        params, prompt, cfg, max_new_tokens=args.max_new,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        eos_token_id=args.eos, key=jax.random.PRNGKey(8))
    np.asarray(out)
    dt = time.perf_counter() - t0
    n_new = out.shape[1] - args.prompt_len
    print(f"generated {out.shape[0]}x{n_new} tokens in {dt:.2f}s "
          f"({out.shape[0] * n_new / dt:,.0f} tok/s)")
    print("first row token ids:", np.asarray(out)[0, args.prompt_len:][:16])


if __name__ == "__main__":
    main()
