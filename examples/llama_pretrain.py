"""BASELINE config 4 — Llama pretraining (the flagship path).

Exercises the full hybrid-parallel recipe: a (pp, dp, sp, tp) device mesh,
fsdp/tp/sp sharded parameters, flash attention, remat, optional 1F1B
pipeline schedule, chunked cross-entropy, and the fused
fwd+bwd+clip+optimizer train step. One chip runs it unsharded; on a
pod slice raise --tp/--pp/--dp to the mesh you have.

Run (one chip, ~740M):   python examples/llama_pretrain.py --size 740m
Run (8-virtual-CPU dev): JAX_PLATFORMS=cpu python examples/llama_pretrain.py \
                           --size tiny --tp 2 --pp 2 --dp 2 --microbatches 4
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402

_common.setup()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models import llama

SIZES = {
    "tiny": lambda: llama.tiny_llama(vocab=512, hidden=128, layers=4,
                                     heads=4, kv_heads=2, seq=128, ffn=256),
    "740m": lambda: llama.LlamaConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=6144,
        num_layers=12, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, remat=True),
    "2.6b": lambda: llama.LlamaConfig(
        vocab_size=32768, hidden_size=3072, intermediate_size=8192,
        num_layers=24, num_heads=24, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, remat=True, loss_chunks=8),
    "8b": llama.llama3_8b,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="740m", choices=sorted(SIZES))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq", type=int, default=0, help="0 = config max")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=0,
                    help=">0 enables the 1F1B pipeline schedule over pp")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--bf16-params", action="store_true",
                    help="bf16 parameter memory mode (fits 2.6b on 16GB)")
    ap.add_argument("--layerwise", action="store_true",
                    help="layer-wise optimizer-in-backward: no full grad "
                         "tree ever exists (fits 4b on one 16GB chip; "
                         "single-device, adafactor)")
    args = ap.parse_args()

    cfg = SIZES[args.size]()
    if args.layerwise:
        from paddle_tpu.optimizer.offload import (
            init_layerwise_train_state, make_layerwise_train_step)
        seq = args.seq or cfg.max_seq_len
        state = init_layerwise_train_state(cfg, jax.random.PRNGKey(0))
        step = make_layerwise_train_step(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch_size, seq + 1), 0,
            cfg.vocab_size)
        state, loss = step(state, tokens)   # compile + first step
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, loss = step(state, tokens)
        print(f"loss {float(loss):.4f}")
        dt = time.perf_counter() - t0
        tps = args.batch_size * seq * args.steps / dt
        print(f"{tps:,.0f} tokens/s (layer-wise optimizer-in-backward)")
        return

    if args.microbatches > 0:
        cfg = dataclasses.replace(cfg, pipeline_microbatches=args.microbatches,
                                  pipeline_schedule="1f1b")
    seq = args.seq or cfg.max_seq_len

    n = args.pp * args.dp * args.sp * args.tp
    devs = jax.devices()
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    mesh = Mesh(np.asarray(devs[:n]).reshape(args.pp, args.dp, args.sp,
                                             args.tp),
                ("pp", "dp", "sp", "tp"))

    # init directly onto the mesh — no unsharded copy on one device, so
    # pod-scale sizes (8b) never exceed a single chip's HBM at startup
    state = llama.init_sharded_train_state(
        cfg, jax.random.PRNGKey(0), llama.make_shardings(cfg, mesh, fsdp=True),
        optimizer=args.optimizer,
        param_dtype=jnp.bfloat16 if args.bf16_params else jnp.float32)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1),
                           (args.batch_size, seq + 1), 0, cfg.vocab_size),
        NamedSharding(mesh, P("dp", None)))

    with llama.activation_mesh(mesh):
        step = jax.jit(lambda s, t: llama.train_step(
            s, t, cfg, optimizer=args.optimizer), donate_argnums=0)
        state, loss = step(state, tokens)  # compile + first step
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, loss = step(state, tokens)
        print(f"loss {float(loss):.4f}")
    dt = time.perf_counter() - t0
    tps = args.batch_size * seq * args.steps / dt
    print(f"{tps:,.0f} tokens/s over {n} device(s) "
          f"({tps / n:,.0f} tokens/s/device)")


if __name__ == "__main__":
    main()
