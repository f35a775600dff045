#!/usr/bin/env python
"""moe tune: warm the grouped-matmul tiling cache ahead of a job's step 0.

The dropless-MoE hot path autotunes its Mosaic grouped-matmul tilings on
the *first encounter* of each shape (kernels/gmm_autotune.py) — a few
seconds of candidate timing folded into the first compile. This CLI runs
that warm-up ahead of time for a given MoEConfig, persists the winners
(``<cache>/gmm_tilings.json`` via paddle_tpu.jit.cache), and prints the
chosen-tilings table, so a production job's step 0 pays nothing::

    python tools/moe_tune.py --preset bench --batch 8 --seq 2048
    JAX_PLATFORMS=cpu python tools/moe_tune.py --preset tiny   # CPU smoke:
        # no Mosaic kernel to time, entries fall back to the heuristic
        # (printed as source=heuristic, kept in-process only)

    python tools/moe_tune.py --clear          # drop the persisted winners

The tier-1 lane runs the CPU smoke invocation
(tests/test_moe_dispatch.py) so the CLI can never rot.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _presets():
    import jax.numpy as jnp

    from paddle_tpu.models import moe

    return {
        # a 2.15B-total / 616M-active proxy: 16 experts, top-2, 2 shared
        "bench": (moe.MoEConfig(
            vocab_size=32768, hidden_size=2048, intermediate_size=6144,
            moe_intermediate_size=1408, num_layers=12, num_heads=16,
            num_kv_heads=8, head_dim=128, num_experts=16, top_k=2,
            n_shared_experts=2, first_dense_layers=1, max_seq_len=2048,
            remat=True, dtype=jnp.bfloat16), 8, 2048),
        "16b": (moe.deepseek_moe_16b(), 4, 2048),
        "tiny": (moe.tiny_moe(), 2, 128),
    }


def gmm_shapes(cfg, batch: int, seq: int, ep: int = 1, dp: int = 1):
    """Every ``grouped_matmul`` call-site shape of the dropless pipeline
    for one step: per MoE layer, A = batch*seq*top_k expert-sorted rows
    hit the fused gate|up GEMM ([m,h] @ [E,h,2f]) and the down GEMM
    ([m,f] @ [E,f,h]). Single program: m = A, all E experts,
    full_rows=True. Expert parallelism (psum AND a2a forms): each rank's
    GEMM runs over its E//ep-expert shard with m = A/dp rows — or
    m = A/(2*dp) per double-buffered half, the default when the
    shared-expert overlap is on — with zero-padded tails
    (full_rows=False). Returns deduplicated (m, k, n, E_groups,
    full_rows) matching the autotune cache keys exactly."""
    T = batch * seq
    A = T * cfg.top_k
    h, f, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    variants = [(A, E, True)]
    if ep > 1:
        variants += [(A // dp, E // ep, False),
                     (A // (2 * dp), E // ep, False)]
    shapes = []
    for m, groups, full in variants:
        shapes += [(m, h, 2 * f, groups, full), (m, f, h, groups, full)]
    return sorted(set(shapes))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", choices=("bench", "16b", "tiny"),
                    default="bench")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--ep", type=int, default=1,
                    help="also warm the per-rank shapes of an ep-way mesh")
    ap.add_argument("--dp", type=int, default=1,
                    help="token-shard count (dp*sp) of that mesh — the "
                         "per-rank row count is A/dp")
    ap.add_argument("--dtype", choices=("bfloat16", "float32"),
                    default="bfloat16")
    ap.add_argument("--cache-dir", default=None,
                    help="override the persist location "
                         "(FLAGS_jit_cache_dir)")
    ap.add_argument("--clear", action="store_true",
                    help="drop the persisted tiling winners and exit")
    args = ap.parse_args()

    if args.cache_dir:
        from paddle_tpu.framework.flags import set_flags

        set_flags({"jit_cache_dir": args.cache_dir})

    from paddle_tpu.jit import cache as jcache
    from paddle_tpu.kernels import gmm_autotune

    if args.clear:
        gmm_autotune.clear(persisted=True)
        print(f"cleared {jcache.cache_path(gmm_autotune.PERSIST_NAME)}")
        return 0

    import jax
    import jax.numpy as jnp

    cfg, batch, seq = _presets()[args.preset]
    batch = args.batch or batch
    seq = args.seq or seq
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    backend = jax.default_backend()
    print(f"backend={backend}  preset={args.preset}  batch={batch} "
          f"seq={seq} experts={cfg.num_experts} top_k={cfg.top_k}\n"
          f"persist: {jcache.cache_path(gmm_autotune.PERSIST_NAME)} "
          f"(measured winners only)\n")

    rows = []
    for m, k, n, E, full in gmm_shapes(cfg, batch, seq, ep=args.ep,
                                       dp=args.dp):
        tri = gmm_autotune.get_tilings(m, k, n, E, dtype, full)
        if tri is None:
            rows.append(((m, k, n, E, full), "ragged_dot", "-", "-", "-"))
            continue
        # re-read the entry so the table shows measured vs heuristic
        src = "heuristic"
        for key, source, _t in gmm_autotune.entries():
            if f"m={m}|k={k}|n={n}|E={E}|" in key and \
                    f"full_rows={full}|" in key:
                src = source
        rows.append(((m, k, n, E, full), src) + tuple(map(str, tri)))

    hdr = ("(m, k, n, E, full_rows)", "source", "fwd", "dgrad", "wgrad")
    widths = [max(len(str(r[i])) for r in rows + [hdr]) for i in range(5)]
    for r in [hdr] + rows:
        print("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    n_meas = sum(1 for r in rows if r[1] == "measured")
    print(f"\n{len(rows)} shapes; {n_meas} measured"
          + ("" if backend == "tpu" else
             " (no TPU backend: heuristic fallback, nothing persisted)"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
