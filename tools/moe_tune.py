#!/usr/bin/env python
"""moe tune: warm the grouped-matmul tiling cache; bisect MoE regressions.

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

``--bisect`` is the evidence-not-vibes regression harness (the r05
postmortem tool, docs/moe.md): it times the FULL train step with each
hot-path lever toggled independently — dispatch form (measured auto /
fused / gmm / dense), tiling autotune on/off, fused vs unfused routing,
remat-ladder rung — plus the per-phase breakdown of the base config
(bench.moe_phase_breakdown), and prints a delta table against the base::

    python tools/moe_tune.py --bisect --preset bench          # on the chip
    JAX_PLATFORMS=cpu python tools/moe_tune.py --bisect --preset tiny
    python tools/moe_tune.py --bisect --out /tmp/bisect.json  # JSON too

The expert-parallel overlap lever (FLAGS_moe_overlap_min_tokens) only
exists under an ep>1 mesh and is noted, not timed, on one chip.

The tier-1 lane runs both CPU smoke invocations
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
        # bench.py bench_moe — the round-metric config
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


def _bisect_levers():
    """(name, config overrides, flag overrides) — each toggles ONE lever
    of the hot path off the base config."""
    return [
        ("dispatch=fused", {"dispatch": "fused"}, {}),
        ("dispatch=gmm", {"dispatch": "gmm"}, {}),
        ("dispatch=dense", {"dispatch": "dense"}, {}),
        ("autotune-off (heuristic tilings)", {"dispatch": "gmm"},
         {"moe_gmm_autotune": False}),
        ("unfused-routing", {"fused_router": False}, {}),
        ("remat=outs", {"remat_policy": "outs"}, {}),
        ("remat=attn", {"remat_policy": "attn"}, {}),
    ]


def run_bisect(cfg, batch, seq, out_path=None, levers="all"):
    """Time the full train step per lever; print the delta table."""
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp

    from bench import _release, _time_train, moe_phase_breakdown
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.models import moe
    from paddle_tpu.observability.perf import mfu as mfu_of

    opt = {"optimizer": "adafactor", "param_dtype": jnp.bfloat16}
    dev = jax.devices()[0]

    def tps_of(c, flag_over):
        saved = get_flags(list(flag_over)) if flag_over else {}
        try:
            if flag_over:
                set_flags(flag_over)
            return _time_train(moe, c, batch, seq, opt, n_steps=3)
        finally:
            if flag_over:
                set_flags(saved)
            _release()

    wanted = None if levers in (None, "all") else {
        s.strip() for s in levers.split(",")}
    rows = []
    base_tps = tps_of(cfg, {})
    rows.append(("base (dispatch=auto)", base_tps, 0.0))
    for name, cfg_over, flag_over in _bisect_levers():
        if wanted is not None and not any(w in name for w in wanted):
            continue
        if cfg.remat is False and name.startswith("remat="):
            continue                 # lever does not exist on this config
        c = dataclasses.replace(cfg, **cfg_over)
        try:
            tps = tps_of(c, flag_over)
            rows.append((name, tps, (tps - base_tps) / base_tps * 100.0))
        except Exception as e:
            print(f"{name}: FAILED {str(e)[:160]}", flush=True)

    print(f"\nbisect @ batch={batch} seq={seq} "
          f"E={cfg.num_experts} top_k={cfg.top_k} "
          f"backend={jax.default_backend()}")
    w = max(len(r[0]) for r in rows)
    for name, tps, delta in rows:
        # FLOPs of one second of tokens over one second: None off-chip
        mfu = mfu_of(moe.flops_per_token(cfg, seq) * tps, 1.0, dev)
        print(f"  {name.ljust(w)}  {tps:>10,.0f} tok/s  mfu="
              + ("undefined on " + dev.platform if mfu is None
                 else f"{mfu:.3f}") + f"  {delta:+6.2f}% vs base")
    print("  (moe_overlap_min_tokens lever: ep>1 meshes only — "
          "not timed on one chip)")

    phases = moe_phase_breakdown(cfg, batch, seq)
    print(f"\nper-phase breakdown (one MoE layer, fwd+bwd, "
          f"layer_ms={phases['layer_ms']}):")
    for p, ms in phases["phase_ms"].items():
        print(f"  {p:<11} {ms:>9.3f} ms")

    if out_path:
        doc = {"batch": batch, "seq": seq,
               "levers": [{"name": n, "tokens_per_sec": round(t, 1),
                           "delta_pct": round(d, 2)}
                          for n, t, d in rows]}
        doc.update(phases)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"\nwrote {out_path}")
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", choices=("bench", "16b", "tiny"),
                    default="bench")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--bisect", action="store_true",
                    help="time the train step per hot-path lever and "
                         "print the delta table + phase breakdown")
    ap.add_argument("--out", default=None,
                    help="with --bisect: also write the table as JSON")
    ap.add_argument("--levers", default="all",
                    help="with --bisect: comma-separated substring "
                         "filter of lever names (the CI smoke runs one)")
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
    if args.bisect:
        return run_bisect(cfg, batch, seq, out_path=args.out,
                          levers=args.levers)
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
