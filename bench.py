"""Round benchmark: train-step throughput on the local chip, multi-metric.

Prints ONE JSON line. Top-level fields are the headline metric (dense Llama
pretrain tokens/s/chip — comparable across rounds); "metrics" carries the
full list: dense 2k, long-context 8k, and MoE (dropless ragged_dot
dispatch). Each entry: {"metric", "value", "unit", "vs_baseline"} with
vs_baseline = achieved MFU / 0.40 (the BASELINE.json north-star: >=40% MFU
— no reference-published numbers exist, see BASELINE.md).

Process model (r4 post-mortem): each section runs in its OWN subprocess
(``bench.py --section NAME``). r4 lost the entire round's metrics to one
TPU RESOURCE_EXHAUSTED late in the run — HBM fragmentation accumulated
across sections until an allocation failed outside a try block and killed
the process before the JSON line printed. Per-section processes give every
section a fresh TPU client and a fully empty HBM, bound each section with a
wall-clock timeout, and guarantee the parent ALWAYS prints the JSON line no
matter how a child dies. The parent never initializes a backend (the chip
is single-tenant; only the one live child may hold it).
"""
import gc
import json
import os
import subprocess
import sys
import time

import jax            # import alone does not initialize a backend;
import jax.numpy as jnp  # the parent never calls jax.devices()


# The per-device-kind spec sheet lives in observability.perf.DEVICE_SPECS
# (one table for the always-on MFU gauges AND the benchmark); imports stay
# lazy so loading bench.py in the parent touches no paddle_tpu package.
def _peak_flops(dev) -> float:
    from paddle_tpu.observability.perf import peak_flops
    return peak_flops(dev)


def _hbm_bytes(dev) -> float:
    from paddle_tpu.observability.perf import hbm_bytes
    return hbm_bytes(dev)


def _hbm_bw(dev) -> float:
    from paddle_tpu.observability.perf import hbm_bandwidth
    return hbm_bandwidth(dev)


def _efficiency(row, mfu=None):
    """Attach the shared efficiency columns to one result row: explicit
    ``mfu`` (vs_baseline already encodes mfu/0.40 for train rows, but the
    raw number should not need arithmetic to read) and the measured
    ``peak_hbm_gb`` watermark from PJRT memory_stats (absent on CPU)."""
    from paddle_tpu.observability import perf
    if mfu is not None:
        row["mfu"] = round(mfu, 4)
    s = perf.hbm_stats()
    if s.get("peak_bytes_in_use"):
        row["peak_hbm_gb"] = round(s["peak_bytes_in_use"] / 1e9, 2)
    return row


def _dense_configs():
    from paddle_tpu.models import llama
    # largest first; each entry carries its optimizer memory mode and a
    # peak-bytes/param estimate for the HBM pre-check.
    # 4B on a 16GB v5e: bf16 params + adafactor + LAYER-WISE
    # optimizer-in-backward (optimizer/offload.make_layerwise_train_step):
    # one layer's grads exist at a time, so params(8G) and the grad
    # tree(8G) never coexist in HBM — the plain fused step OOMs by 1.5G at
    # this size (measured r3: 17.25G used of 15.75G).
    adafactor_bf16 = {"optimizer": "adafactor",
                      "param_dtype": jnp.bfloat16, "bpp": 4}
    layerwise_bf16 = {"optimizer": "adafactor",
                      "param_dtype": jnp.bfloat16, "bpp": 3,
                      "layerwise": True}
    adamw_f32 = {"optimizer": "adamw", "param_dtype": jnp.float32, "bpp": 16}
    # 5.2B: same mechanism, batch 2 (saved layer-inputs scale with batch);
    # measured r3: 3,648 tok/s = 63% MFU on the 16GB v5e
    yield "llama-5.2b-layerwise", llama.LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=11008,
        num_layers=28, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, remat=True), 2, 2048, dict(layerwise_bf16,
                                                     bpp=2.4)
    yield "llama-4b-layerwise", llama.LlamaConfig(
        vocab_size=32768, hidden_size=3584, intermediate_size=9728,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        max_seq_len=2048, remat=True), 4, 2048, layerwise_bf16
    yield "llama-2.6b", llama.LlamaConfig(
        vocab_size=32768, hidden_size=3072, intermediate_size=8192,
        num_layers=24, num_heads=24, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, remat=True), 8, 2048, adafactor_bf16
    yield "llama-740m", llama.LlamaConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=6144,
        num_layers=12, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, remat=True,
        remat_policy="attn"), 8, 2048, adamw_f32  # +10% vs full remat
    yield "llama-510m", llama.LlamaConfig(
        vocab_size=32768, hidden_size=1536, intermediate_size=6144,
        num_layers=12, num_heads=12, num_kv_heads=4, head_dim=128,
        max_seq_len=2048, remat=True), 8, 2048, adamw_f32
    yield "llama-350m", llama.LlamaConfig(
        vocab_size=32768, hidden_size=1024, intermediate_size=4096,
        num_layers=12, num_heads=8, num_kv_heads=8, head_dim=128,
        max_seq_len=1024, remat=True), 8, 1024, adamw_f32
    yield "llama-tiny", llama.tiny_llama(), 4, 128, adamw_f32


def _sync(x):
    """Device-to-host readback of the loss: waits for the step like
    block_until_ready does (chip_smoke.py times a step both ways), and
    checks the value is finite while it is at it."""
    import numpy as np
    v = float(np.asarray(x))
    if not jnp.isfinite(v):
        raise FloatingPointError(f"non-finite loss {v}")
    return v


def _release():
    gc.collect()
    jax.clear_caches()


def _time_train(module, cfg, batch, seq, opt, n_steps=5, **step_kw):
    """Init → compile → warm → time n_steps of module.train_step. Returns
    tokens/s. Frees the state before returning."""
    if opt.get("streaming"):
        from paddle_tpu.optimizer.offload import (
            init_streaming_train_state, make_streaming_train_step)
        state = init_streaming_train_state(
            cfg, jax.random.PRNGKey(0), param_dtype=opt["param_dtype"])
        step = make_streaming_train_step(cfg, optimizer=opt["optimizer"],
                                         **step_kw)
    elif opt.get("layerwise"):
        from paddle_tpu.optimizer.offload import (
            init_layerwise_train_state, make_layerwise_train_step)
        state = init_layerwise_train_state(
            cfg, jax.random.PRNGKey(0), param_dtype=opt["param_dtype"])
        step = make_layerwise_train_step(cfg, optimizer=opt["optimizer"],
                                         **step_kw)
    else:
        state = module.init_train_state(
            cfg, jax.random.PRNGKey(0), optimizer=opt["optimizer"],
            param_dtype=opt["param_dtype"])
        step = jax.jit(
            lambda s, t: module.train_step(s, t, cfg,
                                           optimizer=opt["optimizer"],
                                           **step_kw),
            donate_argnums=0)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    try:
        for _ in range(2):  # compile + warmup
            state, loss = step(state, tokens)
        _sync(loss)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, loss = step(state, tokens)
        _sync(loss)
        dt = time.perf_counter() - t0
        return batch * seq * n_steps / dt
    finally:
        state = tokens = step = loss = None
        _release()


def bench_dense(dev, results):
    """Dense-llama ladder: largest config that fits wins; it is the round
    headline."""
    from paddle_tpu.models import llama
    # seeded so an all-skipped ladder reports WHY instead of error "None"
    last_err = "all configs skipped by HBM precheck"
    for name, cfg, batch, seq, opt in _dense_configs():
        if dev.platform == "cpu" and name != "llama-tiny":
            continue  # CPU lane is a smoke test, not a measurement
        n_params = llama.num_params(llama._abstract_params(cfg))
        if n_params * opt["bpp"] > 0.8 * _hbm_bytes(dev):
            continue
        try:
            tps = _time_train(llama, cfg, batch, seq, opt)
            mfu = llama.flops_per_token(cfg, seq) * tps / _peak_flops(dev)
            results.append(_efficiency({
                "metric": f"{name}_pretrain_tokens_per_sec_per_chip",
                "value": round(tps, 1),
                "unit": "tokens/s",
                "vs_baseline": round(mfu / 0.40, 4),
            }, mfu=mfu))
            return
        except Exception as e:
            last_err = e
            _release()
    results.append({"metric": "dense_bench_failed", "value": 0.0,
                    "unit": "tokens/s", "vs_baseline": 0.0,
                    "error": str(last_err)[:200]})


def bench_8b(dev, results):
    """The north-star scale rung: Llama-3-8B (16 GB of bf16 params) on one
    chip via the host-streamed layerwise step (optimizer/offload.py
    make_streaming_train_step) — params live in pinned_host, at most two
    layers occupy HBM, updated weights stream back per layer. Needs a real
    host memory space; skipped (not failed) where pinned_host is absent."""
    from paddle_tpu.models import llama
    from paddle_tpu.optimizer.offload import supports_compiled_host_memory
    if dev.platform == "cpu" or not supports_compiled_host_memory():
        return
    cfg = llama.LlamaConfig(max_seq_len=2048, remat=True, loss_chunks=16)
    seq = 2048
    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    layer_bytes = 2 * (h * (cfg.num_heads + 2 * cfg.num_kv_heads)
                       * cfg.head_dim + h * cfg.num_heads * cfg.head_dim
                       + 3 * h * cfg.intermediate_size)
    opt = {"optimizer": "adafactor", "param_dtype": jnp.bfloat16,
           "streaming": True}
    last_err = None
    # batch ladder: 12 measured 0.577 MFU on the 16 GB v5e (r4); 8 is the
    # fallback margin. Saved layer-inputs scale with batch (L·B·S·h bf16).
    for batch in (12, 8):
        # HBM pre-check: embed+head (bf16) + f32 embed-grad + saved layer
        # inputs + ~3 streamed layers in flight
        need = (2 * V * h * 2 + V * h * 4 + L * batch * seq * h * 2
                + 3 * layer_bytes + 2e9)
        if need > 0.95 * _hbm_bytes(dev):
            continue
        try:
            tps = _time_train(llama, cfg, batch, seq, opt, n_steps=5)
            mfu = llama.flops_per_token(cfg, seq) * tps / _peak_flops(dev)
            results.append(_efficiency({
                "metric": "llama-8b_pretrain_tokens_per_sec_per_chip",
                "value": round(tps, 1),
                "unit": "tokens/s",
                "vs_baseline": round(mfu / 0.40, 4),
                "batch": batch,
            }, mfu=mfu))
            return
        except Exception as e:
            last_err = e
            _release()
    if last_err is not None:
        results.append({"metric": "llama8b_bench_failed", "value": 0.0,
                        "unit": "tokens/s", "vs_baseline": 0.0,
                        "error": str(last_err)[:200]})
    _release()


def bench_long_context(dev, results):
    """Same 2.6B model at 8k sequence — the long-context lane (flash
    attention + remat keep the 8k activations inside HBM)."""
    from paddle_tpu.models import llama
    if dev.platform == "cpu":
        return  # chip-only section
    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=3072, intermediate_size=8192,
        num_layers=24, num_heads=24, num_kv_heads=8, head_dim=128,
        max_seq_len=8192, remat=True)
    opt = {"optimizer": "adafactor", "param_dtype": jnp.bfloat16}
    try:
        tps = _time_train(llama, cfg, 2, 8192, opt)
        mfu = llama.flops_per_token(cfg, 8192) * tps / _peak_flops(dev)
        results.append(_efficiency({
            "metric": "llama-2.6b@8k_pretrain_tokens_per_sec_per_chip",
            "value": round(tps, 1),
            "unit": "tokens/s",
            "vs_baseline": round(mfu / 0.40, 4),
        }, mfu=mfu))
    except Exception as e:
        results.append({"metric": "long_context_bench_failed", "value": 0.0,
                        "unit": "tokens/s", "vs_baseline": 0.0,
                        "error": str(e)[:200]})
        _release()


def moe_phase_breakdown(cfg, batch, seq, n_steps=3):
    """Per-phase wall-clock of ONE MoE layer's routed FFN (fwd+bwd) at
    the bench shape — the bisect harness behind the MoE row's
    ``phase_ms`` field (and ``tools/moe_tune.py --bisect``). Backend
    agnostic: the CPU mini-config smoke test pins the decomposition.

    Phases (JSON keys, milliseconds):
      routing   — fused router prologue (fp32 matmul + top-k + aux +
                  sort metadata);
      combine   — dispatch data movement: the expert-sort gather of the
                  token rows plus the gate-weighted combine;
      gmm_fwd   — forward grouped GEMMs (total fwd minus the above);
      gmm_bwd   — dgrad+wgrad (total fwd+bwd minus fwd);
      collective — 0.0 on a single program (the EP forms' psum/a2a time
                  lands here when a mesh is active — not yet measured).

    By construction the phases sum to the measured fwd+bwd layer time
    (``layer_ms``) up to clamping of negative subtractions, so a future
    BENCH_r*.json localizes a regression without a bisect session."""
    from paddle_tpu.kernels import moe_dispatch as md
    from paddle_tpu.kernels import moe_fused as mf
    from paddle_tpu.models import moe as moe_mod

    T = batch * seq
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    E, k = cfg.num_experts, cfg.top_k
    dt = cfg.dtype
    x, rw, eg, eu, ed = md.make_moe_operands(T, h, E, f, dt)

    def timed(fn, *args):
        return md.time_best(fn, *args, n=n_steps)

    t_rout = timed(lambda x: md.fused_routing(x, rw, k), x)

    def fwd(x, eg, eu, ed):
        return moe_mod.moe_ffn(x, rw, eg, eu, ed, cfg)[0]

    t_fwd = timed(fwd, x, eg, eu, ed)

    def total(x, eg, eu, ed):
        def loss(*a):
            return jnp.sum(jnp.square(fwd(*a).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, eg, eu, ed)

    t_tot = timed(total, x, eg, eu, ed)

    # dispatch data movement, measured on the fused form's ops
    r = jax.jit(lambda x: md.fused_routing(x, rw, k))(x)
    inv2d = mf._inverse_permutation(r.order).reshape(T, k)
    t_gather = timed(lambda x: jnp.take(x, r.tok, axis=0), x)
    ys = jnp.zeros((T * k, h), dt)
    t_combine = timed(
        lambda ys: mf._combine_rows(ys, inv2d, r.tok), ys)

    phases = {
        "routing": t_rout,
        "gmm_fwd": max(t_fwd - t_rout - t_gather - t_combine, 0.0),
        "gmm_bwd": max(t_tot - t_fwd, 0.0),
        "combine": t_gather + t_combine,
        "collective": 0.0,
    }
    return {"phase_ms": {p: round(v * 1e3, 3) for p, v in phases.items()},
            "layer_ms": round(t_tot * 1e3, 3)}


def _moe_dispatch_evidence(row, cfg, batch, seq):
    """Attach the measured dispatch-form pick (the r05 bisect lever) to
    the bench row so every future BENCH_r*.json records which form won
    and by how much. Matched to THIS bench's routing-shape key — a
    shared cache dir may hold entries for other shapes (serving runs,
    moe_tune warm-ups) and their winners are not this row's evidence."""
    from paddle_tpu.kernels import moe_dispatch as md
    shape_sig = (f"|T={batch * seq}|k={cfg.top_k}|E={cfg.num_experts}"
                 f"|h={cfg.hidden_size}|f={cfg.moe_intermediate_size}|")
    with md._PLAN_LOCK:
        forms = {k: dict(e) for k, e in md._FORM_CACHE.items()}
    for key, ent in sorted(forms.items()):
        if shape_sig in key:
            row["dispatch_form"] = ent.get("winner")
            row["dispatch_form_ms"] = ent.get("ms")
            break
    return row


def bench_moe(dev, results):
    """Dropless MoE (fused routing → measured dispatch form: the fused
    scatter-free grouped-GEMM path, the gmm path, or the dense base —
    kernels/moe_dispatch.pick_dispatch_form) — BASELINE config 5's
    capability measured on chip. MFU uses active params per token.

    Remat ladder (the llama-740m precedent): 'outs' saves attention +
    routed outputs so backward skips the flash AND grouped-GEMM
    recompute (measured +9% / +~0.6 GB residency at the bench config —
    models/moe.py remat_policy notes); 'full' is the fallback if the
    extra residency doesn't fit."""
    from paddle_tpu.models import moe
    if dev.platform == "cpu":
        return  # chip-only section
    opt = {"optimizer": "adafactor", "param_dtype": jnp.bfloat16}
    last_err = "all remat policies failed"
    for policy in ("outs", "full"):
        cfg = moe.MoEConfig(
            vocab_size=32768, hidden_size=2048, intermediate_size=6144,
            moe_intermediate_size=1408, num_layers=12, num_heads=16,
            num_kv_heads=8, head_dim=128, num_experts=16, top_k=2,
            n_shared_experts=2, first_dense_layers=1, max_seq_len=2048,
            remat=True, remat_policy=policy)
        try:
            tps = _time_train(moe, cfg, 8, 2048, opt, n_steps=10)
            mfu = moe.flops_per_token(cfg, 2048) * tps / _peak_flops(dev)
            n_total = moe.num_params(jax.eval_shape(
                lambda k: moe.init_params(cfg, k), jax.random.PRNGKey(0)))
            row = _efficiency({
                "metric": "moe-dropless_pretrain_tokens_per_sec_per_chip",
                "value": round(tps, 1),
                "unit": "tokens/s",
                "vs_baseline": round(mfu / 0.40, 4),
                "total_params": n_total,
                "active_params_per_token": moe.active_params_per_token(cfg),
                "remat_policy": policy,
            }, mfu=mfu)
            _moe_dispatch_evidence(row, cfg, 8, 2048)
            try:
                row.update(moe_phase_breakdown(cfg, 8, 2048))
            except Exception as e:   # the headline survives a harness bug
                row["phase_ms_error"] = str(e)[:120]
            results.append(row)
            return
        except Exception as e:
            last_err = e
            _release()
    results.append({"metric": "moe_bench_failed", "value": 0.0,
                    "unit": "tokens/s", "vs_baseline": 0.0,
                    "error": str(last_err)[:200]})
    _release()


def _decode_cfg_2p6b():
    """The 2.6B decode/serving model — ONE definition so bench_decode and
    bench_serving stay the same model."""
    from paddle_tpu.models import llama
    return llama.LlamaConfig(
        vocab_size=32768, hidden_size=3072, intermediate_size=8192,
        num_layers=24, num_heads=24, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, remat=False, dtype=jnp.bfloat16)


def _init_bf16_params(cfg):
    from paddle_tpu.models import llama
    return jax.jit(lambda k: jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        llama.init_params(cfg, k)))(jax.random.PRNGKey(0))


def bench_decode(dev, results):
    """Decode throughput on the 2.6B config, bf16 vs int8 weight-only
    (models/llama.quantize_params — inline-dequant fused into the matmul).
    Decode is weight-bandwidth-bound: vs_baseline = measured / (40% of the
    HBM roofline B*BW/weight_bytes), mirroring the train-side 40%-MFU
    baseline convention."""
    from paddle_tpu.models import llama
    if dev.platform == "cpu":
        return  # chip-only section
    import numpy as np
    cfg = _decode_cfg_2p6b()
    B, prompt_len, new = 8, 128, 128

    def run(params, tag, wbytes):
        # generate_fused: ONE compiled program (module-level jit cache) —
        # the python-loop generate pays a host dispatch per token and
        # would measure host overhead, not the chip
        prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (B, prompt_len), 0, cfg.vocab_size)
        out = llama.generate_fused(params, prompt, cfg, max_new_tokens=new)
        _ = np.asarray(out)            # compile + warm, full sync
        t0 = time.perf_counter()
        out = llama.generate_fused(params, prompt, cfg, max_new_tokens=new)
        _ = np.asarray(out)
        dt = time.perf_counter() - t0
        tps = B * new / dt
        roofline = B * _hbm_bw(dev) / wbytes
        results.append({
            "metric": f"llama-2.6b_decode_{tag}_tokens_per_sec",
            "value": round(tps, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tps / (0.40 * roofline), 4),
        })
        return tps

    def tree_bytes(p):
        # roofline from ACTUAL weight bytes (int8 q + bf16 scales/norms),
        # matching bench_serving's denominator exactly
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(p))

    try:
        params = _init_bf16_params(cfg)
        t_bf16 = run(params, "bf16", tree_bytes(params))
        qp = jax.jit(llama.quantize_params)(params)
        params = None
        _release()
        t_int8 = run(qp, "int8", tree_bytes(qp))
        results[-1]["speedup_vs_bf16"] = round(t_int8 / t_bf16, 3)
    except Exception as e:
        results.append({"metric": "decode_bench_failed", "value": 0.0,
                        "unit": "tokens/s", "vs_baseline": 0.0,
                        "error": str(e)[:200]})
    finally:
        _release()


def bench_serving(dev, results):
    """Continuous-batching serving-engine throughput: mixed prompt lengths
    through the paged-KV LLMEngine (slot admission, multi-step decode) —
    the serving-layer number on top of bench_decode's fixed-batch loop.
    vs_baseline uses the same weight-bandwidth roofline at full slot
    occupancy as the decode metric."""
    from paddle_tpu.models import llama
    from paddle_tpu.serving import LLMEngine
    if dev.platform == "cpu":
        return  # chip-only section
    import numpy as np
    cfg = _decode_cfg_2p6b()
    SLOTS, NEW = 8, 128

    def attempt(tag, make_params, kv_dtype=None):
        params = make_params()
        # decode_steps=64: one compiled call per 64 tokens/slot amortizes
        # the host's per-call work (admission granularity coarsens to 64,
        # fine for throughput)
        eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                        max_model_len=1024,
                        prompt_buckets=[128, 512, 1024], decode_steps=64,
                        kv_dtype=kv_dtype)
        rng = np.random.default_rng(0)
        # warm: compile the touched prompt buckets + the decode program
        for ln in (100, 400):
            eng.add_request(rng.integers(1, 32768, size=ln).tolist(),
                            max_new_tokens=17, temperature=0.0)
        eng.run()
        reqs = [rng.integers(1, 32768, size=int(ln)).tolist()
                for ln in rng.integers(64, 512, size=2 * SLOTS)]
        t0 = time.perf_counter()
        rids = [eng.add_request(p, max_new_tokens=NEW, temperature=0.0)
                for p in reqs]
        out = eng.run()
        dt = time.perf_counter() - t0
        # engine.results is cumulative — count only the timed requests
        gen = sum(len(out[r]) for r in rids)
        tps = gen / dt
        # decode is weight-bandwidth-bound: roofline from the ACTUAL
        # weight bytes read per step (int8 quantization ~halves them)
        wbytes = sum(x.nbytes
                     for x in jax.tree_util.tree_leaves(params))
        roofline = SLOTS * _hbm_bw(dev) / wbytes
        # decode MFU from the standard 2 x params FLOPs/token estimate
        # (attention-light at these contexts); tiny next to the bandwidth
        # roofline by construction — that IS the decode story
        n_params = llama.num_params(llama._abstract_params(cfg))
        mfu = 2.0 * n_params * tps / _peak_flops(dev)
        results.append(_efficiency({
            "metric": f"llama-2.6b_serving_engine_{tag}_tokens_per_sec",
            "value": round(tps, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tps / (0.40 * roofline), 4),
            "requests": len(reqs),
        }, mfu=mfu))
        return tps

    def attempt_overload(make_params, base_tps, duration=20.0):
        """Sustained-overload row: offered load at 2x the engine's
        measured serving capacity against a bounded admission queue +
        host KV swap tier. Reports the tok/s the engine KEEPS under
        overload (vs_baseline = kept/capacity — graceful degradation,
        not a speedup), the shed rate, and p95 TTFT of the admitted
        requests — the survivability layer's headline numbers
        (docs/serving.md §Degraded modes)."""
        from paddle_tpu.serving import AdmissionConfig, ShedError
        params = make_params()
        new_tok = 64
        eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                        max_model_len=1024,
                        prompt_buckets=[128, 512, 1024], decode_steps=16,
                        kv_dtype="int8", kv_swap_bytes=2 << 30,
                        admission=AdmissionConfig(max_queue=2 * SLOTS))
        rng = np.random.default_rng(0)
        # warm the touched prefill buckets + the decode program
        for ln in (100, 400):
            eng.add_request(rng.integers(1, 32768, size=ln).tolist(),
                            max_new_tokens=17, temperature=0.0)
        eng.run()
        interval = new_tok / (2.0 * max(base_tps, 1.0))  # 2x capacity
        offered = shed = gen = 0
        t_add, ttfts = {}, []
        t0 = time.perf_counter()
        next_arrival = t0
        while True:
            now = time.perf_counter()
            open_window = now - t0 <= duration
            while open_window and now >= next_arrival:
                next_arrival += interval
                offered += 1
                try:
                    rid = eng.add_request(
                        rng.integers(1, 32768,
                                     size=int(rng.integers(64, 256))
                                     ).tolist(),
                        max_new_tokens=new_tok, temperature=0.0)
                    t_add[rid] = now
                except ShedError:
                    shed += 1
            if eng.has_work():
                for rid, _tok in eng.step():
                    gen += 1
                    if rid in t_add:
                        ttfts.append(time.perf_counter() - t_add.pop(rid))
            elif not open_window:
                break            # offered window closed and queue drained
            else:
                time.sleep(min(0.002, max(0.0,
                                          next_arrival - time.perf_counter())))
        dt = time.perf_counter() - t0
        p95 = (sorted(ttfts)[int(0.95 * (len(ttfts) - 1))]
               if ttfts else None)
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_overload2x_tokens_per_sec",
            "value": round(gen / dt, 1),
            "unit": "tokens/s",
            "vs_baseline": round(gen / dt / max(base_tps, 1e-9), 4),
            "offered_requests": offered,
            "shed_rate": round(shed / max(offered, 1), 3),
            "p95_ttft_ms": (round(p95 * 1e3, 1) if p95 is not None
                            else None),
        }))

    def attempt_sharedprefix(make_params):
        """Shared-system-prompt row (r10): N clients whose prompts share
        a long system prefix, cache-on (radix prefix cache + chunked
        prefill) vs cache-off on the SAME workload. Reports kept tok/s
        (vs_baseline = on/off — the prefix-cache speedup), p95 TTFT both
        ways under mixed traffic (chunked prefill must keep it no worse
        than cache-off), the cache hit rate, and the
        serving_prefill_tokens_skipped evidence."""
        from paddle_tpu.serving import LLMEngine
        params = make_params()
        n_clients, new_tok = 24, 48
        rng = np.random.default_rng(0)
        shared = rng.integers(1, 32768, size=384).tolist()
        tails = [rng.integers(1, 32768, size=int(t)).tolist()
                 for t in rng.integers(48, 112, size=n_clients)]
        warm_shared = rng.integers(1, 32768, size=384).tolist()

        def run(cache_on):
            eng = LLMEngine(
                params, cfg, max_slots=SLOTS, block_size=64,
                max_model_len=1024, prompt_buckets=[128, 512, 1024],
                decode_steps=16, kv_dtype="int8",
                prefix_cache=cache_on,
                # 128-token chunks interleave with decode waves; drop
                # (not spill) on eviction — tail blocks of finished
                # requests are junk and a spill would pay d2h for them
                prefill_chunk=128 if cache_on else 0)
            # warm the compiled variants on a DIFFERENT shared prefix,
            # so the measured workload still pays its one cold miss
            for t in tails[:2]:
                eng.add_request(warm_shared + t, max_new_tokens=17)
            eng.run()
            # snapshot the cache counters AFTER warm-up so the reported
            # hit rate / skipped tokens describe ONLY the timed workload
            pc = eng.prefix_cache
            base = ((pc.hits, pc.misses, pc.tokens_skipped)
                    if pc is not None else (0, 0, 0))
            # mixed traffic: two up-front (one burst wave — rows in one
            # wave can't share, so more would only buy guaranteed
            # misses), then one arrival per step — prefills and decode
            # waves genuinely interleave
            t_add, ttfts = {}, []
            pending = [(shared + t) for t in tails]
            gen = 0
            t0 = time.perf_counter()
            for _ in range(2):
                rid = eng.add_request(pending.pop(0),
                                      max_new_tokens=new_tok)
                t_add[rid] = time.perf_counter()
            while eng.has_work() or pending:
                if pending:
                    rid = eng.add_request(pending.pop(0),
                                          max_new_tokens=new_tok)
                    t_add[rid] = time.perf_counter()
                for erid, _tok in eng.step():
                    gen += 1
                    if erid in t_add:
                        ttfts.append(time.perf_counter()
                                     - t_add.pop(erid))
            dt = time.perf_counter() - t0
            p95 = (sorted(ttfts)[int(0.95 * (len(ttfts) - 1))]
                   if ttfts else None)
            stats = (dict(hits=pc.hits - base[0],
                          misses=pc.misses - base[1],
                          skipped=pc.tokens_skipped - base[2])
                     if pc is not None else {})
            return gen / dt, p95, stats

        tps_off, p95_off, _ = run(cache_on=False)
        _release()
        tps_on, p95_on, stats = run(cache_on=True)
        hit_rate = stats["hits"] / max(1, stats["hits"] + stats["misses"])
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_sharedprefix_tokens_per_sec",
            "value": round(tps_on, 1),
            "unit": "tokens/s",
            # acceptance: cache-on >= 1.3x cache-off on this workload
            "vs_baseline": round(tps_on / max(tps_off, 1e-9), 4),
            "cache_off_tokens_per_sec": round(tps_off, 1),
            "clients": n_clients,
            "cache_hit_rate": round(hit_rate, 3),
            "prefill_tokens_skipped": int(stats["skipped"]),
            "p95_ttft_ms": (round(p95_on * 1e3, 1)
                            if p95_on is not None else None),
            "p95_ttft_ms_cache_off": (round(p95_off * 1e3, 1)
                                      if p95_off is not None else None),
        }))

    def attempt_mixedlen(make_params):
        """Mixed short/long decode lengths (r12): the ragged Pallas
        block-walk decode kernel vs the host-side bucketed path on the
        SAME workload. Half the slots decode near 128-token contexts,
        half near 900 — exactly where the bucketed path hurts: its
        power-of-two ceiling covers max(lengths), so the short slots pay
        the long slots' gather/attention. The ragged kernel walks each
        slot at its true length and compiles ONE variant. Reports kept
        tok/s (vs_baseline = ragged/bucketed), the engines' cumulative
        KV-traffic estimates (kv_read_bytes_total: per-call pool reads,
        the bucket-waste evidence) and the compiled decode-variant
        counts."""
        if jax.default_backend() != "tpu":
            # forcing decode_kernel="ragged" off-TPU would time the
            # Pallas INTERPRETER at 2.6B scale (the engine's auto path
            # falls back to bucketed for the same reason) — skip the
            # row rather than wedge the whole serving section
            return
        params = make_params()
        new_tok = 64
        rng0 = np.random.default_rng(0)
        lens = [int(x) for x in
                np.concatenate([rng0.integers(64, 160, size=SLOTS),
                                rng0.integers(704, 900, size=SLOTS)])]
        rng0.shuffle(lens)
        reqs = [rng0.integers(1, 32768, size=ln).tolist() for ln in lens]

        def run(kernel):
            eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                            max_model_len=1024,
                            prompt_buckets=[128, 512, 1024],
                            decode_steps=16, kv_dtype="int8",
                            decode_kernel=kernel)
            # steady-state measurement: one UNTIMED pass of the exact
            # timed workload first, so every prefill bucket and every
            # decode variant either path will touch (the bucketed
            # family shrinks buckets as long slots drain — a fresh
            # 2.6B variant compile inside the window would deflate
            # tps_b and inflate the acceptance ratio) is compiled
            # before the clock starts. The compile-family size itself
            # is reported separately via the variant counts.
            for p in reqs:
                eng.add_request(p, max_new_tokens=new_tok,
                                temperature=0.0)
            eng.run()
            eng.kv_read_bytes_total = 0
            t0 = time.perf_counter()
            rids = [eng.add_request(p, max_new_tokens=new_tok,
                                    temperature=0.0) for p in reqs]
            out = eng.run()
            dt = time.perf_counter() - t0
            gen = sum(len(out[r]) for r in rids)
            return (gen / dt, eng.kv_read_bytes_total,
                    len(eng._decode_cache))

        tps_b, kvb_b, var_b = run("bucketed")
        _release()
        tps_r, kvb_r, var_r = run("ragged")
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_mixedlen_tokens_per_sec",
            "value": round(tps_r, 1),
            "unit": "tokens/s",
            # acceptance (ROADMAP 3): ragged beats bucketed at mixed
            # lengths — vs_baseline is the ragged/bucketed ratio
            "vs_baseline": round(tps_r / max(tps_b, 1e-9), 4),
            "bucketed_tokens_per_sec": round(tps_b, 1),
            "kv_read_bytes_ragged": int(kvb_r),
            "kv_read_bytes_bucketed": int(kvb_b),
            "decode_variants_ragged": int(var_r),
            "decode_variants_bucketed": int(var_b),
        }))

    def attempt_spec(make_params):
        """Speculative-decoding row (r13): draft-then-verify vs the
        plain engine on the SAME greedy workload. The draft is the
        int8-quantized target (same config) — the nncase pairing: ~half
        the weight bytes per draft step on a bandwidth-bound chip, with
        near-1 acceptance because it IS the target modulo quantization
        error. Reports kept tok/s (vs_baseline = spec/plain), the
        measured acceptance rate, committed tokens per verify call, and
        the draft/verify step counts — the evidence bench_diff --check
        guards from the next chip round."""
        from paddle_tpu.models import llama as _llama
        params = make_params()
        draft = jax.jit(_llama.quantize_params)(params)
        new_tok = 96
        rng0 = np.random.default_rng(0)
        reqs = [rng0.integers(1, 32768, size=int(ln)).tolist()
                for ln in rng0.integers(64, 448, size=2 * SLOTS)]

        def run(spec_on):
            eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                            max_model_len=1024,
                            prompt_buckets=[128, 512, 1024],
                            decode_steps=16,
                            draft_params=draft if spec_on else None,
                            draft_config=cfg if spec_on else None,
                            spec_tokens=6)
            # one untimed pass compiles every prefill bucket and every
            # draft/verify (or decode) variant the workload touches
            for p in reqs:
                eng.add_request(p, max_new_tokens=new_tok,
                                temperature=0.0)
            eng.run()
            base = (eng.spec_proposed, eng.spec_accepted,
                    eng.spec_committed, eng.spec_verify_calls,
                    eng.spec_draft_steps)
            t0 = time.perf_counter()
            rids = [eng.add_request(p, max_new_tokens=new_tok,
                                    temperature=0.0) for p in reqs]
            out = eng.run()
            dt = time.perf_counter() - t0
            gen = sum(len(out[r]) for r in rids)
            stats = dict(proposed=eng.spec_proposed - base[0],
                         accepted=eng.spec_accepted - base[1],
                         committed=eng.spec_committed - base[2],
                         verify_calls=eng.spec_verify_calls - base[3],
                         draft_steps=eng.spec_draft_steps - base[4])
            return gen / dt, stats

        tps_off, _ = run(spec_on=False)
        _release()
        tps_on, st = run(spec_on=True)
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_spec_tokens_per_sec",
            "value": round(tps_on, 1),
            "unit": "tokens/s",
            # acceptance (ROADMAP 4): >= 1.5x at acceptance >= 60%
            "vs_baseline": round(tps_on / max(tps_off, 1e-9), 4),
            "spec_off_tokens_per_sec": round(tps_off, 1),
            "acceptance_rate": round(
                st["accepted"] / max(1, st["proposed"]), 3),
            "tokens_per_verify": round(
                st["committed"] / max(1, st["verify_calls"]), 2),
            "draft_steps": int(st["draft_steps"]),
            "verify_calls": int(st["verify_calls"]),
        }))

    def attempt_http(make_params):
        """HTTP/SSE front-door row (r14): the SAME int8 engine serving
        concurrent SSE clients over real localhost sockets vs its own
        direct-call run of the IDENTICAL workload (same engine config,
        same prompts — a baseline from another config would fold
        decode_steps/workload differences into the ratio).
        vs_baseline = http/direct — the front door's tax; near 1.0
        means the socket/asyncio layer rides the step loop's idle time
        instead of the chip's. Also reports p95 client-observed TTFB
        (first SSE frame)."""
        import json as _json
        import socket as _socket
        import threading as _threading

        from paddle_tpu.serving import HTTPFrontDoor
        params = make_params()
        new_tok = 64
        rng0 = np.random.default_rng(0)
        reqs = [rng0.integers(1, 32768, size=int(ln)).tolist()
                for ln in rng0.integers(64, 448, size=2 * SLOTS)]
        eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                        max_model_len=1024,
                        prompt_buckets=[128, 512, 1024], decode_steps=16,
                        kv_dtype="int8")
        # compile everything BEFORE any clock starts
        for p in reqs:
            eng.add_request(p, max_new_tokens=new_tok, temperature=0.0)
        eng.run()
        # direct-call baseline: the exact workload the HTTP pass serves
        t0 = time.perf_counter()
        rids = [eng.add_request(p, max_new_tokens=new_tok,
                                temperature=0.0) for p in reqs]
        out = eng.run()
        base_dt = time.perf_counter() - t0
        base_tps = sum(len(out[r]) for r in rids) / base_dt
        front = HTTPFrontDoor(eng)
        host, port = front.start()
        stats = {"tokens": 0, "ttfb": []}
        lock = _threading.Lock()

        def client(prompt):
            body = _json.dumps({"prompt": prompt,
                                "max_new_tokens": new_tok}).encode()
            s = _socket.create_connection((host, port), timeout=600)
            t_send = time.perf_counter()
            s.sendall((f"POST /v1/generate HTTP/1.1\r\nHost: b\r\n"
                       f"Content-Length: {len(body)}\r\n\r\n"
                       ).encode() + body)
            buf, first = b"", None
            while True:
                c = s.recv(65536)
                if not c:
                    break
                if first is None and b"data:" in buf + c:
                    first = time.perf_counter() - t_send
                buf += c
            s.close()
            n = buf.count(b'{"token":')
            with lock:
                stats["tokens"] += n
                if first is not None:
                    stats["ttfb"].append(first)

        try:
            t0 = time.perf_counter()
            threads = [_threading.Thread(target=client, args=(p,))
                       for p in reqs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        finally:
            front.stop()
        ttfb = sorted(stats["ttfb"])
        p95 = ttfb[int(0.95 * (len(ttfb) - 1))] if ttfb else None
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_http_tokens_per_sec",
            "value": round(stats["tokens"] / dt, 1),
            "unit": "tokens/s",
            "vs_baseline": round(stats["tokens"] / dt
                                 / max(base_tps, 1e-9), 4),
            "direct_tokens_per_sec": round(base_tps, 1),
            "clients": len(reqs),
            "p95_ttfb_ms": (round(p95 * 1e3, 1) if p95 is not None
                            else None),
        }))

    def attempt_offload(make_params):
        """KV working set ~1.5x device pool capacity (r15, ROADMAP 5):
        the block pool is sized to ~2/3 of what the concurrent slots
        want, so preempt-swap and restore run CONTINUOUSLY — exactly
        the regime where the synchronous tier pays every transfer
        inline with decode. Async offload vs forced-sync on the SAME
        workload: reports kept tok/s (vs_baseline = async/sync — the
        overlap win), observed inline-stall seconds both ways, the
        prefetch hit rate, and the recompute-fallback count (the
        acceptance bar: prefetch_hits > 0 and zero fallbacks on the
        async path — the engine SURVIVES the oversubscription with
        graceful degradation, not a preemption storm)."""
        from paddle_tpu.serving import LLMEngine
        params = make_params()
        n_reqs, new_tok = 2 * SLOTS, 96
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 32768, size=int(ln)).tolist()
                   for ln in rng.integers(256, 384, size=n_reqs)]
        # slots want ~SLOTS x ceil((prompt+new)/bs) blocks; give them 2/3
        per_req = -(-(384 + new_tok) // 64)
        pool_blocks = max(2 * per_req, int(SLOTS * per_req / 1.5))

        def run(mode):
            eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                            max_model_len=1024,
                            prompt_buckets=[128, 512, 1024],
                            decode_steps=8, kv_dtype="int8",
                            num_blocks=pool_blocks,
                            kv_swap_bytes=8 << 30, kv_offload=mode)
            # warm the buckets + decode program below swap pressure
            for ln in (100, 300):
                eng.add_request(
                    rng.integers(1, 32768, size=ln).tolist(),
                    max_new_tokens=17, temperature=0.0)
            eng.run()
            t0 = time.perf_counter()
            rids = [eng.add_request(p, max_new_tokens=new_tok,
                                    temperature=0.0) for p in prompts]
            out = eng.run()
            dt = time.perf_counter() - t0
            gen = sum(len(out[r]) for r in rids)
            off = eng.offload
            return gen / dt, dict(
                restores=off.prefetch_hits + off.stalls,
                prefetch_hits=off.prefetch_hits,
                stalls=off.stalls,
                stall_seconds=round(off.stall_seconds, 4),
                # swap_fallbacks alone: a host-full refusal already
                # lands there via swapped=False (refusals would double-
                # count the same preemption)
                fallbacks=eng.swap_fallbacks)

        tps_sync, st_sync = run("sync")
        _release()
        tps_async, st = run("async")
        hit_rate = st["prefetch_hits"] / max(1, st["restores"])
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_offload_tokens_per_sec",
            "value": round(tps_async, 1),
            "unit": "tokens/s",
            # acceptance: async >= sync on this workload, hits > 0,
            # fallbacks == 0 (no preemption-storm recompute)
            "vs_baseline": round(tps_async / max(tps_sync, 1e-9), 4),
            "sync_tokens_per_sec": round(tps_sync, 1),
            "working_set_blocks": SLOTS * per_req,
            "pool_blocks": pool_blocks,
            "prefetch_hit_rate": round(hit_rate, 3),
            "prefetch_hits": st["prefetch_hits"],
            "stall_seconds": st["stall_seconds"],
            "stall_seconds_sync": st_sync["stall_seconds"],
            "recompute_fallbacks": st["fallbacks"],
        }))

    def attempt_router(make_params):
        """Replica scale-out row (r16): the SAME offered load against 2
        router-fronted replicas vs 1 bare engine (identical config,
        identical prompts). vs_baseline = 2-replica/1-engine kept
        tok/s. Both replicas share ONE chip here, so this measures the
        router's TAX, not a speedup — the bar is ~1.0 (placement is
        host-side and rides the step threads' idle time; a multi-chip
        deployment is where the factor exceeds 1). A half-shared-prefix
        workload exercises the affinity scorer (hit rate reported), and
        the clean leg's acceptance bar is failovers == resumes == 0 —
        failover COST is chaos_run --router's job, not bench's."""
        from paddle_tpu.serving import LLMEngine, ReplicaRouter
        params = make_params()
        n_reqs, new_tok = 4 * SLOTS, 64
        rng = np.random.default_rng(0)
        shared = rng.integers(1, 32768, size=128).tolist()
        prompts = []
        for i, ln in enumerate(rng.integers(64, 320, size=n_reqs)):
            tail = rng.integers(1, 32768, size=int(ln)).tolist()
            prompts.append(shared + tail if i % 2 == 0 else tail)

        def mk_engine():
            return LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                             max_model_len=1024,
                             prompt_buckets=[128, 512, 1024],
                             decode_steps=16, kv_dtype="int8",
                             prefix_cache=True)

        # 1-engine baseline on the identical workload (warm first)
        eng = mk_engine()
        for p in prompts[:2]:
            eng.add_request(list(p), max_new_tokens=8, temperature=0.0)
        eng.run()
        t0 = time.perf_counter()
        rids = [eng.add_request(list(p), max_new_tokens=new_tok,
                                temperature=0.0) for p in prompts]
        out = eng.run()
        base_tps = sum(len(out[r]) for r in rids) \
            / (time.perf_counter() - t0)
        _release()

        engines = [mk_engine() for _ in range(2)]
        for e in engines:
            for p in prompts[:2]:
                e.add_request(list(p), max_new_tokens=8, temperature=0.0)
            e.run()
        router = ReplicaRouter(engines, names=["r0", "r1"])
        router.start()
        try:
            t0 = time.perf_counter()
            rrids = [router.submit(list(p), max_new_tokens=new_tok,
                                   temperature=0.0) for p in prompts]
            gen = sum(len(router.wait(r, timeout=1800)) for r in rrids)
            dt = time.perf_counter() - t0
        finally:
            router.stop()
        hits, misses = router.affinity_hits, router.affinity_misses
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_router_tokens_per_sec",
            "value": round(gen / dt, 1),
            "unit": "tokens/s",
            # acceptance: vs_baseline ~1.0 (the router's tax on a
            # shared chip), failovers == resumes == 0 in this clean leg
            "vs_baseline": round(gen / dt / max(base_tps, 1e-9), 4),
            "single_engine_tokens_per_sec": round(base_tps, 1),
            "replicas": 2,
            "affinity_hit_rate": round(hits / max(1, hits + misses), 3),
            "failovers": router.failovers,
            "resumed_streams": router.resumed_streams,
        }))

    def attempt_tp2(make_params):
        """TP-sharded decode hot path (r19): the SAME greedy workload on
        a 2-device ("tp",) mesh vs the unsharded engine — the ragged
        decode partials run under shard_map (the KV heads split across
        the mesh, each device walks half the head dim's blocks), prefill
        stays GSPMD-sharded. Streams must be bit-identical: sharding is
        an execution detail, never a numerics fork (per-kv-head online
        softmax is device-local). vs_baseline = tp2 / unsharded tok/s —
        two real chips with separate HBM paths is where it can exceed
        1."""
        from jax.sharding import Mesh
        if len(jax.devices()) < 2:
            return   # tp=2 needs 2 local devices
        params = make_params()
        rng = np.random.default_rng(0)
        reqs = [rng.integers(1, 32768, size=int(ln)).tolist()
                for ln in rng.integers(64, 512, size=2 * SLOTS)]

        def run(mesh):
            eng = LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                            max_model_len=1024,
                            prompt_buckets=[128, 512, 1024],
                            decode_steps=64, kv_dtype="int8",
                            decode_kernel="ragged", mesh=mesh)
            for p in reqs[:2]:
                eng.add_request(list(p), max_new_tokens=8,
                                temperature=0.0)
            eng.run()
            t0 = time.perf_counter()
            rids = [eng.add_request(list(p), max_new_tokens=NEW,
                                    temperature=0.0) for p in reqs]
            out = eng.run()
            dt = time.perf_counter() - t0
            streams = [out[r] for r in rids]
            return sum(len(s) for s in streams) / dt, streams

        base_tps, base_streams = run(None)
        _release()
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        tp_tps, tp_streams = run(mesh)
        assert tp_streams == base_streams, \
            "tp2 streams diverged from unsharded greedy"
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_tp2_tokens_per_sec",
            "value": round(tp_tps, 1),
            "unit": "tokens/s",
            # acceptance: bit-identical streams (asserted above);
            # vs_baseline is the tp2 scale factor over one engine
            "vs_baseline": round(tp_tps / max(base_tps, 1e-9), 4),
            "unsharded_tokens_per_sec": round(base_tps, 1),
            "tp": 2,
            "requests": len(reqs),
        }))

    def attempt_disagg(make_params):
        """Disaggregated prefill/decode row (r19): a prefill-role +
        decode-role replica pair behind the router vs ONE colocated
        engine on the identical greedy workload. Every stream prefills
        on p0, spills its KV bit-exact into the shared host relay, and
        decodes on d0 after one batched h2d restore. Both replicas
        share one chip here, so vs_baseline measures the HANDOFF TAX
        (relay d2h+h2d + the re-dispatch hop), not a speedup — the
        split pays off when prefill and decode get their own chips and
        neither steals the other's step budget. Acceptance: kept tok/s
        within noise of colocated, handoffs == restores == streams,
        relay drained."""
        from paddle_tpu.serving import LLMEngine, ReplicaRouter
        from paddle_tpu.serving.kv_swap import HostKVPool
        params = make_params()
        n_reqs, new_tok = 4 * SLOTS, 64
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 32768, size=int(ln)).tolist()
                   for ln in rng.integers(64, 320, size=n_reqs)]

        def mk_engine(**kw):
            return LLMEngine(params, cfg, max_slots=SLOTS, block_size=64,
                             max_model_len=1024,
                             prompt_buckets=[128, 512, 1024],
                             decode_steps=16, kv_dtype="int8", **kw)

        # colocated baseline (warm first)
        eng = mk_engine()
        for p in prompts[:2]:
            eng.add_request(list(p), max_new_tokens=8, temperature=0.0)
        eng.run()
        t0 = time.perf_counter()
        rids = [eng.add_request(list(p), max_new_tokens=new_tok,
                                temperature=0.0) for p in prompts]
        out = eng.run()
        base_tps = sum(len(out[r]) for r in rids) \
            / (time.perf_counter() - t0)
        _release()

        relay = HostKVPool(4 << 30, kind="relay")
        p_eng = mk_engine(role="prefill", relay=relay)
        d_eng = mk_engine(role="decode", relay=relay)
        for e in (p_eng, d_eng):
            for p in prompts[:2]:
                e.add_request(list(p), max_new_tokens=8, temperature=0.0)
            e.run()
        router = ReplicaRouter([p_eng, d_eng], names=["p0", "d0"])
        router.start()
        try:
            t0 = time.perf_counter()
            rrids = [router.submit(list(p), max_new_tokens=new_tok,
                                   temperature=0.0) for p in prompts]
            gen = sum(len(router.wait(r, timeout=1800)) for r in rrids)
            dt = time.perf_counter() - t0
        finally:
            router.stop()
        assert len(relay) == 0, "relay pool not drained"
        results.append(_efficiency({
            "metric": "llama-2.6b_serving_disagg_tokens_per_sec",
            "value": round(gen / dt, 1),
            "unit": "tokens/s",
            # acceptance: vs_baseline ~1.0 (the handoff tax on one
            # chip), handoffs == streams, refusals == 0
            "vs_baseline": round(gen / dt / max(base_tps, 1e-9), 4),
            "colocated_tokens_per_sec": round(base_tps, 1),
            "handoffs": p_eng.handoffs,
            "handoff_mb": round(p_eng.handoff_bytes / 2**20, 2),
            "handoff_ms_mean": round(
                1e3 * p_eng.handoff_seconds / max(1, p_eng.handoffs), 2),
            "relay_refusals": relay.refusals,
            "handoff_resumes": router.handoff_resumes,
        }))

    try:
        attempt("bf16", lambda: _init_bf16_params(cfg))
        _release()
        # int8 weight-only serving (quantize_params / the inference-export
        # precision path) — same engine, ~half the weight bytes per step
        attempt(
            "int8",
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # int8 everywhere: int8 weights + int8 KV pools (per-entry-scaled,
        # dequant fused into the bucketed decode attention) — halves the
        # decode KV traffic on top of the halved weight bytes
        tps_kv8 = attempt(
            "int8_kv8",
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)),
            kv_dtype="int8")
        _release()
        # sustained overload at 2x the capacity just measured: the
        # admission queue sheds, deadlines hold, and throughput must
        # degrade gracefully instead of collapsing
        attempt_overload(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)),
            tps_kv8)
        _release()
        # shared-system-prompt clients: the r10 prefix cache + chunked
        # prefill vs the same workload cold (ISSUE 11 acceptance row)
        attempt_sharedprefix(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # mixed short/long decode lengths: the r12 ragged Pallas kernel
        # vs the bucketed path on the same workload (ISSUE 12 row)
        attempt_mixedlen(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # speculative decoding: int8 draft / bf16 target, spec on vs
        # off on the same greedy workload (ISSUE 13 row, ROADMAP 4)
        attempt_spec(lambda: _init_bf16_params(cfg))
        _release()
        # the same int8 engine behind the r14 HTTP/SSE front door:
        # concurrent socket clients vs a direct-call run of the same
        # workload (the front door's tax must be ~zero — it rides the
        # step loop's idle time)
        attempt_http(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # r15 async KV offload: a KV working set ~1.5x the pool, async
        # spill/prefetch vs the forced-sync tier on the same workload
        attempt_offload(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # r16 replica router: 2 router-fronted replicas vs 1 bare
        # engine on the same half-shared-prefix load (scale-out factor,
        # affinity hit rate, zero failovers in the clean leg)
        attempt_router(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # r19 tp=2 sharded decode hot path: shard_mapped ragged decode
        # on a 2-device mesh vs unsharded — bit-identical streams
        # asserted (skips on a single-device host)
        attempt_tp2(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
        _release()
        # r19 disaggregated prefill/decode: prefill+decode replica pair
        # over the shared host relay vs one colocated engine (handoff
        # tax, bytes, latency; relay drained)
        attempt_disagg(
            lambda: jax.jit(llama.quantize_params)(_init_bf16_params(cfg)))
    except Exception as e:
        results.append({"metric": "serving_bench_failed", "value": 0.0,
                        "unit": "tokens/s", "vs_baseline": 0.0,
                        "error": str(e)[:200]})
    finally:
        _release()


# (section name, runner, wall-clock timeout seconds). Ordered: the first
# section's first metric is the round headline.
_SECTIONS = (
    ("dense", bench_dense, 2400),
    ("8b", bench_8b, 2400),
    ("long_context", bench_long_context, 1500),
    ("moe", bench_moe, 1500),
    ("decode", bench_decode, 1500),
    ("serving", bench_serving, 1800),
)


def _run_section(name: str) -> int:
    """Child mode: run ONE section on the chip, print its results list."""
    fn = dict((n, f) for n, f, _ in _SECTIONS)[name]
    results = []
    try:
        dev = jax.devices()[0]
        fn(dev, results)
    except Exception as e:  # belt over each section's own suspenders
        results.append({"metric": f"{name}_bench_failed", "value": 0.0,
                        "unit": "tokens/s", "vs_baseline": 0.0,
                        "error": str(e)[:200]})
    # unique sentinel: the parent parses ONLY this line, so stray
    # JSON-array-looking stdout (atexit hooks, warnings) can't be mistaken
    # for the section's results
    print(_RESULT_SENTINEL + json.dumps(results), flush=True)
    return 0


_RESULT_SENTINEL = "BENCH_RESULT: "


def _spawn_section(name: str, timeout: float):
    """Run one section in a fresh process; return (results, error|None).
    A dead/hung/garbled child yields an error string, never an exception."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--section", name],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=timeout, cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        # deterministic hang: do NOT retry (a second identical wait would
        # burn 2x the budget for the same outcome)
        return None, f"timeout after {timeout:.0f}s (not retried)"
    except Exception as e:
        return None, f"spawn failed: {e}"[:200]
    # only the sentinel-prefixed line is the section's result list
    for line in reversed(proc.stdout.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith(_RESULT_SENTINEL):
            try:
                return json.loads(line[len(_RESULT_SENTINEL):]), None
            except ValueError:
                continue
    tail = proc.stderr.decode(errors="replace")[-400:]
    return None, f"child died rc={proc.returncode}: {tail}"[:400]


def main():
    results = []
    for name, _, timeout in _SECTIONS:
        got, err = _spawn_section(name, timeout)
        if got is None and not (err or "").startswith("timeout after"):
            # crashed child: one retry on a fresh client. Timeouts are
            # deterministic and excluded above — matched against the exact
            # _spawn_section sentinel, NOT a substring, so a crashed child
            # whose stderr merely mentions 'timeout' still gets its retry
            got, err = _spawn_section(name, timeout)
        if got is None:
            results.append({"metric": f"{name}_bench_failed", "value": 0.0,
                            "unit": "tokens/s", "vs_baseline": 0.0,
                            "error": err})
        else:
            results.extend(got)
    if not results:  # cannot happen, but the JSON line must exist
        results = [{"metric": "bench_empty", "value": 0.0, "unit": "",
                    "vs_baseline": 0.0}]
    headline = results[0]
    out = dict(headline)
    out["metrics"] = results
    print(json.dumps(out), flush=True)
    return 0 if headline.get("value", 0.0) > 0 else 1


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--section":
        sys.exit(_run_section(sys.argv[2]))
    sys.exit(main())
