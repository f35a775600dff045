#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the published widths of Llama-3-8B (``LlamaConfig()``'s defaults: hidden
4096, FFN 14336, 32 heads / 8 KV heads, head_dim 128, vocab 128256). Depth
is the only cut; weights are random, made from ``--seed``.

    python chip_smoke.py             # one chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip (the default, what the driver runs):

* train — ``llama.init_sharded_train_state`` + ``jax.jit(llama.train_step,
  donate)`` as examples/llama_pretrain.py builds them (bf16 params,
  adafactor, chunked CE), seq 2048, a fixed seeded batch. Checks: the loss
  is finite and lower at the last step than at the first; the compiled
  step holds the flash kernel. Each timed step is waited for once with
  ``block_until_ready`` and once with a device-to-host read of the loss.
* serve — two ``LLMEngine(decode_kernel="auto")`` sharing one set of bf16
  weights, one with more than 4 slots and one with 4, each behind
  ``HTTPFrontDoor(port=0)`` wired as tools/serve.py wires it, driven over
  real sockets from threads of this process with ``POST /v1/generate``.
  Checks: every stream ends ``finished`` with the asked number of tokens,
  ids < vocab; ``serving_decode_kernel_total{path}`` names the path
  ``auto`` takes on a TPU and no ``*fallback*`` counter moved; the block
  ledger balances after the drain; the ragged walk alone agrees with the
  XLA gather oracle over a length mix that straddles its chunks' edges,
  16 slots at these heads; and the logits the engine sampled its
  first two tokens from (prefill, then the first decode step) agree, to
  ``LOGIT_TOL``, with a float32 ``llama.forward(use_flash=False)`` of the
  same weights run on the chip under ``default_matmul_precision("highest")``.

``--chips 4`` runs only what exists across chips, each next to what it is
compared with: the train phase on a ("dp","tp") = (2,2) mesh with
``make_shardings(fsdp=True)`` against the one-chip train phase at the same
seeds, and a ``Mesh(devices[:2], ("tp",))`` ragged-path engine against the
unsharded engine on the same requests (f32 weights, highest matmul
precision, so the streams are identical). It checks that the state really
is spread over the chips.

It fails — exit code 1, last line ``{"ok": false, ...}`` — when JAX finds
no TPU, when any phase raises, or when any check fails; no phase is caught
and skipped. One process: it imports JAX once and starts no child. The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

The phase functions take their sizes as an argument so that
tests/test_chip_smoke.py can rehearse them tiny on the CPU; ``main`` only
ever runs the full sizes, and only on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import socket
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

# bf16 against float32-"highest" on logits of unit scale (final-norm
# hidden state times a 1/sqrt(hidden)-scaled head): the largest absolute
# difference over the vocabulary, and the rms difference over the rms logit
# (measured on the chip, PR 21: 0.064 and 0.013 at 12 layers)
LOGIT_TOL = 0.2
LOGIT_REL_RMS_TOL = 0.03
# one chip against the (2,2) mesh, same seeds: bf16 params, the same math
# with other reduction orders. The first loss is taken at identical
# parameters; each adafactor step then moves every weight by about 1e-3
# whichever way its gradient points, so differences in the last bits grow
# from step to step (measured on the chip, PR 21: 8e-6 at the first loss,
# 3.5e-3 within four, 9e-2 within thirteen). The check is on the first
# four; all of them are printed.
SHARDED_FIRST_LOSS_RTOL = 1e-3
SHARDED_LOSS_RTOL = 1e-2
SHARDED_STEPS_COMPARED = 4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase is sized by. ``model`` carries the widths."""
    model: Any                      # LlamaConfig: widths (depth set per phase)
    seq: int
    batch: int
    loss_chunks: int
    train_layers: int
    train_steps: int                # timed steps after the first
    serve_layers: int
    max_len: int
    block: int
    # (slots, pool blocks) of the two engines: > 4 slots and <= 4
    wide: Tuple[int, int]
    narrow: Tuple[int, int]
    short_prompts: Tuple[int, int]  # prompt length range (tokens)
    long_prompts: Tuple[int, int]
    n_requests: int                 # per engine
    max_new: Tuple[int, int]
    probe_prompt: int               # the logit check's prompt length
    tp_layers: int
    tp_requests: int


def full_sizes() -> Sizes:
    """Llama-3-8B widths. Depth by what one 16 GB chip holds (asked of the
    compiler for a described v5e before any chip time): the train step
    (bf16 params + grads, adafactor, 8 CE chunks, batch 4 x 2048) compiles
    to 13.0 GiB at 6 layers (14.9 GiB at 8, of 15.75); serving at 12
    layers holds 6.8 GiB of bf16 weights and 4.8 GiB of KV pools, and its
    largest program (an 8 x 2048 prefill) adds 2.0 GiB. Twelve timed train
    steps: the trainer's adafactor moves every weight by at least 1e-3 a
    step (optimizer/functional.py, ``max(eps2, lr)``), and at these widths
    the loss bounces by about one nat for the first steps before its
    trend shows (PERF.md, open questions)."""
    from paddle_tpu.models import llama

    return Sizes(
        model=llama.LlamaConfig(), seq=2048, batch=4, loss_chunks=8,
        train_layers=6, train_steps=12,
        serve_layers=12, max_len=2048, block=16,
        wide=(8, 4352), narrow=(4, 2176),
        short_prompts=(24, 120), long_prompts=(1040, 1500),
        n_requests=10, max_new=(12, 24), probe_prompt=200,
        tp_layers=4, tp_requests=4)


class Run:
    """What every phase shares: sizes, the seed, the device, the log."""

    def __init__(self, sizes: Sizes, seed: int, on_chip: bool = True):
        import jax

        self.sizes = sizes
        self.seed = seed
        # False only in the CPU rehearsal of the tests: no Mosaic kernels,
        # no device memory statistics, auto picks the off-TPU paths
        self.on_chip = on_chip
        self.device = jax.devices()[0]
        self.step_errors: List[str] = []    # tracebacks of engine steps

    def log(self, phase: str, msg: str) -> None:
        print(f"[{phase}] {msg}", flush=True)

    def check(self, phase: str, ok: bool, what: str) -> None:
        self.log(phase, ("ok   " if ok else "FAIL ") + what)
        if not ok:
            raise AssertionError(f"{phase}: {what}")

    def memory(self, phase: str, devices=None) -> None:
        """Peak device bytes. The backend keeps one high-water mark per
        process, so a later phase shows its own peak only where it
        exceeds the earlier ones; ``bytes_in_use`` says what is resident
        as the phase ends."""
        if not self.on_chip:
            return
        for d in devices or [self.device]:
            stats = d.memory_stats()
            if not stats or "peak_bytes_in_use" not in stats:
                raise RuntimeError(
                    f"{d} reports no memory statistics: {stats!r}")
            self.log(phase, f"device {d.id} {d.device_kind}: "
                     f"peak_bytes_in_use={stats['peak_bytes_in_use']} "
                     f"bytes_in_use={stats['bytes_in_use']} "
                     f"bytes_limit={stats.get('bytes_limit')}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def train_phase(run: Run, mesh_shape: Tuple[int, int] = (1, 1),
                phase: str = "train") -> List[float]:
    """A few steps of the fused pretrain step on a ("dp","tp") mesh of
    ``mesh_shape`` — (1,1) is the one-chip phase. Returns the losses."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.models import llama

    sz = run.sizes
    n = mesh_shape[0] * mesh_shape[1]
    devices = jax.devices()[:n]
    mesh = Mesh(np.asarray(devices).reshape(mesh_shape), ("dp", "tp"))
    cfg = dataclasses.replace(
        sz.model, num_layers=sz.train_layers, max_seq_len=sz.seq,
        loss_chunks=sz.loss_chunks)
    optimizer = "adafactor"

    t0 = time.perf_counter()
    state = llama.init_sharded_train_state(
        cfg, jax.random.PRNGKey(run.seed),
        llama.make_shardings(cfg, mesh, fsdp=True), optimizer=optimizer,
        param_dtype=jnp.bfloat16)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(run.seed + 1),
                           (sz.batch, sz.seq + 1), 0, cfg.vocab_size),
        NamedSharding(mesh, P("dp", None)))
    jax.block_until_ready((state, tokens))
    n_params = llama.num_params(state.params)
    run.log(phase, f"mesh dp x tp = {mesh_shape} on {n} device(s); "
            f"depth={cfg.num_layers} params={n_params} "
            f"({n_params / 1e9:.2f}B) batch={sz.batch} seq={sz.seq} "
            f"bf16 params, {optimizer}, loss_chunks={cfg.loss_chunks}; "
            f"init {time.perf_counter() - t0:.1f}s")

    with llama.activation_mesh(mesh):
        # the new state keeps the old one's layout: the step is compiled
        # once, ahead of time, and fed its own output
        step = jax.jit(
            lambda s, t: llama.train_step(s, t, cfg, optimizer=optimizer),
            donate_argnums=0, out_shardings=(
                jax.tree_util.tree_map(lambda a: a.sharding, state),
                NamedSharding(mesh, P())))
        lowered = step.lower(state, tokens)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    ma = compiled.memory_analysis()
    run.log(phase, f"compile_s={compile_s:.1f} tpu_custom_call x{kernels}; "
            "compiler's memory per device: "
            f"peak={ma.peak_memory_in_bytes} "
            f"arguments={ma.argument_size_in_bytes} "
            f"temp={ma.temp_size_in_bytes} "
            f"(outputs alias the donated state)")
    if run.on_chip:
        # forward (rebuilt under remat), dQ and dK/dV: the flash kernel is
        # in the step, it did not give way to reference math
        run.check(phase, kernels >= 3,
                  f"flash kernel in the compiled step ({kernels} Mosaic "
                  "calls)")
    if n > 1:
        for name in ("all-reduce", "all-gather", "reduce-scatter"):
            run.log(phase, f"collectives: {name} x{text.count(name + '(')}")
        _check_spread(run, phase, state.params, devices)

    # the first call, then timed steps, waited for in turn with
    # block_until_ready followed by a device-to-host read of the loss, and
    # with the read alone. If block_until_ready returned before the step
    # had finished, the read behind it would take the rest of the step.
    t0 = time.perf_counter()
    state, loss = compiled(state, tokens)
    losses = [float(np.asarray(loss))]
    first_s = time.perf_counter() - t0
    bur, read_after, read_only = [], [], []
    for i in range(sz.train_steps):
        t0 = time.perf_counter()
        state, loss = compiled(state, tokens)
        if i % 2 == 0:
            jax.block_until_ready(loss)
            t1 = time.perf_counter()
            losses.append(float(np.asarray(loss)))
            bur.append(t1 - t0)
            read_after.append(time.perf_counter() - t1)
        else:
            losses.append(float(np.asarray(loss)))
            read_only.append(time.perf_counter() - t0)
    run.log(phase, "losses " + " ".join(f"{v:.4f}" for v in losses))
    run.log(phase, f"first step {first_s:.3f}s; steps waited for with "
            f"block_until_ready: {_fmt(bur)}s, the read of the loss behind "
            f"them: {_fmt(read_after)}s; steps waited for with the read "
            f"alone: {_fmt(read_only)}s")
    tokens_per_step = sz.batch * sz.seq
    run.log(phase, f"run_s={first_s + sum(bur + read_after + read_only):.1f}"
            f" ({tokens_per_step} tokens/step)")
    run.check(phase, all(np.isfinite(losses)), "every loss is finite")
    run.check(phase, losses[-1] < losses[0],
              f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
    run.memory(phase, devices)
    del state, tokens, compiled, lowered, step
    gc.collect()
    return losses


def _fmt(xs: List[float]) -> str:
    return "[" + ", ".join(f"{x:.4f}" for x in xs) + "]"


def _check_spread(run: Run, phase: str, tree, devices) -> None:
    """The state really is spread: every leaf has a shard on each device,
    and no device holds much more than its share — code that has only
    ever seen virtual CPU devices may put everything on the first chip."""
    import jax

    want = {d.id for d in devices}
    per_device = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        on = {s.device.id for s in leaf.addressable_shards}
        if on != want:
            raise AssertionError(
                f"{phase}: a leaf {leaf.shape} lives on devices {on}, "
                f"not on {want}")
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            per_device[s.device.id] += s.data.nbytes
    share = {d: b / total for d, b in per_device.items()}
    run.log(phase, "share of the parameter bytes per device: "
            + " ".join(f"{d}:{s:.3f}" for d, s in sorted(share.items())))
    run.check(phase, max(share.values()) < 1.5 / len(devices),
              f"no device holds more than 1.5/{len(devices)} of the "
              "parameters")
    if run.on_chip:
        for d in devices:
            used = d.memory_stats()["bytes_in_use"]
            run.check(phase, used > 0,
                      f"device {d.id} has bytes in use ({used})")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _http(port: int, method: str, path: str, body=None,
          timeout: float = 900.0) -> Tuple[int, bytes]:
    """One HTTP/1.1 exchange over a real socket; the server closes."""
    data = b"" if body is None else json.dumps(body).encode()
    head = (f"{method} {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            f"Content-Length: {len(data)}\r\n\r\n").encode()
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.sendall(head + data)
        chunks = []
        while True:
            part = s.recv(1 << 16)
            if not part:
                break
            chunks.append(part)
    head, _, payload = b"".join(chunks).partition(b"\r\n\r\n")
    return int(head.split()[1]), payload


def _generate(port: int, prompt: List[int], max_new: int) -> Dict:
    """POST /v1/generate and read the SSE stream to its terminal frame."""
    status, payload = _http(port, "POST", "/v1/generate",
                            {"prompt": prompt, "max_new_tokens": max_new})
    frames = [json.loads(f[len(b"data: "):])
              for f in payload.split(b"\n\n") if f.startswith(b"data: ")]
    return {"status": status,
            "streamed": [f["token"] for f in frames if "token" in f],
            "terminal": next((f for f in frames if f.get("done")), None)}


def _counters(port: int) -> Dict[Tuple[str, Tuple], float]:
    """Every counter series of the process, read as a scraper would."""
    status, payload = _http(port, "GET", "/metrics.json")
    if status != 200:
        raise RuntimeError(f"/metrics.json answered {status}")
    return {(m["name"], tuple(sorted(s["labels"].items()))): s["value"]
            for m in json.loads(payload)["metrics"]
            if m["kind"] == "counter" for s in m["series"]}


def _requests(run: Run, rng, n: int) -> List[Tuple[List[int], int]]:
    """A seeded mix of short and long prompts (every third one long, so
    prefill, append and a many-block walk all run)."""
    sz = run.sizes
    out = []
    for i in range(n):
        lo, hi = sz.long_prompts if i % 3 == 1 else sz.short_prompts
        length = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, sz.model.vocab_size, size=length).tolist()
        out.append((prompt, int(rng.integers(sz.max_new[0],
                                             sz.max_new[1] + 1))))
    return out


def _start_engine(run: Run, name: str, params, cfg, slots: int,
                  blocks: int):
    """One engine behind the front door, as tools/serve.py wires it."""
    from paddle_tpu.serving import (AdmissionConfig, HTTPFrontDoor,
                                    LLMEngine, ResilientEngine)

    sz = run.sizes
    t0 = time.perf_counter()
    eng = LLMEngine(
        params, cfg, max_slots=slots, block_size=sz.block,
        max_model_len=sz.max_len, num_blocks=blocks, decode_steps=1,
        admission=AdmissionConfig(max_queue=64), decode_kernel="auto",
        seed=run.seed)
    # the front door answers a step that raised with "error" streams and
    # one line in the flight recorder; keep the whole traceback for the log
    step = eng.step

    def recording_step():
        try:
            return step()
        except Exception:
            run.step_errors.append(traceback.format_exc())
            raise

    eng.step = recording_step
    front = HTTPFrontDoor(ResilientEngine(eng), host="127.0.0.1", port=0)
    _host, port = front.start()
    pool_bytes = sum(a.nbytes for a in eng.pools.values())
    run.log(f"serve:{name}", f"slots={slots} pool={blocks} blocks x "
            f"{sz.block} tokens ({pool_bytes} bytes) max_model_len="
            f"{sz.max_len} on port {port}; up in "
            f"{time.perf_counter() - t0:.1f}s")
    return eng, front


def _drive_engine(run: Run, name: str, front, cfg, rng,
                  expect_paths) -> None:
    """Requests over real sockets; check the streams and the counters."""
    phase = f"serve:{name}"
    sz = run.sizes
    port = front.port
    requests = _requests(run, rng, sz.n_requests)
    before = _counters(port)
    results: List[Optional[Dict]] = [None] * len(requests)

    def client(i):
        results[i] = _generate(port, *requests[i])

    # the first request alone pays the first compiles; the rest arrive
    # together, so slots fill, queue and refill
    t0 = time.perf_counter()
    client(0)
    warm_s = time.perf_counter() - t0
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(1, len(requests))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    burst_s = time.perf_counter() - t0
    run.check(phase, not any(t.is_alive() for t in threads),
              "every client returned")
    run.log(phase, f"first request (compiles included) {warm_s:.1f}s; "
            f"{len(threads)} concurrent requests {burst_s:.1f}s (their "
            "compiles included); prompt lengths "
            f"{[len(p) for p, _ in requests]}")
    if not front.ready:
        raise RuntimeError(f"{phase}: the step loop died:\n"
                           + "\n".join(run.step_errors))
    n_tokens = 0
    for (prompt, max_new), res in zip(requests, results):
        term = res and res["terminal"]
        good = (res is not None and res["status"] == 200
                and term is not None and term["reason"] == "finished"
                and len(term["tokens"]) == max_new
                and res["streamed"] == term["tokens"]
                and all(0 <= t < cfg.vocab_size for t in term["tokens"]))
        if not good:
            raise AssertionError(
                f"{phase}: request of {len(prompt)} prompt tokens, "
                f"{max_new} asked: {res}")
        n_tokens += max_new
    run.check(phase, True, f"{len(requests)} streams finished with the "
              f"asked number of tokens ({n_tokens}), all ids < vocab")

    after = _counters(port)
    moved = {k: v - before.get(k, 0.0) for k, v in after.items()
             if v != before.get(k, 0.0)}
    paths = {dict(labels)["path"]: int(v)
             for (metric, labels), v in moved.items()
             if metric == "serving_decode_kernel_total" and labels}
    run.log(phase, f"serving_decode_kernel_total moved by {paths}")
    run.check(phase, bool(paths) and set(paths) <= set(expect_paths),
              f"decode ran on {sorted(paths)}, the path auto takes here "
              f"({'/'.join(expect_paths)})")
    fell_back = {k: v for k, v in moved.items() if "fallback" in k[0]}
    run.check(phase, not fell_back,
              f"no fallback counter moved ({fell_back or 'none'})")


def _check_ragged_walk(run: Run, cfg, rng) -> None:
    """The ragged walk alone against the XLA gather oracle, at the shapes
    the benchmark's serving cells give it (16 slots; this model's heads,
    head dim and KV block; bf16 pools of two layers read at layer 1): a
    length mix that straddles every edge of the walk's chunks — empty
    slots, one token, a block more or less, a chunk more or less, the
    table's full width — beside lengths drawn at random."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.paged_attention import (
        PagedKVCache, _walk_chunk_blocks, paged_attention,
        ragged_paged_decode)

    phase = "serve:walk"
    sz = run.sizes
    n, bs, mb = 16, sz.block, sz.max_len // sz.block
    hkv, g, d = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, \
        cfg.head_dim
    c = _walk_chunk_blocks(bs, hkv, d, 2, mb)
    edges = [0, 1, bs - 1, bs, bs + 1, c * bs - 1, c * bs, c * bs + 1,
             mb * bs - 1, mb * bs, 0]
    lens = np.asarray(
        [min(x, mb * bs) for x in edges]
        + rng.integers(1, mb * bs, size=n - len(edges)).tolist(), np.int32)
    nb = n * mb + 1
    pool = lambda: jnp.asarray(
        rng.standard_normal((2, nb, bs, hkv, d), np.float32), jnp.bfloat16)
    kp, vp = pool(), pool()
    table = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(n, mb),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((n, hkv * g, d), np.float32),
                    jnp.bfloat16)
    got = np.asarray(ragged_paged_decode(
        q, PagedKVCache(kp, vp, table, jnp.asarray(lens)), layer=1),
        np.float32)
    want = np.asarray(paged_attention(
        q, PagedKVCache(kp[1], vp[1], table, jnp.asarray(lens))),
        np.float32)
    live = lens > 0
    err = float(np.abs(got - want)[live].max())
    run.log(phase, f"{n} slots x {hkv * g}/{hkv} heads x {d}, blocks of "
            f"{bs}, table {mb} wide, {c} blocks a chunk; lengths "
            f"{lens.tolist()}; max|walk - oracle| = {err:.4f}")
    run.check(phase, bool(np.isfinite(got).all()) and err <= 5e-2
              and bool((got[~live] == 0).all()),
              f"the walk agrees with the XLA gather oracle to 5e-2 at "
              f"every live slot ({err:.4f}) and emits 0 for the empty ones")


def _probe_logits(run: Run, params, cfg, rng) -> None:
    """The engine's own logits against the float32 reference, on the chip.

    The engine hands out tokens, not logits, so the smoke taps the one
    function every engine program samples through (``_sample_rows``) for
    the life of one small engine: the tap ships the logits the program
    sampled from to the host and changes nothing else."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import llama
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.serving import engine as engine_mod

    phase = "serve:logits"
    sz = run.sizes
    prompt = rng.integers(0, cfg.vocab_size, size=sz.probe_prompt).tolist()
    taps: List[np.ndarray] = []
    sample_rows = engine_mod._sample_rows

    def tapped(logits, *args, **kw):
        jax.debug.callback(lambda x: taps.append(np.asarray(x)), logits)
        return sample_rows(logits, *args, **kw)

    engine_mod._sample_rows = tapped
    try:
        eng = LLMEngine(params, cfg, max_slots=2, block_size=sz.block,
                        max_model_len=sz.max_len, num_blocks=2 * (
                            sz.max_len // sz.block), decode_kernel="auto",
                        seed=run.seed)
        rid = eng.add_request(prompt, max_new_tokens=2)
        toks = eng.run()[rid]
        jax.effects_barrier()
    finally:
        engine_mod._sample_rows = sample_rows
    del eng
    gc.collect()
    # one admission prefills as a batch of one; decode samples all slots
    prefill = [t for t in taps if t.shape[0] == 1]
    decode = [t for t in taps if t.shape[0] == 2]
    run.check(phase, len(toks) == 2 and prefill and decode,
              f"tapped {len(prefill)} prefill and {len(decode)} decode "
              f"sampling steps, tokens {toks}")
    got = np.stack([prefill[0][0], decode[0][0]])

    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32, use_flash=False,
                                  remat=False)
    tokens = jnp.asarray([prompt + toks[:1]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: llama.forward(p, t, ref_cfg)[0, -2:])(
            params, tokens)
    ref = np.asarray(ref)
    diff = np.abs(got - ref)
    rel = np.sqrt((diff ** 2).mean(axis=1) / (ref ** 2).mean(axis=1))
    for i, what in enumerate(("prefill (first token)",
                              "first decode step (second token)")):
        run.log(phase, f"{what}: max|engine - float32| = {diff[i].max():.4f}"
                f", rms ratio = {rel[i]:.4f}, logits rms = "
                f"{np.sqrt((ref[i] ** 2).mean()):.3f}; engine sampled "
                f"{toks[i]}, float32 argmax {int(ref[i].argmax())}")
    run.check(phase, float(diff.max()) <= LOGIT_TOL
              and float(rel.max()) <= LOGIT_REL_RMS_TOL,
              f"logits within the bf16 tolerance (max abs <= {LOGIT_TOL}, "
              f"rms ratio <= {LOGIT_REL_RMS_TOL})")
    run.check(phase, [int(g.argmax()) for g in got] == toks,
              "the tapped logits are the ones the tokens were sampled from")


def serve_phase(run: Run) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.observability as obs
    from paddle_tpu.models import llama

    phase = "serve"
    sz = run.sizes
    cfg = dataclasses.replace(sz.model, num_layers=sz.serve_layers,
                              max_seq_len=sz.max_len, remat=False)
    obs.enable()          # the path counters are no-ops until enabled
    t0 = time.perf_counter()
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), llama.init_params(cfg, k)))(
            jax.random.PRNGKey(run.seed))
    jax.block_until_ready(params)
    run.log(phase, f"depth={cfg.num_layers} params="
            f"{llama.num_params(params)} bf16 "
            f"({sum(a.nbytes for a in jax.tree_util.tree_leaves(params))} "
            f"bytes), shared by both engines; init "
            f"{time.perf_counter() - t0:.1f}s")
    rng = np.random.default_rng(run.seed)
    expect = ("ragged",) if run.on_chip else ("bucketed", "dense")
    engines = []
    try:
        # both engines resident, as two replicas of one deployment are:
        # weights once, two KV pools; driven one after the other so the
        # process-wide counters say which engine moved them
        try:
            for name, (slots, blocks) in (("wide", sz.wide),
                                          ("narrow", sz.narrow)):
                engines.append((name, *_start_engine(
                    run, name, params, cfg, slots, blocks)))
            for name, _eng, front in engines:
                _drive_engine(run, name, front, cfg, rng, expect)
        finally:
            for _name, _eng, front in engines:
                front.begin_drain()
                front.wait_drained(60)
                front.stop()
        for name, eng, _front in engines:
            ledger = eng.block_accounting()
            run.check(f"serve:{name}",
                      ledger["free"] + ledger["cached"] == ledger["total"]
                      and ledger["backed"] == 0,
                      f"block ledger balanced after the drain: {ledger}")
        run.memory(phase)
        del engines, eng, front
        gc.collect()
        _check_ragged_walk(run, cfg, rng)
        _probe_logits(run, params, cfg, rng)
    finally:
        obs.disable()
    del params
    gc.collect()


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------
def sharded_train_phase(run: Run) -> None:
    import numpy as np

    phase = "train:compare"
    one = train_phase(run, (1, 1), "train:1chip")
    four = train_phase(run, (2, 2), "train:dp2xtp2")
    rel = [abs(a - b) / abs(a) for a, b in zip(one, four)]
    run.log(phase, "one chip  " + " ".join(f"{v:.4f}" for v in one))
    run.log(phase, "dp2 x tp2 " + " ".join(f"{v:.4f}" for v in four))
    run.log(phase, "relative  " + " ".join(f"{v:.1e}" for v in rel))
    k = SHARDED_STEPS_COMPARED
    run.check(phase, bool(np.isfinite(rel).all())
              and rel[0] <= SHARDED_FIRST_LOSS_RTOL
              and max(rel[:k]) <= SHARDED_LOSS_RTOL,
              f"loss per step agrees: {rel[0]:.1e} at the first loss (<= "
              f"{SHARDED_FIRST_LOSS_RTOL}), {max(rel[:k]):.1e} at worst "
              f"over the first {k} (<= {SHARDED_LOSS_RTOL}); "
              f"{max(rel):.1e} over all {len(rel)}")


def tp_serve_phase(run: Run) -> None:
    """The tp-sharded ragged engine against the unsharded one: float32
    weights at highest matmul precision, so sharding changes only the
    order of float32 sums and greedy streams are identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models import llama
    from paddle_tpu.serving import LLMEngine

    phase = "serve:tp2"
    sz = run.sizes
    cfg = dataclasses.replace(sz.model, num_layers=sz.tp_layers,
                              max_seq_len=sz.max_len, remat=False,
                              dtype=jnp.float32)
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        params = jax.jit(lambda k: llama.init_params(cfg, k))(
            jax.random.PRNGKey(run.seed))
        rng = np.random.default_rng(run.seed)
        requests = _requests(run, rng, sz.tp_requests)
        run.log(phase, f"depth={cfg.num_layers} float32 params="
                f"{llama.num_params(params)}; prompt lengths "
                f"{[len(p) for p, _ in requests]}")
        streams = {}
        for name, mesh in (("unsharded", None),
                           ("tp2", Mesh(np.asarray(jax.devices()[:2]),
                                        ("tp",)))):
            t0 = time.perf_counter()
            eng = LLMEngine(params, cfg, max_slots=4, block_size=sz.block,
                            max_model_len=sz.max_len, decode_kernel="ragged",
                            mesh=mesh, seed=run.seed)
            ids = [eng.add_request(p, max_new_tokens=m) for p, m in requests]
            out = eng.run()
            streams[name] = [out[i] for i in ids]
            run.log(phase, f"{name}: {sum(map(len, streams[name]))} tokens "
                    f"in {time.perf_counter() - t0:.1f}s (compiles "
                    "included)")
            run.check(phase, all(len(s) == m for s, (_p, m)
                                 in zip(streams[name], requests)),
                      f"{name}: every request got the asked number of "
                      "tokens")
            if mesh is not None:
                _check_spread(run, phase, (eng.params["layers"], eng.pools),
                              list(mesh.devices.flat))
                run.memory(phase, list(mesh.devices.flat))
            del eng
            gc.collect()
        run.check(phase, streams["tp2"] == streams["unsharded"],
                  "tp=2 streams are identical to the unsharded engine's")
    finally:
        jax.config.update("jax_default_matmul_precision", None)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the paths that exist across chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    result: Dict[str, Any] = {"ok": False}
    try:
        # look at the device before building anything
        import jax

        devices = jax.devices()
        result["device"] = {"platform": devices[0].platform,
                            "kind": devices[0].device_kind,
                            "count": len(devices)}
        if devices[0].platform != "tpu":
            raise RuntimeError(
                f"no TPU: JAX found {devices[0].platform} "
                f"({devices[0].device_kind}); this script proves the chip "
                "path and never passes without one")
        if len(devices) < args.chips:
            raise RuntimeError(
                f"--chips {args.chips} on {len(devices)} device(s)")
        sys.path.insert(0, HERE)
        import paddle_tpu  # noqa: F401  (places the compile cache)

        cache = jax.config.jax_compilation_cache_dir
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        print(f"[start] jax {jax.__version__}, {len(devices)} x "
              f"{devices[0].device_kind}; compile cache at {cache} "
              f"({entries} entries at start); seed {args.seed}", flush=True)
        run = Run(full_sizes(), args.seed)
        t0 = time.perf_counter()
        if args.chips == 4:
            sharded_train_phase(run)
            tp_serve_phase(run)
        else:
            train_phase(run)
            serve_phase(run)
        print(f"[done] all phases passed in {time.perf_counter() - t0:.0f}s",
              flush=True)
        result["ok"] = True
    except Exception as e:           # the last line must still be printed
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:500]
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
