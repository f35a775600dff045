"""A training cell: the trainer's fused step on a (dp, tp) mesh.

The system under test is the ``train_step`` of the program's module that
the configuration's family names (``trainer``), with the program's own
shardings and optimizer state, built as ``examples/llama_pretrain.py`` and
``chip_smoke.py`` build it: one compiled step, donated state, fed its own
output. The benchmark makes the initial weights from the seed
(``benchmark/weights.py``, float32) directly onto the mesh, and feeds rows
made on the host from the seed by a prefetching thread. Set-up builds ONE
object (the compiled step and its state), drives it through its first three
steps with the window's own call and feed, and hands that same object to
the window.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List

import numpy as np

from . import traffic, weights
from .manifest import family_of
from .reference.train_ref import leaf_norms

FOLLOWED_STEPS = 2          # steps the reference follows


class Feed:
    """Batches in step order, made ahead by one thread and put on the mesh."""

    def __init__(self, seed, plan, vocab, sharding, depth: int = 2):
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.args = (seed, plan["batch"], plan["seq"], vocab)
        self.sharding = sharding
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        import jax

        seed, batch, seq, vocab = self.args
        step = 0
        while not self.stop.is_set():
            rows = traffic.train_batch(seed, step, batch, seq, vocab)
            item = jax.device_put(rows, self.sharding)
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def get(self):
        return self.q.get(timeout=120)

    def close(self):
        self.stop.set()
        self.thread.join(10)


def hyper(model: Dict) -> Dict:
    t = model["train"]
    return {k: t[k] for k in ("lr", "beta1", "beta2", "eps", "weight_decay",
                              "clip_norm")}


def build(model: Dict, plan: Dict, seed: int, devices, log) -> Dict:
    """State on the mesh from the seed, the step compiled ahead of time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.optimizer.functional import init_moments, moment_shardings

    t = model["train"]
    if (t["param_dtype"], t["moment_dtype"], t["compute_dtype"],
            t["optimizer"]) != ("float32", "float32", "bfloat16", "adamw"):
        raise ValueError("the training cell runs float32 AdamW state with "
                         "bf16 compute")
    dp, tp = t["mesh"]["dp"], t["mesh"]["tp"]
    mesh = Mesh(np.asarray(devices[:dp * tp]).reshape(dp, tp), ("dp", "tp"))
    fam = family_of(model)
    tr = fam.trainer(model)
    cfg = fam.program_config(model, max_seq_len=plan["seq"], remat=True,
                             remat_policy=t["remat_policy"],
                             loss_chunks=t["loss_chunks"])
    psh = tr.make_shardings(cfg, mesh, fsdp=t["fsdp"])
    rep = NamedSharding(mesh, P())

    def init(key):
        params = weights.make_params(model, key, jnp.float32)
        mu, nu = init_moments(params, "adamw", jnp.float32)
        return tr.TrainState(params, mu, nu, jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init, weights.seed_key(seed))
    mu_sh, nu_sh = moment_shardings(psh, shapes.params, "adamw")
    ssh = tr.TrainState(psh, mu_sh, nu_sh, rep)
    t0 = time.monotonic()
    state = jax.jit(init, out_shardings=ssh)(weights.seed_key(seed))
    jax.block_until_ready(state)
    log(f"state: {sum(a.nbytes for a in jax.tree_util.tree_leaves(state))} "
        f"bytes over {mesh.size} device(s) in {time.monotonic() - t0:.1f}s")
    rows = NamedSharding(mesh, P("dp", None))
    hp = hyper(model)
    with tr.activation_mesh(mesh):
        step = jax.jit(
            lambda s, tok: tr.train_step(
                s, tok, cfg, lr=hp["lr"], beta1=hp["beta1"],
                beta2=hp["beta2"], eps=hp["eps"], wd=hp["weight_decay"],
                clip_norm=hp["clip_norm"], optimizer="adamw"),
            donate_argnums=0, out_shardings=(ssh, rep))
        tok = jax.ShapeDtypeStruct((plan["batch"], plan["seq"] + 1),
                                   jnp.int32, sharding=rows)
        t0 = time.monotonic()
        compiled = step.lower(state, tok).compile()
    ma = compiled.memory_analysis()
    log(f"step compiled or loaded in {time.monotonic() - t0:.1f}s; "
        f"compiler's peak per device {ma.peak_memory_in_bytes} bytes, "
        f"{compiled.as_text().count('tpu_custom_call')} Mosaic calls")
    return {"state": state, "step": compiled, "mesh": mesh, "psh": psh,
            "rows": rows, "hp": hp,
            "compiler_peak_bytes": int(ma.peak_memory_in_bytes)}


def first_steps(cell: Dict, model: Dict, feed: Feed, seed: int, log) -> Dict:
    """The first three steps through the window's own call and feed, with
    what the ``correct`` check reads taken on the way: each loss, the first
    gradient's norms as the optimizer got them (from mu after one step,
    mu = (1 - beta1) * g), the parameters' change after two steps."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(leaf_norms)
    out: Dict = {"losses": []}
    for i in range(3):
        cell["state"], loss = cell["step"](cell["state"], feed.get())
        out["losses"].append(float(np.asarray(loss)))
        if i == 0:
            out["grad_norms"] = {
                k: np.asarray(v) / (1.0 - cell["hp"]["beta1"])
                for k, v in norms(cell["state"].mu).items()}
        if i == FOLLOWED_STEPS - 1:
            p0 = jax.jit(lambda k: weights.make_params(model, k, jnp.float32),
                         out_shardings=cell["psh"])(weights.seed_key(seed))
            delta = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
                lambda x, y: x - y, a, b)))
            out["delta_norms"] = {
                k: np.asarray(v) for k, v in
                delta(cell["state"].params, p0).items()}
            del p0
    log("first losses " + " ".join(f"{v:.5f}" for v in out["losses"]))
    return out


def window(cell: Dict, feed: Feed, plan: Dict, seconds: float,
           trace_dir, trace_steps: int, log) -> Dict:
    """Steps for ``seconds``, each waited for with ``block_until_ready``."""
    import jax

    tokens_per_step = plan["batch"] * plan["seq"]
    step_s: List[float] = []
    wait_s: List[float] = []
    losses: List[float] = []
    traced = None
    t_open = time.monotonic()
    while True:
        t0 = time.monotonic()
        if t0 - t_open >= seconds:
            break
        n = len(step_s)
        if trace_dir and traced is None and n == 3:
            jax.profiler.start_trace(trace_dir)
            traced = n
        with jax.profiler.TraceAnnotation("bench.input_wait"):
            batch = feed.get()
        t1 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step"):
            cell["state"], loss = cell["step"](cell["state"], batch)
            jax.block_until_ready(loss)
        t2 = time.monotonic()
        wait_s.append(t1 - t0)
        step_s.append(t2 - t0)
        losses.append(loss)
        if traced is not None and n + 1 - traced == trace_steps:
            jax.profiler.stop_trace()
            traced = -1
    t_close = time.monotonic()
    if traced is not None and traced >= 0:
        jax.profiler.stop_trace()
    losses = [float(np.asarray(l)) for l in losses]
    log(f"window: {len(step_s)} steps in {t_close - t_open:.3f}s, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"steps": len(step_s), "window_s": t_close - t_open,
            "tokens": len(step_s) * tokens_per_step, "step_s": step_s,
            "wait_s": wait_s, "losses": losses,
            "trace_dir": trace_dir if traced == -1 else None}
