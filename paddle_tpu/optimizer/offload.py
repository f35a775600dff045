"""Host-offloaded training memory modes: fit a bigger model on one chip.

Parity: the reference's sharding-offload knobs — sharding stage-2/3
``offload`` (distributed/sharding/group_sharded.py: offload=True moves
optimizer state + master weights to CPU) and the fused-LAMB offload path
(incubate/distributed_fused_lamb). Those stream optimizer state over PCIe
around a CUDA update kernel.

TPU-native re-design over XLA memories (jax Device.addressable_memories):

* **Gradient offload** (``make_offload_train_step(offload_grads=True)``):
  the fwd+bwd program writes its gradient outputs to ``pinned_host``
  memory (jit ``out_shardings`` with a host memory kind) and the update
  phase walks the param tree LEAF BY LEAF (each leaf's grad device_put
  back h2d, updated, freed). Measured caveat (r3, v5e): XLA's buffer
  assignment still materializes the full grad tree in HBM before the d2h
  copy, so this mode reduces steady-state residency (grads don't occupy
  HBM between phases) but NOT the backward's peak — it did not fit 4B on
  16 GB alone.

* **Moment offload** (``offload_moments=True``): adamw's mu/nu live in
  pinned_host between steps and stream through the device per leaf inside
  the update. 16 bytes/param of optimizer state stops occupying HBM; the
  PCIe cost amortizes on big-HBM parts (v5p 8B-class) and is the direct
  analogue of the reference's ``offload=True``.

* **Layer-wise optimizer-in-backward**
  (``make_layerwise_train_step`` + ``init_layerwise_train_state``): the
  peak-memory fix that DOES fit ~4B on a 16 GB chip — no grad tree is
  ever formed; each layer's backward and update run in one donated
  program. See its docstring for the measured numbers.

All modes compose with optimizers in optimizer/functional.py; math is
identical to the fused path (tests assert step equivalence).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .functional import (adafactor_update, adamw_update, init_moments)

__all__ = ["host_put", "device_put_leaf", "make_offload_train_step",
           "make_layerwise_train_step", "init_offload_train_state",
           "StreamTrainState", "init_streaming_train_state",
           "make_streaming_train_step", "streaming_state_from_layerwise",
           "layerwise_state_from_streaming",
           "init_streaming_moe_train_state", "make_streaming_moe_train_step",
           "supports_host_memory", "supports_compiled_host_memory"]

_f32 = jnp.float32


def supports_host_memory(dev=None) -> bool:
    dev = dev or jax.devices()[0]
    try:
        return "pinned_host" in {m.kind for m in dev.addressable_memories()}
    except Exception:
        return False


@functools.lru_cache(maxsize=1)
def supports_compiled_host_memory() -> bool:
    """True when COMPILED programs can read/write pinned_host (TPU yes;
    the CPU backend advertises the memory space but lacks the
    annotate_device_placement lowering, so offload degrades to device
    memory there — same two-phase structure, no host staging)."""
    dev = jax.devices()[0]
    if not supports_host_memory(dev):
        return False
    try:
        sh = _kind_sharding(dev, "pinned_host")
        out = jax.jit(lambda: jnp.zeros((2,)), out_shardings=sh)()
        jax.jit(lambda x: jax.device_put(x, _kind_sharding(dev, "device"))
                + 1)(out)
        return True
    except Exception:
        return False


def _kind_sharding(dev, kind: str):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(dev, memory_kind=kind)


def host_put(tree, dev=None):
    """Move a pytree to pinned host memory (no-op values stay usable as
    inputs to jitted programs; XLA inserts the h2d streams)."""
    dev = dev or jax.devices()[0]
    sh = _kind_sharding(dev, "pinned_host")
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def device_put_leaf(x, dev=None):
    dev = dev or jax.devices()[0]
    return jax.device_put(x, _kind_sharding(dev, "device"))


def init_offload_train_state(module, config, key, optimizer: str = "adamw",
                             moment_dtype=jnp.float32,
                             param_dtype=jnp.float32,
                             offload_moments: bool = True):
    """``module.init_train_state`` with the moment trees parked in pinned
    host memory."""
    # jitted init: the f32 master intermediates are freed per-leaf inside
    # the program, so a 4B bf16 init peaks at ~one f32 leaf, not the full
    # f32 tree (which alone would fill a 16 GB chip)
    state = jax.jit(lambda k: module.init_train_state(
        config, k, optimizer=optimizer, moment_dtype=moment_dtype,
        param_dtype=param_dtype))(key)
    if offload_moments and supports_compiled_host_memory():
        state.mu = host_put(state.mu)
        state.nu = host_put(state.nu)
    return state


def make_offload_train_step(module, config, optimizer: str = "adamw",
                            lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8,
                            wd=0.1, clip_norm=1.0, loss_function=None,
                            offload_grads: bool = True,
                            offload_moments: bool = False,
                            adafactor_clip=1.0):
    """Build a two-phase host-offloaded train step for ``module`` (a model
    module exposing ``loss_fn(params, tokens, config)`` — llama/moe/bert).

    Returns ``step(state, tokens) -> (state, loss)`` semantically identical
    to ``module.train_step`` (same clip + update math), with gradients
    and/or optimizer moments staged through pinned host memory.
    """
    dev = jax.devices()[0]
    have_host = supports_compiled_host_memory()
    use_host = have_host and offload_grads
    host_sh = _kind_sharding(dev, "pinned_host") if have_host else None
    dev_sh = _kind_sharding(dev, "device")
    lf = loss_function or module.loss_fn

    # ---- phase A: fwd+bwd; grads stream out to host ----------------------
    def _grads(params, tokens):
        loss, grads = jax.value_and_grad(lf)(params, tokens, config)
        gsq = sum(jnp.sum(jnp.square(g.astype(_f32)))
                  for g in jax.tree_util.tree_leaves(grads))
        return loss, gsq, grads

    grads_jit = None  # built lazily: out_shardings needs the grad structure

    # ---- phase B: per-leaf update (one compiled fn per leaf shape) -------
    @functools.partial(jax.jit, static_argnames=("ghost", "mhost"),
                       donate_argnums=(0,))
    def _leaf_adamw(p, g, m, n, scale, bc1, bc2, *, ghost, mhost):
        if ghost:
            g = jax.device_put(g, dev_sh)
        if mhost:
            m = jax.device_put(m, dev_sh)
            n = jax.device_put(n, dev_sh)
        return adamw_update(p, g, m, n, lr=lr, beta1=beta1, beta2=beta2,
                            eps=eps, wd=wd, scale=scale, bc1=bc1, bc2=bc2)

    @functools.partial(jax.jit, static_argnames=("ghost",),
                       donate_argnums=(0,))
    def _leaf_adafactor(p, g, nu, scale, beta2t, *, ghost):
        if ghost:
            g = jax.device_put(g, dev_sh)
        return adafactor_update(p, g, nu, lr=lr, beta2t=beta2t, eps1=1e-30,
                                eps2=1e-3, clip=adafactor_clip, wd=wd,
                                scale=scale)

    def _is_host(x) -> bool:
        return getattr(x.sharding, "memory_kind", None) == "pinned_host"

    def step(state, tokens):
        nonlocal grads_jit
        params = state.params
        if grads_jit is None:
            if use_host:
                out_tree = jax.eval_shape(_grads, params, tokens)
                grad_sh = jax.tree_util.tree_map(lambda _: host_sh,
                                                 out_tree[2])
                grads_jit = jax.jit(
                    _grads, out_shardings=(dev_sh, dev_sh, grad_sh))
            else:
                grads_jit = jax.jit(_grads)
        loss, gsq, grads = grads_jit(params, tokens)
        gnorm = jnp.sqrt(gsq)
        scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))

        t = (state.step + 1).astype(_f32)
        treedef = jax.tree_util.tree_structure(params)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_g = jax.tree_util.tree_leaves(grads)

        if optimizer == "adamw":
            bc1 = 1.0 - beta1 ** t
            bc2 = 1.0 - beta2 ** t
            flat_m = jax.tree_util.tree_leaves(state.mu)
            flat_n = jax.tree_util.tree_leaves(state.nu)
            outs = []
            for p, g, m, n in zip(flat_p, flat_g, flat_m, flat_n):
                mhost = _is_host(m)
                np_, nm, nn = _leaf_adamw(p, g, m, n, scale, bc1, bc2,
                                          ghost=_is_host(g), mhost=mhost)
                if mhost:   # moments go back to their home memory
                    nm, nn = host_put(nm, dev), host_put(nn, dev)
                outs.append((np_, nm, nn))
            unflat = lambda i: jax.tree_util.tree_unflatten(
                treedef, [o[i] for o in outs])
            new_state = module.TrainState(unflat(0), unflat(1), unflat(2),
                                          state.step + 1)
            return new_state, loss
        if optimizer == "adafactor":
            beta2t = 1.0 - t ** -0.8
            flat_nu = treedef.flatten_up_to(state.nu)
            new_p, new_nu = [], []
            for p, g, nu in zip(flat_p, flat_g, flat_nu):
                np_, nnu = _leaf_adafactor(p, g, nu, scale, beta2t,
                                           ghost=_is_host(g))
                new_p.append(np_)
                new_nu.append(nnu)
            new_state = module.TrainState(
                jax.tree_util.tree_unflatten(treedef, new_p), state.mu,
                jax.tree_util.tree_unflatten(treedef, new_nu),
                state.step + 1)
            return new_state, loss
        raise ValueError(f"unknown optimizer {optimizer!r}")

    return step


# ---------------------------------------------------------------------------
# layer-wise optimizer-in-backward (the ~4B-on-16GB enabler)
# ---------------------------------------------------------------------------
def _build_head_tail(c, fac):
    """Compiled head-gradient and embed/norm/head-update programs shared by
    the layerwise and streaming steps (identical math in both)."""
    from ..models import llama as _llama

    dt = c.dtype

    def head_loss(x_final, fn_w, head, targets):
        xn = _llama._rms_norm(x_final, fn_w, c.rms_eps)
        B, S, _ = xn.shape
        if c.loss_chunks > 1:
            total = _llama._chunked_ce_sum(xn, targets, head.astype(dt),
                                           c.loss_chunks)
        else:
            logits = (xn @ head.astype(dt)).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, targets[..., None],
                                       axis=-1)[..., 0]
            total = jnp.sum(logz - gold)
        return total / (B * S)

    @jax.jit
    def head_grads(x_final, fn_w, head, targets):
        loss, grads = jax.value_and_grad(
            head_loss, argnums=(0, 1, 2))(x_final, fn_w, head, targets)
        return loss, grads          # (dx_final, d_final_norm, d_head)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def tail_update(embed, fn_w, head, nu_e, nu_f, nu_h, tokens_in, dx0,
                    dfn, dhead, beta2t):
        d_embed = jnp.zeros(embed.shape, jnp.float32).at[tokens_in].add(
            dx0.astype(jnp.float32))
        new_e, nnu_e = fac(embed, d_embed, nu_e, beta2t)
        new_f, nnu_f = fac(fn_w, dfn, nu_f, beta2t)
        new_h, nnu_h = fac(head, dhead, nu_h, beta2t)
        return new_e, new_f, new_h, nnu_e, nnu_f, nnu_h

    return head_grads, tail_update

def init_layerwise_train_state(config, key, param_dtype=jnp.bfloat16):
    """Train state for :func:`make_layerwise_train_step`.

    The layers subtree's adafactor second moments use PER-LAYER semantics:
    a stacked matmul weight [L, K, N] factors over (K, N) with the stack
    dim kept (identical to the fused path), but a stacked norm weight
    [L, h] keeps a FULL per-layer second moment {"v": [L, h]} — the fused
    path would factor the L×h matrix across layers, which has no per-layer
    meaning when layers update independently."""
    from ..models import llama as _llama

    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda p: p.astype(param_dtype),
        _llama.init_params(config, k)))(key)

    def nu_layers_like(p):
        if p.ndim - 1 >= 2:     # [L, K, N, ...]: factor trailing two dims
            return {"vr": jnp.zeros(p.shape[:-1], _f32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], _f32)}
        return {"v": jnp.zeros(p.shape, _f32)}   # [L, h] norms: full

    nu = {k: (jax.tree_util.tree_map(nu_layers_like, v) if k == "layers"
              else jax.tree_util.tree_map(_nu_like_perlayer, v))
          for k, v in params.items()}
    mu = jax.tree_util.tree_map(lambda p: jnp.zeros((), _f32), params)
    return _llama.TrainState(params, mu, nu, jnp.zeros((), jnp.int32))


def make_layerwise_train_step(config, optimizer: str = "adafactor",
                              lr=3e-4, wd=0.1, adafactor_clip=1.0):
    """Optimizer-in-backward at LAYER granularity for llama-family configs.

    The fused train step's peak HBM is params + the FULL gradient tree
    (bf16 4B: 8 GB + 8 GB — measured 17.25 GB on a 15.75 GB v5e, OOM, and
    gradient out_shardings to pinned_host does not help: XLA materializes
    the grad tree on device before the d2h copy). This step never forms
    that tree. It runs forward once (saving each layer's input, ~60 MB per
    layer), takes the loss/head gradients, then walks the layers in
    REVERSE: one compiled program re-runs layer l's forward, takes its vjp,
    applies the adafactor update to that layer's weights in place (donated
    buffers), and passes the input-cotangent down. A layer's gradients
    (~0.3 GB at 4B) exist only inside its own program invocation.

    Device peak: params + per-layer working set + saved inputs ≈ 10-11 GB
    at 4B — the measured difference between OOM and training.

    Parity analogue: the reference's sharding offload / fused-LAMB offload
    free optimizer+grad HBM by staging through CPU; this achieves the same
    residency bound by scheduling (optimizer-in-backward), which on TPU is
    the cheaper currency (no PCIe round-trip at all).

    Global-norm clipping is not available (it needs the full grad tree by
    definition); adafactor's per-tensor update-RMS clip is the stabilizer,
    as in the Adafactor paper. Tied embeddings are not supported.
    Returns ``step(state, tokens) -> (state, loss)``.
    """
    from ..models import llama as _llama

    c = config
    if optimizer != "adafactor":
        raise NotImplementedError(
            "layerwise step supports adafactor (the no-first-moment "
            "optimizer is what makes per-layer in-place updates free)")
    if c.tie_embeddings:
        raise NotImplementedError("layerwise step: untied embeddings only")
    if getattr(c, "pipeline_microbatches", 0):
        raise NotImplementedError("layerwise step is a single-chip memory "
                                  "mode; use pipeline schedules on meshes")
    dt = c.dtype

    @jax.jit
    def fwd_collect(layers, embed, tokens):
        x = embed.astype(dt)[tokens]
        cos, sin = _llama._rope_tables(tokens.shape[1], c.head_dim,
                                       c.rope_theta)

        def scan_fn(carry, lp):
            return _llama._layer_body(carry, lp, cos, sin, c), carry

        x_final, xs = jax.lax.scan(scan_fn, x, layers)
        return x_final, xs          # xs[l] = layer l's INPUT

    def _fac(p, g, nu, beta2t):
        return adafactor_update(p, g, nu, lr=lr, beta2t=beta2t, eps1=1e-30,
                                eps2=1e-3, clip=adafactor_clip, wd=wd,
                                scale=1.0)

    head_grads, tail_update = _build_head_tail(c, _fac)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def layers_backward(layers, nu_layers, xs, cot, beta2t):
        """Reverse layer walk as ONE compiled program (a lax.scan over the
        layer index). A python-loop-of-jits variant has the same residency
        but pays a host dispatch round-trip per layer. The scan body
        still materializes only one layer's gradients at a time (donated
        carries update layers/nu in place via dynamic-update-slice)."""
        cos, sin = _llama._rope_tables(xs.shape[2], c.head_dim,
                                       c.rope_theta)

        def body(carry, l):
            layers, nu_layers, dx = carry
            x_in = xs[l]
            lp = jax.tree_util.tree_map(lambda a: a[l], layers)
            nu_l = jax.tree_util.tree_map(lambda a: a[l], nu_layers)

            def run(lp_, xi):
                return _llama._layer_body(xi, lp_, cos, sin, c)

            _, vjp = jax.vjp(run, lp, x_in)
            dlp, dx = vjp(dx)
            new_lp, new_nu = {}, {}
            for k in lp:
                new_lp[k], new_nu[k] = _fac(lp[k], dlp[k], nu_l[k], beta2t)
            layers = jax.tree_util.tree_map(
                lambda big, new: big.at[l].set(new), layers, new_lp)
            nu_layers = jax.tree_util.tree_map(
                lambda big, new: big.at[l].set(new), nu_layers, new_nu)
            return (layers, nu_layers, dx), None

        (layers, nu_layers, dx), _ = jax.lax.scan(
            body, (layers, nu_layers, cot),
            jnp.arange(c.num_layers - 1, -1, -1))
        return layers, nu_layers, dx

    def step(state, tokens):
        params = state.params
        layers = params["layers"]
        nu = state.nu
        nu_layers = nu["layers"]
        t = (state.step + 1).astype(_f32)
        beta2t = 1.0 - t ** -0.8
        inp = tokens[:, :-1]
        tgt = tokens[:, 1:]

        x_final, xs = fwd_collect(layers, params["embed"], inp)
        loss, (dx, dfn, dhead) = head_grads(x_final, params["final_norm"],
                                            params["lm_head"], tgt)
        layers, nu_layers, dx = layers_backward(layers, nu_layers, xs, dx,
                                                beta2t)
        new_e, new_f, new_h, nnu_e, nnu_f, nnu_h = tail_update(
            params["embed"], params["final_norm"], params["lm_head"],
            nu["embed"], nu["final_norm"], nu["lm_head"], inp, dx, dfn,
            dhead, beta2t)
        new_params = {"layers": layers, "embed": new_e,
                      "final_norm": new_f, "lm_head": new_h}
        new_nu = {"layers": nu_layers, "embed": nnu_e,
                  "final_norm": nnu_f, "lm_head": nnu_h}
        from ..models.llama import TrainState
        return TrainState(new_params, state.mu, new_nu,
                          state.step + 1), loss

    return step


# ---------------------------------------------------------------------------
# host-streamed layer-wise step (the 8B-on-16GB enabler)
# ---------------------------------------------------------------------------
class StreamTrainState:
    """Train state for :func:`make_streaming_train_step`.

    ``layers``/``nu_layers`` are *lists* of per-layer pytrees parked in
    ``pinned_host`` memory (device memory on backends without a host
    space); ``embed``/``final_norm``/``lm_head`` and their second moments
    stay in HBM. ``step`` is a host int — the step loop is host-driven, so
    a device scalar would only add dispatches.
    """

    def __init__(self, layers, nu_layers, embed, final_norm, lm_head,
                 nu_embed, nu_fn, nu_head, step: int = 0):
        self.layers = layers
        self.nu_layers = nu_layers
        self.embed = embed
        self.final_norm = final_norm
        self.lm_head = lm_head
        self.nu_embed = nu_embed
        self.nu_fn = nu_fn
        self.nu_head = nu_head
        self.step = int(step)


def _make_fetch_park(dev, to_host):
    """The streaming steps' h2d/d2h movers (shared by the llama and MoE
    variants — one place for transfer-path fixes)."""
    dev_sh = _kind_sharding(dev, "device")

    def fetch(tree):
        if not to_host:
            return tree
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, dev_sh), tree)

    def park(tree):
        return host_put(tree, dev) if to_host else tree

    return fetch, park


def _nu_like_perlayer(p):
    """Per-layer adafactor second-moment slot (factored for matrices)."""
    if p.ndim >= 2:
        return {"vr": jnp.zeros(p.shape[:-1], _f32),
                "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], _f32)}
    return {"v": jnp.zeros(p.shape, _f32)}


def init_streaming_train_state(config, key, param_dtype=jnp.bfloat16):
    """Init an 8B-class model without ever holding the full parameter set
    in HBM: each layer is initialised on device by one (reused) compiled
    program and immediately streamed to pinned host memory."""
    import math

    from ..models import llama as _llama  # noqa: F401  (config family)

    c = config
    h, f, L = c.hidden_size, c.intermediate_size, c.num_layers
    nq, nkv, d = c.num_heads, c.num_kv_heads, c.head_dim
    s = 1.0 / math.sqrt(h)
    dev = jax.devices()[0]
    to_host = supports_compiled_host_memory()

    @jax.jit
    def init_layer(k):
        ks = jax.random.split(k, 7)

        def g(kk, shape, scale):
            return (jax.random.normal(kk, shape, jnp.float32)
                    * scale).astype(param_dtype)

        return {
            "attn_norm": jnp.ones((h,), param_dtype),
            "wq": g(ks[0], (h, nq * d), s),
            "wk": g(ks[1], (h, nkv * d), s),
            "wv": g(ks[2], (h, nkv * d), s),
            "wo": g(ks[3], (nq * d, h), s / math.sqrt(2 * L)),
            "mlp_norm": jnp.ones((h,), param_dtype),
            "w_gate": g(ks[4], (h, f), s),
            "w_up": g(ks[5], (h, f), s),
            "w_down": g(ks[6], (f, h), 1.0 / math.sqrt(f) / math.sqrt(2 * L)),
        }

    keys = jax.random.split(key, L + 2)
    layers, nu_layers = [], []
    for l in range(L):
        lp = init_layer(keys[l])
        nu_layers.append(jax.tree_util.tree_map(_nu_like_perlayer, lp))
        layers.append(host_put(lp, dev) if to_host else lp)

    @jax.jit
    def init_tail(ke, kh):
        embed = (jax.random.normal(ke, (c.vocab_size, h), jnp.float32)
                 * (1.0 / math.sqrt(h))).astype(param_dtype)
        head = (jax.random.normal(kh, (h, c.vocab_size), jnp.float32)
                * s).astype(param_dtype)
        return embed, jnp.ones((h,), param_dtype), head

    if c.tie_embeddings:
        raise NotImplementedError("streaming step: untied embeddings only")
    embed, fn_w, head = init_tail(keys[L], keys[L + 1])
    return StreamTrainState(
        layers, nu_layers, embed, fn_w, head,
        _nu_like_perlayer(embed), _nu_like_perlayer(fn_w),
        _nu_like_perlayer(head), 0)


def streaming_state_from_layerwise(state, to_host: Optional[bool] = None):
    """Slice a stacked layerwise TrainState into a StreamTrainState (used
    by tests for step-equivalence and by checkpoint conversion). Needs the
    stacked tree addressable — fine on CPU/big-HBM hosts."""
    params, nu = state.params, state.nu
    L = params["layers"]["wq"].shape[0]
    to_host = (supports_compiled_host_memory()
               if to_host is None else to_host)
    dev = jax.devices()[0]
    layers, nu_layers = [], []
    for l in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        nl = jax.tree_util.tree_map(lambda a: a[l], nu["layers"])
        layers.append(host_put(lp, dev) if to_host else lp)
        nu_layers.append(nl)
    return StreamTrainState(
        layers, nu_layers, params["embed"], params["final_norm"],
        params["lm_head"], nu["embed"], nu["final_norm"], nu["lm_head"],
        int(state.step))


def layerwise_state_from_streaming(state):
    """Re-stack a StreamTrainState into the layerwise TrainState layout
    (for checkpoint save via the existing stacked-tree paths)."""
    from ..models.llama import TrainState

    stack = lambda trees: jax.tree_util.tree_map(
        lambda *xs: jnp.stack([device_put_leaf(x) for x in xs]), *trees)
    layers = stack(state.layers)
    nu_layers = stack(state.nu_layers)
    params = {"layers": layers, "embed": state.embed,
              "final_norm": state.final_norm, "lm_head": state.lm_head}
    nu = {"layers": nu_layers, "embed": state.nu_embed,
          "final_norm": state.nu_fn, "lm_head": state.nu_head}
    mu = jax.tree_util.tree_map(lambda p: jnp.zeros((), _f32), params)
    return TrainState(params, mu, nu, jnp.asarray(state.step, jnp.int32))


def make_streaming_train_step(config, optimizer: str = "adafactor",
                              lr=3e-4, wd=0.1, adafactor_clip=1.0):
    """Layer-wise optimizer-in-backward with **host-streamed parameters**:
    trains a model whose parameters alone exceed HBM (Llama-3-8B bf16 =
    16 GB on a 16 GB chip).

    Mechanism — three compiled programs, a host-driven layer loop, and
    double-buffered PCIe transfers:

    * parameters live per-layer in ``pinned_host``; at any moment at most
      two layers (current + prefetched next) occupy HBM (~0.9 GB at 8B);
    * forward: while layer *l*'s (reused) compiled program runs, layer
      *l+1*'s weights are already streaming h2d — ``jax.device_put`` and
      dispatch are async, so the DMA rides under the matmuls. Only each
      layer's *input* (B·S·h bf16) is saved;
    * backward: one compiled program per layer (again reused) re-runs the
      layer forward, takes its vjp, and applies the adafactor update to
      the **donated** weight buffers; updated weights stream back d2h
      while layer *l-1* computes. A layer's gradients exist only inside
      its own program invocation — no gradient tree, ever.

    PCIe traffic is 3× params/step (fwd h2d + bwd h2d + updated d2h,
    ~48 GB at 8B) — amortized under compute at batch·seq ≥ 16k tokens.

    Parity: the reference's stage-3 ``offload=True`` sharding
    (distributed/sharding/group_sharded.py) and fused-LAMB offload stream
    params/optimizer state over PCIe around CUDA update kernels; this is
    the single-chip TPU equivalent, scheduled rather than sharded.
    Global-norm clipping is unavailable by construction (no full grad
    tree); adafactor's update-RMS clip is the stabilizer.
    Returns ``step(state, tokens) -> (state, loss)``.
    """
    from ..models import llama as _llama

    c = config
    if optimizer != "adafactor":
        raise NotImplementedError("streaming step supports adafactor")
    if c.tie_embeddings:
        raise NotImplementedError("streaming step: untied embeddings only")
    if getattr(c, "pipeline_microbatches", 0):
        raise NotImplementedError("streaming step is a single-chip memory "
                                  "mode; use pipeline schedules on meshes")
    dt = c.dtype
    dev = jax.devices()[0]
    to_host = supports_compiled_host_memory()

    def _fac(p, g, nu, beta2t):
        return adafactor_update(p, g, nu, lr=lr, beta2t=beta2t, eps1=1e-30,
                                eps2=1e-3, clip=adafactor_clip, wd=wd,
                                scale=1.0)

    head_grads, tail_update = _build_head_tail(c, _fac)
    _fetch, _park = _make_fetch_park(dev, to_host)

    @jax.jit
    def embed_fwd(embed, tokens):
        return embed.astype(dt)[tokens]

    @jax.jit
    def layer_fwd(x, lp):
        cos, sin = _llama._rope_tables(x.shape[1], c.head_dim, c.rope_theta)
        return _llama._layer_body(x, lp, cos, sin, c)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def layer_bwd_update(lp, nu_l, x_in, dx, beta2t):
        cos, sin = _llama._rope_tables(x_in.shape[1], c.head_dim,
                                       c.rope_theta)

        def run(lp_, xi):
            return _llama._layer_body(xi, lp_, cos, sin, c)

        _, vjp = jax.vjp(run, lp, x_in)
        dlp, dx_prev = vjp(dx)
        new_lp, new_nu = {}, {}
        for k in lp:
            new_lp[k], new_nu[k] = _fac(lp[k], dlp[k], nu_l[k], beta2t)
        return new_lp, new_nu, dx_prev

    def step(state: StreamTrainState, tokens):
        L = c.num_layers
        inp = tokens[:, :-1]
        tgt = tokens[:, 1:]
        beta2t = 1.0 - float(state.step + 1) ** -0.8

        # ---- forward: prefetch l+1 while l computes ---------------------
        xs = [None] * L
        x = embed_fwd(state.embed, inp)
        nxt = _fetch(state.layers[0])
        for l in range(L):
            cur, nxt = nxt, (_fetch(state.layers[l + 1])
                             if l + 1 < L else None)
            xs[l] = x
            x = layer_fwd(x, cur)
            cur = None      # drop the HBM copy as soon as dispatched

        loss, (dx, dfn, dhead) = head_grads(
            x, state.final_norm, state.lm_head, tgt)

        # ---- backward: reverse walk, update in place, stream back -------
        new_layers = list(state.layers)
        new_nu_layers = list(state.nu_layers)
        nxt = _fetch(state.layers[L - 1])
        for l in range(L - 1, -1, -1):
            cur, nxt = nxt, (_fetch(state.layers[l - 1]) if l > 0 else None)
            new_lp, new_nu, dx = layer_bwd_update(
                cur, state.nu_layers[l], xs[l], dx, beta2t)
            new_layers[l] = _park(new_lp)
            new_nu_layers[l] = new_nu
            xs[l] = None    # free the saved input

        new_e, new_f, new_h, nnu_e, nnu_f, nnu_h = tail_update(
            state.embed, state.final_norm, state.lm_head,
            state.nu_embed, state.nu_fn, state.nu_head, inp, dx, dfn,
            dhead, beta2t)
        return StreamTrainState(
            new_layers, new_nu_layers, new_e, new_f, new_h,
            nnu_e, nnu_f, nnu_h, state.step + 1), loss

    return step


# ---------------------------------------------------------------------------
# host-streamed MoE step (DeepSeekMoE-16B — BASELINE config 5 — on one chip)
# ---------------------------------------------------------------------------
def init_streaming_moe_train_state(config, key, param_dtype=jnp.bfloat16):
    """Streaming state for MoE configs: each layer (attention + router +
    stacked experts + shared experts, ~1.2 GB at DeepSeekMoE-16B) is
    initialised on device by one reused compiled program and parked in
    pinned host memory — the full 33 GB parameter set never exists in
    HBM."""
    import math

    c = config
    h, L, E = c.hidden_size, c.num_layers, c.num_experts
    nq, nkv, d = c.num_heads, c.num_kv_heads, c.head_dim
    fm = c.moe_intermediate_size
    fs = c.n_shared_experts * fm
    s = 1.0 / math.sqrt(h)
    o = s / math.sqrt(2 * L)
    dev = jax.devices()[0]
    to_host = supports_compiled_host_memory()

    @functools.partial(jax.jit, static_argnames=("dense",))
    def init_layer(k, *, dense):
        ks = jax.random.split(k, 12)

        def g(kk, shape, scale):
            return (jax.random.normal(kk, shape, jnp.float32)
                    * scale).astype(param_dtype)

        lp = {
            "attn_norm": jnp.ones((h,), param_dtype),
            "wq": g(ks[0], (h, nq * d), s),
            "wk": g(ks[1], (h, nkv * d), s),
            "wv": g(ks[2], (h, nkv * d), s),
            "wo": g(ks[3], (nq * d, h), o),
            "mlp_norm": jnp.ones((h,), param_dtype),
            "s_gate": g(ks[8], (h, fs), s),
            "s_up": g(ks[9], (h, fs), s),
            "s_down": g(ks[10], (fs, h), o),
        }
        if not dense:
            # dense (first_dense_layers) layers never touch the router or
            # experts — per-layer trees may simply omit them, saving their
            # init, pinned-host residency, and per-step PCIe round trip
            # (~2.2 GB/step at DeepSeekMoE-16B)
            lp.update({
                "router": g(ks[4], (h, E), s),
                "e_gate": g(ks[5], (E, h, fm), s),
                "e_up": g(ks[6], (E, h, fm), s),
                "e_down": g(ks[7], (E, fm, h), o / math.sqrt(fm / h)),
            })
        return lp

    keys = jax.random.split(key, L + 2)
    layers, nu_layers = [], []
    for l in range(L):
        lp = init_layer(keys[l], dense=l < c.first_dense_layers)
        nu_layers.append(jax.tree_util.tree_map(_nu_like_perlayer, lp))
        layers.append(host_put(lp, dev) if to_host else lp)

    @jax.jit
    def init_tail(ke, kh):
        embed = (jax.random.normal(ke, (c.vocab_size, h), jnp.float32)
                 * s).astype(param_dtype)
        head = (jax.random.normal(kh, (h, c.vocab_size), jnp.float32)
                * s).astype(param_dtype)
        return embed, jnp.ones((h,), param_dtype), head

    embed, fn_w, head = init_tail(keys[L], keys[L + 1])
    return StreamTrainState(
        layers, nu_layers, embed, fn_w, head,
        _nu_like_perlayer(embed), _nu_like_perlayer(fn_w),
        _nu_like_perlayer(head), 0)


def make_streaming_moe_train_step(config, optimizer: str = "adafactor",
                                  lr=3e-4, wd=0.1, adafactor_clip=1.0):
    """Host-streamed layerwise train step for MoE configs — trains
    DeepSeekMoE-16B (33 GB of bf16 params, BASELINE config 5) on one
    16 GB chip, the MoE twin of :func:`make_streaming_train_step`.

    Same mechanism (pinned_host residency, prefetch-next-layer, per-layer
    vjp + donated adafactor update, stream-back), plus the MoE-specific
    piece: the router aux loss. ``loss = CE + coef · Σ_l aux_l`` and each
    layer's aux contribution is LOCAL to that layer, so its gradient
    enters the per-layer vjp as a constant cotangent ``coef`` on the
    layer's aux output — no cross-layer aux state is ever needed.

    Parity: incubate/distributed/models/moe (the reference's MoE stack)
    has no single-device answer at this scale; the capability here is the
    scheduling trade (PCIe streaming) the reference buys with multi-GPU
    sharding. Returns ``step(state, tokens) -> (state, loss)``.
    """
    from ..models import moe as _moe

    c = config
    if optimizer != "adafactor":
        raise NotImplementedError("streaming step supports adafactor")
    if getattr(c, "context_parallel", False):
        raise NotImplementedError("streaming step is single-chip")
    dt = c.dtype
    dev = jax.devices()[0]
    to_host = supports_compiled_host_memory()
    coef = float(c.router_aux_coef)
    n_dense = c.first_dense_layers

    def _fac(p, g, nu, beta2t):
        return adafactor_update(p, g, nu, lr=lr, beta2t=beta2t, eps1=1e-30,
                                eps2=1e-3, clip=adafactor_clip, wd=wd,
                                scale=1.0)

    head_grads, tail_update = _build_head_tail(c, _fac)
    _fetch, _park = _make_fetch_park(dev, to_host)

    @jax.jit
    def embed_fwd(embed, tokens):
        return embed.astype(dt)[tokens]

    @functools.partial(jax.jit, static_argnames=("dense",))
    def layer_fwd(x, aux_sum, lp, *, dense):
        cos, sin = _moe._rope_tables(x.shape[1], c.head_dim, c.rope_theta)
        (xo, aux) = _moe._layer_body((x, jnp.zeros((), jnp.float32)), lp,
                                     cos, sin, c, 0, dense)
        return xo, aux_sum + aux

    @functools.partial(jax.jit, static_argnames=("dense",),
                       donate_argnums=(0, 1))
    def layer_bwd_update(lp, nu_l, x_in, dx, beta2t, *, dense):
        cos, sin = _moe._rope_tables(x_in.shape[1], c.head_dim,
                                     c.rope_theta)

        def run(lp_, xi):
            xo, aux = _moe._layer_body((xi, jnp.zeros((), jnp.float32)),
                                       lp_, cos, sin, c, 0, dense)
            return xo, aux

        _, vjp = jax.vjp(run, lp, x_in)
        # aux cotangent = coef: d(loss)/d(aux_l) for loss = ce + coef·Σaux
        dlp, dx_prev = vjp((dx, jnp.asarray(coef, jnp.float32)))
        new_lp, new_nu = {}, {}
        for k in lp:
            new_lp[k], new_nu[k] = _fac(lp[k], dlp[k], nu_l[k], beta2t)
        return new_lp, new_nu, dx_prev

    def step(state: StreamTrainState, tokens):
        L = c.num_layers
        inp = tokens[:, :-1]
        tgt = tokens[:, 1:]
        beta2t = 1.0 - float(state.step + 1) ** -0.8

        xs = [None] * L
        x = embed_fwd(state.embed, inp)
        aux_sum = jnp.zeros((), jnp.float32)
        nxt = _fetch(state.layers[0])
        for l in range(L):
            cur, nxt = nxt, (_fetch(state.layers[l + 1])
                             if l + 1 < L else None)
            xs[l] = x
            x, aux_sum = layer_fwd(x, aux_sum, cur, dense=l < n_dense)
            cur = None

        ce, (dx, dfn, dhead) = head_grads(
            x, state.final_norm, state.lm_head, tgt)

        new_layers = list(state.layers)
        new_nu_layers = list(state.nu_layers)
        nxt = _fetch(state.layers[L - 1])
        for l in range(L - 1, -1, -1):
            cur, nxt = nxt, (_fetch(state.layers[l - 1]) if l > 0 else None)
            new_lp, new_nu, dx = layer_bwd_update(
                cur, state.nu_layers[l], xs[l], dx, beta2t,
                dense=l < n_dense)
            new_layers[l] = _park(new_lp)
            new_nu_layers[l] = new_nu
            xs[l] = None

        new_e, new_f, new_h, nnu_e, nnu_f, nnu_h = tail_update(
            state.embed, state.final_norm, state.lm_head,
            state.nu_embed, state.nu_fn, state.nu_head, inp, dx, dfn,
            dhead, beta2t)
        loss = ce + coef * aux_sum
        return StreamTrainState(
            new_layers, new_nu_layers, new_e, new_f, new_h,
            nnu_e, nnu_f, nnu_h, state.step + 1), loss

    return step
