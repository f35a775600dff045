"""Llama model family — the flagship pretraining path, TPU-first.

Capability parity: the reference ships its auto-parallel Llama as the
hybrid-strategy e2e blueprint (reference:
test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py:35-50 —
per-layer dist.shard_tensor placements over a dp*mp*pp mesh; driver
semi_auto_llama.py), trained through fleet TP+PP (python/paddle/distributed/
fleet/layers/mpu/mp_layers.py ColumnParallelLinear:336/RowParallelLinear:543).

TPU-native re-design (NOT a translation):
- Pure functional: params are a pytree of jax.Arrays; the model is
  ``forward(params, tokens)``. The paddle-like eager Layer surface wraps this
  (see paddle_tpu.nn); the training hot path stays functional so one
  ``jax.jit`` compiles the whole step.
- Per-layer weights are STACKED on a leading layer axis and the decoder stack
  is a single ``lax.scan`` — one compiled layer body regardless of depth
  (compile time O(1) in num_layers), and the natural substrate for pipeline
  stages (slice the layer axis per stage).
- Parallelism is a sharding recipe, not parallel Layer classes:
  ``param_specs`` / ``act_spec`` map every weight and activation onto a
  ('dp','sp','tp') mesh; GSPMD inserts the collectives the reference's
  mp_ops.py (_c_identity/_mp_allreduce) issues by hand. fsdp (ZeRO-3) is the
  same recipe with the non-tp param axis sharded over 'dp'.
- Sequence parallelism (the reference's SEP axis, topology.py:199-260) is the
  'sp' mesh axis sharding the token axis of activations; attention gathers
  KV over 'sp' (Ulysses/ring handled in kernels/ — see kernels/ring_attention).
- bf16 compute / f32 params+optimizer by default (MXU-native), the analogue of
  the reference's AMP O2 master-weight scheme (python/paddle/amp/auto_cast.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import numerics as _numerics

__all__ = [
    "LlamaConfig", "llama3_8b", "tiny_llama", "draft_config",
    "init_params", "forward",
    "loss_fn", "param_specs", "make_shardings", "make_serving_shardings",
    "num_params",
    "TrainState", "init_train_state", "train_step", "make_mesh",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    # compute dtype (MXU-native); params/optimizer stay f32 master
    dtype: Any = jnp.bfloat16
    # gradient checkpointing of the layer body (reference: fleet/recompute)
    remat: bool = True
    # remat policy: "full" recomputes everything; "dots" saves matmul
    # outputs (jax checkpoint_dots) — fewer recomputed MXU ops when HBM
    # allows; "attn" saves only the attention outputs (skips flash-kernel
    # recompute in backward — +10% at 2k seq on 740m, costs [B,S,h]/layer)
    # (reference analogue: recompute_granularity="core_attn")
    remat_policy: str = "full"
    use_flash: bool = True
    # exact blockwise ring attention over the 'sp' mesh axis (long-context;
    # capability the reference's SEP axis delegates to model code — §5.7)
    context_parallel: bool = False
    # >0 enables the compiled GPipe schedule over the 'pp' mesh axis
    # (distributed/pipeline.py); value = microbatches per step
    pipeline_microbatches: int = 0
    # >1 switches to the circular interleaved (VPP) schedule with this many
    # chunks per stage (requires num_layers % (pp * chunks) == 0)
    pipeline_chunks: int = 1
    # "gpipe" (fwd pipeline, XLA-derived bwd), "1f1b" (fused fwd+bwd with
    # O(pp) live activations — the reference's default hybrid schedule,
    # pipeline_parallel.py:684), or "zb" (ZeroBubble ZB-H1: backward split
    # into dgrad/wgrad slots that fill the bubbles —
    # pipeline_zero_bubble.py:62). 1f1b/zb apply to train_step only.
    pipeline_schedule: str = "gpipe"
    # >1 computes the training cross-entropy in sequence chunks under
    # jax.checkpoint, so the [B, S, vocab] f32 logits tensor is never
    # materialized (peak logits memory ÷ chunks for ~1% recomputed vocab
    # matmul FLOPs). The reference's fused_linear_param_grad_add /
    # parallel_cross_entropy serve the same memory goal on GPU.
    loss_chunks: int = 1

    def served_model(self):
        """This family behind the serving engine's model interface."""
        from .llama_served import LlamaServed

        return LlamaServed(self)


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def tiny_llama(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2,
               seq=128, ffn=128) -> LlamaConfig:
    return LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
        num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
        head_dim=hidden // heads, max_seq_len=seq, remat=False,
        use_flash=False)


def draft_config(target: LlamaConfig, *, num_layers: Optional[int] = None,
                 hidden_size: Optional[int] = None,
                 intermediate_size: Optional[int] = None,
                 num_heads: Optional[int] = None,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None) -> LlamaConfig:
    """A draft-model config compatible with ``target`` for speculative
    decoding (serving/engine.py r13): same vocabulary (the two models
    MUST share a tokenizer — the engine enforces it), same max context
    and compute dtype, with the capacity knobs shrunk. Defaults halve
    the depth and width — the classic ~1/8-cost draft; RoPE theta is
    inherited (a draft is free to differ, but keeping it makes a
    layer-sliced or distilled draft's positional geometry line up).

    >>> dcfg = llama.draft_config(cfg, num_layers=4)
    >>> eng = LLMEngine(params, cfg, draft_params=dp, draft_config=dcfg)
    """
    t = target
    hidden = hidden_size if hidden_size is not None else t.hidden_size // 2
    heads = num_heads if num_heads is not None else max(1, t.num_heads // 2)
    return dataclasses.replace(
        t,
        num_layers=(num_layers if num_layers is not None
                    else max(1, t.num_layers // 2)),
        hidden_size=hidden,
        intermediate_size=(intermediate_size if intermediate_size
                           is not None else t.intermediate_size // 2),
        num_heads=heads,
        num_kv_heads=(num_kv_heads if num_kv_heads is not None
                      else max(1, min(t.num_kv_heads, heads))),
        head_dim=(head_dim if head_dim is not None else hidden // heads),
    )


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _init(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale)


def init_params(config: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Stacked-layer parameter pytree (all f32 masters)."""
    c = config
    ks = jax.random.split(key, 10)
    h, f, L = c.hidden_size, c.intermediate_size, c.num_layers
    nq, nkv, d = c.num_heads, c.num_kv_heads, c.head_dim
    s = 1.0 / math.sqrt(h)
    params = {
        "embed": _init(ks[0], (c.vocab_size, h), 1.0 / math.sqrt(h)),
        "layers": {
            "attn_norm": jnp.ones((L, h), jnp.float32),
            "wq": _init(ks[1], (L, h, nq * d), s),
            "wk": _init(ks[2], (L, h, nkv * d), s),
            "wv": _init(ks[3], (L, h, nkv * d), s),
            "wo": _init(ks[4], (L, nq * d, h), s / math.sqrt(2 * L)),
            "mlp_norm": jnp.ones((L, h), jnp.float32),
            "w_gate": _init(ks[5], (L, h, f), s),
            "w_up": _init(ks[6], (L, h, f), s),
            "w_down": _init(ks[7], (L, f, h), 1.0 / math.sqrt(f) / math.sqrt(2 * L)),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    if not c.tie_embeddings:
        params["lm_head"] = _init(ks[8], (h, c.vocab_size), s)
    return params


def num_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))


# ---------------------------------------------------------------------------
# int8 weight-only quantization for the decode/serving path
# (parity: nn/quant/quantized_linear.py weight_only_linear over the cutlass
#  fpA_intB GEMMs — phi/kernels/fusion/cutlass_kernels/. TPU-native: weights
#  stay int8 in HBM; XLA fuses the convert+scale into the matmul read, so
#  bandwidth-bound decode moves half the bytes.)
# ---------------------------------------------------------------------------
_QUANT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})


def quantize_params(params, include_lm_head: bool = True):
    """Per-output-channel absmax int8 quantization of the matmul weights
    ([L, K, N] stacked leaves → {"q": int8 [L, K, N], "s": bf16 [L, N]}).
    Norms and the embedding stay full precision (gathers, not matmuls)."""
    def q(w):
        wf = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wf), axis=-2) / 127.0
        qv = jnp.clip(
            jnp.round(wf / jnp.maximum(scale[..., None, :], 1e-9)),
            -128, 127).astype(jnp.int8)
        return {"q": qv, "s": scale.astype(jnp.bfloat16)}

    out = dict(params)
    out["layers"] = {k: (q(v) if k in _QUANT_KEYS else v)
                     for k, v in params["layers"].items()}
    if include_lm_head and "lm_head" in params:
        out["lm_head"] = q(params["lm_head"])
    if _numerics.active():
        # paired pre/post-quant probe: the weight-only site's relative
        # error lands in numerics_quant_error{site="weight_only"} (the
        # scale rides axis -2 — one scale per output channel)
        pairs = [(params["layers"][k], out["layers"][k]["q"],
                  out["layers"][k]["s"], -2) for k in _QUANT_KEYS]
        if include_lm_head and "lm_head" in params:
            pairs.append((params["lm_head"], out["lm_head"]["q"],
                          out["lm_head"]["s"], -2))
        _numerics.record_quant_error("weight_only", pairs)
    return out


def _wmat(p, name, dt):
    """Weight leaf → dense matmul operand in ``dt``; dequantizes int8
    weight-only leaves inline (XLA fuses it into the matmul).

    NOTE: hot decode paths should prefer
    ``kernels.quant_matmul.weight_only_matmul`` (used below by
    ``forward_with_cache`` and by serving/engine.py), which feeds the
    int8 matrix to the dot UNCONVERTED and applies the per-channel scale
    to the output — this helper's explicit ``q * s`` epilogue can
    materialize a full-width dequantized copy when XLA declines to fuse
    it. Kept for cold paths (export tracing, debugging)."""
    w = p[name] if isinstance(name, str) else name
    if isinstance(w, dict) and "q" in w:
        return (w["q"].astype(jnp.float32)
                * w["s"].astype(jnp.float32)[..., None, :]).astype(dt)
    return w.astype(dt)


# ---------------------------------------------------------------------------
# sharding recipe  (mesh axes: 'dp' data, 'sp' sequence, 'tp' model)
# ---------------------------------------------------------------------------

def param_specs(config: LlamaConfig, fsdp: bool = True) -> Dict[str, Any]:
    """PartitionSpec per weight. 'tp' shards the Megatron axis (column for
    qkv/gate/up, row for wo/down, vocab for embed/lm_head); fsdp additionally
    shards the other matrix axis over 'dp' (ZeRO-3 — reference:
    DygraphShardingOptimizer V2, dygraph_sharding_optimizer.py:592)."""
    dp = "dp" if fsdp else None
    # leading (layer) axis shards over 'pp' when the mesh has one — the
    # pipeline schedule slices stages from it (dropped on pp-less meshes)
    specs = {
        "embed": P("tp", dp),
        "layers": {
            "attn_norm": P("pp", None),
            "wq": P("pp", dp, "tp"),
            "wk": P("pp", dp, "tp"),
            "wv": P("pp", dp, "tp"),
            "wo": P("pp", "tp", dp),
            "mlp_norm": P("pp", None),
            "w_gate": P("pp", dp, "tp"),
            "w_up": P("pp", dp, "tp"),
            "w_down": P("pp", "tp", dp),
        },
        "final_norm": P(None),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = P(dp, "tp")
    return specs


def act_spec() -> P:
    # activations: [batch, seq, hidden] — batch over dp, sequence over sp
    return P("dp", "sp", None)


def make_mesh(n_devices: Optional[int] = None,
              axes: Tuple[str, ...] = ("dp", "sp", "tp"),
              shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Build a Mesh over the available devices. Default factorization puts
    tp innermost (fast ICI axis), dp outermost — the reference's hybrid
    topology order ['dp','pp','sharding','sep','mp'] outside→inside
    (fleet/base/distributed_strategy.py:1892)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if shape is None:
        # greedy: tp gets the largest power-of-two factor up to 8, sp next
        rem = n
        tp = 1
        while tp * 2 <= min(rem, 8) and rem % (tp * 2) == 0:
            tp *= 2
        rem //= tp
        sp = 1
        while sp * 2 <= min(rem, 2) and rem % (sp * 2) == 0:
            sp *= 2
        dp = rem // sp
        shape = (dp, sp, tp)
    arr = np.asarray(devs[:n]).reshape(shape)
    return Mesh(arr, axes)


_FIT_SPEC_WARNED: set = set()


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes that don't evenly divide the tensor dim (e.g. dp=3
    fsdp over hidden=128) — falls back to replication on that axis, the
    same degradation the reference's sharding pass applies to odd shapes.
    Warns once per dropped (axis, shape) so a typo'd mesh doesn't silently
    train replicated."""
    entries = []
    for d, entry in enumerate(spec):
        if entry is None:
            entries.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        keep, size = [], shape[d]
        for nm in names:
            ax = dict(mesh.shape).get(nm, 1)  # absent mesh axis → replicate
            if ax > 1 and size % ax == 0:
                keep.append(nm)
                size //= ax
            elif ax > 1:
                sig = (nm, ax, d, tuple(shape))
                if sig not in _FIT_SPEC_WARNED:
                    _FIT_SPEC_WARNED.add(sig)
                    import warnings
                    warnings.warn(
                        f"sharding axis '{nm}'={ax} does not divide dim {d} "
                        f"of shape {tuple(shape)} — replicating on that "
                        "axis (throughput may drop)", stacklevel=3)
        entries.append(tuple(keep) if len(keep) > 1 else
                       (keep[0] if keep else None))
    return P(*entries)


def make_shardings(config: LlamaConfig, mesh: Mesh, fsdp: bool = True):
    shapes = _abstract_params(config)
    return jax.tree_util.tree_map(
        lambda spec, arr: NamedSharding(mesh, _fit_spec(spec, arr.shape, mesh)),
        param_specs(config, fsdp), shapes,
        is_leaf=lambda x: isinstance(x, P))


def make_serving_shardings(params, config: LlamaConfig, mesh: Mesh,
                           fsdp: bool = False):
    """``make_shardings`` generalized over the ACTUAL param tree, so int8
    weight-only params (quantize_params) shard for tp serving: each
    quantized leaf's ``q`` matrix takes the dense weight's Megatron spec
    and its per-output-channel ``s`` vector keeps the spec of the OUTPUT
    axis (sharded over 'tp' for column-parallel qkv/gate/up and lm_head,
    replicated for row-parallel wo/down whose outputs are not
    tp-sharded) — the scale always lives with the channels it scales, so
    the weight-only dot needs no extra collectives."""
    dense = param_specs(config, fsdp)

    def one(spec, leaf):
        if isinstance(leaf, dict) and "q" in leaf:
            s_spec = (P(spec[0], spec[-1]) if leaf["q"].ndim == 3
                      else P(spec[-1]))
            return {"q": NamedSharding(
                        mesh, _fit_spec(spec, leaf["q"].shape, mesh)),
                    "s": NamedSharding(
                        mesh, _fit_spec(s_spec, leaf["s"].shape, mesh))}
        return NamedSharding(mesh, _fit_spec(spec, leaf.shape, mesh))

    out = {"embed": one(dense["embed"], params["embed"]),
           "layers": {k: one(dense["layers"][k], params["layers"][k])
                      for k in params["layers"]},
           "final_norm": one(dense["final_norm"], params["final_norm"])}
    if "lm_head" in params:
        out["lm_head"] = one(dense["lm_head"], params["lm_head"])
    return out


def make_replicated_shardings(params, mesh: Mesh):
    """A sharding tree placing every leaf fully REPLICATED on ``mesh``
    (spec ``P()``). The serving engine uses this for the speculative
    DRAFT under tp serving (r19): the draft is small, so replicating it
    beats sharding a model whose kv heads may not divide the tp size —
    every device runs the identical draft program while the target's
    verify rides the sharded collectives."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: rep, params)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    # f32 statistics regardless of compute dtype (TPU bf16-safe)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _rope_tables(seq_len: int, head_dim: int, theta: float):
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ang = pos[:, None] * freq[None, :]            # [S, D/2]
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope(x, cos, sin):
    # x: [B, S, H, D]; rotate-half convention
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _apply_rope_at(x, cos, sin):
    """Rotate-half RoPE with PER-ROW positions: ``cos``/``sin`` are
    [B, S, D/2] (each batch row carries its own absolute offsets — the
    serving engine's chunked/suffix prefill, where row b's chunk starts
    ``hist_len[b]`` tokens into its sequence). ``_apply_rope`` stays the
    shared-position fast path."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _flash_attention(q, k, v, mesh: Optional[Mesh]):
    """The Pallas flash kernel (GQA-native: no repeated K/V materialized),
    run PER SHARD under a multi-device mesh: GSPMD cannot partition a
    Mosaic kernel ("wrap the call in a shard_map"), so the call shard_maps
    itself — batch over 'dp', heads over 'tp' (Megatron's column-parallel
    qkv leaves them sharded that way, and contiguous head shards keep each
    query head with its kv head while both head counts divide 'tp').
    Attention mixes neither batch rows nor heads, so no collective is
    added; any other mesh axis sees the inputs replicated."""
    from ..kernels.pallas_attention import flash_attention_fwd
    flash = functools.partial(flash_attention_fwd, causal=True)
    if mesh is None or mesh.size == 1:
        return flash(q, k, v)
    sizes = dict(mesh.shape)
    dp, tp = sizes.get("dp", 1), sizes.get("tp", 1)
    batch = "dp" if dp > 1 and q.shape[0] % dp == 0 else None
    heads = ("tp" if tp > 1 and q.shape[2] % tp == 0
             and k.shape[2] % tp == 0 else None)
    spec = P(batch, None, heads, None)
    return jax.shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _attention(q, k, v, config: LlamaConfig, mesh: Optional[Mesh] = None):
    """Causal GQA attention. [B, S, H, D] layout. Uses the Pallas flash
    kernel when shapes allow (kernels/pallas_attention.py — the
    replacement for the reference's third_party/flashattn), else fused-XLA
    reference math. Under a multi-device mesh the kernel needs the mesh
    at trace time: ``mesh`` (the serving engine passes its own), else
    the ambient :class:`activation_mesh` (the trainer's)."""
    B, S, H, D = q.shape
    groups = H // k.shape[2]
    if mesh is None:
        mesh = _ACT_MESH
    use_ring = (config.context_parallel and mesh is not None
                and dict(mesh.shape).get("sp", 1) > 1)
    if (not use_ring and config.use_flash and S >= 128 and D % 128 == 0):
        # selected by shape; a kernel that then fails to build is an error
        return _flash_attention(q, k, v, mesh)
    if use_ring:
        # GQA-native ring: unrepeated K/V blocks ride the ICI ring
        from ..kernels.ring_attention import ring_attention_sharded
        return ring_attention_sharded(q, k, v, mesh, "sp", causal=True)
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    scale = 1.0 / math.sqrt(D)
    qt = jnp.einsum("bshd->bhsd", q)
    kt = jnp.einsum("bshd->bhsd", k)
    vt = jnp.einsum("bshd->bhsd", v)
    scores = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.einsum("bhsd->bshd", out)


def _layer_body(x, layer_params, cos, sin, config: LlamaConfig):
    c = config
    B, S, h = x.shape
    p = layer_params
    dt = c.dtype

    hn = _rms_norm(x, p["attn_norm"], c.rms_eps)
    q = (hn @ p["wq"].astype(dt)).reshape(B, S, c.num_heads, c.head_dim)
    k = (hn @ p["wk"].astype(dt)).reshape(B, S, c.num_kv_heads, c.head_dim)
    v = (hn @ p["wv"].astype(dt)).reshape(B, S, c.num_kv_heads, c.head_dim)
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    from jax.ad_checkpoint import checkpoint_name
    att = _attention(q, k, v, c).reshape(B, S, c.num_heads * c.head_dim)
    att = checkpoint_name(att, "attn_out")
    x = x + att @ p["wo"].astype(dt)
    x = _constrain(x)

    hn = _rms_norm(x, p["mlp_norm"], c.rms_eps)
    gate = jax.nn.silu(hn @ p["w_gate"].astype(dt))
    up = hn @ p["w_up"].astype(dt)
    x = x + (gate * up) @ p["w_down"].astype(dt)
    return _constrain(x)


def _remat(body, config: LlamaConfig):
    if config.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(body, policy=policy)
    if config.remat_policy == "attn":
        # save only the attention outputs ([B,S,h] per layer): backward
        # skips re-running the flash kernel but still recomputes the cheap
        # elementwise/FFN chain — the middle point between "full" (all
        # recomputed) and "dots" (all matmul outputs saved, OOMs at 2.6B)
        policy = jax.checkpoint_policies.save_only_these_names("attn_out")
        return jax.checkpoint(body, policy=policy)
    if config.remat_policy != "full":
        raise ValueError(
            f"remat_policy={config.remat_policy!r}: expected 'full', "
            "'dots', or 'attn'")
    return jax.checkpoint(body)


_ACT_MESH: Optional[Mesh] = None


class activation_mesh:
    """Context declaring the mesh used to pin activation layouts during
    tracing (replaces the reference's per-op SPMD rule table — GSPMD
    propagates everything else)."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        global _ACT_MESH
        self._prev, _ACT_MESH = _ACT_MESH, self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _ACT_MESH
        _ACT_MESH = self._prev


def _constrain(x):
    """Pin activation layout to [dp, sp, -] when tracing under a mesh."""
    mesh = _ACT_MESH
    if mesh is None or not {"dp", "sp"} <= set(mesh.axis_names):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, act_spec()))


def hidden_states(params, tokens, config: LlamaConfig):
    """tokens [B, S] int32 → final-norm hidden states [B, S, h] (model
    dtype); runs the pipeline schedule when one is configured."""
    c = config
    S = tokens.shape[1]
    x = params["embed"].astype(c.dtype)[tokens]
    x = _constrain(x)
    cos, sin = _rope_tables(S, c.head_dim, c.rope_theta)

    body = functools.partial(_layer_body, cos=cos, sin=sin, config=c)
    if c.remat:
        body = _remat(body, c)  # trade FLOPs for HBM (reference: recompute)

    def scan_fn(carry, layer_params):
        return body(carry, layer_params), None

    mesh = _ACT_MESH
    pp = dict(mesh.shape).get("pp", 1) if mesh is not None else 1
    # NOTE: the pipeline-parallel branch below carries NO numerics
    # ladder — stage bodies run inside the manual-'pp' shard_map region
    # where the ys side-channel doesn't compose. NaN provenance is a
    # pp=1 feature for now (documented in docs/observability.md).
    if pp > 1 and c.pipeline_microbatches > 0:
        from ..distributed.pipeline import (pipeline_apply,
                                            pipeline_apply_interleaved)

        def stage_fn(local_layers, xx):
            # inside the manual-'pp' shard_map region full-mesh sharding
            # constraints are illegal — let GSPMD place the stage body
            with activation_mesh(None):
                out, _ = jax.lax.scan(scan_fn, xx, local_layers)
            return out

        if c.pipeline_chunks > 1:
            x = pipeline_apply_interleaved(
                stage_fn, params["layers"], x, mesh,
                c.pipeline_microbatches, c.pipeline_chunks, "pp")
        else:
            x = pipeline_apply(stage_fn, params["layers"], x, mesh,
                               c.pipeline_microbatches, "pp")
    elif _numerics.active():
        # numerics ladder: each layer's output contributes one stats
        # rung (absmax/rms/NaN count) via the scan's ys — the rungs
        # accumulate into one [L, 5] device buffer shipped off-graph by
        # a single async outfeed, and the provenance walk names the
        # first rung whose NaN/Inf count goes nonzero. Trace-time
        # gated: with FLAGS_obs_numerics off this branch never exists
        # and the scan below lowers to the identical jaxpr.
        def ladder_fn(carry, layer_params):
            out = body(carry, layer_params)
            return out, _numerics.tensor_stats(out)

        x, ladder = jax.lax.scan(ladder_fn, x, params["layers"])
        _numerics.ladder_record("llama.layer", ladder)
    else:
        x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    return _rms_norm(x, params["final_norm"], c.rms_eps)


def forward(params, tokens, config: LlamaConfig):
    """tokens [B, S] int32 → logits [B, S, vocab] (f32)."""
    c = config
    x = hidden_states(params, tokens, c)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(c.dtype)
    return logits.astype(jnp.float32)


def _chunked_ce_sum(x, targets, head, n_chunks: int):
    """Summed next-token CE over [B, S, h] hidden states without ever
    materializing [B, S, vocab] logits: scan over S/n_chunks-sized chunks,
    each chunk's logits rebuilt in backward (jax.checkpoint)."""
    B, S, h = x.shape
    if S % n_chunks:
        raise ValueError(
            f"loss_chunks={n_chunks} must divide the next-token sequence "
            f"length {S} (= seq - 1 of the training batch); pick a "
            "divisor or a sequence length with small factors")
    xc = jnp.moveaxis(x.reshape(B, n_chunks, S // n_chunks, h), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, n_chunks, S // n_chunks), 1, 0)

    @jax.checkpoint
    def chunk(carry, inp):
        xi, ti = inp
        logits = (xi @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xc, tc))
    return total


def loss_fn(params, tokens, config: LlamaConfig):
    """Next-token cross-entropy, mean over positions."""
    c = config
    if c.loss_chunks > 1:
        x = hidden_states(params, tokens[:, :-1], c)
        head = (params["embed"].T if c.tie_embeddings
                else params["lm_head"]).astype(c.dtype)
        total = _chunked_ce_sum(x, tokens[:, 1:], head, c.loss_chunks)
        return total / (x.shape[0] * x.shape[1])
    logits = forward(params, tokens[:, :-1], config)
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _loss_and_grads_1f1b(params, tokens, config: LlamaConfig, mesh: Mesh):
    """Fused 1F1B/ZB loss+grad pass (distributed/pipeline.pipeline_train_1f1b
    or pipeline_train_zb by config.pipeline_schedule): embed runs on stage 0,
    final-norm+head+CE inside the last stage, so only token ids and one
    boundary activation per in-flight microbatch exist per device — the
    reference 1F1B memory profile (ZB-H1 adds the deferred-wgrad ring)."""
    from ..distributed.pipeline import pipeline_train_1f1b, pipeline_train_zb

    c = config
    assert not c.tie_embeddings, "1f1b schedule requires untied embeddings"
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def first_fn(fp, tok_mb):
        return fp["embed"].astype(c.dtype)[tok_mb]

    def stage_fn(lp, x):
        with activation_mesh(None):
            cos, sin = _rope_tables(x.shape[1], c.head_dim, c.rope_theta)
            body = functools.partial(_layer_body, cos=cos, sin=sin, config=c)
            if c.remat:
                body = _remat(body, c)
            x, _ = jax.lax.scan(lambda h, p: (body(h, p), None), x, lp)
        return x

    def last_fn(lp, y, tgt_mb):
        x = _rms_norm(y, lp["final_norm"], c.rms_eps)
        head = lp["lm_head"].astype(c.dtype)
        if c.loss_chunks > 1:
            total = _chunked_ce_sum(x, tgt_mb, head, c.loss_chunks)
            return total / (x.shape[0] * x.shape[1])
        logits = (x @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt_mb[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    first_params = {"embed": params["embed"]}
    last_params = {"final_norm": params["final_norm"],
                   "lm_head": params["lm_head"]}
    train = (pipeline_train_zb if c.pipeline_schedule == "zb"
             else pipeline_train_1f1b)
    loss, (gf, gs, gl) = train(
        first_fn, stage_fn, last_fn, first_params, params["layers"],
        last_params, inputs, targets, mesh, c.pipeline_microbatches,
        axis_name="pp", hidden_dtype=c.dtype)
    grads = {"embed": gf["embed"], "layers": gs,
             "final_norm": gl["final_norm"], "lm_head": gl["lm_head"]}
    return loss, grads


# ---------------------------------------------------------------------------
# train state / step  (adamw in plain jax — the whole step is one jit)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class TrainState:
    """params + adam moments + step, all shardable pytrees."""

    def __init__(self, params, mu, nu, step):
        self.params, self.mu, self.nu, self.step = params, mu, nu, step

    def tree_flatten(self):
        return (self.params, self.mu, self.nu, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_train_state(config: LlamaConfig, key: jax.Array,
                     optimizer: str = "adamw",
                     moment_dtype=jnp.float32,
                     param_dtype=jnp.float32) -> TrainState:
    """``optimizer``/``moment_dtype``/``param_dtype`` select the memory mode
    (optimizer/functional.py): adamw+f32 is the default 16-bytes/param
    recipe; adafactor+bf16 params is ~4 bytes/param — how a >2B model fits
    one 16GB chip."""
    from ..optimizer.functional import init_moments

    params = init_params(config, key)
    if param_dtype != jnp.float32:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(param_dtype), params)
    mu, nu = init_moments(params, optimizer, moment_dtype)
    return TrainState(params, mu, nu, jnp.zeros((), jnp.int32))


def init_sharded_train_state(config: LlamaConfig, key: jax.Array,
                             param_shardings, optimizer: str = "adamw",
                             param_dtype=jnp.float32) -> TrainState:
    """Initialize the train state DIRECTLY onto the mesh: the init is jitted
    with ``out_shardings`` so no unsharded copy ever materializes on one
    device — required for pod-scale models (an 8B f32 state is ~96 GB,
    far over a single chip's HBM)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..optimizer.functional import moment_shardings

    mu_sh, nu_sh = moment_shardings(
        param_shardings, _abstract_params(config), optimizer)
    mesh = jax.tree_util.tree_leaves(param_shardings)[0].mesh
    out_sh = TrainState(param_shardings, mu_sh, nu_sh,
                        NamedSharding(mesh, P()))
    fn = jax.jit(
        lambda k: init_train_state(config, k, optimizer=optimizer,
                                   param_dtype=param_dtype),
        out_shardings=out_sh)
    return fn(key)


def put_train_state(state: TrainState, param_shardings,
                    optimizer: str = "adamw") -> TrainState:
    """device_put a TrainState onto the mesh: params take
    ``param_shardings``; optimizer moments get moment-shaped shardings
    (adafactor's scalar mu / factored nu are NOT param-shaped —
    optimizer/functional.moment_shardings)."""
    from ..optimizer.functional import moment_shardings

    mu_sh, nu_sh = moment_shardings(param_shardings, state.params, optimizer)
    return TrainState(jax.device_put(state.params, param_shardings),
                      jax.device_put(state.mu, mu_sh),
                      jax.device_put(state.nu, nu_sh), state.step)


def train_step(state: TrainState, tokens, config,
               lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1,
               clip_norm=1.0, loss_function=None, optimizer="adamw",
               accum_steps=1):
    """One fused pretrain step: fwd+bwd, global-norm clip, optimizer update
    (optimizer/functional.py — adamw or factored-moment adafactor).
    The reference splits this across EagerReducer buckets +
    HybridParallelOptimizer (hybrid_parallel_optimizer.py:540); here the whole
    thing is one traced program and GSPMD/XLA overlap the collectives.
    ``loss_function(params, tokens, config)`` defaults to the llama loss —
    MoE passes its own (models/moe.py). ``accum_steps`` > 1 scans fwd+bwd
    over batch slices, accumulating grads in f32 (activation memory ÷ N —
    the reference's GradientMergePass)."""
    from ..optimizer.functional import optimizer_update

    mesh = _ACT_MESH
    pp = dict(mesh.shape).get("pp", 1) if mesh is not None else 1
    if (loss_function is None and pp > 1 and config.pipeline_microbatches > 0
            and config.pipeline_schedule in ("1f1b", "zb")):
        if accum_steps > 1:
            raise ValueError(
                "accum_steps>1 is redundant under the 1f1b/zb schedules — "
                "raise pipeline_microbatches instead (it already slices the "
                "batch)")
        if config.pipeline_chunks > 1:
            raise NotImplementedError(
                "interleaved chunks are a gpipe-schedule feature; 1f1b/zb "
                "run one chunk per stage (set pipeline_chunks=1)")
        loss, grads = _loss_and_grads_1f1b(state.params, tokens, config, mesh)
    elif accum_steps > 1:
        lf = loss_function or loss_fn
        if not hasattr(tokens, "shape"):
            raise ValueError(
                "accum_steps>1 requires an array batch; tuple batches "
                "(e.g. bert's (ids, labels)) must pre-slice themselves")
        B = tokens.shape[0]
        assert B % accum_steps == 0, (B, accum_steps)
        slices = tokens.reshape((accum_steps, B // accum_steps)
                                + tokens.shape[1:])

        def acc(carry, mb):
            acc_l, acc_g = carry
            l, g = jax.value_and_grad(lf)(state.params, mb, config)
            return (acc_l + l, jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc_g, g)), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros((), jnp.float32),
                                              zeros), slices)
        loss = loss / accum_steps
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
    else:
        lf = loss_function or loss_fn
        loss, grads = jax.value_and_grad(lf)(state.params, tokens, config)

    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))

    new_p, new_m, new_n = optimizer_update(
        state.params, grads, state.mu, state.nu, state.step,
        optimizer=optimizer, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
        wd=wd, scale=scale)
    return TrainState(new_p, new_m, new_n, state.step + 1), loss


def flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Matmul FLOPs per trained token, fwd+bwd: 6*N for the dense weights
    plus the 12*L*h*S causal-attention term (PaLM appendix accounting)."""
    c = config
    n = num_params(_abstract_params(c))
    return 6.0 * n + 12.0 * c.num_layers * c.hidden_size * seq_len


@functools.lru_cache(maxsize=8)
def _abstract_params(config: LlamaConfig):
    return jax.eval_shape(
        functools.partial(init_params, config), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# inference: KV-cache decode + generation
# (the reference's decode path: fused block_multihead_attention decode
#  kernels — phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
#  incubate/nn/functional/block_multihead_attention; here: static-shape KV
#  cache ring with masked attention — jit compiles one decode step)
# ---------------------------------------------------------------------------

def init_kv_cache(config: LlamaConfig, batch: int, max_len: int):
    c = config
    shape = (c.num_layers, batch, max_len, c.num_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _cached_attention(q, k_cache, v_cache, pos, config: LlamaConfig):
    """q: [B, S_new, Hq, D]; caches: [B, max_len, Hkv, D]; valid keys < pos +
    S_new with causality inside the new block. GQA-native: query heads are
    grouped against their kv head in the einsum — the KV cache is never
    materialized repeated (decode is KV-bandwidth-bound; a 3x repeat at
    Hq/Hkv=3 would triple the per-step HBM traffic)."""
    c = config
    B, S, Hq, D = q.shape
    groups = Hq // c.num_kv_heads
    qg = q.reshape(B, S, c.num_kv_heads, groups, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    max_len = k_cache.shape[1]
    key_idx = jnp.arange(max_len)[None, :]
    qry_idx = pos + jnp.arange(S)[:, None]
    mask = key_idx <= qry_idx                        # [S, max_len]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache)
    return out.reshape(B, S, Hq, D)


def forward_with_cache(params, tokens, cache, config: LlamaConfig,
                       logits_all: bool = False):
    """Append `tokens` [B, S_new] to the cache, return (logits_last, cache).
    Works for prefill (S_new = prompt len) and decode (S_new = 1).

    ``logits_all=True`` returns logits at EVERY position ([B, S_new,
    vocab] instead of [B, vocab]) — the speculative-decoding verify
    primitive: score a piece of k draft tokens in one batched forward
    and read the model's next-token distribution after each of them
    (serving/engine.py runs the paged-pool analogue; this is the
    fixed-batch reference the parity tests check against)."""
    c = config
    dt = c.dtype
    B, S = tokens.shape
    pos = cache["pos"]
    x = params["embed"].astype(dt)[tokens]
    max_len = cache["k"].shape[2]
    # rope tables over absolute positions pos..pos+S
    ang_pos = (pos + jnp.arange(S)).astype(jnp.float32)
    freq = c.rope_theta ** (-jnp.arange(0, c.head_dim, 2, jnp.float32)
                            / c.head_dim)
    ang = ang_pos[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    # python loop over layers (decode is matmul-small; L is static and the
    # cache-threading stays explicit). Cache writes are per-layer slice
    # updates on the STACKED arrays — XLA aliases them in place inside the
    # fused decode while_loop; a rebuild (stack of per-layer copies) would
    # move the whole multi-GB cache through HBM every step.
    # Weight matmuls go through weight_only_matmul: int8 weight-only
    # leaves contract unconverted with the scale applied to the output —
    # the weight-bandwidth-bound decode step reads half the bytes and
    # never materializes a dequantized weight copy.
    from ..kernels.quant_matmul import weight_only_matmul as _wo_mm

    ck, cv = cache["k"], cache["v"]
    for l in range(c.num_layers):
        p = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        hn = _rms_norm(x, p["attn_norm"], c.rms_eps)
        q = _wo_mm(hn, p["wq"], dt).reshape(B, S, c.num_heads, c.head_dim)
        k = _wo_mm(hn, p["wk"], dt).reshape(B, S, c.num_kv_heads, c.head_dim)
        v = _wo_mm(hn, p["wv"], dt).reshape(B, S, c.num_kv_heads, c.head_dim)
        q = _apply_rope(q, cos, sin)
        k = _apply_rope(k, cos, sin)
        ck = jax.lax.dynamic_update_slice(ck, k[None], (l, 0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v[None], (l, 0, pos, 0, 0))
        att = _cached_attention(q, ck[l], cv[l], pos, c)
        x = x + _wo_mm(att.reshape(B, S, c.num_heads * c.head_dim),
                       p["wo"], dt)
        hn = _rms_norm(x, p["mlp_norm"], c.rms_eps)
        gate = jax.nn.silu(_wo_mm(hn, p["w_gate"], dt))
        x = x + _wo_mm(gate * _wo_mm(hn, p["w_up"], dt), p["w_down"], dt)

    x = _rms_norm(x, params["final_norm"], c.rms_eps)
    xh = x if logits_all else x[:, -1]
    if c.tie_embeddings:
        logits = (xh @ params["embed"].astype(dt).T).astype(jnp.float32)
    else:
        logits = _wo_mm(xh, params["lm_head"], dt).astype(jnp.float32)
    cache = {"k": ck, "v": cv, "pos": pos + S}
    return logits, cache


def _sample_impl(logits, key, temperature, top_k, top_p, *, sampled: bool,
                 use_top_k: bool, use_top_p: bool):
    """Next-token sampling from [B, vocab] logits. The three keyword flags
    are STATIC (they shape the program); temperature/top_k/top_p values may
    be traced scalars, so the fused decode loop never recompiles when a
    serving loop varies them per request."""
    if not sampled:
        return jnp.argmax(logits, axis=-1)
    lg = logits / temperature
    B, vocab = lg.shape
    if use_top_k:
        srt = jnp.sort(lg, axis=-1)
        idx = jnp.clip(vocab - top_k, 0, vocab - 1)
        kth = jnp.take_along_axis(
            srt, jnp.full((B, 1), idx, jnp.int32), axis=-1)
        lg = jnp.where(lg < kth, -1e30, lg)
    if use_top_p:
        sort_idx = jnp.argsort(-lg, axis=-1)
        sort_p = jnp.take_along_axis(
            jax.nn.softmax(lg, axis=-1), sort_idx, axis=-1)
        cum = jnp.cumsum(sort_p, axis=-1)
        drop_sorted = cum - sort_p >= top_p      # keep the first >=p prefix
        drop = jnp.zeros_like(drop_sorted).at[
            jnp.arange(B)[:, None], sort_idx].set(drop_sorted)
        lg = jnp.where(drop, -1e30, lg)
    return jax.random.categorical(key, lg, axis=-1)


def _sample_logits(logits, key, temperature: float, top_k: int,
                   top_p: float):
    """Eager entry: flags derived from the python values."""
    return _sample_impl(logits, key, temperature, top_k, top_p,
                        sampled=temperature > 0, use_top_k=top_k > 0,
                        use_top_p=top_p < 1.0)


@functools.partial(
    jax.jit, static_argnames=("config", "max_new_tokens", "sampled",
                              "use_top_k", "use_top_p", "has_eos"))
def _generate_fused_jit(params, prompt_tokens, key, temperature, top_k,
                        top_p, eos_id, config: LlamaConfig,
                        max_new_tokens: int, sampled: bool, use_top_k: bool,
                        use_top_p: bool, has_eos: bool):
    B, S0 = prompt_tokens.shape
    cache = init_kv_cache(config, B, S0 + max_new_tokens)

    def sample(logits, finished, key):
        key, sub = jax.random.split(key)
        nxt = _sample_impl(logits, sub, temperature, top_k, top_p,
                           sampled=sampled, use_top_k=use_top_k,
                           use_top_p=use_top_p)
        if has_eos:
            nxt = jnp.where(finished, eos_id, nxt)
            finished = finished | (nxt == eos_id)
        return nxt.astype(prompt_tokens.dtype), finished, key

    logits, cache = forward_with_cache(params, prompt_tokens, cache, config)
    nxt, finished, key = sample(logits, jnp.zeros((B,), bool), key)
    toks = jnp.zeros((B, max_new_tokens), prompt_tokens.dtype)
    toks = toks.at[:, 0].set(nxt)

    # carry holds the LAST token, not logits: the forward for step i runs at
    # the TOP of iteration i, so no trailing forward is wasted after the
    # final sample (and the [B, vocab] f32 logits stay out of the carry)
    def cond(st):
        i, _, _, _, finished, _ = st
        return jnp.logical_and(i < max_new_tokens,
                               jnp.logical_not(jnp.all(finished)))

    def body(st):
        i, last, cache, toks, finished, key = st
        logits, cache = forward_with_cache(
            params, last[:, None], cache, config)
        nxt, finished, key = sample(logits, finished, key)
        toks = jax.lax.dynamic_update_slice(toks, nxt[:, None], (0, i))
        return (i + 1, nxt, cache, toks, finished, key)

    i, _, _, toks, finished, _ = jax.lax.while_loop(
        cond, body, (jnp.ones((), jnp.int32), nxt, cache, toks, finished,
                     key))
    return jnp.concatenate([prompt_tokens, toks], axis=1), i


def generate_fused(params, prompt_tokens, config: LlamaConfig,
                   max_new_tokens: int, temperature: float = 0.0, key=None,
                   eos_token_id=None, top_k: int = 0, top_p: float = 1.0):
    """Whole generation as ONE compiled program: prefill + a
    ``lax.while_loop`` decode with on-device sampling and EOS early exit.
    The python-loop ``generate`` pays a host->device dispatch per token;
    this is the analogue of the reference's fused block-decode path
    (block_multihead_attention + top_p_sampling ops in one graph).
    Same output contract as ``generate``; sampling VALUES (temperature /
    top_k / top_p / eos id) are traced, so varying them per request does
    not recompile — but crossing an on/off boundary (greedy <-> sampled,
    top_k 0 <-> >0, top_p 1.0 <-> <1.0, eos None <-> set) changes the
    program shape and compiles once per regime."""
    if max_new_tokens <= 0:
        return prompt_tokens
    key = key if key is not None else jax.random.PRNGKey(0)
    temperature = float(temperature)
    eos_arr = jnp.asarray(
        0 if eos_token_id is None else eos_token_id, jnp.int32)
    out, n = _generate_fused_jit(
        params, prompt_tokens, key, jnp.float32(max(temperature, 1e-6)),
        jnp.int32(top_k), jnp.float32(top_p), eos_arr, config,
        max_new_tokens, sampled=temperature > 0,
        use_top_k=int(top_k) > 0, use_top_p=float(top_p) < 1.0,
        has_eos=eos_token_id is not None)
    S0 = prompt_tokens.shape[1]
    return out[:, :S0 + int(n)]


def generate(params, prompt_tokens, config: LlamaConfig, max_new_tokens: int,
             temperature: float = 0.0, key=None, eos_token_id=None,
             top_k: int = 0, top_p: float = 1.0):
    """Greedy (temperature=0) or sampled generation with a jitted decode
    step; ``top_k``/``top_p`` restrict the sampling pool (nucleus — the
    reference's top_p_sampling op). prompt_tokens: [B, S_prompt] →
    [B, S_prompt + n] with n <= max_new_tokens: when ``eos_token_id`` is set
    and every row has finished, generation stops early (finished rows pad
    with eos up to the last emitted step)."""
    B, S0 = prompt_tokens.shape
    max_len = S0 + max_new_tokens
    cache = init_kv_cache(config, B, max_len)

    prefill = jax.jit(functools.partial(forward_with_cache, config=config))
    logits, cache = prefill(params, prompt_tokens, cache)

    decode = jax.jit(functools.partial(forward_with_cache, config=config))
    out = [prompt_tokens]
    key = key if key is not None else jax.random.PRNGKey(0)
    finished = jnp.zeros((B,), bool)
    for i in range(max_new_tokens):
        key, sub = jax.random.split(key)
        nxt = _sample_logits(logits, sub, temperature, top_k, top_p)
        if eos_token_id is not None:
            # finished rows keep emitting eos (the reference's EOS stop)
            nxt = jnp.where(finished, eos_token_id, nxt)
            finished = finished | (nxt == eos_token_id)
        nxt = nxt[:, None].astype(prompt_tokens.dtype)
        out.append(nxt)
        if eos_token_id is not None and bool(jnp.all(finished)):
            break
        if i + 1 < max_new_tokens:
            logits, cache = decode(params, nxt, cache)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# HF / torch checkpoint interchange
# (the reference ecosystem's convert utilities live in PaddleNLP; this is
#  the in-core equivalent so a switching user can load public weights)
# ---------------------------------------------------------------------------

def convert_hf_state_dict(state_dict, config: LlamaConfig):
    """HuggingFace Llama ``state_dict`` (torch tensors / numpy arrays keyed
    ``model.layers.{i}.self_attn.q_proj.weight`` …) → this module's
    stacked-layer params. torch Linear stores [out, in], so projection
    weights transpose; HF checkpoints already carry the rotate-half RoPE
    layout this module uses, so no head permutation is needed."""
    c = config
    import re as _re

    ckpt_layers = {int(m.group(1)) for k in state_dict
                   for m in [_re.match(r"model\.layers\.(\d+)\.", str(k))]
                   if m}
    if ckpt_layers and max(ckpt_layers) + 1 != c.num_layers:
        raise ValueError(
            f"checkpoint has {max(ckpt_layers) + 1} layers but "
            f"config.num_layers={c.num_layers} — a truncated load would "
            "silently produce garbage")

    def arr(name):
        v = state_dict[name]
        if hasattr(v, "detach"):
            # .float() first: torch bf16/f16 tensors reject .numpy()
            v = v.detach().cpu().float().numpy()
        return jnp.asarray(np.asarray(v), jnp.float32)

    def stacked(fmt, transpose=True):
        mats = [arr(fmt.format(i=i)) for i in range(c.num_layers)]
        if transpose:
            mats = [m.T for m in mats]
        return jnp.stack(mats)

    embed = arr("model.embed_tokens.weight")
    if embed.shape != (c.vocab_size, c.hidden_size):
        raise ValueError(
            f"checkpoint embed {embed.shape} vs config "
            f"(vocab={c.vocab_size}, hidden={c.hidden_size})")
    params = {
        "embed": embed,
        "layers": {
            "attn_norm": stacked(
                "model.layers.{i}.input_layernorm.weight", transpose=False),
            "wq": stacked("model.layers.{i}.self_attn.q_proj.weight"),
            "wk": stacked("model.layers.{i}.self_attn.k_proj.weight"),
            "wv": stacked("model.layers.{i}.self_attn.v_proj.weight"),
            "wo": stacked("model.layers.{i}.self_attn.o_proj.weight"),
            "mlp_norm": stacked(
                "model.layers.{i}.post_attention_layernorm.weight",
                transpose=False),
            "w_gate": stacked("model.layers.{i}.mlp.gate_proj.weight"),
            "w_up": stacked("model.layers.{i}.mlp.up_proj.weight"),
            "w_down": stacked("model.layers.{i}.mlp.down_proj.weight"),
        },
        "final_norm": arr("model.norm.weight"),
    }
    if not c.tie_embeddings:
        key = ("lm_head.weight" if "lm_head.weight" in state_dict
               else "model.embed_tokens.weight")  # tied checkpoints
        params["lm_head"] = arr(key).T
    return params


def to_hf_state_dict(params, config: LlamaConfig):
    """Inverse of ``convert_hf_state_dict`` (numpy values, HF names)."""
    c = config
    out = {"model.embed_tokens.weight": np.asarray(params["embed"]),
           "model.norm.weight": np.asarray(params["final_norm"])}
    lay = params["layers"]
    names = [("input_layernorm.weight", "attn_norm", False),
             ("self_attn.q_proj.weight", "wq", True),
             ("self_attn.k_proj.weight", "wk", True),
             ("self_attn.v_proj.weight", "wv", True),
             ("self_attn.o_proj.weight", "wo", True),
             ("post_attention_layernorm.weight", "mlp_norm", False),
             ("mlp.gate_proj.weight", "w_gate", True),
             ("mlp.up_proj.weight", "w_up", True),
             ("mlp.down_proj.weight", "w_down", True)]
    for i in range(c.num_layers):
        for hf, ours, transpose in names:
            m = np.asarray(lay[ours][i])
            out[f"model.layers.{i}.{hf}"] = m.T if transpose else m
    if not c.tie_embeddings:
        out["lm_head.weight"] = np.asarray(params["lm_head"]).T
    return out


def export_for_inference(params, config: LlamaConfig, path: str,
                         prompt_len: int, max_new_tokens: int,
                         batch: int = 1, quantize: bool = False):
    """Export a serving-ready greedy generation program in the
    ``paddle.jit.save`` artifact format (``.pdmodel`` StableHLO +
    ``.pdiparams``), optionally with int8 weight-only parameters — the
    end-to-end path from a trained model to ``paddle.inference``.

    Parity: the reference's save_optimized_model / AnalysisPredictor
    pipeline with a quant pass
    (paddle/fluid/inference/api/analysis_predictor.cc:1574); TPU-native,
    the "optimization pass" is quantize_params (the dequant fuses into
    the XLA matmuls) + jax.export ahead-of-time lowering of the fused
    prefill+decode while_loop.

    The artifact loads through ``paddle.jit.load`` /
    ``paddle.inference.create_predictor``: one input ``[batch,
    prompt_len]`` int32 prompt, one output ``[batch, prompt_len +
    max_new_tokens]`` generated ids (greedy, no eos early-exit so the
    program shape is static).
    """
    from ..jit import write_artifact

    p_exp = jax.jit(quantize_params)(params) if quantize else params

    def pure(p, bufs, prompt):
        out, _ = _generate_fused_jit(
            p, prompt, jax.random.PRNGKey(0), jnp.float32(1e-6),
            jnp.int32(0), jnp.float32(1.0), jnp.asarray(0, jnp.int32),
            config, max_new_tokens, sampled=False, use_top_k=False,
            use_top_p=False, has_eos=False)
        return (out,)

    example = jnp.zeros((batch, prompt_len), jnp.int32)
    exported = jax.export.export(jax.jit(pure))(p_exp, {}, example)
    write_artifact(path, exported, p_exp, {})
    return exported
