"""paddle_tpu.jit — program capture over jax.jit.

Parity surface: python/paddle/jit/ (to_static — api.py:197; SOT bytecode JIT
under jit/sot/; save/load TranslatedLayer). TPU-native re-design: instead of a
CPython bytecode translator building a PIR program, capture IS jax tracing —
``to_static`` wraps a Layer/function into a pure jax function over its
parameter pytree, jit-compiles per input-signature (guard-based retrace =
one cache entry per (shapes, dtypes, static-arg) key, the analogue of SOT's
guard/compile_cache — jit/sot/symbolic/compile_cache.py), and re-enters the
eager autograd tape through one fused GradNode whose vjp is the compiled
backward (so ``loss.backward()`` through a captured program works, the
analogue of the reference's pir_run_program op —
python/paddle/jit/dy2static/pir_partial_program.py:555,630).

Buffer state (BatchNorm running stats) threads through capture: mutations
land on the bound traced values (framework/capture.py), ride out of the
jitted program as extra outputs, and are committed back to the layer's
buffers after each call — so ``to_static(model)`` training matches eager.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..autograd import no_grad
from ..observability import flight_recorder as _flight
from ..observability import goodput as _goodput
from ..observability import trace_span
from ..observability.catalog import instrument as _instrument
from ..core.tensor import Tensor
from ..framework import dtype as dtypes
from ..framework.random import next_key, rng_context
from ..nn.layer.layers import Layer
from ..ops.dispatch import apply
from .branch_capture import GraphBreak as _BranchGraphBreak

__all__ = ["to_static", "InputSpec", "save", "load", "not_to_static",
           "ignore_module", "enable_to_static", "TranslatedLayer",
           "BuildStrategy", "segment_scope", "cache"]

from . import cache  # noqa: E402  (persistent compile-artifact store —
# measured-not-traced products like the MoE gmm tiling winners survive
# the process; see jit/cache.py)

from .segments import segment_scope  # noqa: E402  (public: eager code can
# opt into lazy-segment batching directly — ops defer into cached compiled
# segments, any .item()/numpy() materializes; avoids per-op dispatch and
# per-op compiles)

_to_static_enabled = True

# compile-path telemetry (no-ops until FLAGS_obs_enabled; names in
# observability.catalog)
_M_JIT_HITS = _instrument("jit_cache_hits_total")
_M_JIT_MISSES = _instrument("jit_cache_misses_total")
_M_JIT_COMPILE = _instrument("jit_compile_seconds")


class BuildStrategy:
    """Capture-behavior knobs (parity surface: paddle.static.BuildStrategy
    as accepted by jit.to_static — api.py:197).

    ``allow_graph_break`` (default True): when tracing fails on
    data-dependent Python control flow (``if tensor.item() > 0:`` — a jax
    ConcretizationTypeError), run that input signature SEGMENT-COMPILED
    (jit/segments.py: ops defer into cached jitted segments, the break
    itself runs eagerly, autograd composes across segments) and cache
    the decision — the reference SOT's compile-prefix/resume-after-break
    fallback (jit/sot/.../eval_frame_callback.py:54). False = re-raise
    (the reference's full_graph=True strictness).
    """

    def __init__(self, allow_graph_break: bool = True):
        self.allow_graph_break = allow_graph_break


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = flag


class InputSpec:
    """parity: paddle.static.InputSpec."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        self.dtype = dtypes.convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype.name})"

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tensor.shape, tensor.dtype, name)


def _guard_key(args, kwargs):
    parts = []

    def walk(o):
        if isinstance(o, Tensor):
            parts.append(("T", tuple(o._value.shape), str(o._value.dtype)))
        elif isinstance(o, (list, tuple)):
            # the container TYPE is part of the guard: two namedtuple
            # classes (different field orders) with identical tensor
            # layouts must not share a compiled program
            parts.append(("L", type(o), len(o)))
            for e in o:
                walk(e)
        elif isinstance(o, dict):
            parts.append(("D", tuple(sorted(o))))
            for k in sorted(o):
                walk(o[k])
        elif isinstance(o, np.ndarray):
            parts.append(("A", o.tobytes()))
        else:
            parts.append(("S", o))

    walk(args)
    walk(kwargs)
    return tuple(parts)


def _split_tensors(obj, acc):
    """Replace Tensors with index placeholders; return skeleton."""
    if isinstance(obj, Tensor):
        acc.append(obj)
        return ("__tensor__", len(acc) - 1)
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # namedtuple
        return type(obj)(*(_split_tensors(e, acc) for e in obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_split_tensors(e, acc) for e in obj)
    if isinstance(obj, dict):
        # sorted-key order must match _guard_key, so two calls with the same
        # keys in different insertion order share one compile cache entry
        # with identical tensor slot assignment
        return {k: _split_tensors(obj[k], acc) for k in sorted(obj)}
    return obj


def _rebuild(skel, vals, wrap):
    if isinstance(skel, tuple) and len(skel) == 2 and skel[0] == "__tensor__":
        return wrap(vals[skel[1]])
    if isinstance(skel, tuple) and hasattr(skel, "_fields"):  # namedtuple
        return type(skel)(*(_rebuild(e, vals, wrap) for e in skel))
    if isinstance(skel, (list, tuple)) and not (
        isinstance(skel, tuple) and len(skel) == 2 and skel[0] == "__tensor__"
    ):
        return type(skel)(_rebuild(e, vals, wrap) for e in skel)
    if isinstance(skel, dict):
        return {k: _rebuild(v, vals, wrap) for k, v in skel.items()}
    return skel


_GRAPH_BREAK_ERRORS = tuple(
    e for e in (
        getattr(jax.errors, "ConcretizationTypeError", None),
        getattr(jax.errors, "TracerArrayConversionError", None),
        getattr(jax.errors, "TracerBoolConversionError", None),
        getattr(jax.errors, "TracerIntegerConversionError", None),
    ) if e is not None)


class StaticFunction:
    """Guard-cached jit wrapper around a function or Layer.forward."""

    def __init__(self, function: Callable, layer: Optional[Layer] = None,
                 input_spec=None, full_graph=True, backend=None,
                 build_strategy: Optional[BuildStrategy] = None):
        self._fn = function
        self._layer = layer
        self._input_spec = input_spec
        self._cache = {}
        self._build_strategy = build_strategy or BuildStrategy()
        self._segment_keys = set()  # graph-broke: segment-compiled mode
        self._warned_break = False
        # observability: compiles = traced whole-graph programs;
        # cond_branches = Python ifs converted to lax.cond; eager_calls =
        # uncacheable-signature fallbacks; segment_runs = calls executed
        # in segment-compiled mode; segments = compiled-segment
        # executions; segment_compiles = segments that newly compiled
        self._stats = {"compiles": 0, "cond_branches": 0, "eager_calls": 0,
                       "segment_runs": 0, "segments": 0,
                       "segment_compiles": 0}
        functools.update_wrapper(self, function)

    @property
    def forward(self):
        return self

    def concrete_program(self):
        return [e["jitted"] for e in self._cache.values()]

    def program(self, *example_args, **example_kwargs):
        """Op-graph view of this function traced at the example signature
        (reference ConcreteProgram.main_program): a static.Program whose
        Operators are the jaxpr equations — layer parameters appear as
        persistable consts. Inspection-only (passes belong to XLA)."""
        from ..static.program import Program

        return Program.from_callable(self._fn, *example_args,
                                     **example_kwargs)

    def _build(self, skel_args, skel_kwargs, n_args, out_box):
        from ..framework.capture import capture_buffer_updates
        from .branch_capture import capture_branches, combine_tensor_leaves

        layer = self._layer
        fn = self._fn
        stats = self._stats

        def pure(params, bufs, key_data, *arg_vals):
            key = jax.random.wrap_key_data(key_data)
            wrap = lambda v: Tensor(v, stop_gradient=True)

            def body():
                # re-runnable per branch path: state binding and the RNG
                # stream both reset at entry, so every arm of a captured
                # lax.cond sees identical starting state
                args = _rebuild(skel_args, arg_vals, wrap)
                kwargs = _rebuild(skel_kwargs, arg_vals, wrap)
                new_bufs = {}
                with rng_context(key), no_grad():
                    if layer is not None:
                        # buffer mutations (BN running stats) land on the
                        # bound traced values and ride out as extra outputs,
                        # so to_static(model) trains running stats correctly
                        with layer.bind_state(params, bufs), \
                                capture_buffer_updates():
                            out = fn(*args, **kwargs)
                            new_bufs = {k: b._value
                                        for k, b in layer.named_buffers()}
                    else:
                        out = fn(*args, **kwargs)
                tensors: List[Tensor] = []
                skel_out = _split_tensors(out, tensors)
                return skel_out, [t._value for t in tensors], new_bufs

            (skel_out, vals, new_bufs), n_cond = capture_branches(
                body, combine_tensor_leaves)
            stats["compiles"] += 1
            stats["cond_branches"] += n_cond
            out_box["skel"] = skel_out
            out_box["n_real"] = len(vals)
            out_box["buf_names"] = sorted(new_bufs)
            return tuple(vals) + tuple(
                new_bufs[k] for k in out_box["buf_names"])

        return jax.jit(pure)

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            return self._fn(*args, **kwargs)
        try:
            # training mode is part of the guard: train/eval trace different
            # programs (BN batch-vs-running stats, dropout)
            mode = self._layer.training if self._layer is not None else None
            key = (mode, _guard_key(args, kwargs))
            hash(key)
        except TypeError:
            key = None  # unhashable guard state → uncacheable: run eager
        if key is None:             # unhashable guard state: uncacheable
            self._stats["eager_calls"] += 1
            return self._fn(*args, **kwargs)
        if key in self._segment_keys:
            return self._run_segmented(args, kwargs)
        arg_tensors: List[Tensor] = []
        skel_args = _split_tensors(args, arg_tensors)
        skel_kwargs = _split_tensors(kwargs, arg_tensors)
        entry = self._cache.get(key)
        fresh = entry is None
        if fresh:
            _M_JIT_MISSES.inc()
            out_box = {}
            jitted = self._build(skel_args, skel_kwargs, len(arg_tensors), out_box)
            entry = {"jitted": jitted, "out_box": out_box}
            self._cache[key] = entry
        else:
            _M_JIT_HITS.inc()
        jitted = entry["jitted"]
        out_box = entry["out_box"]

        if self._layer is not None:
            named_p = list(self._layer.named_parameters())
            bufs = {k: b._value for k, b in self._layer.named_buffers()}
            pnames = [k for k, _ in named_p]
            ptensors = [p for _, p in named_p]
        else:
            pnames, ptensors, bufs = [], [], {}

        key_data = jax.random.key_data(next_key())

        def runner(pvals, avals):
            params = dict(zip(pnames, pvals))
            return jitted(params, bufs, key_data, *avals)

        try:
            fn_name = getattr(self._fn, "__name__", "fn")
            if fresh and _obs.enabled():
                # a fresh cache entry's first run traces + compiles: the
                # observed duration IS the compile cost (steady-state runs
                # take the cached-program path below untimed)
                t0 = time.perf_counter()
                with trace_span("jit.compile", fn=fn_name):
                    outs = apply("jit::" + fn_name,
                                 lambda pvals, avals: runner(pvals, avals),
                                 list(ptensors), list(arg_tensors))
                dt = time.perf_counter() - t0
                _M_JIT_COMPILE.observe(dt)
                _goodput.account("compile", dt)
                _flight.record("compile", fn=fn_name,
                               seconds=round(dt, 6))
            else:
                outs = apply("jit::" + fn_name,
                             lambda pvals, avals: runner(pvals, avals),
                             list(ptensors), list(arg_tensors))
        except _GRAPH_BREAK_ERRORS + (_BranchGraphBreak,) as e:
            # data-dependent Python control flow the branch-capture oracle
            # could not convert to lax.cond (int/float/item concretization,
            # mismatched arm structures, tensor while-loops, >MAX depth) —
            # the reference's SOT would break the frame here; we fall back
            # to eager for this signature and cache the decision
            if not self._build_strategy.allow_graph_break:
                raise
            self._cache.pop(key, None)
            self._segment_keys.add(key)
            if not self._warned_break:
                self._warned_break = True
                import warnings
                warnings.warn(
                    f"to_static({getattr(self._fn, '__name__', 'fn')}): "
                    f"graph break ({type(e).__name__}: {e}) — this input "
                    "signature now runs SEGMENT-COMPILED: ops between "
                    "value materializations execute as cached jitted "
                    "segments, the break itself runs eagerly (the SOT "
                    "subgraph fallback). Scalar-tensor ifs with matching "
                    "arms stay whole-graph automatically; "
                    "BuildStrategy(allow_graph_break=False) makes this an "
                    "error.", stacklevel=2)
            return self._run_segmented(args, kwargs)
        if not isinstance(outs, tuple):
            outs = (outs,)
        n_real = out_box.get("n_real", len(outs))
        buf_names = out_box.get("buf_names", [])
        if buf_names and self._layer is not None:
            named_b = dict(self._layer.named_buffers())
            with no_grad():
                for k, t in zip(buf_names, outs[n_real:]):
                    if k in named_b:
                        named_b[k]._replace_value(t._value)
        wrapped = _rebuild(out_box["skel"], list(outs[:n_real]), lambda t: t)
        return wrapped

    def _run_segmented(self, args, kwargs):
        """Graph-broken path: re-execute the python (so value-dependent
        control flow is exact) with every op deferred into cached compiled
        segments — jit/segments.py, the reference SOT's
        compile-prefix/resume-after-break semantics in trace-based form."""
        from .segments import segment_scope

        with segment_scope() as rec:
            out = self._fn(*args, **kwargs)
        self._stats["segment_runs"] += 1
        self._stats["segments"] += rec.flushes
        self._stats["segment_compiles"] += rec.compiles
        return out


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """paddle.jit.to_static parity (api.py:197). ``full_graph=False`` (the
    reference default — SOT mode) permits graph-break fallback to eager;
    ``full_graph=True`` makes tracing failures raise. An explicit
    ``build_strategy`` overrides."""

    if isinstance(build_strategy, BuildStrategy):
        bs = build_strategy
    else:
        bs = BuildStrategy(allow_graph_break=not full_graph)

    def decorate(obj):
        if isinstance(obj, Layer):
            static_fwd = StaticFunction(obj.forward, layer=obj,
                                        input_spec=input_spec,
                                        build_strategy=bs)
            obj.forward = static_fwd
            obj._static_function = static_fwd
            return obj
        layer = getattr(obj, "__self__", None)
        if isinstance(layer, Layer):
            return StaticFunction(obj, layer=layer, input_spec=input_spec,
                                  build_strategy=bs)
        return StaticFunction(obj, input_spec=input_spec, build_strategy=bs)

    if function is None:
        return decorate
    return decorate(function)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# ---------------------------------------------------------------------------
# save / load — exported StableHLO + weights (the inference path; parity:
# paddle.jit.save / TranslatedLayer, reference jit/translated_layer.py; the
# serialized artifact is the analogue of the PIR model format,
# fluid/pir/serialize_deserialize)
# ---------------------------------------------------------------------------
def save(layer, path, input_spec=None, **configs):
    if input_spec is None and getattr(layer, "_static_function", None):
        raise ValueError("input_spec is required to export")
    specs = input_spec or []
    example_args = []
    for spec in specs:
        if isinstance(spec, InputSpec):
            shape = [1 if s in (None, -1) else int(s) for s in spec.shape]
            example_args.append(jnp.zeros(shape, spec.dtype.np_dtype))
        elif isinstance(spec, Tensor):
            example_args.append(spec._value)
        else:
            example_args.append(jnp.asarray(spec))

    params, bufs = layer.functional_state() if isinstance(layer, Layer) else ({}, {})

    def pure(params, bufs, *arg_vals):
        wrap = lambda v: Tensor(v, stop_gradient=True)
        args = [wrap(v) for v in arg_vals]
        with no_grad():
            if isinstance(layer, Layer):
                was_training = layer.training
                layer.eval()
                try:
                    with layer.bind_state(params, bufs):
                        fwd = layer.forward
                        if isinstance(fwd, StaticFunction):
                            fwd = fwd._fn
                        out = fwd(*args)
                finally:
                    if was_training:
                        layer.train()
            else:
                out = layer(*args)
        tensors: List[Tensor] = []
        _split_tensors(out, tensors)
        return tuple(t._value for t in tensors)

    jitted = jax.jit(pure)
    exported = jax.export.export(jitted)(params, bufs, *example_args)
    write_artifact(path, exported, params, bufs)


def write_artifact(path: str, exported, params_tree, buffers_tree):
    """THE writer of the ``.pdmodel``/``.pdiparams`` artifact pair —
    shared by :func:`save` and model-level exporters
    (llama.export_for_inference), so the format :func:`load` parses has
    exactly one producer. Param trees may be nested (int8 exports carry
    {"q","s"} leaves)."""
    import pickle

    from ..framework.io import _to_serializable

    with open(path + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    wrap = lambda v: v if isinstance(v, Tensor) else Tensor(
        v, stop_gradient=True)
    is_leaf = lambda v: isinstance(v, Tensor)   # Tensor is a pytree node
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump(_to_serializable(
            {"params": jax.tree_util.tree_map(wrap, params_tree,
                                              is_leaf=is_leaf),
             "buffers": jax.tree_util.tree_map(wrap, buffers_tree,
                                               is_leaf=is_leaf)}), f)


class TranslatedLayer(Layer):
    """Loaded inference program (parity: jit/translated_layer.py)."""

    def __init__(self, exported, params, buffers):
        super().__init__()
        self._exported = exported
        self._params = params
        self._buffers_vals = buffers

    def forward(self, *args):
        vals = [a._value if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        outs = self._exported.call(self._params, self._buffers_vals, *vals)
        outs = [Tensor(o) for o in outs]
        return outs[0] if len(outs) == 1 else tuple(outs)


def load(path, **configs) -> TranslatedLayer:
    import pickle

    from ..framework.io import _from_serializable

    with open(path + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(f.read())
    with open(path + ".pdiparams", "rb") as f:
        state = _from_serializable(pickle.load(f))
    unwrap = lambda tree: jax.tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else v, tree,
        is_leaf=lambda v: isinstance(v, Tensor))
    # params may be a NESTED tree (llama.export_for_inference int8
    # exports carry {"q","s"} leaves per weight), not just a flat dict
    params = unwrap(state["params"])
    buffers = unwrap(state["buffers"])
    return TranslatedLayer(exported, params, buffers)


# parity: jit/sot debug knobs (python/paddle/jit/__init__.py set_code_level /
# set_verbosity — utils/envs.py). Here they gate the capture layer's logging.
_debug = {"code_level": 0, "verbosity": 0}


def set_code_level(level=100, also_to_stderr=False):
    _debug["code_level"] = int(level)


def set_verbosity(level=0, also_to_stderr=False):
    _debug["verbosity"] = int(level)
