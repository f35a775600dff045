"""Segment-compiled execution for graph-broken functions (SOT parity).

The reference's SOT compiles the bytecode BEFORE an unconvertible break,
runs the break eagerly, and resumes capture after it
(python/paddle/jit/sot/opcode_translator/eval_frame_callback.py:54,
sot/symbolic/compile_cache.py). This is the trace-based TPU-native
equivalent, shaped like torch/XLA's lazy-tensor core rather than a
bytecode translator:

* the python function RE-EXECUTES every call (so value-dependent control
  flow — ``.item()`` branches, host-side logic — is always correct);
* every registry op it issues is DEFERRED onto a linear tape instead of
  dispatched to the device (ops/dispatch.py hands the call to the active
  ``SegmentRecorder``);
* any value materialization — ``.item()``, ``bool()``, ``numpy()``,
  printing — CUTS a segment: the pending tape compiles into ONE jitted
  program (cached by tape structure, so steady state never retraces) and
  executes through the normal ``apply`` path, which records a single
  GradNode per segment — autograd composes across segments through the
  eager tape, so graph-broken models still train.

This is also an eager-mode win: an N-op python region costs ~1 dispatch
and one compile instead of N (speedup on this chip: not measured).

Anything the recorder cannot defer (data-dependent output shapes, ops
whose abstract eval fails, nested already-compiled programs) flushes the
tape and runs that op eagerly — the mode degrades toward plain eager,
never toward wrong answers.
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SegmentRecorder", "segment_scope", "current_recorder"]

_tls = threading.local()


def current_recorder() -> Optional["SegmentRecorder"]:
    if getattr(_tls, "flushing", 0):
        return None               # a flush's own apply must not re-record
    return getattr(_tls, "rec", None)


class _Lazy:
    """Placeholder value carried by a Tensor whose op is still on the
    tape. Shape/dtype queries answer from the abstract value; anything
    that needs data forces a flush and then delegates to the real array."""

    __slots__ = ("aval", "rec", "real", "__weakref__")
    _is_segment_lazy = True

    def __init__(self, aval, rec):
        object.__setattr__(self, "aval", aval)
        object.__setattr__(self, "rec", rec)
        object.__setattr__(self, "real", None)

    # -- metadata (no flush) ---------------------------------------------
    @property
    def shape(self):
        return tuple(self.aval.shape)

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def size(self):
        return int(np.prod(self.aval.shape)) if self.aval.shape else 1

    # -- forcing ----------------------------------------------------------
    def _force(self):
        if self.real is None:
            self.rec.flush()
        if self.real is None:
            raise RuntimeError(
                "segment value was dropped before it was bound — a lazy "
                "tensor escaped its recording scope with no live wrapper")
        return self.real

    def item(self, *a):
        return self._force().item(*a)

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self._force())
        return out.astype(dtype) if dtype is not None else out

    def __bool__(self):
        return bool(self._force())

    def __int__(self):
        return int(self._force())

    def __float__(self):
        return float(self._force())

    def __index__(self):
        return self._force().__index__()

    def __getattr__(self, name):
        # safety net: unknown attribute/method → materialize and delegate
        return getattr(self._force(), name)

    def __repr__(self):
        if self.real is not None:
            return repr(self.real)
        return f"<lazy {self.aval.dtype}{list(self.aval.shape)}>"


class _InSnap:
    """RECORD-TIME snapshot of one op input: the value reference and the
    autograd provenance as they were when the op was issued. In-place ops
    (`_adopt`) may rebind the live tensor before flush — the tape must
    not see that."""

    __slots__ = ("value", "sg", "grad_node", "out_index", "accum")

    def __init__(self, t):
        self.value = t._value
        self.sg = t.stop_gradient
        self.grad_node = t._grad_node
        self.out_index = t._output_index
        self.accum = t._accumulate_node

    def key(self):
        return (id(self.value), self.sg, id(self.grad_node),
                self.out_index, id(self.accum))

    def raw(self):
        v = self.value
        return v.real if isinstance(v, _Lazy) else v


class _Node:
    __slots__ = ("name", "fn", "s_args", "s_kwargs", "in_snaps",
                 "out_lazies", "multi", "grad_on")

    def __init__(self, name, fn, s_args, s_kwargs, in_snaps, out_lazies,
                 multi, grad_on):
        self.name = name
        self.fn = fn
        self.s_args = s_args
        self.s_kwargs = s_kwargs
        self.in_snaps = in_snaps
        self.out_lazies = out_lazies
        self.multi = multi
        self.grad_on = grad_on


# compiled segment programs, keyed by tape structure — shared across
# recorders so repeated calls of a graph-broken function hit the cache
_SEGMENT_CACHE: dict = {}


def note_lazy_ref(lazy, tensor):
    """Called by core.Tensor whenever a tensor starts referencing a lazy
    value (creation, aliasing constructor, in-place `_adopt`): the
    recorder binds the computed value and grad linkage onto every live
    owner at flush."""
    lazy.rec._owners.setdefault(id(lazy), []).append(weakref.ref(tensor))


def _tensor_with_lazy(lazy, stop_gradient):
    """Build a framework Tensor around a _Lazy without the constructor's
    jnp.asarray coercion."""
    from ..core.tensor import Tensor

    t = Tensor.__new__(Tensor)
    t._value = lazy
    t.stop_gradient = stop_gradient
    t._grad = None
    t._grad_node = None
    t._output_index = 0
    t._accumulate_node = None
    t.name = None
    t.persistable = False
    t.is_parameter = False
    t._version = 0
    note_lazy_ref(lazy, t)
    return t


def _shim_tensor(snap: _InSnap):
    """Tensor view of an input snapshot: carries the RECORDED value and
    autograd provenance into the flush's apply call, immune to later
    in-place rebinds of the original tensor."""
    from ..core.tensor import Tensor

    t = Tensor.__new__(Tensor)
    t._value = snap.raw()
    t.stop_gradient = snap.sg
    t._grad = None
    t._grad_node = snap.grad_node
    t._output_index = snap.out_index
    t._accumulate_node = snap.accum
    t.name = None
    t.persistable = False
    t.is_parameter = False
    t._version = 0
    return t


class SegmentRecorder:
    """Records registry-op calls into segments; see module docstring."""

    def __init__(self):
        self.nodes: List[_Node] = []
        self.flushes = 0           # segments executed (compiled or cached)
        self.compiles = 0          # segments that actually compiled
        self._owners: dict = {}    # id(lazy) -> [weakref(Tensor)]

    # -- recording --------------------------------------------------------
    def record(self, name: str, fn: Callable, args, kwargs):
        """Defer one op. Returns (outs tuple, multi) or None if the op
        cannot be deferred (caller runs it eagerly after our flush)."""
        from ..autograd.tape import AccumulateGrad, is_grad_enabled
        from ..framework import dtype as _dtypes
        from ..ops.dispatch import _fill, _scan

        if name.startswith("jit::"):
            # an inner already-compiled StaticFunction: its closure bakes
            # per-call state (rng key data, buffers) no structural key can
            # see — run it as its own dispatch instead of poisoning the
            # segment cache with never-hitting entries
            return None

        tensors: List = []
        s_args = _scan(args, tensors)
        s_kwargs = _scan(kwargs, tensors)
        avals = []
        for t in tensors:
            v = t._value
            if isinstance(v, _Lazy) and v.real is None and v.rec is not self:
                v.rec.flush()      # nested scope: force the OUTER tape
            v = t._value
            if isinstance(v, _Lazy):
                avals.append(v.aval if v.real is None
                             else jax.ShapeDtypeStruct(
                                 tuple(v.real.shape), v.real.dtype))
            else:
                avals.append(jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
        try:
            out_avals = jax.eval_shape(
                lambda *vs: fn(*_fill(s_args, vs), **_fill(s_kwargs, vs)),
                *avals)
        except Exception:
            self.flush()           # op needs real values → run it eagerly
            return None
        multi = isinstance(out_avals, (tuple, list))
        flat_avals = tuple(out_avals) if multi else (out_avals,)
        if not all(hasattr(a, "shape") and hasattr(a, "dtype")
                   for a in flat_avals):
            self.flush()
            return None

        grad_on = is_grad_enabled()
        any_grad = grad_on and any(
            not t.stop_gradient
            and _dtypes.np_is_floating(np.dtype(a.dtype))
            for t, a in zip(tensors, avals))
        snaps = []
        for t in tensors:
            if (not t.stop_gradient and t._grad_node is None
                    and t._accumulate_node is None):
                # leaf requiring grad: pin its AccumulateGrad to the
                # ORIGINAL tensor now, so the flush-time shim routes
                # cotangents to it
                t._accumulate_node = AccumulateGrad(t)
            snaps.append(_InSnap(t))
        outs, lazies = [], []
        for a in flat_avals:
            lz = _Lazy(jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), self)
            is_float = _dtypes.np_is_floating(np.dtype(a.dtype))
            t = _tensor_with_lazy(lz, stop_gradient=not (is_float
                                                         and any_grad))
            outs.append(t)
            lazies.append(lz)
        self.nodes.append(_Node(name, fn, s_args, s_kwargs, snaps, lazies,
                                multi, grad_on and any_grad))
        return tuple(outs), multi

    # -- flushing ---------------------------------------------------------
    def flush(self):
        """Compile-and-run the pending tape as one program; bind results."""
        if getattr(_tls, "flushing", 0) or not self.nodes:
            return
        nodes, self.nodes = self.nodes, []
        _tls.flushing = getattr(_tls, "flushing", 0) + 1
        try:
            self._run_segment(nodes)
        finally:
            _tls.flushing -= 1

    def _live_owners(self, lz):
        out = []
        for wr in self._owners.get(id(lz), ()):
            t = wr()
            if t is not None and t._value is lz:
                out.append(t)
        return out

    def _run_segment(self, nodes: List[_Node]):
        from ..ops.dispatch import _fill, apply

        # segment inputs: every op input whose snapshot value is real;
        # dedup only on identical (value, grad-provenance) — a tensor and
        # its detach() share a value but must stay separate inputs
        in_snaps: List[_InSnap] = []
        in_index: dict = {}            # snap.key() -> position
        lazy_pos: dict = {}            # id(lazy) -> (node_i, out_j)
        key_parts: List = ["seg"]
        for ni, nd in enumerate(nodes):
            key_parts.append(nd.name)
            # fn identity is part of the key: closures bake per-call
            # constants (scalars, rng keys) invisible to the arg skeleton.
            # _fn_key hashes (code object, closure-cell contents) so the
            # per-call lambdas most ops build still cache-hit when their
            # constants repeat; opaque cells fall back to the fn object
            # (never stale — at worst a recompile).
            key_parts.append(_fn_key(nd.fn))
            key_parts.append(_skel_key(nd.s_args))
            key_parts.append(_skel_key(nd.s_kwargs))
            key_parts.append(nd.grad_on)
            for sn in nd.in_snaps:
                v = sn.value
                if isinstance(v, _Lazy) and v.real is None:
                    key_parts.append(("lz", lazy_pos[id(v)], sn.sg))
                else:
                    k = sn.key()
                    if k not in in_index:
                        in_index[k] = len(in_snaps)
                        in_snaps.append(sn)
                    raw = sn.raw()
                    key_parts.append(
                        ("in", in_index[k], tuple(raw.shape),
                         str(raw.dtype), sn.sg))
            for j, lz in enumerate(nd.out_lazies):
                lazy_pos[id(lz)] = (ni, j)

        # outputs: lazies still referenced by a live Tensor (everything
        # else is a dead intermediate XLA can fuse away)
        out_sel: List[Tuple[int, int]] = []
        for ni, nd in enumerate(nodes):
            for j, lz in enumerate(nd.out_lazies):
                if self._live_owners(lz):
                    out_sel.append((ni, j))
        key_parts.append(tuple(out_sel))
        key = _hashable(key_parts)

        if len(_SEGMENT_CACHE) > 512:     # opaque-keyed entries never hit
            _SEGMENT_CACHE.clear()
        jitted = _SEGMENT_CACHE.get(key)
        if jitted is None:
            # the cached closure must reference ONLY the extracted plan —
            # never nodes/snaps/lazies, which pin the first call's input
            # arrays, results, and GradNode vjp residuals (activations)
            # for the cache entry's lifetime
            plan = []
            for nd in nodes:
                srcs = []
                for sn in nd.in_snaps:
                    v = sn.value
                    if isinstance(v, _Lazy) and v.real is None:
                        # sg at the USE site: a detached view of a lazy
                        # intermediate resolves to the same traced value —
                        # the stop_gradient must wrap this use
                        srcs.append(("env",) + lazy_pos[id(v)] + (sn.sg,))
                    else:
                        srcs.append(("in", in_index[sn.key()]))
                plan.append((nd.fn, nd.s_args, nd.s_kwargs, tuple(srcs),
                             nd.grad_on, len(nd.out_lazies)))

            def seg_fn(*in_vals):
                env: dict = {}
                for ni, (fn, sa, sk, srcs, grad_on, n_out) in enumerate(
                        plan):
                    vals = []
                    for s in srcs:
                        if s[0] == "env":
                            v = env[s[1:3]]
                            vals.append(jax.lax.stop_gradient(v)
                                        if s[3] else v)
                        else:
                            vals.append(in_vals[s[1]])
                    out = fn(*_fill(sa, vals), **_fill(sk, vals))
                    outs = (tuple(out) if isinstance(out, (tuple, list))
                            else (out,))
                    if not grad_on:
                        outs = tuple(jax.lax.stop_gradient(o)
                                     for o in outs)
                    for j, o in enumerate(outs):
                        env[(ni, j)] = o
                return tuple(env[k] for k in out_sel)

            jitted = jax.jit(seg_fn)
            _SEGMENT_CACHE[key] = jitted
            self.compiles += 1
        self.flushes += 1

        # the flush may be triggered from inside no_grad() (loss logging);
        # the segment's grad recording is decided by the tape as RECORDED
        from ..autograd.tape import enable_grad, no_grad
        grad_ctx = (enable_grad() if any(nd.grad_on for nd in nodes)
                    else no_grad())
        seg_inputs = [_shim_tensor(sn) for sn in in_snaps]
        with grad_ctx:
            outs = apply("jit_segment", lambda *vs: jitted(*vs),
                         *seg_inputs)
        if not isinstance(outs, tuple):
            outs = (outs,)
        # bind: real value + grad linkage onto every live owner tensor
        for (ni, j), res in zip(out_sel, outs):
            lz = nodes[ni].out_lazies[j]
            object.__setattr__(lz, "real", res._value)
            for t in self._live_owners(lz):
                t._value = res._value
                if not t.stop_gradient:
                    # owners that detached (detach()/detach_() set
                    # stop_gradient=True while sharing the lazy) keep
                    # their detachment — no grad node reattached
                    t._grad_node = res._grad_node
                    t._output_index = res._output_index
                    t.stop_gradient = res.stop_gradient
        for nd in nodes:
            for lz in nd.out_lazies:
                self._owners.pop(id(lz), None)


def _fn_key(fn):
    """Structural identity for an op's fn: behavior is determined by its
    code object plus closed-over constants, so equal (code, cells) from
    the same definition site may share one compiled segment. Anything
    opaque degrades to object identity (strong-ref'd in the cache key, so
    id() reuse can never alias two different fns)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn
    parts: List[Any] = [code]
    for cell in (getattr(fn, "__closure__", None) or ()):
        v = cell.cell_contents
        key = _const_key(v)
        if key is None:
            return fn
        parts.append(key)
    for v in (getattr(fn, "__defaults__", None) or ()):
        key = _const_key(v)
        if key is None:
            return fn
        parts.append(key)
    return tuple(parts)


def _const_key(v):
    """Hashable content key for a closure constant, or None if opaque."""
    if v is None or v is Ellipsis or v is NotImplemented:
        return ("singleton", repr(v))
    if isinstance(v, (jax.Array, np.ndarray)):
        try:
            if jnp.issubdtype(v.dtype, jax.dtypes.prng_key):
                v = jax.random.key_data(v)
            if v.size <= 64:
                return ("arr", str(v.dtype), tuple(v.shape),
                        tuple(np.asarray(v).ravel().tolist()))
        except Exception:
            pass
        return None
    if callable(v):
        k = _fn_key(v)
        return None if k is v else ("fn",) + tuple(
            k if isinstance(k, tuple) else (k,))
    try:
        hash(v)
    except TypeError:
        return None
    if type(v).__hash__ is object.__hash__:
        return None                     # identity hash: not content-stable
    return v


def _skel_key(obj):
    from ..ops.dispatch import _Ph

    if isinstance(obj, _Ph):
        return ("ph", obj.i)
    if isinstance(obj, (list, tuple)):
        return (type(obj).__name__,) + tuple(_skel_key(o) for o in obj)
    if isinstance(obj, dict):
        return ("d",) + tuple((k, _skel_key(v))
                              for k, v in sorted(obj.items()))
    try:
        hash(obj)
        return obj
    except TypeError:
        return repr(obj)


def _hashable(parts):
    def conv(o):
        if isinstance(o, list):
            return tuple(conv(x) for x in o)
        if isinstance(o, tuple):
            return tuple(conv(x) for x in o)
        return o
    return conv(tuple(parts))


class segment_scope:
    """Context manager activating a SegmentRecorder for the thread."""

    def __init__(self):
        self.rec = SegmentRecorder()

    def __enter__(self):
        self._prev = getattr(_tls, "rec", None)
        _tls.rec = self.rec
        return self.rec

    def __exit__(self, *exc):
        try:
            try:
                # flush even on error: escaped tensors (buffers rebound by
                # in-place ops) must not be left referencing a dropped
                # tape — the recorded ops are valid regardless of why the
                # python after them raised
                self.rec.flush()
            except Exception:
                if exc[0] is None:
                    raise
                self.rec.nodes.clear()   # already unwinding: best effort
        finally:
            _tls.rec = self._prev
        return False
