"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capability surface of PaddlePaddle
(see SURVEY.md for the reference map), built on JAX/XLA/Pallas/pjit:
- eager Tensors with per-op autograd (tape of jax.vjp closures),
- a functional op corpus lowering to XLA,
- nn/optimizer/amp/io layers,
- jit capture ("to_static") over jax.jit with guard-based retrace,
- a distributed stack (DP/TP/PP/SEP/EP/ZeRO + SPMD auto-parallel) expressed as
  GSPMD shardings over a TPU mesh instead of NCCL process groups.
"""
from __future__ import annotations

import os as _os

import jax as _jax

# TPU-first numerics: stay in JAX's 32-bit mode. The reference defaults
# integer tensors to int64, but on TPU 64-bit index math costs throughput,
# doubles index memory, and Mosaic (Pallas) rejects i64 scalars — so int32 is
# the default here (documented divergence). Set PADDLE_TPU_X64=1 to restore
# first-class int64/float64 (CPU workflows, numeric-grad checking).
if _os.environ.get("PADDLE_TPU_X64", "0") == "1":
    _jax.config.update("jax_enable_x64", True)

# JAX's persistent compilation cache, at a fixed path inside the checkout
# unless JAX_COMPILATION_CACHE_DIR places it from outside
from .framework.cache_dirs import configure_compile_cache as _cc  # noqa: E402

_cc()

# Multi-process bootstrap (the PADDLE_* env contract from
# distributed.launch) must run BEFORE anything touches the XLA backend, so
# it happens here rather than in init_parallel_env (which becomes a no-op
# confirmation). Importing this package itself initialises NO backend: a
# chip belongs to one process, and a parent that only imports the package
# (the launcher, a tool that spawns) must leave it to its children.
if int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1 and \
        _os.environ.get("PADDLE_MASTER"):
    try:
        _jax.distributed.initialize(
            coordinator_address=_os.environ["PADDLE_MASTER"],
            num_processes=int(_os.environ["PADDLE_TRAINERS_NUM"]),
            process_id=int(_os.environ.get("PADDLE_TRAINER_ID", "0")))
    except RuntimeError:
        pass  # already initialized (re-import or user-managed)

from .framework import dtype as _dtype_mod
from .framework.dtype import (  # noqa: F401
    bfloat16, bool_, complex64, complex128, float16, float32, float64,
    get_default_dtype, int8, int16, int32, int64, set_default_dtype, uint8,
    DType as dtype, finfo, float8_e4m3fn, float8_e5m2, iinfo, pstring, raw,
)
from .framework.dtype import bool_ as bool  # noqa: F401,A001
from .framework.flags import get_flags, set_flags  # noqa: F401
from .framework.random import get_rng_state, seed, set_rng_state  # noqa: F401
from .core.tensor import Parameter, Tensor, is_tensor  # noqa: F401
from . import device  # noqa: F401
from .device import (  # noqa: F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, Place, TPUPlace,
    XPUPlace, get_device, is_compiled_with_tpu, set_device,
)
from .framework.param_attr import ParamAttr  # noqa: F401
from . import autograd  # noqa: F401
from .autograd import enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401
from .autograd.py_layer import PyLayer  # noqa: F401
from . import ops  # noqa: F401
from .ops import *  # noqa: F401,F403
from .ops import _C_ops  # noqa: F401
from . import amp  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import jit  # noqa: F401
from .framework import io as _fio
from .framework.io import load, save  # noqa: F401
from .jit import to_static  # noqa: F401
from . import distributed  # noqa: F401
from . import incubate  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import static  # noqa: F401
from . import hapi  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import geometric  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import quantization  # noqa: F401
from . import inference  # noqa: F401
from . import utils  # noqa: F401
from . import callbacks  # noqa: F401
from . import hub  # noqa: F401
from . import onnx  # noqa: F401
from . import regularizer  # noqa: F401
from . import sysconfig  # noqa: F401
from . import cost_model  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi.summary import summary  # noqa: F401
from .hapi.dynamic_flops import flops  # noqa: F401
from .nn.layer.layers import Layer  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .utils.dlpack import from_dlpack, to_dlpack  # noqa: F401

# paddle-parity aliases
disable_static = lambda place=None: None  # dygraph is the only eager mode
enable_static = lambda: None


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """parity: paddle.create_parameter (tensor/creation.py) — a standalone
    trainable Parameter outside any Layer."""
    import numpy as _np

    from .framework.dtype import convert_dtype as _cd
    from .nn import initializer as _init

    d = _cd(dtype)
    # precedence mirrors the reference LayerHelper: an attr-supplied
    # initializer wins; default_initializer applies only when absent
    init = None
    if attr is not None:
        init = getattr(ParamAttr._to_attr(attr), "initializer", None)
    if init is None:
        init = default_initializer
    if init is None:
        init = (_init.Constant(0.0) if is_bias
                else _init.XavierNormal())
    p = Parameter(_np.zeros(shape, d.np_dtype))
    init(p)
    return p


class LazyGuard:
    """parity: paddle.LazyGuard (python/paddle/base/dygraph/base.py).
    The reference defers parameter materialization inside the guard; here
    parameters are cheap host-initialized jax arrays, so the guard simply
    marks the scope (layers initialize eagerly — documented divergence)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """parity: paddle.set_printoptions — governs Tensor repr (numpy-backed)."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def disable_signal_handler():
    """parity: paddle.disable_signal_handler — no custom signal handlers are
    installed in this framework, so nothing to disable."""


def get_cuda_rng_state():
    """parity: paddle.get_cuda_rng_state — no CUDA generators in a TPU
    build; returns an empty list like the reference on a CPU-only build."""
    return []


def set_cuda_rng_state(state_list):
    if state_list:
        raise RuntimeError("set_cuda_rng_state: no CUDA devices available")


def batch(reader, batch_size, drop_last=False):
    """parity: paddle.batch (python/paddle/reader/decorator.py) — wrap a
    sample reader into a batch reader."""
    def batch_reader():
        b = []
        for sample in reader():
            b.append(sample)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    if batch_size <= 0:
        raise ValueError("batch_size must be positive")
    return batch_reader


def check_shape(shape):
    """parity: paddle.check_shape (static graph shape validation)."""
    from collections.abc import Sequence as _Seq

    if isinstance(shape, Tensor):
        return
    if not isinstance(shape, _Seq):
        raise TypeError(f"shape must be a list/tuple/Tensor, got {type(shape)}")
    for s in shape:
        if not isinstance(s, (int, Tensor)) or (isinstance(s, int) and s < -1):
            raise ValueError(f"invalid dim {s!r} in shape {shape}")

def in_dynamic_mode():
    return True


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_distribute():
    return True


version = type("version", (), {"full_version": "0.1.0", "major": 0, "minor": 1,
                               "patch": 0, "cuda": staticmethod(lambda: False),
                               "show": staticmethod(lambda: print("paddle_tpu 0.1.0"))})
__version__ = "0.1.0"


# ---------------------------------------------------------------------------
# Tensor method surface completion
# (reference: python/paddle/tensor/__init__.py tensor_method_func — ~394
# functions patched onto Tensor; the core set is attached in ops/__init__,
# this block attaches the long tail once every namespace exists)
# ---------------------------------------------------------------------------
def _attach_tensor_method_long_tail():
    import sys as _sys

    from . import signal as _signal
    from .ops import linalg as _linalg

    this = _sys.modules[__name__]
    names = [
        "acosh", "acosh_", "add_n", "addmm", "as_complex", "as_real",
        "as_strided", "asinh", "asinh_", "atanh", "atanh_", "atleast_1d",
        "atleast_2d", "atleast_3d", "bincount", "bitwise_invert",
        "bitwise_left_shift", "bitwise_right_shift", "block_diag",
        "broadcast_shape", "broadcast_tensors", "cdist", "cholesky_inverse",
        "cholesky_solve", "combinations", "concat", "cond", "copysign",
        "corrcoef", "cov", "create_parameter", "create_tensor",
        "cumulative_trapezoid", "diag", "diag_embed", "diagflat",
        "diagonal_scatter", "diff", "dsplit", "eig", "eigvalsh", "erfinv_",
        "exponential_", "floor_mod", "frexp", "gammainc", "gammaincc",
        "gammaln", "gcd", "histogram", "histogram_bin_edges", "histogramdd",
        "householder_product", "hsplit", "hypot", "i0", "i0e", "i1", "i1e",
        "index_fill", "inner", "inverse", "is_complex", "is_floating_point",
        "is_integer", "is_tensor", "isin", "isneginf", "isposinf", "isreal",
        "istft", "kron", "lcm", "ldexp", "less", "log1p_", "logaddexp",
        "lu", "lu_unpack", "matrix_transpose", "multigammaln",
        "multinomial", "multiplex", "negative", "nextafter", "ormqr",
        "outer", "pca_lowrank", "polar", "polygamma", "put_along_axis_",
        "rank", "reduce_as", "renorm", "reverse", "scatter_nd",
        "select_scatter", "sgn", "shard_index", "signbit", "sinc", "slice",
        "slice_scatter", "stack", "stanh", "stft", "svd_lowrank", "take",
        "tensor_split", "top_p_sampling", "trapezoid", "triangular_solve",
        "unflatten", "unfold", "unstack", "vander", "view_as", "vsplit",
    ]
    for n in names:
        if hasattr(Tensor, n):
            continue
        base = n[:-1] if n.endswith("_") else n
        fn = None
        for src in (this, _linalg, _signal):
            fn = getattr(src, n, None) or getattr(src, base, None)
            if fn is not None:
                break
        if fn is None:
            continue
        if n.endswith("_") and getattr(this, n, fn) is fn and \
                not getattr(fn, "__name__", "").endswith("_"):
            def _mk(f):
                def m(self, *a, **k):
                    return self._adopt(f(self, *a, **k))

                return m

            setattr(Tensor, n, _mk(fn))
        else:
            setattr(Tensor, n, fn)

    # random fills (reference Tensor.normal_/uniform_/bernoulli_ semantics:
    # fill self with samples, keep shape/dtype)
    import jax as _jx
    import jax.numpy as _jnp

    from .framework.random import next_key as _nk
    from .ops.dispatch import apply as _apply

    def _fill(name, sample):
        def m(self, *args, **kwargs):
            key = _nk()

            def fn(v):
                return sample(key, v.shape, *args, **kwargs).astype(v.dtype)

            return self._adopt(_apply(name, fn, self))

        m.__name__ = name
        return m

    if not hasattr(Tensor, "normal_"):
        Tensor.normal_ = _fill(
            "normal_", lambda k, s, mean=0.0, std=1.0:
            mean + std * _jx.random.normal(k, s, _jnp.float32))
    if not hasattr(Tensor, "uniform_"):
        Tensor.uniform_ = _fill(
            "uniform_", lambda k, s, min=-1.0, max=1.0, seed=0:  # noqa: A002
            _jx.random.uniform(k, s, _jnp.float32, min, max))
    if not hasattr(Tensor, "bernoulli_"):
        Tensor.bernoulli_ = _fill(
            "bernoulli_", lambda k, s, p=0.5:
            _jx.random.bernoulli(k, p, s))

    def _resize_(self, shape):
        """numpy-style resize: flat data truncated/tiled to the new numel."""
        import numpy as _np

        def fn(v):
            flat = v.reshape(-1)
            n = int(_np.prod(shape))
            if flat.shape[0] == 0:  # numpy resize zero-fills empty input
                return _jnp.zeros(shape, v.dtype)
            reps = -(-n // flat.shape[0])
            return _jnp.tile(flat, reps)[:n].reshape(shape)

        return self._adopt(_apply("resize_", fn, self))

    def _set_(self, source=None, shape=None):
        """Replace storage with source's (reference Tensor.set_)."""
        if source is not None:
            self._replace_value(source._value if hasattr(source, "_value")
                                else _jnp.asarray(source))
        if shape is not None:
            self._replace_value(self._value.reshape(shape))
        return self

    if not hasattr(Tensor, "resize_"):
        Tensor.resize_ = _resize_
    if not hasattr(Tensor, "set_"):
        Tensor.set_ = _set_
    if not hasattr(Tensor, "inverse"):
        Tensor.inverse = _linalg.inv

    def _create_tensor(self, dtype=None, name=None):
        """parity: Tensor.create_tensor — an empty tensor of this dtype."""
        import numpy as _np

        from .framework.dtype import convert_dtype as _cd

        d = _cd(dtype) if dtype is not None else None
        return Tensor(_np.zeros(
            (0,), d.np_dtype if d else _np.asarray(self._value).dtype))

    if not hasattr(Tensor, "create_tensor"):
        Tensor.create_tensor = _create_tensor


_attach_tensor_method_long_tail()
del _attach_tensor_method_long_tail
