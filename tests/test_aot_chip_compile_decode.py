"""Compile the dense family's serving programs for a DESCRIBED TPU v5e, in
``tests/test_aot_chip_compile_mla.py``'s manner, and read the compiled text:
nothing executes. At Mistral-7B-v0.3 widths (hidden 4096, FFN 14336, 32/8
heads of 128, vocab 32768; four layers keep a compile near 5 s) the q/k/v
projections must read ``params["layers"]["wq"|"wk"|"wv"]`` where they lie,
as ``w_up`` and ``wo`` are read, and no operation of the program may write a
weight-sized array. PR 29's parent wrote 3.15 GB of them a decode step (a
transposition of every layer's ``wq``, ``wk`` and most ``wv``) because the
reshape into heads reached back into the dot (``LlamaServed._qkv``).

How the text is read. Only the ENTRY computation counts: the layers are
unrolled into it. An instruction's result is the type between ``=`` and the
operation's name. Parameters, tuples, ``get-tuple-element`` and ``bitcast``
write nothing. The pools' write-back is known by its shape. What remains of
4 MiB or more must be a PREFETCH: at a few layers a whole ``wk`` stack fits
the chip's fast memory (``S(1)`` in a layout), and the compiler then brings
it there in slices (``slice-start``/``slice-done``/``ConcatBitcast``, or
``copy-start``/``copy-done``) in the layout it has. At the cell's 16 layers
it does not fit and the same dots take the parameter itself. A prefetch
keeps the order of the dimensions; a re-layout (``copy``, a ``kLoop``
fusion) does not, and is what this test refuses."""
import functools
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

paged_attention, pallas_attention = (
    importlib.import_module("paddle_tpu.kernels." + name)
    for name in ("paged_attention", "pallas_attention"))
from paddle_tpu.models import llama
from paddle_tpu.models.llama_served import ServeOpts
from paddle_tpu.serving import engine

BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
LAYERS, HIDDEN, Q_OUT, KV_OUT = 4, 4096, 32 * 128, 8 * 128
NB, BS, TABLE = 2561, 16, 160          # the cells' pool, block and table
BIG = 4 << 20
GREEDY = (False, False, False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"cannot describe v5e:2x2: {e}")


@pytest.fixture(autouse=True)
def _chip_lowering(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    for mod in (pallas_attention, paged_attention):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


# -- the programs, from shapes ------------------------------------------------
def _shapes(topo):
    """(model, params, pools, sds): the bf16 tree and the pools as shapes on
    one described chip."""
    sh = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=HIDDEN, intermediate_size=14336,
        num_layers=LAYERS, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1e6, dtype=BF16)
    model = cfg.served_model()
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(BF16),
        llama.init_params(cfg, jax.random.PRNGKey(0))))
    pools = jax.eval_shape(lambda: model.make_pools(NB, BS))
    return model, on_chip(params), on_chip(pools), sds


def _decode_text(topo, N):
    model, params, pools, sds = _shapes(topo)
    fn = jax.jit(functools.partial(
        engine._paged_decode, model=model, n_steps=1,
        opts=ServeOpts(ragged=True), sample_flags=GREEDY),
        donate_argnums=(8,))
    return fn.lower(
        params, sds((N,), I32), sds((N,), I32), sds((N,), jnp.bool_),
        sds((N,), I32), sds((2,), jnp.uint32), sds((N,), jnp.bool_),
        sds((N, TABLE), I32), pools, sds((N,), F32), sds((N,), I32),
        sds((N,), F32), sds((N,), I32)).compile().as_text()


def _prefill_text(topo, B, S):
    model, params, pools, sds = _shapes(topo)
    fn = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY), donate_argnums=(4,))
    return fn.lower(
        params, sds((B, S), I32), sds((B, S // BS), I32), sds((B,), I32),
        pools, sds((B,), F32), sds((B,), I32), sds((B,), F32),
        sds((2,), jnp.uint32)).compile().as_text()


# -- reading the compiled text ------------------------------------------------
_INSTR = re.compile(r"(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_ARRAY = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\]\{([\d,]*)")
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}
_NO_WRITE = ("parameter", "tuple", "get-tuple-element", "bitcast")
_PREFETCH = ("slice-start", "slice-done", "copy-start", "copy-done",
             "ConcatBitcast")


def _arrays(ty):
    """[(dims, order of the dimensions, bytes)] of every array in a type."""
    out = []
    for dt, dims, order in _ARRAY.findall(ty):
        dims = tuple(int(d) for d in dims.split(",") if d)
        n = _WIDTH.get(dt, 4)
        for d in dims:
            n *= d
        out.append((dims, order, n))
    return out


def _entry(text):
    """{name: (result type, operation, operand names)} of ENTRY."""
    lines = text.splitlines()
    at = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY"))
    out = {}
    for ln in lines[at + 1:]:
        if ln.startswith("}"):
            break
        m = _INSTR.match(ln.strip())
        if m:
            name, ty, op, rest = m.groups()
            if op == "custom-call" and '"ConcatBitcast"' in rest:
                op = "ConcatBitcast"
            out[name] = (ty, op, re.findall(r"%([\w.\-]+)", rest))
    return out


def _big_orders(entry, name):
    return {o for _, o, n in _arrays(entry[name][0]) if n >= BIG}


def _weight_sized_writes(entry, pool_dims):
    """Names of ENTRY's instructions that write an array of 4 MiB or more:
    not the pools' write-back, and not a prefetch that keeps the order of
    the dimensions its operand has."""
    bad = []
    for name, (ty, op, operands) in entry.items():
        big = [a for a in _arrays(ty) if a[2] >= BIG and a[0] != pool_dims]
        if not big or op in _NO_WRITE:
            continue
        had = set().union(*(_big_orders(entry, o) for o in operands
                            if o in entry))
        if op in _PREFETCH and _big_orders(entry, name) <= had:
            continue
        bad.append(f"{name} = {ty[:80]} {op}")
    return bad


def _reads(entry, name, param):
    """Does ``name`` take ``param`` as it lies: directly, or through
    prefetches and bitcasts?"""
    ty, op, operands = entry[name]
    if name == param:
        return True
    if op not in _PREFETCH + ("bitcast", "get-tuple-element"):
        return False
    return any(_reads(entry, o, param) for o in operands if o in entry)


def _projections(entry, leaf, rows, out):
    """ENTRY's fusions that take ``params['layers'][leaf]`` as it lies and
    give ``rows`` rows of ``out`` columns."""
    param = next(n for n, (_, op, _) in entry.items()
                 if op == "parameter"
                 and n.startswith(f"params__layers____{leaf}__"))
    return [n for n, (ty, op, operands) in entry.items()
            if op == "fusion"
            and any(_reads(entry, o, param) for o in operands if o in entry)
            and any(d[-1:] == (out,) and n_ == rows * out * 2
                    for d, _, n_ in _arrays(ty))]


def _check(text, rows):
    entry = _entry(text)
    pool_dims = (LAYERS, NB, BS, 8, 128)
    assert _weight_sized_writes(entry, pool_dims) == []
    for leaf, out in (("wq", Q_OUT), ("wk", KV_OUT), ("wv", KV_OUT)):
        assert len(_projections(entry, leaf, rows, out)) == LAYERS, leaf
    # the yardstick of "as it lies": the weights the parent already read so
    assert len(_projections(entry, "wo", rows, HIDDEN)) == LAYERS


@pytest.mark.parametrize("slots", [4, 16])
def test_decode_reads_the_attention_weights_where_they_lie(topo, slots):
    text = _decode_text(topo, slots)
    assert "tpu_custom_call" in text           # the ragged walk is in it
    _check(text, slots)


def test_one_row_prefill_reads_the_attention_weights_where_they_lie(topo):
    _check(_prefill_text(topo, 1, 128), 128)
