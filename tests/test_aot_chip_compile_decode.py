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


# -- the third family: convolutions with a per-slot state, experts -----------
def _lfm2_shapes(topo, slots):
    """LFM2-8B-A1B's first period (conv conv attention conv: both dense
    layers, two expert layers) at the published widths, as shapes."""
    from paddle_tpu.models import lfm2_moe

    moe_dispatch = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
    sh = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    cfg = lfm2_moe.Lfm2MoeConfig(
        layer_types=lfm2_moe.PUBLISHED_LAYER_TYPES[:4], dtype=BF16)
    model = cfg.served_model()
    h, f, fe, E = (cfg.hidden_size, cfg.intermediate_size,
                   cfg.moe_intermediate_size, cfg.num_experts)
    norms = {"op_norm": (h,), "ffn_norm": (h,)}
    conv = {"w_in": (h, 3 * h), "conv_w": (h, 3), "w_out": (h, h)}
    attn = {"wq": (h, h), "wk": (h, 512), "wv": (h, 512), "wo": (h, h),
            "q_norm": (64,), "k_norm": (64,)}
    dense = {"w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    moe = {"router": (h, E), "e_gu": (E, h, 2 * fe), "e_down": (E, fe, h)}
    tree = lambda *parts: {n: sds(s, BF16) for p in parts
                           for n, s in p.items()}
    layers = [tree(norms, conv, dense), tree(norms, conv, dense),
              tree(norms, attn, moe), tree(norms, conv, moe)]
    for l in layers[2:]:
        l["expert_bias"] = sds((E,), F32)
    params = {"embed": sds((cfg.vocab_size, h), BF16), "layers": layers,
              "final_norm": sds((h,), BF16)}
    pools = jax.eval_shape(lambda: {**model.make_pools(LFM2_NB, BS),
                                    **model.make_state(slots)})
    pools = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), pools)
    return model, params, pools, sds, moe_dispatch


LFM2_NB = 12289     # the cell's pool: too large for the chip's fast memory


def _lfm2_big_writes(text):
    """Writes of 4 MiB or more that are neither a pool's write-back in
    place (the pool's own dims and layout: a pool re-laid out around a
    scatter has another) nor a prefetch of a weight into the chip's fast
    memory in slices."""
    entry = _entry(text)
    return _lfm2_relaid(entry) + [
        b for b in _weight_sized_writes(entry, (LFM2_NB, BS, 1024))
        if not b.endswith(_PREFETCH)]


def _lfm2_relaid(entry):
    """A pool in another layout than the parameter's."""
    lies = re.compile(r"bf16\[(1,)?12289,16,1024\]\{(3,2,1,0|2,1,0):"
                      r"T\(8,128\)\(2,1\)\}")
    return [f"{n} = {ty[:70]} {op}" for n, (ty, op, _) in entry.items()
            if "12289,16,1024]" in ty and not lies.search(ty)]


def test_lfm2_decode_writes_no_weight_sized_array(topo, monkeypatch):
    """32 slots (the walk's f32 partials over whole rows stay under the
    reader's 4 MiB) through convolutions, the flat walk and two expert
    layers: w_in's product stays [rows, 3h] behind its dot, the tied head
    contracts the embedding where it lies (no transposed copy of 268 MB),
    a layer's state is rewritten alone (0.5 MB) and never the stack, and a
    layer's pool of [V | K] rows takes the step's rows in place (over a
    pool with a leading layer axis, or with rows of two heads, XLA re-lays
    the whole pool out and back around a scatter: 0.8 GB each way)."""
    N = 32
    model, params, pools, sds, moe_dispatch = _lfm2_shapes(topo, N)
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    traced = jax.jit(functools.partial(
        engine._paged_decode, model=model, n_steps=1,
        opts=ServeOpts(ragged=True), sample_flags=GREEDY),
        donate_argnums=(8,)).trace(
        params, sds((N,), I32), sds((N,), I32), sds((N,), jnp.bool_),
        sds((N,), I32), sds((2,), jnp.uint32), sds((N,), jnp.bool_),
        sds((N, TABLE), I32), pools, sds((N,), F32), sds((N,), I32),
        sds((N,), F32), sds((N,), I32))
    assert _gmm_tiles(traced) == LFM2_TILES
    text = traced.lower().compile().as_text()
    assert "%lfm2_ragged_walk" in text and "%gmm" in text
    assert _lfm2_big_writes(text) == []


@pytest.mark.parametrize("history", [0, TABLE // 2],
                         ids=["first", "continuing"])
def test_lfm2_one_row_prefill_writes_no_weight_sized_array(topo, monkeypatch,
                                                           history):
    """A piece of 128 tokens, from zero state and from a carried one with
    a history: the same reading. The history is TABLE / 2 blocks wide, so
    that its own gathered rows (one array of [V | K] rows, 2.6 MB) stay
    under the reader's 4 MiB: they are the history, not a weight or a
    pool."""
    model, params, pools, sds, moe_dispatch = _lfm2_shapes(topo, 64)
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    args = [params, sds((1, 128), I32), sds((1, 128 // BS), I32),
            sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
            sds((1,), F32), sds((2,), jnp.uint32)]
    if history:
        args += [sds((1,), I32), sds((1, history), I32)]
    text = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY, prefix_nbk=history), donate_argnums=(4,)).lower(
        *args, slot=sds((1,), I32)).compile().as_text()
    assert "%lfm2_prefill_chunk" in text
    assert ("%lfm2_prefill_history" in text) == bool(history)
    assert _lfm2_big_writes(text) == []


def _dec_operands(sds, N, table, ring=None):
    """``_paged_prefill``'s ``dec``: the decode call's operands as shapes."""
    dec = (sds((N,), I32), sds((N,), I32), sds((N,), jnp.bool_),
           sds((N,), I32), sds((2,), jnp.uint32), sds((N,), jnp.bool_),
           sds((N, table), I32), sds((N,), F32), sds((N,), I32),
           sds((N,), F32), sds((N,), I32))
    return dec + ((sds((N, ring), I32),) if ring else ())


def _gmm_calls(text):
    return [n for n, (_ty, op, _) in _entry(text).items()
            if op == "custom-call" and n.startswith("gmm")]


_GMM_KERNEL = re.compile(
    r"Ref\{bf16\[128,(\d+)\]\}\s+\w+:Ref\{bf16\[(\d+),(\d+)\]\}\s+"
    r"\w+:Ref\{bf16\[128,(\d+)\]\}\s+\w+:Ref<vmem>\{f32\[128,(\d+)\]\}")


def _gmm_tiles(traced):
    """{(contraction tile, column tile)} of a traced program's grouped
    matmuls, read from the kernel's own operands in the jaxpr (the compiled
    text holds the kernel as bytes): a row tile ``[128, tk]``, a weight
    block ``[tk, tn]``, an output tile and an accumulator ``[128, tn]``."""
    tiles = set()
    for tk, wk, wn, tn, acc in _GMM_KERNEL.findall(str(traced.jaxpr)):
        assert tk == wk and wn == tn == acc
        tiles.add((int(tk), int(tn)))
    return tiles


# what ``_static_gmm``'s rule answers at the published widths (PR 41): the
# whole contraction, and the widest column tile that divides the side and
# fits; a tile that did not fit would fail these compiles
LFM2_TILES = {(2048, 896), (1792, 1024)}
MEL_TILES = {(2304, 896), (896, 2304)}


@pytest.mark.parametrize("history", [0, TABLE // 2],
                         ids=["first", "continuing"])
def test_lfm2_piece_with_the_decode_rows_is_one_pass_over_the_experts(
        topo, monkeypatch, history):
    """The ONE program of a step that has a piece (PR 36): a piece of 1024
    tokens (the cell's bucket) and a decode step of the cell's 64 slots.
    Both kinds' kernels are in it under their names, every expert layer has
    ONE grouped-matmul pair over 1,088 x 4 pairs on tile boundaries (4,352 +
    32 x 128 rows), and now that two write-backs share a program no pool is
    re-laid out around either scatter, no weight-sized array is written."""
    N = 64
    model, params, pools, sds, moe_dispatch = _lfm2_shapes(topo, N)
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    S = 1024
    args = [params, sds((1, S), I32), sds((1, S // BS), I32),
            sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
            sds((1,), F32), sds((2,), jnp.uint32)]
    if history:
        args += [sds((1,), I32), sds((1, history), I32)]
    traced = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY, prefix_nbk=history), donate_argnums=(4,)).trace(
        *args, slot=sds((1,), I32), dec=_dec_operands(sds, N, TABLE))
    assert _gmm_tiles(traced) == LFM2_TILES
    text = traced.lower().compile().as_text()
    assert "%lfm2_prefill_chunk" in text and "%lfm2_ragged_walk" in text
    assert ("%lfm2_prefill_history" in text) == bool(history)
    entry = _entry(text)
    gmm = _gmm_calls(text)
    assert len(gmm) == 2 * 2, gmm            # gate|up and down, two layers
    assert all("bf16[8448," in entry[n][0] for n in gmm), \
        [entry[n][0] for n in gmm]
    # (a piece of 1024 x 2048 is itself 4 MiB, the reader's weight size:
    # what it writes of that size is read at a piece of 128, above)
    assert _lfm2_relaid(entry) == []
    stacks = {ty.split("{")[0] for ty, _op, _ in entry.values()} & {
        "bf16[32,2048,3584]", "bf16[32,1792,2048]", "bf16[65536,2048]"}
    assert stacks == set() or all(
        op in _NO_WRITE + _PREFETCH for ty, op, _ in entry.values()
        if ty.split("{")[0] in stacks)


# -- the fourth family: window layers beside full ones ------------------------
MEL_NB, MEL_NBW, MEL_TABLE, MEL_RING = 24577, 2113, 2112, 65


def _mellum_shapes(topo):
    """Mellum2's first period (three window layers and a full one) at the
    published widths and the ``repo-offline`` cell's pools, as shapes."""
    from paddle_tpu.models import mellum

    moe_dispatch = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
    sh = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    cfg = mellum.MellumConfig(
        layer_types=mellum.PUBLISHED_LAYER_TYPES[:4], dtype=BF16)
    model = cfg.served_model()
    h, f, E, V = (cfg.hidden_size, cfg.moe_intermediate_size,
                  cfg.num_experts, cfg.vocab_size)
    layer = {"wq": (h, 4096), "wk": (h, 512), "wv": (h, 512),
             "wo": (4096, h), "q_norm": (128,), "k_norm": (128,),
             "attn_norm": (h,), "ffn_norm": (h,), "router": (h, E),
             "e_gu": (E, h, 2 * f), "e_down": (E, f, h)}
    params = {"embed": sds((V, h), BF16), "head": sds((V, h), BF16),
              "final_norm": sds((h,), BF16),
              "layers": [{n: sds(s, BF16) for n, s in layer.items()}
                         for _ in range(4)]}
    pools = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.make_pools(MEL_NB, BS, nb_window=MEL_NBW)))
    return model, params, pools, sds, moe_dispatch


# a pool as it lies: the parameter's dims and layout (S(1): a window pool
# of 69 MB prefetched whole into the chip's fast memory before its walk, in
# the layout it has; the walks of 32 full windows read as much)
_MEL_POOL = re.compile(r"(24577|2113),16,1024\]")
_MEL_LIES = re.compile(r"bf16\[(1,)?(24577|2113),16,1024\]\{(3,2,1,0|2,1,0):"
                       r"T\(8,128\)\(2,1\)(S\(1\))?\}")


def _mellum_big_writes(text):
    """Writes of 4 MiB or more that are neither a pool's write-back in
    place (a pool re-laid out around a scatter has another layout than the
    parameter's: rows of [4, 128], the dense family's layout at 4 KV
    heads, were re-laid out whole around the prefill's scatter, which is
    why a row holds all of a token's heads) nor a weight's prefetch."""
    entry = _entry(text)
    relaid = [f"{n} = {ty[:70]} {op}" for n, (ty, op, _) in entry.items()
              if _MEL_POOL.search(ty) and not _MEL_LIES.search(ty)]
    big = [b for dims in ((MEL_NB, BS, 1024), (MEL_NBW, BS, 1024))
           for b in _weight_sized_writes(entry, dims)]
    return relaid + [b for b in set(big) if big.count(b) == 2
                     and not b.endswith(_PREFETCH)]


def test_mellum_decode_writes_no_weight_sized_array(topo, monkeypatch):
    """32 slots through three window layers and a full one: both walks are
    in the program under their names, the window kind's table is an
    operand of its own, the untied head contracts its matrix where it lies
    (no transposed copy of 453 MB), every pool takes the step's rows in
    place, and Mosaic accepts the walk's fourth scalar operand."""
    N = 32
    model, params, pools, sds, moe_dispatch = _mellum_shapes(topo)
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    traced = jax.jit(functools.partial(
        engine._paged_decode, model=model, n_steps=1,
        opts=ServeOpts(ragged=True), sample_flags=GREEDY),
        donate_argnums=(8,)).trace(
        params, sds((N,), I32), sds((N,), I32), sds((N,), jnp.bool_),
        sds((N,), I32), sds((2,), jnp.uint32), sds((N,), jnp.bool_),
        sds((N, MEL_TABLE), I32), pools, sds((N,), F32), sds((N,), I32),
        sds((N,), F32), sds((N,), I32), sds((N, MEL_RING), I32))
    assert _gmm_tiles(traced) == MEL_TILES
    text = traced.lower().compile().as_text()
    assert text.count("%mellum_walk_full") >= 1
    assert text.count("%mellum_walk_window") >= 3 and "%gmm" in text
    assert _mellum_big_writes(text) == []


@pytest.mark.parametrize("history", [0, MEL_TABLE],
                         ids=["first", "continuing"])
def test_mellum_one_row_prefill_writes_no_weight_sized_array(
        topo, monkeypatch, history):
    """A piece of 1024 tokens (the cell's bucket), alone and with a
    history: a full layer gathers the slot's whole table (its length a
    runtime operand), a window layer the ring's 65 blocks under the band;
    the gathered histories (69 MB a full layer at this width) are the
    history, not a weight or a pool, and are told apart by their dims."""
    model, params, pools, sds, moe_dispatch = _mellum_shapes(topo)
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    S = 1024
    args = [params, sds((1, S), I32), sds((1, S // BS), I32),
            sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
            sds((1,), F32), sds((2,), jnp.uint32)]
    win = {"blk_ids": sds((1, S // BS), I32)}
    if history:
        args += [sds((1,), I32), sds((1, history), I32)]
        win.update(ctx_tbl=sds((1, MEL_RING), I32),
                   ctx_start=sds((1,), I32))
    text = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY, prefix_nbk=history), donate_argnums=(4,)).lower(
        *args, win=win).compile().as_text()
    assert "%mellum_prefill_chunk" in text and "%gmm" in text
    for name in ("%mellum_history_full", "%mellum_history_window"):
        assert (name in text) == bool(history)
    assert [n for n, (ty, _op, _) in _entry(text).items()
            if _MEL_POOL.search(ty) and not _MEL_LIES.search(ty)] == []


@pytest.mark.parametrize("history", [0, MEL_TABLE],
                         ids=["first", "continuing"])
def test_mellum_piece_with_the_decode_rows_is_one_pass_over_the_experts(
        topo, monkeypatch, history):
    """The ONE program of a step that has a piece (PR 36) at the
    ``repo-offline`` cell's shapes: a piece of 1024 tokens and a decode
    step of 32 slots. Both kinds' walks and the piece's kernels are in it,
    every layer has ONE grouped-matmul pair over 1,056 x 8 = 8,448 pairs in
    ONE pass (8,448 + 64 x 128 rows: over the old bound of 8,192 pairs), and
    neither kind's pool is re-laid out around the two write-backs."""
    N = 32
    model, params, pools, sds, moe_dispatch = _mellum_shapes(topo)
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    S = 1024
    args = [params, sds((1, S), I32), sds((1, S // BS), I32),
            sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
            sds((1,), F32), sds((2,), jnp.uint32)]
    win = {"blk_ids": sds((1, S // BS), I32)}
    if history:
        args += [sds((1,), I32), sds((1, history), I32)]
        win.update(ctx_tbl=sds((1, MEL_RING), I32),
                   ctx_start=sds((1,), I32))
    traced = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY, prefix_nbk=history), donate_argnums=(4,)).trace(
        *args, win=win, dec=_dec_operands(sds, N, MEL_TABLE, MEL_RING))
    assert _gmm_tiles(traced) == MEL_TILES
    text = traced.lower().compile().as_text()
    assert "%mellum_prefill_chunk" in text
    assert text.count("%mellum_walk_full") >= 1
    assert text.count("%mellum_walk_window") >= 3
    for name in ("%mellum_history_full", "%mellum_history_window"):
        assert (name in text) == bool(history)
    entry = _entry(text)
    gmm = _gmm_calls(text)
    assert len(gmm) == 2 * 4, gmm            # gate|up and down, four layers
    assert all("bf16[16640," in entry[n][0] for n in gmm), \
        [entry[n][0] for n in gmm]
    assert [n for n, (ty, _op, _) in entry.items()
            if _MEL_POOL.search(ty) and not _MEL_LIES.search(ty)] == []


# -- the families' programs are the parent's ----------------------------------
# sha256 (first 16 hex digits) of the programs' jaxpr text, kernels lowered,
# with source positions and addresses taken out, read on the PARENT of PR 35
# (8908c52) and equal on its change: the walk's start, the flash kernel's
# band and the engine's window operands are absent operands for a model of
# one kind, so its programs are the parent's to the letter. A later PR that
# means to change one of these programs prints the new text's hash here.
# PR 36 (a step's last piece carries the decode rows): every hash above the
# ``piece+rows`` entries is its parent's (c8c7cc0), Mellum2's read there for
# the first time: the layers' split into ``prefill_mix`` / ``decode_mix`` /
# ``ffn`` keeps the order of every operation of the lone programs, and
# ``_paged_prefill`` without ``dec`` is the program it was. The
# ``piece+rows`` entries are the ONE program of a step that has a piece (a
# history's operands always: the engine runs no other form), new in PR 36.
# PR 41 (the grouped matmul's column tile by what divides and fits) leaves
# every hash: the dense family runs no grouped matmul, the latent one takes
# the other branch, and at these widths (sides of 256) the old rule and the
# new answer one column tile alike. What the rule answers at the published
# widths is held above (``LFM2_TILES``, ``MEL_TILES``).
# PR 43 (``flash_partial``'s tile: a KV head's whole query group in one grid
# step, the mask only in the tiles a mask cuts, the statistics left as they
# lie in their scratch) means to change every program that calls the kernel:
# the nine ``prefill0`` / ``prefill16`` / ``piece+rows16`` hashes of the
# latent, LFM2 and Mellum2 families are read on its tree (their
# ``pallas_call``s have another grid, other blocks and a body of two
# branches). ``dense.*`` and every ``*.decode`` hash are the parent's
# (e87d884): the dense family's prefill is ``paged_prefill``'s and no decode
# program calls the kernel, which is this test's word that the bypassing
# cells' programs did not move.
# PR 44 (a latent piece's history in the expanded form: ``latent_history_
# partial`` in place of ``_absorb``, ``flash_partial(v_cols=)`` and the
# ``W_UV`` einsum after it) means to change the latent family's two programs
# with a history, ``latent.prefill16`` and ``latent.piece+rows16``, read on
# its tree. The other thirteen are the parent's (caf9c12), ``latent.
# prefill0`` and the six of LFM2 and Mellum2 among them, though ``flash_
# partial`` lost its ``v_cols`` branch and shares its softmax step with the
# new kernel: a static Python branch's going leaves their text as it was.
PARENT_PROGRAMS = {
    "dense.decode": "7921a0ad28675c6f", "dense.prefill0": "1cad0efaa529c317",
    "dense.prefill16": "53e7a3a2e58a42bd",
    "latent.decode": "387d28af185258ef",
    "latent.prefill0": "983d6d18051b9133",
    "latent.prefill16": "06e7ddbc8fe9f377",
    "lfm2.decode": "b4d382c4e1eff7be", "lfm2.prefill0": "448f46f78c37c4c9",
    "lfm2.prefill16": "e9e2632e826ea0d5",
    "mellum.decode": "e0f446c50b3116c4",
    "mellum.prefill0": "628fb8245afb434c",
    "mellum.prefill16": "8a0ea42f56e669f5",
    "latent.piece+rows16": "e6b4cc88ea56074b",
    "lfm2.piece+rows16": "6864cc65ce4c6c1b",
    "mellum.piece+rows16": "e8b6cddd43734101"}


def _small_family(name):
    """(model, params as shapes): a family at widths the Mosaic grouped
    matmul takes (multiples of 128), small enough to trace in a second."""
    from benchmark import manifest

    if name == "dense":
        cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=512,
            num_layers=2, num_heads=2, num_kv_heads=1, head_dim=128,
            dtype=BF16)
        return cfg.served_model(), jax.eval_shape(
            lambda: jax.tree_util.tree_map(
                lambda a: a.astype(BF16),
                llama.init_params(cfg, jax.random.PRNGKey(0))))
    family = {"latent": "deepseek_v2", "lfm2": "lfm2_moe",
              "mellum": "mellum"}[name]
    fam = manifest.load_family(family)
    man = manifest.Manifest()
    doc = next(d for d in (man.config(c["name"]) for c in man.doc["configs"])
               if d["family"] == family)
    m = {**doc, **fam.tiny(doc), "moe_intermediate_size": 128,
         "hidden_size": 256}
    return fam.program_config(m).served_model(), jax.eval_shape(
        lambda: fam.make_params(m, jax.random.PRNGKey(0), BF16))


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_the_families_programs_are_the_parents(program, monkeypatch):
    import hashlib

    family, which = program.split(".")
    moe_dispatch = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    model, params = _small_family(family)
    N, NB_, MB, RING = 4, 65, 16, 3
    sds = jax.ShapeDtypeStruct
    window = bool(getattr(model, "window_entries", ()))
    pools = jax.eval_shape(lambda: {
        **(model.make_pools(NB_, BS, nb_window=N * RING + 1) if window
           else model.make_pools(NB_, BS)),
        **(model.make_state(N) if model.state_entries else {})})
    dec = _dec_operands(lambda shape, dt: sds(shape, dt), N, MB,
                        RING if window else None)
    if which == "decode":
        text = jax.make_jaxpr(functools.partial(
            engine._paged_decode, model=model, n_steps=1,
            opts=ServeOpts(ragged=True), sample_flags=GREEDY))(
            params, *dec[:7], pools, *dec[7:])
    else:
        rows = which.startswith("piece+rows")
        hist, S = int(which[len("piece+rows" if rows else "prefill"):]), 128
        args = [params, sds((1, S), I32), sds((1, S // BS), I32),
                sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
                sds((1,), F32), sds((2,), jnp.uint32)]
        args += [sds((1,), I32), sds((1, hist), I32)] if hist else []
        kw = {}
        if model.state_entries:
            kw["slot"] = sds((1,), I32)
        if window:
            kw["win"] = {"blk_ids": sds((1, S // BS), I32)}
            if hist:
                kw["win"].update(ctx_tbl=sds((1, RING), I32),
                                 ctx_start=sds((1,), I32))
        if rows:
            kw["dec"] = dec
        text = jax.make_jaxpr(functools.partial(
            engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
            sample_flags=GREEDY, prefix_nbk=hist))(*args, **kw)
    text = re.sub(r" at [^\s\]\)]+:\d+", "", str(text))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_PROGRAMS[program]
