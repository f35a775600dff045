"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

The sandbox has no accelerator, but the TPU compiler is installed and
compiles for a topology that is described and not attached
(``jax.experimental.topologies``). Interpret-mode tests cannot see what
Mosaic refuses — a slice not aligned to the tiling, a block shape the
lowering rejects, a removed Pallas name — so every kernel that ``auto``
selection can reach on a TPU is compiled here at the widths
``chip_smoke.py`` runs (Llama-3-8B: 32 heads / 8 KV heads, head_dim 128,
hidden 4096, FFN 14336, KV block 16). Nothing executes: a pass says the
chip's compiler accepts the kernel, never that a result or a time is
right.

The kernels pick interpret mode from ``jax.default_backend()``, which is
the CPU here, so the tests steer ``_interpret`` themselves. Kernels
withdrawn from selection keep their compile as ``xfail(strict=True)``
with the compiler's message: the day Mosaic accepts one, the strict
xfail fails and the withdrawal can be undone.
"""
import functools
import importlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# import_module: kernels/__init__ re-exports a FUNCTION named
# paged_attention, which shadows the module on attribute access
_mod = lambda name: importlib.import_module("paddle_tpu.kernels." + name)
moe_fused = _mod("moe_fused")
paged_attention = _mod("paged_attention")
pallas_attention = _mod("pallas_attention")

# Llama-3-8B widths (models/llama.py LlamaConfig defaults), depth cut
HQ, HKV, D = 32, 8, 128
BS, NB, LAYERS = 16, 1024, 2
BF16 = jnp.bfloat16


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
    """Mosaic lowering instead of the interpreter, and the persistent
    compile cache off: a described-topology executable is written to the
    cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    for mod in (pallas_attention, paged_attention):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


class Refused(Exception):
    """The chip's compiler refused a kernel with the recorded message."""


def refused(message):
    """Strict xfail for a kernel withdrawn from selection, held to the
    compiler's message kept beside the kernel: the test errors if the
    compile fails with anything else, and fails (XPASS) the day it
    succeeds — the withdrawal can then be undone."""
    def deco(test):
        @functools.wraps(test)
        def run(*a, **kw):
            try:
                test(*a, **kw)
            except Exception as e:
                if message not in str(e):
                    raise
                raise Refused(message) from e
        return pytest.mark.xfail(strict=True, raises=Refused,
                                 reason=message)(run)
    return deco


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _one(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_flash_attention_fwd_bwd(topo):
    def loss(q, k, v):
        out = pallas_attention.flash_attention_fwd(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), _one(topo),
                 ((2, 2048, HQ, D), BF16), ((2, 2048, HKV, D), BF16),
                 ((2, 2048, HKV, D), BF16))
    # forward (rebuilt for the residuals), dQ, dK/dV
    assert c.as_text().count("tpu_custom_call") >= 3


def test_paged_append_token_and_blocks(topo):
    pool = ((LAYERS, NB, BS, HKV, D), BF16)
    _compile(lambda kp, vp, kn, vn, b, o: paged_attention.paged_append_token(
                 kp, vp, kn, vn, b, o, layer=1),
             _one(topo), pool, pool, ((8, HKV, D), BF16), ((8, HKV, D), BF16),
             ((8,), jnp.int32), ((8,), jnp.int32))
    _compile(lambda kp, vp, kb, vb, ids: paged_attention.paged_append_blocks(
                 kp, vp, kb, vb, ids, layer=1),
             _one(topo), pool, pool, ((4, BS, HKV, D), BF16),
             ((4, BS, HKV, D), BF16), ((4,), jnp.int32))


def _ragged_specs(n, kv_dtype, d=D):
    specs = [((n, HQ, d), BF16),
             ((LAYERS, NB, BS, HKV, d), kv_dtype),
             ((LAYERS, NB, BS, HKV, d), kv_dtype),
             ((n, 128), jnp.int32), ((n,), jnp.int32)]
    if kv_dtype == jnp.int8:
        specs += [((LAYERS, NB, BS, HKV), jnp.float32)] * 2
    return specs


def _ragged(q, kp, vp, tbl, lens, ks=None, vs=None, mesh=None):
    return paged_attention.ragged_decode_partial(
        q, kp, vp, tbl, lens, layer=1, ks_pool=ks, vs_pool=vs, mesh=mesh)


def test_ragged_walk_bf16(topo):
    _compile(_ragged, _one(topo), *_ragged_specs(8, BF16))


@pytest.mark.parametrize("hkv,kv_dtype", [(8, BF16), (4, BF16),
                                          (4, jnp.float32)],
                         ids=["cell", "tp2-shard", "tp2-shard-f32"])
def test_ragged_walk_cell_shapes(topo, hkv, kv_dtype):
    """The chunked walk at the benchmark's serving cells' shapes (16 slots,
    32 query heads on 8 KV heads, head dim 128, blocks of 16, a table 160
    wide) and at what one shard of a tp=2 engine sees of them (4 KV heads;
    chip_smoke.py's tp engine keeps float32 pools): the flat view of a
    chunk and the all-heads dot have to hold at both."""
    g = HQ // HKV
    chunk = paged_attention._walk_chunk_blocks(
        BS, hkv, D, jnp.dtype(kv_dtype).itemsize, 160)
    assert chunk * BS * hkv == 1024, chunk     # 128 tokens x 8 heads' worth
    pool = ((LAYERS, NB, BS, hkv, D), kv_dtype)
    _compile(_ragged, _one(topo), ((16, g * hkv, D), BF16), pool, pool,
             ((16, 160), jnp.int32), ((16,), jnp.int32))


@refused(paged_attention.ragged_tpu_refusal(D, kv_int8=True))
def test_ragged_walk_int8_kv(topo):
    _compile(_ragged, _one(topo), *_ragged_specs(8, jnp.int8))


@refused(paged_attention.ragged_tpu_refusal(64, kv_int8=False))
def test_ragged_walk_head_dim_64(topo):
    """Found on the chip, not by the planner: the walk compiles only for
    head dims that fill the 128-lane tile, so the engine's auto selects it
    by that shape."""
    _compile(_ragged, _one(topo), *_ragged_specs(8, BF16, d=64))


def test_flat_walk_head_dim_64(topo):
    """Head dim 64 runs on the chip where a pool row holds ALL of a token's
    heads, values then keys (2 x 8 x 64 = 1024 lanes, one pool a layer;
    models/lfm2_moe.py): the latent walk as it is, the query in its own
    head's key columns and zero over the values. At the
    ``rag-offline`` cell's shapes: 64 slots, 32 query heads on 8 KV heads
    of 64, a pool of 12,289 blocks of 16, a table 576 wide."""
    c = _compile(
        lambda q, pool, tbl, lens: paged_attention.flat_decode_partial(
            q, pool, tbl, lens, n_kv=8, name="lfm2_ragged_walk"),
        _one(topo), ((64, 32, 64), BF16), ((1, 12289, BS, 1024), BF16),
        ((64, 576), jnp.int32), ((64,), jnp.int32))
    assert "%lfm2_ragged_walk" in c.as_text()   # the name a trace shows


def test_flash_partial_head_dim_64(topo):
    """The prefill's blockwise attention at heads of 64 as they are (a
    block's minor dim may be the array's own): a piece of 1024, causal,
    and a history of 9,216 rows with a runtime length."""
    c = _compile(
        lambda q, k, v: pallas_attention.flash_partial(
            q, k, v, scale=0.125, causal=True, name="lfm2_prefill_chunk"),
        _one(topo), ((32, 1024, 64), BF16), ((8, 1024, 64), BF16),
        ((8, 1024, 64), BF16))
    assert "%lfm2_prefill_chunk" in c.as_text()
    c = _compile(
        lambda q, k, v, n: pallas_attention.flash_partial(
            q, k, v, scale=0.125, kv_len=n, name="lfm2_prefill_history"),
        _one(topo), ((32, 1024, 64), BF16), ((8, 9216, 64), BF16),
        ((8, 9216, 64), BF16), ((8,), jnp.int32))
    assert "%lfm2_prefill_history" in c.as_text()


@pytest.mark.parametrize("tokens,rows", [(64, 256), (1024, 8192)],
                         ids=["decode", "piece"])
def test_held_expert_ffn_narrow_experts(topo, monkeypatch, tokens, rows):
    """All 32 of LFM2's experts held (2048 x 1792, top-4): the grouped
    matmul's whole-contraction tile of 128 rows in both regimes, chosen
    from the shapes, never timed. A decode step of 64 slots keeps its 256
    pairs packed (the program the parent had, but for the fifth count); a
    one-row piece of 1024 tokens lays its 4,096 pairs out on tile
    boundaries, 4,096 + 32 x 128 static rows."""
    import importlib

    moe_dispatch = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    text = _compile(
        lambda x, g, i, v, gu, dn: moe_dispatch.held_expert_ffn(
            x, g, i, v, gu, dn, 0),
        _one(topo), ((tokens, 2048), BF16), ((tokens, 4), jnp.float32),
        ((tokens, 4), jnp.int32), ((tokens,), jnp.bool_),
        ((32, 2048, 3584), BF16), ((32, 1792, 2048), BF16)).as_text()
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln
             and "%gmm" in ln]
    # gate|up and down, each over the regime's static rows, and no other
    assert sorted(ln.split(" = ")[1].split("{")[0] for ln in calls) == [
        f"bf16[{rows},2048]", f"bf16[{rows},3584]"], calls
    # the scalar-prefetched tile ids: rows / 128 tiles + 31 groups' seams
    assert all(f"s32[{rows // 128 + 31}]" in ln for ln in calls)
    if rows == 256:
        assert "[8192," not in text and "[4352," not in text


@pytest.mark.parametrize("starts", [False, True], ids=["full", "window"])
def test_flat_walk_with_a_start(topo, starts):
    """Mellum2's two walks at the ``repo-offline`` cell's shapes: 32 slots,
    32 query heads on 4 KV heads of 128 in one row of 1024 lanes; a full
    layer over a pool of 24,577 blocks and a table 2,112 wide, a window
    layer over its own pool of 2,113 blocks and a RING of 65 columns, with
    a fourth scalar operand (the start) that Mosaic must take."""
    nb, width = (2113, 65) if starts else (24577, 2112)
    specs = [((32, 32, 128), BF16), ((1, nb, BS, 1024), BF16),
             ((32, width), jnp.int32), ((32,), jnp.int32)]
    name = "mellum_walk_window" if starts else "mellum_walk_full"
    if starts:
        fn = lambda q, pool, tbl, lens, st: \
            paged_attention.flat_decode_partial(
                q, pool, tbl, lens, n_kv=4, starts=st, name=name)
        specs.append(((32,), jnp.int32))
    else:
        fn = lambda q, pool, tbl, lens: paged_attention.flat_decode_partial(
            q, pool, tbl, lens, n_kv=4, name=name)
    assert "%" + name in _compile(fn, _one(topo), *specs).as_text()


@pytest.mark.parametrize("keys,causal", [(1536, False), (1024, True)],
                         ids=["history", "chunk"])
def test_flash_partial_with_a_band(topo, keys, causal):
    """The banded blockwise attention at Mellum2's shapes: a piece of 1024
    queries of 32 heads against the window's gathered history (1,536 rows:
    the ring's 1,040 padded to the key tile) under a key length and a
    lower bound, both runtime operands; and a piece banded inside itself
    (what a bucket longer than the window runs)."""
    c = _compile(
        lambda q, k, v, n, lo: pallas_attention.flash_partial(
            q, k, v, scale=0.088, causal=causal, kv_len=n, band_lo=lo,
            name="mellum_history_window"),
        _one(topo), ((32, 1024, 128), BF16), ((4, keys, 128), BF16),
        ((4, keys, 128), BF16), ((4,), jnp.int32), ((4,), jnp.int32))
    assert "%mellum_history_window" in c.as_text()


@pytest.mark.parametrize("name,heads,keys,causal,band", [
    ("afmoe_prefill_chunk", (48, 8), 1024, True, False),
    ("afmoe_history_window", (48, 8), 4608, False, True),
    ("afmoe_history_full", (48, 8), 34816, False, False),
    ("mellum_history_full", (32, 4), 33792, False, False)],
    ids=["trinity-chunk", "trinity-window", "trinity-full", "mellum-full"])
def test_flash_partial_with_a_query_group_in_the_tile(topo, name, heads,
                                                      keys, causal, band):
    """The tile that holds a KV head's whole query group, at the tile the
    rule chooses from the shapes (``flash_tiles``), for the tallest
    groups served: Trinity's 6 query heads a KV head (a piece of 1024,
    causal; the window's 4,608 gathered keys under a band; the full
    layers' table of 34,816 keys) and Mellum2's 8 over 33,792 keys.
    Mosaic takes the stacked tile (the heads' rows merged into one
    matmul's, split again under the edge tiles' mask) and its fast-memory
    footprint; the kernel keeps the name the trace readers match."""
    G, Gk = heads
    specs = [((G, 1024, 128), BF16), ((Gk, keys, 128), BF16),
             ((Gk, keys, 128), BF16), ((Gk,), jnp.int32)]
    if band:
        specs.append(((Gk,), jnp.int32))
    c = _compile(
        lambda q, k, v, n, lo=None: pallas_attention.flash_partial(
            q, k, v, scale=0.088, causal=causal, kv_len=n, band_lo=lo,
            name=name), _one(topo), *specs)
    assert "%" + name in c.as_text()


@pytest.mark.parametrize("tokens,rows", [(32, 256), (1024, 16384)],
                         ids=["decode", "piece"])
def test_held_expert_ffn_sixty_four_experts(topo, monkeypatch, tokens, rows):
    """All 64 of Mellum2's experts held (2304 x 896, top-8): the whole
    contraction in one tile and the widest column tile that divides the
    side and fits the kernel's fast memory (896 of 1792, all of 2304; 256
    until PR 41), chosen from the shapes, never timed. A decode
    step of 32 slots keeps its 256 pairs packed; a piece of 1024 tokens is
    8,192 pairs, ONE pass, laid out on tile boundaries: 8,192 + 64 x 128
    static rows."""
    moe_dispatch = importlib.import_module("paddle_tpu.kernels.moe_dispatch")
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    text = _compile(
        lambda x, g, i, v, gu, dn: moe_dispatch.held_expert_ffn(
            x, g, i, v, gu, dn, 0),
        _one(topo), ((tokens, 2304), BF16), ((tokens, 8), jnp.float32),
        ((tokens, 8), jnp.int32), ((tokens,), jnp.bool_),
        ((64, 2304, 1792), BF16), ((64, 896, 2304), BF16)).as_text()
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln
             and "%gmm" in ln]
    # gate|up and down, each once (one pass) over the regime's static rows
    assert sorted(ln.split(" = ")[1].split("{")[0] for ln in calls) == [
        f"bf16[{rows},1792]", f"bf16[{rows},2304]"], calls
    assert "conditional(" not in text          # no second pass to choose


def test_ragged_walk_tp2(topo):
    """The shard_mapped walk on two described devices: pools sharded on
    the KV-head axis, tables and lengths replicated."""
    mesh = Mesh(np.asarray(topo.devices[:2]), ("tp",))
    specs = _ragged_specs(8, BF16)
    shard = [P(None, "tp", None), P(None, None, None, "tp", None),
             P(None, None, None, "tp", None), P(), P()]
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, p))
            for (s, d), p in zip(specs, shard)]
    compiled = jax.jit(
        lambda *a: _ragged(*a, mesh=mesh)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@refused(moe_fused.GATHER_GMM_TPU_REFUSAL)
def test_moe_gather_gmm(topo):
    tm = moe_fused._KTM
    _compile(lambda x, idx, rhs, gid: moe_fused.gather_gmm(
                 x, idx, rhs, gid, tm=tm),
             _one(topo), ((4096, 2048), BF16), ((64 * tm,), jnp.int32),
             ((64, 2048, 2048), BF16), ((64,), jnp.int32))
