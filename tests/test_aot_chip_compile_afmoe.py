"""Compile Trinity's (``afmoe``) serving programs WHOLE for a DESCRIBED TPU
v5e, in ``tests/test_aot_chip_compile_decode.py``'s manner: nothing
executes. The programs are the ``agent-offline`` cell's own: the
configuration file's five layers (a dense window layer, three window expert
layers, a full one) at the published widths, 32 held experts of 256, 32
slots, a full pool of 32,768 blocks under a table 2,176 wide, window pools
of 32 rings of 257 blocks.

What the compiled text must show. Both walks and the three flash kernels
are in the programs under this model's names, and Mosaic takes the flat
walk at a row of 2,048 lanes with its fourth scalar operand. A pool lies as
its parameter lies (rows of ``[V | K]`` scatter in place: no pool is re-laid
out around a write-back), no weight-sized array is written (the gate's
projection, a fifth as large as ``W_q``, is read where it lies as the other
four are), one expert takes BOTH rules of the grouped matmul's tiling
(gate|up 3072 x 6144: (512, 1024); down 3072 x 3072: the whole contraction,
1,024 columns), and the programs fit the chip beside the cell's weights and
pools."""
import functools
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from paddle_tpu.models.llama_served import ServeOpts
from paddle_tpu.serving import engine
from paddle_tpu.serving.window_ledger import WindowLedger
from test_aot_chip_compile_decode import (_PREFETCH, _dec_operands, _entry,
                                          _gmm_calls, _gmm_tiles,
                                          _weight_sized_writes)

_mod = lambda name: importlib.import_module("paddle_tpu.kernels." + name)
BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
GREEDY = (False, False, False)
MAN = manifest.Manifest()
MODEL = MAN.config("trinity-large-preview-serve-ep8")
FAM = manifest.load_family("afmoe")
SV = MODEL["serve"]
N, BS, PIECE = SV["max_slots"], SV["block_size"], SV["prefill_chunk"]
NB, TABLE = SV["num_blocks"] + 1, SV["max_model_len"] // BS
LEDGER = WindowLedger(N, MODEL["sliding_window"], BS)
NBW, RING = LEDGER.nb, LEDGER.width
AFM_TILES = {(512, 1024), (3072, 1024)}
HBM = 16 * 2 ** 30


def test_the_cell_s_pools_are_the_issue_s():
    assert (N, NB, TABLE, NBW, RING) == (32, 32769, 2176, 8225, 257)


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

    for name in ("pallas_attention", "paged_attention"):
        monkeypatch.setattr(_mod(name), "_interpret", lambda: False)
    monkeypatch.setattr(_mod("moe_dispatch"), "_mosaic", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(topo):
    """(model, params, pools, sds): the cell's bf16 tree and pools as shapes
    on a described device."""
    sh = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    on = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    model = FAM.program_config(
        MODEL, max_seq_len=SV["max_model_len"]).served_model()
    params = on(jax.eval_shape(
        lambda: FAM.make_params(MODEL, jax.random.PRNGKey(0), BF16)))
    pools = on(jax.eval_shape(
        lambda: model.make_pools(NB, BS, nb_window=NBW)))
    return model, params, pools, sds


# a pool as it lies: the parameter's dims and layout (S(1): prefetched into
# the chip's fast memory in the layout it has)
_POOL = re.compile(r"(%d|%d),16,2048\]" % (NB, NBW))
_LIES = re.compile(r"bf16\[(1,)?(%d|%d),16,2048\]\{(3,2,1,0|2,1,0):"
                   r"T\(8,128\)\(2,1\)(S\(1\))?\}" % (NB, NBW))


# the flat walk's own operands at 32 slots and 48 query heads: queries
# packed into their KV head's 128 of a row's 1,024 key columns (zeros in the
# others, and over the value columns), the weighted sum of WHOLE value rows,
# and each head's own 128 columns taken out of it: 8x what the algorithm
# needs, 6.3 MB each, most of them in the chip's fast memory (ROADMAP D16)
_WALK_OPERANDS = re.compile(r"bf16\[32,48,2048\]|f32\[32,48,1024\]|"
                            r"f32\[32,48,8,128\]|f32\[192,8,8,128\]")


def _big_writes(text):
    """Writes of 4 MiB or more that are neither a pool's write-back in place
    nor a weight's prefetch nor the walk's own operands; and any pool in
    another layout than its parameter's."""
    entry = _entry(text)
    relaid = [f"{n} = {ty[:70]} {op}" for n, (ty, op, _) in entry.items()
              if _POOL.search(ty) and not _LIES.search(ty)]
    big = [b for dims in ((NB, BS, 2048), (NBW, BS, 2048))
           for b in _weight_sized_writes(entry, dims)]
    return relaid + [b for b in set(big) if big.count(b) == 2
                     and not b.endswith(_PREFETCH)
                     and not _WALK_OPERANDS.search(b)]


def _fits(compiled):
    """Arguments (weights and pools) + temporaries under the chip's memory."""
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM, (used, ma)
    return used


def test_decode_writes_no_weight_or_pool_sized_array(topo):
    """32 slots through four window layers and a full one: both walks under
    their names (Mosaic accepts 2,048-lane rows and the fourth scalar
    operand), the window kind's table an operand of its own, every pool
    takes the step's rows in place, the untied head contracts its matrix
    where it lies, and the five projections of the attention are read where
    they lie."""
    model, params, pools, sds = _shapes(topo)
    traced = jax.jit(functools.partial(
        engine._paged_decode, model=model, n_steps=1,
        opts=ServeOpts(ragged=True), sample_flags=GREEDY),
        donate_argnums=(8,)).trace(
        params, sds((N,), I32), sds((N,), I32), sds((N,), jnp.bool_),
        sds((N,), I32), sds((2,), jnp.uint32), sds((N,), jnp.bool_),
        sds((N, TABLE), I32), pools, sds((N,), F32), sds((N,), I32),
        sds((N,), F32), sds((N,), I32), sds((N, RING), I32))
    assert _gmm_tiles(traced) == AFM_TILES
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert text.count("%afmoe_walk_full") >= 1
    assert text.count("%afmoe_walk_window") >= 4 and "%gmm" in text
    assert _big_writes(text) == []
    _fits(compiled)


@pytest.mark.parametrize("history", [0, TABLE], ids=["first", "continuing"])
def test_piece_with_the_decode_rows_writes_no_pool_sized_array(topo,
                                                               history):
    """The ONE program of a step that has a piece: a piece of 1,024 tokens
    and a decode step of 32 slots. Both kinds' walks and the piece's
    kernels are in it (the histories only where the piece continues a
    row), every expert layer has ONE grouped-matmul pair over 1,056 x 4 =
    4,224 pairs on tile boundaries (4,224 + 32 x 128 rows) under both
    rules of the tiling, and neither kind's pool is re-laid out around the
    two write-backs."""
    model, params, pools, sds = _shapes(topo)
    args = [params, sds((1, PIECE), I32), sds((1, PIECE // BS), I32),
            sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
            sds((1,), F32), sds((2,), jnp.uint32)]
    win = {"blk_ids": sds((1, PIECE // BS), I32)}
    if history:
        args += [sds((1,), I32), sds((1, history), I32)]
        win.update(ctx_tbl=sds((1, RING), I32), ctx_start=sds((1,), I32))
    traced = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY, prefix_nbk=history), donate_argnums=(4,)).trace(
        *args, win=win, dec=_dec_operands(sds, N, TABLE, RING))
    assert _gmm_tiles(traced) == AFM_TILES
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert "%afmoe_prefill_chunk" in text
    assert text.count("%afmoe_walk_full") >= 1
    assert text.count("%afmoe_walk_window") >= 4
    for name in ("%afmoe_history_full", "%afmoe_history_window"):
        assert (name in text) == bool(history)
    entry = _entry(text)
    gmm = _gmm_calls(text)
    assert len(gmm) == 2 * 4, gmm          # gate|up and down, four layers
    assert all("bf16[8320," in entry[n][0] for n in gmm), \
        [entry[n][0] for n in gmm]
    assert [n for n, (ty, _op, _) in entry.items()
            if _POOL.search(ty) and not _LIES.search(ty)] == []
    _fits(compiled)
