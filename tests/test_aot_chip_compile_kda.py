"""Compile the KDA kernels and Ling-3.0-flash's two serving programs for a
DESCRIBED TPU v5e, in ``tests/test_aot_chip_compile_decode.py``'s manner:
nothing executes. The programs are the ``reason-offline`` cell's own: the
configuration file's seven layers at the published widths, 128 held
experts, 64 slots, a latent pool of 32,768 blocks and a table 1,600 wide.

What the compiled text must show. Both Mosaic kernels are taken at these
shapes (``ling_kda_step``: a slot's 2 MB of matrices a program, aliased in
and out; ``ling_kda_chunk``: chunks of 64 under ``highest``-precision
products). The matrix state entries (136 MB each) are written by nothing
but the aliased step kernel and the piece's in-place row update: a copy of
one would be 0.8 GB a step over the six layers, which is what advancing the
state in place is for. No weight stack and no pool is re-laid out. The
program fits the chip: arguments and temporaries under 14 GB."""
import functools
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest, weights
from paddle_tpu.models.llama_served import ServeOpts
from paddle_tpu.serving import engine
from test_aot_chip_compile_decode import _NO_WRITE, _PREFETCH, _entry

_mod = lambda name: importlib.import_module("paddle_tpu.kernels." + name)
kda = _mod("kda")
BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
GREEDY = (False, False, False)
N, NB, BS, TABLE, PIECE = 64, 32769, 16, 1600, 1024
STATE = "f32[1,65,32,128,128]"
POOL = "bf16[1,32769,16,640]"


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
    for name in ("moe_dispatch", "kda"):
        monkeypatch.setattr(_mod(name), "_mosaic", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(topo):
    sh = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)


def test_the_step_kernel(topo):
    sds = _sds(topo)
    vec = ((N, 32, 128), F32)
    c = jax.jit(lambda *a: kda.kda_step(*a, name="ling_kda_step"),
                donate_argnums=(5,)).lower(
        *(sds(*s) for s in (vec, vec, vec, vec, ((N, 32), F32),
                            ((1, N + 1, 32, 128, 128), F32),
                            ((N,), jnp.bool_)))).compile()
    text = c.as_text()
    assert "%ling_kda_step" in text and "tpu_custom_call" in text
    ma = c.memory_analysis()
    # the entry goes out where it came in: nothing of its size beside it
    assert ma.alias_size_in_bytes >= 65 * 32 * 128 * 128 * 4
    assert ma.temp_size_in_bytes < 4 << 20


def test_the_chunk_kernel(topo):
    sds = _sds(topo)
    tok = ((PIECE, 32, 128), F32)
    text = jax.jit(lambda *a: kda.kda_chunk(*a, name="ling_kda_chunk")).lower(
        *(sds(*s) for s in (tok, tok, tok, tok, ((PIECE, 32), F32),
                            ((32, 128, 128), F32), ((), I32)))
    ).compile().as_text()
    assert "%ling_kda_chunk" in text and "tpu_custom_call" in text


# -- the cell's two programs --------------------------------------------------
def _cell(topo):
    sds = _sds(topo)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    m = manifest.Manifest().config("ling-3.0-flash-serve-ep4")
    sv = m["serve"]
    assert (sv["max_slots"], sv["num_blocks"] + 1, sv["block_size"],
            sv["max_model_len"] // sv["block_size"], sv["prefill_chunk"]
            ) == (N, NB, BS, TABLE, PIECE)
    fam = manifest.family_of(m)
    model = fam.program_config(m, max_seq_len=sv["max_model_len"]
                               ).served_model()
    params = on_chip(jax.eval_shape(
        lambda k: fam.make_params(m, k, BF16), weights.seed_key(0)))
    pools = on_chip(jax.eval_shape(lambda: {
        **model.make_pools(NB, BS), **model.make_state(N)}))
    return model, params, pools, sds


def _dec(sds):
    return (sds((N,), I32), sds((N,), I32), sds((N,), jnp.bool_),
            sds((N,), I32), sds((2,), jnp.uint32), sds((N,), jnp.bool_),
            sds((N, TABLE), I32), sds((N,), F32), sds((N,), I32),
            sds((N,), F32), sds((N,), I32))


_LINE = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")


def _entry_lines(text):
    lines = text.splitlines()
    at = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY"))
    out = {}
    for ln in lines[at + 1:]:
        if ln.startswith("}"):
            break
        m = _LINE.match(ln)
        if m:
            out[m.group(1)] = ln
    return out


def _check(compiled, kernels):
    text = compiled.as_text()
    for name in kernels:
        assert "%" + name in text, name
    entry, lines = _entry(text), _entry_lines(text)
    # who writes an array of a matrix entry's size: the step kernel (its
    # aliased output) and the piece's row update in place, nothing else
    for n, (ty, op, _) in entry.items():
        if op in _NO_WRITE or STATE not in ty:
            continue
        in_place = '"aliasing_operands"' in lines[n] and "scatter" in lines[n]
        assert n.startswith("ling_kda_step") or (op == "fusion" and in_place
                                                 ), lines[n][:300]
    # the latent pool only as it lies, the weight stacks never written
    relaid = [lines[n][:200] for n, (ty, _op, _) in entry.items()
              if "32769,16,640]" in ty and not re.search(
                  r"bf16\[(1,)?32769,16,640\]\{(3,2,1,0|2,1,0):"
                  r"T\(8,128\)\(2,1\)\}", ty)]
    assert relaid == []
    stacks = ("bf16[128,2560,1536]", "bf16[128,768,2560]",
              "bf16[2560,39296]", "bf16[39296,2560]")
    written = [lines[n][:200] for n, (ty, op, _) in entry.items()
               if ty.split("{")[0] in stacks
               and op not in _NO_WRITE + _PREFETCH]
    assert written == []
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1 << 30
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 14e9
    # every pool goes out where it came in (the tokens and the carry are
    # the few KB that are not aliased)
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < 1 << 20
    assert ma.alias_size_in_bytes > 1.4e9
    return entry


def test_the_decode_program_advances_the_state_where_it_lies(topo):
    model, params, pools, sds = _cell(topo)
    dec = _dec(sds)
    compiled = jax.jit(functools.partial(
        engine._paged_decode, model=model, n_steps=1,
        opts=ServeOpts(ragged=True), sample_flags=GREEDY),
        donate_argnums=(8,)).lower(
        params, *dec[:7], pools, *dec[7:]).compile()
    entry = _check(compiled, ("ling_kda_step", "mla_latent_walk", "gmm"))
    steps = [n for n in entry if n.startswith("ling_kda_step")]
    assert len(steps) == 6, steps


def test_the_piece_that_carries_the_decode_rows(topo):
    """A continuing piece of 1,024 tokens with a full-width history and the
    64 slots' decode step in ONE program: both KDA kernels, both MLA
    prefill kernels and the walk, one grouped-matmul pair an expert layer
    over (1,024 + 64) x 8 pairs on tile boundaries."""
    model, params, pools, sds = _cell(topo)
    args = [params, sds((1, PIECE), I32), sds((1, PIECE // BS), I32),
            sds((1,), I32), pools, sds((1,), F32), sds((1,), I32),
            sds((1,), F32), sds((2,), jnp.uint32), sds((1,), I32),
            sds((1, TABLE), I32)]
    compiled = jax.jit(functools.partial(
        engine._paged_prefill, model=model, opts=ServeOpts(ragged=True),
        sample_flags=GREEDY, prefix_nbk=TABLE), donate_argnums=(4,)).lower(
        *args, slot=sds((1,), I32), dec=_dec(sds)).compile()
    entry = _check(compiled, (
        "ling_kda_step", "ling_kda_chunk", "mla_latent_walk",
        "mla_prefill_chunk", "mla_prefill_history", "gmm"))
    gmm = [n for n, (_ty, op, _) in entry.items()
           if op == "custom-call" and n.startswith("gmm")]
    assert len(gmm) == 2 * 6, gmm            # gate|up and down, six layers
    rows = (PIECE + N) * 8 + 128 * 128       # the pairs + a tile an expert
    assert all(f"bf16[{rows}," in entry[n][0] for n in gmm), \
        [entry[n][0] for n in gmm]
