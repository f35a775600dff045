"""Compile the latent-attention and expert-share kernels for a DESCRIBED
TPU v5e, in ``tests/test_aot_chip_compile.py``'s manner: nothing executes,
a pass says the chip's compiler accepts the kernel at the widths the
``longdoc-offline`` cell runs (DeepSeek-V2: 128 heads, latent 512 + rope 64
padded to 640, q/k 192 padded to 256, v 128, experts 5120 x 1536, blocks of
16, 24 slots, a table 1152 wide)."""
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

_mod = lambda name: importlib.import_module("paddle_tpu.kernels." + name)
paged_attention, pallas_attention = _mod("paged_attention"), \
    _mod("pallas_attention")
moe_dispatch = _mod("moe_dispatch")
BF16, I32 = jnp.bfloat16, jnp.int32
SCALE = 0.11472


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
    monkeypatch.setattr(moe_dispatch, "_mosaic", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, topo, *specs):
    sh = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_latent_walk(topo):
    text = _compile(
        lambda q, pool, tbl, lens: paged_attention.latent_decode_partial(
            q, pool, tbl, lens, layer=0, v_cols=512, sm_scale=SCALE),
        topo, ((24, 128, 640), BF16), ((1, 16385, 16, 640), BF16),
        ((24, 1152), I32), ((24,), I32))
    assert "%mla_latent_walk" in text      # the name a device trace shows


def test_prefill_chunk_attention_at_unequal_widths(topo):
    text = _compile(
        lambda q, k, v: pallas_attention.flash_partial(
            q, k, v, scale=SCALE, causal=True, name="mla_prefill_chunk"),
        topo, ((128, 1024, 256), BF16), ((128, 1024, 256), BF16),
        ((128, 1024, 128), BF16))
    assert "%mla_prefill_chunk" in text


@pytest.mark.parametrize("heads,keys", [(128, 18432), (32, 25600)],
                         ids=["deepseek-v2", "ling-3.0-flash"])
def test_prefill_history_attention_over_latent_rows(topo, heads, keys):
    """The history in the expanded form, a key tile's K and V made a head
    inside the kernel, at both published shapes (a 1,024-token piece of
    ``longdoc-offline`` against its table of 18,432 keys, of
    ``reason-offline`` against 25,600): the fast memory the compiled call
    scopes, as its own configuration says it, is under the kernel's 16
    MiB (a step that asked for more would have failed the compile)."""
    text = _compile(
        lambda q, c, uk, uv, n: pallas_attention.latent_history_partial(
            q, c, uk, uv, scale=SCALE, kv_len=n, name="mla_prefill_history"),
        topo, ((heads, 1024, 256), BF16), ((1, keys, 640), BF16),
        ((heads, 128, 512), BF16), ((heads, 512, 128), BF16), ((1,), I32))
    call, = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "%mla_prefill_history" in ln]
    scoped = [int(n) for n in re.findall(
        r'"memory_space":"1","offset":"\d+","size":"(\d+)"', call)]
    assert scoped and 0 < sum(scoped) <= pallas_attention._FLASH_VMEM, scoped


@pytest.mark.parametrize("tokens,rows", [
    (24, 256), (1024, 6144 + 20 * 128), (24 * 1024, 24 * 1024 + 20 * 128)],
    ids=["decode", "one-row-chunk", "padded-wave"])
def test_held_expert_ffn_with_its_static_tiling(topo, tokens, rows):
    """The chip's share of an expert layer: the grouped matmul's tiling
    comes from the shapes (no timing), a 128-row tile in every regime. A
    decode step keeps its 144 pairs packed in 256 rows (the program the
    parent had, but for the fifth count); a one-row chunk and a padded wave
    (which walks its pairs in passes) lay each held expert's rows out on
    tile boundaries, 20 x 128 static rows more."""
    text = _compile(
        lambda x, g, i, v, gu, dn: moe_dispatch.held_expert_ffn(
            x, g, i, v, gu, dn, 0),
        topo, ((tokens, 5120), BF16), ((tokens, 6), jnp.float32),
        ((tokens, 6), I32), ((tokens,), jnp.bool_),
        ((20, 5120, 3072), BF16), ((20, 1536, 5120), BF16))
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln
             and "%gmm" in ln]
    assert {ln.split(" = ")[1].split("{")[0] for ln in calls} == {
        f"bf16[{rows},5120]", f"bf16[{rows},3072]"}, calls
    assert all(f"s32[{rows // 128 + 19}]" in ln for ln in calls)
