"""8B-class scale proof: AOT-compile the FULL hybrid-parallel train step for
llama3-8b (32 layers, 4096 hidden, 128256 vocab) over a (pp=2, dp=2, tp=2)
mesh — the pod-slice recipe — without materializing any 8B-sized buffer
(``jit(...).lower(abstract_args).compile()``).

One chip holds a model of about 2.6B to train; the 8B target runs on a pod slice.
This test proves the sharded 1F1B train step for the 8B config compiles end
to end: GSPMD partitioning, the 1F1B shard_map schedule, collective layout —
everything except the physical chips. Reference scale target:
test/auto_parallel/hybrid_strategy/semi_auto_llama.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models import llama


def test_llama8b_hybrid_1f1b_train_step_aot_compiles():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 (virtual) devices")
    mesh = Mesh(np.asarray(devs[:8]).reshape(2, 2, 1, 2),
                ("pp", "dp", "sp", "tp"))
    cfg = dataclasses.replace(
        llama.llama3_8b(), max_seq_len=512, use_flash=False,
        pipeline_microbatches=4, pipeline_schedule="1f1b")
    assert llama.num_params(llama._abstract_params(cfg)) > 7e9

    sh = llama.make_shardings(cfg, mesh, fsdp=True)
    state_abs = jax.eval_shape(
        lambda k: llama.init_train_state(cfg, k), jax.random.PRNGKey(0))
    state_sh = llama.TrainState(sh, sh, sh, NamedSharding(mesh, P()))
    state_abs = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state_abs, state_sh)
    tok_abs = jax.ShapeDtypeStruct(
        (8, 513), jnp.int32, sharding=NamedSharding(mesh, P("dp", None)))

    with llama.activation_mesh(mesh):
        compiled = jax.jit(
            lambda s, t: llama.train_step(s, t, cfg)).lower(
                state_abs, tok_abs).compile()

    # the executable exists and its output shapes are the full train state
    out_state, out_loss = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure((state_abs, jnp.float32(0))),
        jax.tree_util.tree_leaves(compiled.out_info))
    assert out_loss.shape == ()
    assert (out_state.params["embed"].shape
            == state_abs.params["embed"].shape)
