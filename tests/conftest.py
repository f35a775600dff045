"""Test configuration: force CPU with 8 virtual devices BEFORE jax import so
distributed/sharding tests can exercise an 8-chip mesh on any host
(the reference's analogue: multi-process cluster simulation in
test/legacy_test/test_parallel_dygraph_dataparallel.py:30)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests want 8 virtual devices
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# a plugin may have imported jax (and snapshotted JAX_PLATFORMS) before this
# conftest ran; XLA_FLAGS is read later, when the backend starts
jax.config.update("jax_platforms", "cpu")

import paddle_tpu  # noqa: E402,F401

import pytest  # noqa: E402

# -- fast-lane / full-lane split (VERDICT r3 weak #4) -------------------------
# The suite is compile-dominated: ~60 tests account for ~20 of its 31 CPU
# minutes. They carry @pytest.mark.slow (auto-applied from the list below,
# measured via --durations) and are SKIPPED by default so a plain
#   python -m pytest tests/ -q
# gives a broad signal in a few minutes. Round snapshots / CI run everything:
#   PADDLE_TPU_FULL_TESTS=1 python -m pytest tests/ -q
_SLOW = {
    "test_auto_tuner_measured.py::test_llama_trial_on_virtual_mesh",
    "test_bert.py::test_finetune_step_overfits",
    "test_dist_model.py::test_dist_model_trains_and_matches_dynamic",
    "test_dist_model.py::test_dist_model_transformer_lm_semi_auto",
    "test_flash_gqa.py::test_gqa_flash_matches_reference",
    "test_fleet_tp.py::test_eager_moe_layer",
    "test_fleet_workflow.py::test_llama_learns_copy_task_and_generates",
    "test_generate.py::test_cached_forward_matches_full",
    "test_generate.py::test_generate_fused_matches_python_loop",
    "test_generate.py::test_generate_matches_no_cache_argmax",
    "test_group_sharded.py::test_sharded_matches_unsharded",
    "test_hf_convert.py::test_hf_llama_logits_match_transformers",
    "test_llama.py::test_chunked_ce_matches_dense",
    "test_llama.py::test_remat_policy_dots_matches_full",
    "test_llama.py::test_sharded_train_step_8dev",
    "test_llama.py::test_train_step_loss_decreases",
    "test_moe.py::test_capacity_train_step_improves",
    "test_moe.py::test_dropless_ep_shard_map_matches_replicated",
    "test_moe.py::test_expert_parallel_matches_replicated",
    "test_moe.py::test_forward_and_train_step",
    "test_moe.py::test_remat_policy_attn_matches_full",
    "test_offload.py::test_grads_stream_through_host",
    "test_offload.py::test_layerwise_step_matches_fused",
    "test_offload.py::test_offload_step_matches_fused",
    "test_op_ledger_gaps.py::test_yolo_loss_grad_descends",
    "test_optimizer_functional.py::test_adafactor_bf16_params_train",
    "test_optimizer_functional.py::test_adafactor_moment_shardings_put",
    "test_optimizer_functional.py::test_adamw_bf16_moments_train",
    "test_optimizer_functional.py::test_grad_accumulation_matches_full_batch",
    "test_pipeline.py::test_1f1b_chunked_ce_matches_dense",
    "test_pipeline.py::test_1f1b_matches_unpipelined_grads",
    "test_pipeline.py::test_1f1b_memory_below_gpipe",
    "test_pipeline.py::test_1f1b_train_step_converges",
    "test_pipeline.py::test_interleaved_pipeline_matches_sequential",
    "test_pipeline.py::test_llama_pipeline_train_step",
    "test_pipeline.py::test_pipeline_matches_sequential",
    "test_pipeline.py::test_zb_matches_unpipelined_grads",
    "test_pipeline.py::test_zb_memory_at_most_1f1b",
    "test_pipeline.py::test_zb_train_step_converges",
    # a wall-clock race: what it guarded of the loader's contract is
    # test_process_workers_run_cpu_bound_transforms_in_order_elsewhere
    "test_mp_loader.py::test_process_workers_beat_threads_on_cpu_bound_transforms",
    "test_quant_generate.py::test_serving_engine_with_int8_weights",
    # r19 tp/disagg legs: each compiles sharded (or multi-engine) decode
    # variants — the contracts stay covered in the fast lane by the
    # colocated/unsharded parity tests they extend
    "test_router.py::test_disagg_pair_matches_colocated_greedy",
    "test_router.py::test_disagg_decode_replica_kill_recovers_with_parity",
    "test_router.py::test_disagg_prefill_replica_kill_recovers_with_parity",
    "test_router.py::test_disagg_placement_respects_roles",
    "test_serving_engine.py::test_tp_sharded_ragged_decode_matches_unsharded",
    "test_serving_engine.py::test_tp_sharded_ragged_int8_weights_matches_unsharded",
    "test_serving_engine.py::test_tp_sharded_prefix_cache_chunked_matches_unsharded",
    "test_spec_decode.py::test_spec_tp_sharded_parity",
    "test_ring_attention.py::test_ring_gradients",
    "test_rnn.py::test_bidirectional_multilayer_shapes_and_grads",
    "test_round2_surface.py::test_static_nn_layers",
    "test_scale_aot.py::test_llama8b_hybrid_1f1b_train_step_aot_compiles",
    # test_serving.py is deliberately NOT all-slow: the streaming and eos
    # tests stay in the fast lane so a plain `pytest tests/` still covers
    # the engine's step/admission/processing machinery
    "test_serving.py::test_admission_mid_decode_continuous_batching",
    "test_serving.py::test_mixed_prompts_match_dense_generate",
    "test_serving.py::test_multistep_decode_matches_single_step",
    "test_serving.py::test_multistep_horizon_clamped_to_budget",
    "test_serving.py::test_preemption_under_pool_pressure",
    "test_serving.py::test_tp_sharded_engine_matches_dense",
    "test_serving_perf.py::test_engine_overhead_within_10pct_of_raw_decode",
    "test_ulysses_amp_hapi.py::test_hapi_lenet_mnist_e2e",
    "test_vision.py::test_googlenet_and_inception",
    "test_vision.py::test_model_forward",
}


def pytest_collection_modifyitems(config, items):
    full = os.environ.get("PADDLE_TPU_FULL_TESTS") == "1"
    skip = pytest.mark.skip(
        reason="slow lane: set PADDLE_TPU_FULL_TESTS=1 to run")
    for item in items:
        base = f"{item.fspath.basename}::{item.originalname or item.name}"
        if base in _SLOW or item.get_closest_marker("slow") is not None:
            item.add_marker(pytest.mark.slow)
            if not full:
                item.add_marker(skip)


def assert_blocks_balanced(eng):
    """Shared leak-regression helper (r8/r10/r15): the block ledger
    ``free + backed + cached + squeezed + in_flight == total``, no
    block id counted twice (async-offload custody blocks included), and
    the host swap tier's incrementally-maintained block count
    cross-checked against the entry walk it replaced."""
    acct = eng.block_accounting()
    assert acct["free"] + acct["backed"] + acct["cached"] \
        + acct["squeezed"] + acct["in_flight"] == acct["total"], acct
    used = [int(eng.table[i, j]) for i in range(eng.N)
            for j in range(int(eng.n_alloc[i]))]
    squeezed = [b for _, blocks in eng._squeezed for b in blocks]
    held = ([b for t in eng.offload._spills.values() for b in t.blocks]
            if eng.offload is not None else [])
    all_ids = list(eng.free_blocks) + used + squeezed + held
    assert len(all_ids) == len(set(all_ids)), "duplicate block ids"
    assert 0 not in all_ids, "trash block leaked into the allocator"
    if eng.swap_pool is not None:
        walk = sum(e.n_blocks for e in eng.swap_pool._entries.values())
        assert eng.swap_pool.swapped_blocks == walk
    return acct
