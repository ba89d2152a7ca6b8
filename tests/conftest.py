"""Test config: force an 8-device virtual CPU mesh so sharding/collective
paths are exercised without TPU hardware (the driver's dryrun does the same).

Note: plugins (jaxtyping) import jax before this conftest runs, so setting
os.environ alone is not enough — jax.config.update("jax_platforms") is the
authoritative override. The persistent compile cache stays off here
(flags.enable_compile_cache is for entry points, never the tests).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# prepare-time program verification (analysis/) is ON suite-wide: every
# program the Executor compiles gets shape inference + lint first, so a
# latent shape bug fails with op provenance instead of a JAX trace error.
# Individual tests can monkeypatch it off to exercise the raw path.
os.environ.setdefault("PADDLE_TPU_VALIDATE", "1")
# kernel tests must keep exercising the Pallas path (interpret mode on
# CPU) regardless of the short-S composed dispatch; policy tests
# monkeypatch PADDLE_TPU_FLASH_MIN_SEQ themselves
os.environ.setdefault("PADDLE_TPU_FLASH_MIN_SEQ", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running integration test")
    config.addinivalue_line(
        "markers", "fast: sub-5s smoke tier (auto-applied; run with -m fast)")
    config.addinivalue_line(
        "markers", "dist: real-subprocess cluster / collective test")


# Tiering (VERDICT r3 task 7): the full suite is ~18 min; `-m fast` is the
# sub-5-minute default tier covering every subsystem's smoke path. The table
# lists the long tests (>5s measured on the 8-device CPU mesh) — everything
# else is auto-marked `fast`. A test that outgrows 5s belongs here; a new
# subsystem keeps at least one un-listed test so the fast tier smokes it.
SLOW_TESTS = {
    "test_amp.py::TestAmp::test_matches_f32_training",
    # re-tiered 2026-07-31 (fast tier crept past 8 min): each demoted
    # test has a cheaper fast-tier sibling covering the same path
    "test_ring_attention.py::test_zigzag_plain_causal_with_bias_and_grads",
    "test_moe_engine.py::test_moe_top2_expert_parallel_matches_dense_fallback",
    "test_gpt_decode.py::test_kv_cache_decode_matches_full_forward",
    "test_gpt_decode.py::test_kv_cache_decode_matches_full_forward_gqa",
    "test_gpt_decode.py::test_gqa_training_fused_matches_composed",
    "test_gpt_decode.py::test_generate_sampling_modes",
    "test_gpt_decode.py::test_prefill_one_dispatch_matches_stepwise_generate",
    "test_gpt_decode.py::test_prefill_with_grouped_query_attention_matches_decode_loop",
    "test_rope.py::test_gpt_rope_trains_and_paths_match",
    "test_rope.py::test_gpt_rope_decode_matches_full_forward",
    "test_modern_decoder.py::test_llama_style_stack_fused_matches_composed",
    "test_modern_decoder.py::test_llama_style_decode_matches_full_forward",
    "test_modern_decoder.py::test_swiglu_ffn_has_gate_param_and_trains",
    "test_modern_decoder.py::test_tied_embeddings_train_and_decode",
    "test_packed_training.py::test_packed_with_rope_resets_positions",
    "test_packed_training.py::test_packed_windows_scan_composition",
    "test_packed_training.py::test_packed_loss_equals_separate_documents",
    "test_packed_training.py::test_packed_fused_matches_composed",
    "test_zero1.py::test_zero1_exact_parity_with_plain_dp",
    "test_zero1.py::test_zero1_composes_with_run_repeated",
    "test_zero1.py::test_zero1_step_hlo_gains_param_gather",
    "test_tpu_lowering.py::test_sp_train_step_lowers_for_tpu_with_ring",
    "test_pipeline_engine.py::test_pipeline_dropout_dp_pp_trains_deterministically",
    "test_pipeline_engine.py::test_pipeline_dropout_exact_parity_on_pipe_mesh",
    "test_pipeline_engine.py::test_pipeline_with_grad_accum_matches_plain",
    "test_moe_engine.py::test_moe_z_loss_through_program_and_engine",
    "test_models.py::test_machine_translation_trains",
    "test_datasets.py::test_wmt14_seq2seq_book_trains",
    "test_vit.py::test_vit_trains_and_paths_match",
    "test_vit.py::test_vit_overfits_tiny_batch",
    "test_examples.py::test_train_mnist_example",
    "test_examples.py::test_train_gpt_tpu_example",
    "test_examples.py::test_train_multichip_example",
    "test_attention.py::test_transformer_with_fused_attention_trains",
    "test_imperative_capture.py::test_captured_replay_2x_faster_than_eager",
    "test_book.py::test_image_classification_cifar_conv_bn",
    "test_book.py::test_label_semantic_roles_crf",
    "test_book.py::test_machine_translation_seq2seq_with_beam_decode",
    "test_book.py::test_recommender_system",
    "test_book_mnist.py::test_recognize_digits_conv",
    "test_contrib_decoder.py::test_training_decoder_and_beam_decode_copy_task",
    "test_dist_collective.py::test_two_process_collective_matches_single",
    "test_dist_ps.py::test_async_ps_converges",
    "test_dist_ps.py::test_sync_ps_matches_single_process",
    "test_dist_ps.py::test_sync_ps_sliced_two_pservers",
    "test_layers_extra.py::test_crf_tagger_trains",
    "test_layers_extra.py::test_warpctc_layer_trains",
    "test_misc_layers3.py::test_dynamic_lstmp_and_stacked_lstm",
    "test_misc_layers3.py::test_final_four_layers",
    "test_models.py::test_bert_mlm_trains",
    "test_models.py::test_mnist_model_builds",
    "test_models.py::test_resnet50_builds_and_steps",
    "test_models.py::test_se_resnext_builds_and_steps",
    "test_models.py::test_stacked_lstm_trains",
    "test_models.py::test_transformer_trains",
    "test_models.py::test_gpt_causal_lm_trains_fused_matches_composed",
    "test_moe_engine.py::test_moe_aux_loss_changes_routing",
    "test_moe_engine.py::test_moe_expert_parallel_matches_dense_fallback",
    "test_moe_engine.py::test_moe_step_hlo_contains_expert_collective",
    "test_mosaic_constraints.py::TestRaggedAndBiasGrad::test_ragged_seq_forward_backward",
    "test_mosaic_constraints.py::TestRaggedAndBiasGrad::test_trainable_bias_cotangent",
    "test_mosaic_constraints.py::TestRaggedAndBiasGrad::test_trainable_bias_cotangent_ragged",
    "test_native_serving.py::test_c_driver_int64_inputs",
    "test_native_serving.py::test_c_driver_matches_python_predictor",
    "test_native_train.py::test_c_trainer_trains_and_saves",
    "test_parallel_engine.py::test_data_parallel_parity",
    "test_parallel_engine.py::test_sequence_parallel_feed_rules",
    "test_parallel_engine.py::test_sp_fused_attention_rides_ring",
    "test_pipeline.py::test_pipeline_gradients_match",
    "test_pipeline_engine.py::test_pipeline_matches_sequential_through_training",
    "test_pipeline_engine.py::test_pipeline_step_hlo_contains_collective_permute",
    "test_recompute.py::test_recompute_grads_match_plain_grads",
    "test_recompute.py::test_recompute_matches_plain",
    "test_recompute.py::test_recompute_with_dropout_trains_and_is_deterministic",
    "test_recompute.py::test_transformer_model_recompute_builds_and_trains",
    "test_recompute_interplay.py::test_recompute_under_parallel_engine_matches_single",
    "test_recompute_interplay.py::test_recompute_with_amp_matches_plain_amp",
    "test_recompute_interplay.py::test_recompute_with_grad_accum_matches_plain_batch",
    "test_ring_attention.py::test_ring_flash_causal_grads_match_dense",
    "test_ring_attention.py::test_zigzag_causal_matches_dense_with_padding_bias",
    "test_ring_attention.py::test_ring_flash_matches_full_attention",
    "test_ring_attention.py::test_ring_flash_with_padding_bias",
    "test_rnn_blocks.py::test_machine_translation_dynamic_rnn_trains",
    "test_rnn_controlflow.py::test_lstm_gru_train",
    "test_sanitizers.py::test_asan_tensor_store_and_datafeed",
    "test_ssd_stack.py::test_ssd_pipeline_trains",
    # re-tiered 2026-08-07 (fast tier crept past the 870s budget):
    # the three heaviest gates split — their expensive tails (multi-
    # minute zoo sweeps, RPC soak, long spec-decode parity runs) move
    # here while each file keeps cheaper fast-tier siblings pinning
    # the same invariants (smaller zoo models, in-process fleet
    # aggregation, the remaining spec-decode/prefix parity tests)
    "test_memory.py::test_zoo_static_within_stated_factor_of_xla",
    "test_fleet_telemetry.py::test_fleet_push_over_rpc",
    "test_fleet_telemetry.py::test_fleet_demo_elastic_job_and_router",
    "test_serving_fleet.py::test_spec_decode_agreeing_draft_accepts_k_per_dispatch",
    "test_serving_fleet.py::test_spec_decode_bitwise_with_disagreeing_draft",
    "test_serving_fleet.py::test_spec_decode_plain_fallback_near_cache_end",
    "test_serving_fleet.py::test_prefix_store_shared_across_fresh_engine_stays_bitwise",
}

# real-subprocess cluster tests (excluded from `-m fast` via their own tier)
DIST_FILES = ("test_dist_ps.py", "test_dist_collective.py",
              "test_dist_rpc.py")


def pytest_collection_modifyitems(config, items):
    matched = set()
    collected_files = set()
    for item in items:
        rel = item.nodeid.split("tests/")[-1]
        fname = rel.split("::")[0]
        collected_files.add(fname)
        if fname in DIST_FILES:
            item.add_marker(pytest.mark.dist)
        if rel in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
            matched.add(rel)
        elif item.get_closest_marker("slow") is None \
                and fname not in DIST_FILES:
            item.add_marker(pytest.mark.fast)
    # staleness guard: a renamed/moved test must not silently fall out of
    # the slow tier into `-m fast`. Tolerates single-file/-k runs (only
    # files actually collected are checked) and `file.py::test` node-id
    # selection (which collects a file partially — skip the guard then).
    if any("::" in str(a) for a in config.args):
        return
    stale = {n for n in SLOW_TESTS
             if n.split("::")[0] in collected_files and n not in matched}
    if stale:
        raise pytest.UsageError(
            "SLOW_TESTS entries no longer match any collected test "
            "(renamed/removed?): %s" % sorted(stale))


def pytest_sessionstart(session):
    assert all(d.platform == "cpu" for d in jax.devices()), (
        "test suite must run on the virtual CPU mesh, got %s" % jax.devices()
    )


@pytest.fixture
def fresh_programs():
    """Give a test its own main/startup programs and scope."""
    import paddle_tpu as fluid
    from paddle_tpu.core.program import (
        Program,
        switch_main_program,
        switch_startup_program,
    )
    from paddle_tpu.core.scope import Scope, scope_guard

    main, startup = Program(), Program()
    old_main = switch_main_program(main)
    old_startup = switch_startup_program(startup)
    scope = Scope()
    with scope_guard(scope):
        yield main, startup, scope
    switch_main_program(old_main)
    switch_startup_program(old_startup)


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)


@pytest.fixture(autouse=True)
def _one_traced_rehearsal_at_a_time(request):
    """``benchmarks/run.py`` keeps ONE ``<checkout>/.bench_trace`` and
    empties it around every traced run, so two ``--trace 1`` rehearsals
    from this checkout at once (xdist runs the files of tests/benchmarks
    side by side) delete each other's profile. A test of that directory
    with a true ``trace`` parameter holds a lock between processes."""
    spec = getattr(request.node, "callspec", None)
    if spec is None or not spec.params.get("trace") \
            or "benchmarks" not in str(request.node.fspath).split(os.sep):
        yield
        return
    import fcntl

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, ".bench_trace.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
