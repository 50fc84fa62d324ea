"""Test harness: 8 virtual CPU devices (SURVEY.md §4).

The JAX-native analog of a fake backend: mesh/psum/sharding/checkpoint tests
run hermetically with no TPU. The device count must be set before the CPU
backend is created, which is lazy — so configuring it here, before first
device use, takes effect.
"""

import os

# XLA's in-process CPU collective rendezvous SIGABRTs the whole pytest
# process when the box is oversubscribed (8 virtual devices on 1-2 cores
# under a loaded CI: "Expected 8 threads to join ... only N arrived").
# Raise the warn/terminate timeouts well past any scheduler hiccup. The
# device count rides XLA_FLAGS as well as the config option below because
# subprocess-isolated tests inherit the environment, not the config.
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + (
    " --xla_force_host_platform_device_count=8"
    " --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
    " --xla_cpu_collective_call_terminate_timeout_seconds=1200"
)
# No persistent compile cache under the suite (children inherit the env):
# test_recompile/test_boot/test_memwatch pin compile counts and seconds that
# a cache hit would change, and a .jax_cache filled here would ride along
# with the tree to the chip machine.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Smoke tier (VERDICT r3 next-round #8): one fast, load-bearing test per
# subsystem, runnable in <3 minutes on one core — `pytest -m smoke`. The
# full 300-test suite stays as the deep tier. Maintained here (not as
# scattered decorators) so the subsystem coverage is reviewable in one
# place; names are nodeid bases (parametrized variants inherit the mark).
SMOKE = {
    "test_models.py::test_bn_cnn_param_count_matches_keras",   # models/cnn
    "test_data.py::test_from_tensor_slices_roundtrip",         # data pipeline
    "test_data.py::test_shard_partitions_examples",            # sharding math
    "test_losses.py::test_ce_matches_hand_computed",           # ops/losses
    "test_mesh.py::test_data_parallel_mesh_spans_all_devices", # runtime/mesh
    "test_train_dp.py::test_dp_matches_single_device_numerics",  # DP psum
    "test_lifecycle.py::test_train_and_evaluate_end_to_end",   # lifecycle
    "test_checkpoint.py::test_save_and_restore_roundtrip",     # checkpoint
    "test_export.py::test_export_and_load_roundtrip",          # export
    "test_tensorboard.py::test_event_file_structure",          # observability
    "test_fs.py::test_fs_helpers_on_memory",                   # remote fs
    "test_optimizers.py::test_mask_excludes_biases_and_scales",  # optimizers
    "test_tensor_parallel.py::test_tp_matches_dp_numerics",    # TP
    "test_pipeline.py::test_pipeline_gradients_match_sequential",  # PP core
    "test_decode.py::test_greedy_cache_matches_full_forward_rollout",  # KV
    "test_speculative.py::test_perfect_draft_full_acceptance", # speculation
    "test_flash_attention.py::test_flash_single_block",        # Pallas kernel
    "test_ring_attention.py::test_ring_causal_matches_reference",  # SP ring
    "test_native_loader.py::test_one_epoch_covers_every_row_once",  # C++ IO
    "test_tfrecord.py::test_round_trip",                       # TFRecord IO
    "test_gpt.py::test_gpt_is_causal",                         # GPT family
    "test_bert.py::test_bert_tiny_forward_shapes",             # BERT family
    "test_vit.py::test_vit_tiny_forward",                      # ViT family
    "test_resnet.py::test_resnet18_forward",                   # ResNet family
    "test_moe.py::test_moe_output_shape_and_aux_loss",         # MoE/EP
    "test_grad_accum.py::test_grad_accum_rejects_indivisible_batch",
    "test_transformer.py::test_causal_masking_blocks_future",  # attention
    "test_transformer.py::test_fused_qkv_matches_unfused",     # fused qkv
    "test_streaming.py::test_one_epoch_exact_multiset",   # streaming input
    "test_pipelined_lm.py::test_1f1b_single_stage_direct",  # 1F1B schedule
    "test_rotary.py",  # whole file: tiny pure-math checks            (RoPE)
    "test_lora.py::test_zero_init_is_identity",            # LoRA adapters
    "test_bert_classifier.py::test_classifier_shapes_and_mask",  # clf head
    # round-5 subsystems
    "test_t5.py::test_t5_cache_decode_equals_full_forward",  # T5 seq2seq
    "test_packing.py::test_packed_forward_equals_solo_forward",  # packing
    "test_rolling_cache.py::test_rolling_cache_is_window_bounded",
    "test_preemption.py::test_preemption_guard_sets_flag_and_restores_handler",
    "test_ema.py::test_ema_tracks_post_update_params",     # param EMA
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.nodeid.split("[")[0]
        base = base.split("tests/")[-1]
        if base in SMOKE or base.split("::")[0] in SMOKE:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(scope="session", autouse=True)
def _assert_fake_devices():
    assert jax.device_count() == 8, "tests expect 8 virtual CPU devices"
    yield


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def bert_base_shapes():
    """BERT-base's parameters as shapes, nothing allocated: what the byte
    accounting of parallel/comms.py and parallel/zero.py is counted on."""
    from tfde_tpu.models.bert import BertBase

    model = BertBase(dropout_rate=0.0, pad_vocab=True)
    return jax.eval_shape(
        lambda: model.init(jax.random.key(0), np.zeros((2, 8), np.int32),
                           train=False))["params"]
