"""Entrypoint integration tests (SURVEY.md §4): each reference-equivalent
example runs a few steps on the fake-device mesh, loss decreases, and the
expected artifacts (checkpoint, export) appear — mirroring §3.1-3.4."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from examples import mnist_estimator, mnist_multiworker, mnist_tf2  # noqa: E402


def test_multiworker_example_runs(tmp_path):
    state = mnist_multiworker.main(
        ["--epochs", "2", "--steps-per-epoch", "3", "--model-dir", str(tmp_path)]
    )
    assert int(jax.device_get(state.step)) == 6


def test_estimator_example_end_to_end(tmp_path):
    state, metrics = mnist_estimator.main(
        [
            "--working-dir", str(tmp_path / "wd"),
            "--num-epochs", "0.02",  # ~9 steps at batch 128 over 60k
            "--batch-size", "128",
            "--learning-rate", "0.1",
            "--no-tensorboard",
        ]
    )
    assert int(jax.device_get(state.step)) == int(0.02 * 60000 // 128)
    assert np.isfinite(metrics["loss"])
    # checkpoint + export artifacts (mnist_keras:245-248, §3.4)
    assert os.path.isdir(tmp_path / "wd" / "checkpoints")
    export_root = tmp_path / "wd" / "export" / "exporter"
    stamps = os.listdir(export_root)
    assert stamps, "FinalExporter must write a timestamped artifact"
    from tfde_tpu.export.serving import load_serving

    served = load_serving(str(export_root))
    probs = served.predict(np.zeros((2, 784), np.float32))
    assert probs.shape == (2, 10)


def test_tf2_example_custom_loop():
    state = mnist_tf2.main(["--custom-loop", "--max-steps", "5"])
    assert int(jax.device_get(state.step)) == 5


def test_tf2_example_estimator_path(tmp_path):
    state, metrics = mnist_tf2.main(
        ["--model-dir", str(tmp_path / "m"), "--max-steps", "4"]
    )
    assert int(jax.device_get(state.step)) == 4
    assert np.isfinite(metrics["loss"])


@pytest.mark.slow
def test_cifar_resnet_example_smoke():
    from examples import cifar10_resnet

    state = cifar10_resnet.main(
        ["--max-steps", "2", "--batch-size", "8"]  # 8 fake devices -> divisible
    )
    assert int(jax.device_get(state.step)) == 2


def test_gpt_lm_example_3d_smoke():
    """gpt_lm's 3D surface (--pipeline x --tensor) runs a couple of steps
    end-to-end on the fake mesh."""
    from examples import gpt_lm

    state, metrics = gpt_lm.main(
        ["--tiny", "--seq-len", "32", "--max-steps", "2", "--batch-size",
         "16", "--pipeline", "2", "--tensor", "2"]
    )
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_gpt_lm_example_moe_smoke():
    from examples import gpt_lm

    state, metrics = gpt_lm.main(
        ["--tiny", "--seq-len", "32", "--max-steps", "2", "--batch-size",
         "16", "--moe", "4"]
    )
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


@pytest.mark.slow
def test_lora_finetune_example():
    """The LoRA entrypoint end to end: inline base pretrain, q/v-adapter
    fine-tune, merge, generate from the merged params — all on the fake
    mesh. The merged tree must be base-shaped (the export contract)."""
    from examples import lora_finetune

    base, merged = lora_finetune.main(
        ["--tiny", "--max-steps", "5", "--pretrain-steps", "5",
         "--seq-len", "16", "--batch-size", "16", "--generate", "4"]
    )
    # base-shaped: same tree structure and leaf shapes as the frozen base
    assert (jax.tree_util.tree_structure(merged)
            == jax.tree_util.tree_structure(base))
    for mb, bb in zip(jax.tree_util.tree_leaves(merged),
                      jax.tree_util.tree_leaves(base)):
        assert mb.shape == bb.shape
        assert np.isfinite(np.asarray(mb)).all()


def test_serve_gpt_text_requests(tmp_path):
    """--tokenizer + --prompt: text requests ride the continuous batcher
    end to end — encoded offline, decoded back to text. The tokenizer is
    built programmatically (hermetic; nothing downloaded)."""
    pytest.importorskip("tokenizers")
    transformers = pytest.importorskip("transformers")
    from tokenizers import Tokenizer, models, pre_tokenizers

    PreTrainedTokenizerFast = transformers.PreTrainedTokenizerFast

    from examples import serve_gpt

    vocab = {w: i for i, w in enumerate(
        ["[UNK]", "the", "cat", "sat", "on", "mat"]
    )}
    t = Tokenizer(models.WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = pre_tokenizers.Whitespace()
    tok = PreTrainedTokenizerFast(tokenizer_object=t, unk_token="[UNK]")
    tok.save_pretrained(str(tmp_path))

    done = serve_gpt.main(
        ["--tiny", "--tokenizer", str(tmp_path),
         "--prompt", "the cat sat", "--prompt", "on the mat",
         "--max-new-tokens", "4", "--batch-size", "2", "--max-len", "32"]
    )
    assert len(done) == 2 and all(len(toks) for _, toks in done)


def test_serve_gpt_example():
    """The continuous-batching serving demo drains its queue with every
    request completed at full budget."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples import serve_gpt

    done = serve_gpt.main(
        ["--tiny", "--requests", "5", "--batch-size", "2",
         "--max-new-tokens", "6", "--max-len", "32"]
    )
    assert len(done) == 5
    assert all(len(toks) == 6 for _, toks in done)
    # the draft-accelerated path drains the same queue
    done = serve_gpt.main(
        ["--tiny", "--requests", "3", "--batch-size", "2",
         "--max-new-tokens", "5", "--max-len", "32", "--num-draft", "2"]
    )
    assert len(done) == 3
    assert all(len(toks) == 5 for _, toks in done)


@pytest.mark.slow
def test_t5_seq2seq_example_smoke():
    """The encoder-decoder entrypoint: seq2seq training + generation run
    end-to-end on the fake mesh."""
    from examples import t5_seq2seq

    state, metrics = t5_seq2seq.main(
        ["--tiny", "--seq-len", "8", "--max-steps", "2", "--batch-size",
         "16", "--generate", "2"]
    )
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    assert int(jax.device_get(state.step)) == 2


def test_gpt_lm_packed_smoke():
    from examples import gpt_lm

    state, metrics = gpt_lm.main(
        ["--tiny", "--rope", "--packed", "--seq-len", "32", "--max-steps",
         "2", "--batch-size", "16", "--train-examples", "64"]
    )
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
