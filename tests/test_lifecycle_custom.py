"""Custom-objective Estimator lifecycle (training/lifecycle.py loss_fn /
eval_fn): a causal LM rides the FULL train_and_evaluate machinery —
checkpoints, resume, summaries, throttled eval — instead of a hand-rolled
loop."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tfde_tpu.data.pipeline import Dataset
from tfde_tpu.models.gpt import gpt_tiny_test, next_token_loss
from tfde_tpu.ops.losses import masked_lm_loss
from tfde_tpu.training.lifecycle import Estimator, EvalSpec, RunConfig, TrainSpec


def lm_eval_fn(state, params, batch):
    """Deterministic eval twin of next_token_loss: per-batch means + the
    token count as the aggregation weight."""
    (tokens,) = batch if isinstance(batch, tuple) else (batch,)
    logits = state.apply_fn({"params": params}, tokens, train=False)
    labels = tokens[:, 1:].astype(jnp.int32)
    loss, acc = masked_lm_loss(logits[:, :-1], labels)
    n = jnp.asarray(labels.size, jnp.float32)
    return {"loss": loss, "next_token_accuracy": acc, "weight": n}


def _token_input_fn(seed, n=256, batch=16, seq=16, repeat=None):
    from tfde_tpu.data.datasets import synthetic_tokens

    tokens = synthetic_tokens(n, seq, vocab=96)

    def input_fn():
        ds = Dataset.from_tensor_slices((tokens,)).shuffle(n, seed=seed)
        if repeat is None:
            ds = ds.repeat()
        return iter(ds.batch(batch, drop_remainder=True))

    return input_fn


@pytest.mark.slow
def test_lora_estimator_lifecycle(tmp_path):
    """LoRA through the FULL lifecycle: adapters-only TrainState (tiny
    checkpoints), resume-by-default, eval/predict on the MERGED params,
    base frozen throughout."""
    from tfde_tpu.training.lora import LoraConfig

    model = gpt_tiny_test()
    base = model.init(jax.random.key(5), jnp.zeros((2, 8), jnp.int32),
                      train=False)["params"]
    n_base = sum(x.size for x in jax.tree_util.tree_leaves(base))
    cfg = RunConfig(model_dir=str(tmp_path), save_checkpoints_steps=10)
    mk = lambda: Estimator(
        model, optax.adamw(5e-3), config=cfg, loss_fn=next_token_loss,
        eval_fn=lm_eval_fn, lora=LoraConfig(rank=4),
        lora_base_params=base,
    )
    est = mk()
    state = est.train(_token_input_fn(0), max_steps=20)
    # the TrainState holds adapters, not the base — the checkpoint is tiny
    n_train = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    assert n_train < n_base / 5
    first = est.evaluate(_token_input_fn(1, repeat=1), name="eval")
    assert np.isfinite(first["loss"])
    est.close()

    # resume: a fresh estimator restores the adapters and continues
    est2 = mk()
    state = est2.train(_token_input_fn(2), max_steps=70)
    assert int(jax.device_get(state.step)) == 70
    second = est2.evaluate(_token_input_fn(1, repeat=1), name="eval")
    assert second["loss"] < first["loss"]
    # the frozen base never changed
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(est2._lora_base)[0]),
        np.asarray(jax.tree_util.tree_leaves(base)[0]),
    )
    est2.close()


def test_lora_continuous_eval_from_checkpoint(tmp_path):
    """LoRA + eval_mode='from_checkpoint': the background evaluator must
    build the same adapters-only state template to restore the trainer's
    tiny checkpoints, and evaluate MERGED params — the regression case
    for the evaluator inheriting lora/lora_base_params."""
    from tfde_tpu.training.lora import LoraConfig

    model = gpt_tiny_test()
    base = model.init(jax.random.key(5), jnp.zeros((2, 8), jnp.int32),
                      train=False)["params"]
    cfg = RunConfig(model_dir=str(tmp_path), save_checkpoints_steps=5,
                    save_summary_steps=100)
    est = Estimator(model, optax.adamw(5e-3), config=cfg,
                    loss_fn=next_token_loss, eval_fn=lm_eval_fn,
                    lora=LoraConfig(rank=4), lora_base_params=base)
    from tfde_tpu.training.lifecycle import train_and_evaluate

    state, metrics = train_and_evaluate(
        est,
        TrainSpec(_token_input_fn(0), max_steps=15),
        EvalSpec(_token_input_fn(1, repeat=1), start_delay_secs=0,
                 throttle_secs=0.2),
        eval_mode="from_checkpoint",
    )
    est.close()
    assert int(jax.device_get(state.step)) == 15
    assert np.isfinite(metrics["loss"])


@pytest.mark.slow
def test_lm_estimator_lifecycle_and_resume(tmp_path):
    cfg = RunConfig(model_dir=str(tmp_path), save_summary_steps=5,
                    save_checkpoints_steps=10, log_step_count_steps=10)
    est = Estimator(gpt_tiny_test(), optax.adamw(3e-3), config=cfg,
                    loss_fn=next_token_loss, eval_fn=lm_eval_fn)
    est.train(_token_input_fn(0), max_steps=20)
    first = est.evaluate(_token_input_fn(1, repeat=1), name="eval")
    assert np.isfinite(first["loss"])
    assert 0.0 <= first["next_token_accuracy"] <= 1.0
    est.close()

    # resume-by-default: a fresh estimator picks up step 20 and trains the
    # remainder only; loss must keep improving on the structured stream
    est2 = Estimator(gpt_tiny_test(), optax.adamw(3e-3), config=cfg,
                     loss_fn=next_token_loss, eval_fn=lm_eval_fn)
    state = est2.train(_token_input_fn(2), max_steps=60)
    assert int(jax.device_get(state.step)) == 60
    second = est2.evaluate(_token_input_fn(1, repeat=1), name="eval")
    assert second["loss"] < first["loss"]
    est2.close()

    # summaries were written for train and eval
    files = []
    for root, _, names in os.walk(tmp_path):
        files += [os.path.join(root, f) for f in names if "tfevents" in f]
    assert len(files) >= 2


def test_lm_train_and_evaluate_interleaves(tmp_path):
    cfg = RunConfig(model_dir=str(tmp_path), save_checkpoints_steps=10)
    est = Estimator(gpt_tiny_test(), optax.adamw(3e-3), config=cfg,
                    loss_fn=next_token_loss, eval_fn=lm_eval_fn)
    from tfde_tpu.training.lifecycle import train_and_evaluate

    state, metrics = train_and_evaluate(
        est,
        TrainSpec(input_fn=_token_input_fn(0), max_steps=15),
        EvalSpec(input_fn=_token_input_fn(1, repeat=1), steps=2,
                 start_delay_secs=0, throttle_secs=0),
    )
    assert int(jax.device_get(state.step)) == 15
    assert np.isfinite(metrics["loss"])
    est.close()


@pytest.mark.slow
def test_lm_continuous_eval_from_checkpoint(tmp_path):
    """The evaluator job inherits the custom objective: a background
    evaluator on a custom-loss Estimator must run the eval_fn path, not
    crash in the classification padding protocol."""
    from tfde_tpu.training.lifecycle import train_and_evaluate

    cfg = RunConfig(model_dir=str(tmp_path), save_checkpoints_steps=5)
    est = Estimator(gpt_tiny_test(), optax.adamw(3e-3), config=cfg,
                    loss_fn=next_token_loss, eval_fn=lm_eval_fn)
    state, metrics = train_and_evaluate(
        est,
        TrainSpec(input_fn=_token_input_fn(0), max_steps=10),
        EvalSpec(input_fn=_token_input_fn(1, repeat=1), steps=2,
                 start_delay_secs=0, throttle_secs=0),
        eval_mode="from_checkpoint",
    )
    assert int(jax.device_get(state.step)) == 10
    assert np.isfinite(metrics.get("loss", float("nan")))
    est.close()


def test_train_and_evaluate_fails_fast_without_eval_fn(tmp_path):
    """The missing-eval_fn error must fire BEFORE training, not after the
    budget is spent at the first throttled eval."""
    from tfde_tpu.training.lifecycle import train_and_evaluate

    cfg = RunConfig(model_dir=str(tmp_path))
    est = Estimator(gpt_tiny_test(), optax.adamw(1e-3), config=cfg,
                    loss_fn=next_token_loss)
    with pytest.raises(RuntimeError, match="eval_fn"):
        train_and_evaluate(
            est,
            TrainSpec(input_fn=_token_input_fn(0), max_steps=5),
            EvalSpec(input_fn=_token_input_fn(1, repeat=1), steps=1),
        )
    # nothing trained: the check fired at entry
    assert est._state is None
    est.close()


def test_custom_loss_without_eval_fn_refuses(tmp_path):
    cfg = RunConfig(model_dir=str(tmp_path))
    est = Estimator(gpt_tiny_test(), optax.adamw(1e-3), config=cfg,
                    loss_fn=next_token_loss)
    est.train(_token_input_fn(0), max_steps=2)
    with pytest.raises(RuntimeError, match="eval_fn"):
        est.evaluate(_token_input_fn(1, repeat=1))
    est.close()


def test_lm_estimator_grad_accum(tmp_path):
    cfg = RunConfig(model_dir=None)
    est = Estimator(gpt_tiny_test(), optax.adamw(3e-3), config=cfg,
                    loss_fn=next_token_loss, eval_fn=lm_eval_fn,
                    grad_accum=2)
    state = est.train(_token_input_fn(0), max_steps=5)
    assert int(jax.device_get(state.step)) == 5
    est.close()


def test_partial_eval_batch_fails_with_named_cause(tmp_path):
    """A trailing partial batch (input_fn without drop_remainder) must fail
    with an error naming drop_remainder, not an opaque sharding error
    inside device_put/jit (advisor r3)."""
    from tfde_tpu.data.datasets import synthetic_tokens
    from tfde_tpu.parallel.strategies import MirroredStrategy

    cfg = RunConfig(model_dir=str(tmp_path))
    est = Estimator(gpt_tiny_test(), optax.sgd(0.1), config=cfg,
                    loss_fn=next_token_loss, eval_fn=lm_eval_fn,
                    strategy=MirroredStrategy())
    est.train(_token_input_fn(3), max_steps=1)
    tokens = synthetic_tokens(37, 16, vocab=96)  # 37 % 8 devices != 0

    def ragged_input_fn():
        # one full batch of 32, then a partial batch of 5
        return iter(Dataset.from_tensor_slices((tokens,)).batch(32))

    with pytest.raises(ValueError, match="drop_remainder"):
        est.evaluate(ragged_input_fn, name="ragged")


def test_pipelined_1f1b_estimator_lifecycle_and_resume(tmp_path):
    """The full Estimator machinery — checkpointing the pipe-sharded
    [S, L, ...] stage params via orbax, resume-by-default, throttled eval
    — over a PipelinedLM training on the 1F1B schedule. Proves the
    round-4 schedule composes with the round-1 lifecycle, not just with
    bare train steps."""
    from tfde_tpu.models.pipelined import (
        pipelined_next_token_loss,
        pipelined_tiny_test,
    )
    from tfde_tpu.parallel.strategies import PipelineParallelStrategy

    def eval_fn(state, params, batch):
        (tokens,) = batch if isinstance(batch, tuple) else (batch,)
        model = state.apply_fn.__self__
        loss, metrics = model.loss_and_metrics(
            {"params": params}, tokens, train=False
        )
        n = float(tokens.shape[0] * (tokens.shape[1] - 1))
        return {"loss": loss, **metrics,
                "weight": jnp.asarray(n, jnp.float32)}

    model = pipelined_tiny_test(schedule="1f1b")
    cfg = RunConfig(model_dir=str(tmp_path), save_checkpoints_steps=5,
                    save_summary_steps=5, log_step_count_steps=5)

    def make_est():
        return Estimator(
            model, optax.adamw(3e-3),
            strategy=PipelineParallelStrategy(data=2, pipe=2),
            config=cfg, loss_fn=pipelined_next_token_loss, eval_fn=eval_fn,
        )

    est = make_est()
    est.train(_token_input_fn(0), max_steps=10)
    first = est.evaluate(_token_input_fn(1, repeat=1), name="eval")
    assert np.isfinite(first["loss"])
    est.close()

    # resume-by-default: fresh estimator picks up step 10, trains on
    est2 = make_est()
    state = est2.train(_token_input_fn(0), max_steps=14)
    assert int(jax.device_get(state.step)) == 14
    second = est2.evaluate(_token_input_fn(1, repeat=1), name="eval")
    assert second["loss"] < first["loss"] + 0.05  # still improving-ish
    est2.close()


def test_merged_params_restores_in_fresh_process(tmp_path):
    """The deploy step runs in a new process: merged_params(sample_input)
    restores the latest adapters-only checkpoint and returns base-shaped
    params; without a checkpoint it refuses loudly."""
    from tfde_tpu.training.lora import LoraConfig

    model = gpt_tiny_test()
    base = model.init(jax.random.key(5), jnp.zeros((2, 8), jnp.int32),
                      train=False)["params"]
    cfg = RunConfig(model_dir=str(tmp_path), save_checkpoints_steps=3)
    mk = lambda: Estimator(model, optax.adamw(5e-3), config=cfg,
                           loss_fn=next_token_loss,
                           lora=LoraConfig(rank=4), lora_base_params=base)
    est = mk()
    est.train(_token_input_fn(0), max_steps=6)
    est.close()

    est2 = mk()  # fresh-process analog
    merged = est2.merged_params(sample_input=np.zeros((16, 16), np.int32))
    assert (jax.tree_util.tree_structure(merged)
            == jax.tree_util.tree_structure(base))
    est2.close()

    empty_cfg = RunConfig(model_dir=str(tmp_path / "empty"),
                          save_checkpoints_steps=3)
    est3 = Estimator(model, optax.adamw(5e-3), config=empty_cfg,
                     loss_fn=next_token_loss, lora=LoraConfig(rank=4),
                     lora_base_params=base)
    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="no checkpoint|no trained"):
        est3.merged_params(sample_input=np.zeros((16, 16), np.int32))
