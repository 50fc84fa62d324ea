"""LoRA fine-tuning — adapt a pretrained decoder with rank-r adapters.

The parameter-efficient fine-tuning entrypoint (training/lora.py): the
base model stays frozen (its params are constants of the compiled step),
only the rank-r `a`/`b` adapter pairs — and their AdamW slots — train.
The analog of the reference's train loop (its optimizer updates every
variable, /root/reference/tf2_mnist_distributed.py:85-90) restricted to
the adapter subspace, which is the standard recipe at converted-LLM size.

Two modes:

- `--hf-dir DIR`: fine-tune a converted checkpoint (models/convert.py
  artifact — GPT-2/LLaMA/Mistral), the real workflow.
- default: pretrain a tiny decoder on the synthetic structured stream
  for a few steps, then LoRA-adapt it — a hermetic demo of the same
  path (CPU smoke: `python examples/lora_finetune.py --fake-devices 8
  --tiny --max-steps 20`).

After training the adapters are merged (`merge_lora`) into a plain
base-shaped checkpoint: `--generate N` samples from the merged model
through the standard decode path, proving the export contract.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np
import optax

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from tfde_tpu import bootstrap
from tfde_tpu.data import datasets
from tfde_tpu.models.gpt import GPT, gpt_tiny_test, next_token_loss
from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy
from tfde_tpu.training.lora import (
    LoraConfig,
    init_lora_state,
    lora_param_count,
    make_lora_loss,
    merge_lora,
)
from tfde_tpu.training.step import init_state, make_custom_train_step

log = logging.getLogger(__name__)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--hf-dir", type=str, default=None,
                        help="converted checkpoint dir (models/convert.py); "
                             "default: pretrain a tiny base inline")
    parser.add_argument("--rank", type=int, default=8)
    parser.add_argument("--alpha", type=float, default=16.0)
    parser.add_argument("--target", type=str,
                        default=r"attn/(query|value)/kernel$",
                        help="regex over param paths (the HF-standard "
                             "q/v-projection default); use 'kernel$' to "
                             "adapt every projection")
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--seq-len", type=int, default=64)
    parser.add_argument("--max-steps", type=int, default=200)
    parser.add_argument("--learning-rate", type=float, default=1e-3)
    parser.add_argument("--pretrain-steps", type=int, default=60,
                        help="inline base pretraining steps (no --hf-dir)")
    parser.add_argument("--generate", type=int, default=0, metavar="N",
                        help="sample N tokens from the MERGED model after "
                             "fine-tuning (the export contract)")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--fake-devices", type=int, default=None)
    args, _ = parser.parse_known_args(argv)

    if args.fake_devices:
        jax.config.update("jax_platforms", "cpu")
        from tfde_tpu.utils.devices import request_cpu_devices
        request_cpu_devices(args.fake_devices)

    # force: an early jax import may already have attached handlers
    logging.basicConfig(level=logging.INFO, format="%(message)s", force=True)
    bootstrap()
    strategy = MultiWorkerMirroredStrategy()
    rng = np.random.default_rng(0)
    key = jax.random.key(0)

    # -- the frozen base --------------------------------------------------
    if args.hf_dir:
        from tfde_tpu.models.convert import load_converted

        model, base_params = load_converted(args.hf_dir)
        vocab = model.vocab_size
        base_params = jax.device_put(
            base_params, strategy.params_sharding(base_params)
        )
    else:
        vocab = 97
        model = (gpt_tiny_test() if args.tiny else
                 GPT(vocab_size=vocab, hidden_size=64, depth=4, num_heads=4,
                     mlp_dim=128, max_position=args.seq_len,
                     dtype=jax.numpy.float32))
        vocab = model.vocab_size
        state, _ = init_state(model, optax.adamw(3e-3), strategy,
                              np.zeros((args.batch_size, args.seq_len),
                                       np.int32))
        pre_step = make_custom_train_step(strategy, state, next_token_loss,
                                          donate=False)
        toks = datasets.synthetic_tokens(2048, args.seq_len, vocab=vocab - 1)
        m = None
        for i in range(args.pretrain_steps):
            idx = rng.integers(0, len(toks), args.batch_size)
            state, m = pre_step(state, (jax.numpy.asarray(toks[idx]),), key)
        if m is not None:
            log.info("base pretrained %d steps, loss %.4f",
                     args.pretrain_steps, float(m["loss"]))
        base_params = state.params

    # -- adapters ---------------------------------------------------------
    cfg = LoraConfig(rank=args.rank, alpha=args.alpha, target=args.target)
    lstate, _ = init_lora_state(
        model, optax.adamw(args.learning_rate), strategy, base_params, cfg
    )
    n_base = sum(x.size for x in jax.tree_util.tree_leaves(base_params))
    n_lora = lora_param_count(lstate.params)
    log.info("LoRA rank %d on %r: %d trainable params (%.2f%% of %d)",
             args.rank, args.target, n_lora, 100.0 * n_lora / n_base, n_base)

    step = make_custom_train_step(
        strategy, lstate, make_lora_loss(base_params, next_token_loss, cfg),
        donate=False,
    )
    # a genuinely SHIFTED domain: relabel every token t -> (t + 11) mod V.
    # The stream's Markov successor relation changes (the pretrained
    # "t predicts 31t+7" rule no longer holds on the relabeled ids), so
    # the adapters must learn the new transition structure, not just
    # continue pretraining on identically-distributed data
    ft = (datasets.synthetic_tokens(2048, args.seq_len, vocab=vocab - 1)
          + 11) % (vocab - 1)
    t0 = time.time()
    first = None
    m = None
    for i in range(args.max_steps):
        idx = rng.integers(0, len(ft), args.batch_size)
        lstate, m = step(lstate, (jax.numpy.asarray(ft[idx]),), key)
        if first is None:
            first = float(m["loss"])
        if (i + 1) % 50 == 0:
            log.info("step %d loss %.4f", i + 1, float(m["loss"]))
    if m is not None:
        log.info("fine-tune: loss %.4f -> %.4f in %.1fs",
                 first, float(m["loss"]), time.time() - t0)

    # -- merge + the export contract --------------------------------------
    merged = merge_lora(base_params, lstate.params, cfg)
    if args.generate:
        from tfde_tpu.inference.decode import generate

        prompt = jax.numpy.asarray(ft[:1, : args.seq_len // 2])
        out, _ = generate(model, merged, prompt,
                          max_new_tokens=args.generate)
        log.info("merged-model sample: %s",
                 np.asarray(out[0, -args.generate:]).tolist())
    return base_params, merged


if __name__ == "__main__":
    main()
