"""Train state: the checkpointable unit {step, params, batch_stats, opt_state}.

The analog of the reference's checkpoint contents (global step + variables +
optimizer slots saved by SaveV2 every 500 steps, mnist_keras:245-248), as one
pytree so Orbax can shard-save it and `jit` can donate it whole.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.struct
import jax
import optax


class TrainState(flax.struct.PyTreeNode):
    step: Any
    params: Any
    batch_stats: Any
    opt_state: Any
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    # Error-feedback residual for the quantized gradient transport
    # (parallel/comms.py): params-congruent fp32 tree holding what the int8
    # quantizer dropped last step, re-injected into the next exchange. None
    # under grad_transport='fp32' — None is an empty pytree, so the default
    # keeps the state structure (and every existing checkpoint/jaxpr)
    # byte-identical. Per-device contents (each replica carries ITS OWN
    # compression error); only the exchange ever reads it. Deliberately
    # NOT checkpointed (checkpoint/manager.py saves {step, params,
    # batch_stats, opt_state}): a resumed run restarts the residual from
    # zeros — a few warm-up steps of extra quantization error, and
    # fp32<->int8 checkpoint resume stays compatible in both directions.
    comm_residual: Any = None
    # ZeRO weight-update sharding (parallel/zero.py): the static chunk
    # layout when the optimizer state is packed/sharded over the data axis
    # ({packed_big: [N, Cb], packed_small: [N, Cs]} slots instead of
    # params-congruent ones), or None for the replicated default. Static
    # (non-pytree) so the step builder can branch on it at trace time; a
    # Layout is hashable, so treedefs still compare/jit-cache correctly.
    opt_layout: Any = flax.struct.field(pytree_node=False, default=None)

    @property
    def opt_sharded(self) -> bool:
        return self.opt_layout is not None

    def apply_chunk_gradients(self, grad_chunks, param_chunks):
        """The ZeRO owner-chunk update: run the optimizer on this replica's
        1/N packed slice only. `grad_chunks`/`param_chunks` are local
        {packed_big: [1, Cb], packed_small: [1, Cs]} trees and
        `self.opt_state` the matching local slice (inside the step's
        shard_map body). Returns (new_param_chunks, new_opt_state). For
        elementwise transforms this is bit-identical to the replicated
        per-leaf update — see parallel/zero.py's correctness contract."""
        with jax.named_scope("optimizer_update"):
            updates, new_opt_state = self.tx.update(
                grad_chunks, self.opt_state, param_chunks
            )
            return optax.apply_updates(param_chunks, updates), new_opt_state

    def apply_gradients(self, grads, new_batch_stats=None,
                        new_comm_residual=None):
        with jax.named_scope("optimizer_update"):
            updates, new_opt_state = self.tx.update(
                grads, self.opt_state, self.params)
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            batch_stats=(
                new_batch_stats if new_batch_stats is not None else self.batch_stats
            ),
            opt_state=new_opt_state,
            comm_residual=(
                new_comm_residual if new_comm_residual is not None
                else self.comm_residual
            ),
        )
