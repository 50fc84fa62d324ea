"""Sharded train-state checkpointing with auto-resume.

The reference's checkpoint contract (SURVEY.md §5): Estimator saves every
`save_checkpoints_steps=500` into `model_dir` (mnist_keras:245-248), restarted
processes transparently resume from the latest checkpoint, and `--working-dir`
may be a remote (GCS) path (mnist_keras:41-44). TPU-native equivalent: Orbax
async checkpointing of the {step, params, batch_stats, opt_state} pytree —
each host writes only its own shards of sharded arrays, restore respects the
target shardings, and writes go through Orbax's atomic-rename protocol (the
SaveV2/RestoreV2 + MonitoredTrainingSession analog).
"""

from __future__ import annotations

import logging
import os
from typing import TYPE_CHECKING, Any, Optional

import jax
import orbax.checkpoint as ocp

from tfde_tpu.observability import metrics
from tfde_tpu.observability.spans import span
from tfde_tpu.resilience.policy import RetryPolicy, policy_from_env, retry_call
from tfde_tpu.utils.fs import is_remote

if TYPE_CHECKING:  # avoid the training<->checkpoint import cycle at runtime
    from tfde_tpu.training.train_state import TrainState

log = logging.getLogger(__name__)


class CheckpointManager:
    """Thin Orbax wrapper bound to a model_dir.

    Saves the pytree-node part of a TrainState (apply_fn/tx are static code,
    not state). `restore_latest` returns a state with the *caller's* shardings
    — pass the live/abstract state so restored arrays land where training
    expects them.

    Save/restore are fallible remote I/O (gs:// blips are routine at pod
    scale), so both run under a retry policy — the operator's
    ``TFDE_RETRY_*`` knobs by default, or an explicit `retry_policy`.
    Retries only transient classes (OSError/timeouts); a structure-mismatch
    ValueError still fails fast on the first attempt.
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: Optional[int] = 5,
        async_save: bool = True,
        retry_policy: Optional[RetryPolicy] = None,
    ):
        if not is_remote(directory):
            # orbax refuses a relative path at the first save, not here
            directory = os.path.abspath(directory)
        self._dir = directory
        self._retry = retry_policy or policy_from_env()
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save,
        )
        self._mngr = ocp.CheckpointManager(directory, options=options)

    # -- save ---------------------------------------------------------------
    def save(self, state: "TrainState", force: bool = False) -> bool:
        step = int(jax.device_get(state.step))
        if step in (self._mngr.all_steps() or ()):  # already on disk
            return False
        with span("checkpoint/save"):
            saved = retry_call(
                self._mngr.save,
                step,
                args=ocp.args.StandardSave(self._tree(state)),
                force=force,
                policy=self._retry,
                what=f"checkpoint save(step={step})",
                counter="resilience/checkpoint_retries",
            )
        if saved:
            metrics.counter("checkpoint/saves").incr()
            metrics.gauge("checkpoint/latest_saved_step").set(step)
            log.info("checkpoint saved at step %d -> %s", step, self._dir)
            from tfde_tpu.observability import flightrec

            flightrec.record("ckpt_save", step=step, forced=bool(force))
        return saved

    def wait(self) -> None:
        """Block until pending async saves commit (call before process exit)."""
        with span("checkpoint/wait"):
            self._mngr.wait_until_finished()

    # -- restore ------------------------------------------------------------
    @property
    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def reload(self) -> None:
        """Re-read the checkpoint directory. Orbax caches the step listing
        at construction; an evaluator job following a live trainer's
        model_dir must reload to see checkpoints written since."""
        self._mngr.reload()

    def restore_latest(self, state: "TrainState") -> Optional["TrainState"]:
        """Resume-by-default: restore the newest checkpoint into the given
        state's shardings, or None if the directory has no checkpoint."""
        step = self._mngr.latest_step()
        if step is None:
            return None
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if hasattr(x, "sharding")
            else x,
            self._tree(state),
        )
        if self._packed_geometry_differs(step, state):
            # ZeRO checkpoint from a DIFFERENT world size: same container
            # skeleton, different [N, C] chunk shapes. The direct path must
            # not even be attempted — orbax does not reliably reject the
            # shape change (with sharded targets it can silently reshard
            # the wrong bytes into the new chunks), so route straight to
            # the cross-format bridge's re-chunk branch.
            bridged = self._restore_cross_format(step, state, abstract)
            if bridged is not None:
                log.info(
                    "restored checkpoint step %d from %s "
                    "(cross-format opt state)",
                    int(jax.device_get(bridged.step)), self._dir,
                )
                from tfde_tpu.observability import flightrec

                flightrec.record(
                    "ckpt_restore",
                    step=int(jax.device_get(bridged.step)),
                    cross_format=True,
                )
                return bridged
            raise ValueError(
                f"checkpoint step {step} in {self._dir} holds ZeRO-packed "
                f"optimizer state with a different chunk geometry than the "
                f"current state (written at a different world size or with "
                f"different comms blocking), and the cross-world re-chunk "
                f"could not bridge it. Resume at the writer's world size, "
                f"or clear the checkpoint directory to restart"
            )
        try:
            # NOTE goodput accounting: restores run inside the train loop's
            # init span, so "checkpoint/restore" is observability-only and
            # the ledger's checkpoint category counts save+wait alone
            import time as _time

            t_restore = _time.perf_counter()
            with span("checkpoint/restore"):
                restored = retry_call(
                    self._mngr.restore,
                    step,
                    args=ocp.args.StandardRestore(abstract),
                    policy=self._retry,
                    what=f"checkpoint restore(step={step})",
                    counter="resilience/checkpoint_retries",
                )
            self._note_boot_restore(
                restored, _time.perf_counter() - t_restore)
        except ValueError as e:
            # Reword ONLY genuine structure mismatches: compare the saved
            # checkpoint's tree structure (orbax metadata) against the
            # requested abstract tree, instead of sniffing the error text —
            # an unrelated ValueError that happens to mention "structure"
            # must surface unrelabeled.
            if (self._saved_structure_differs(step, abstract)
                    or self._packed_geometry_differs(step, state)):
                bridged = self._restore_cross_format(step, state, abstract)
                if bridged is not None:
                    log.info(
                        "restored checkpoint step %d from %s "
                        "(cross-format opt state)",
                        int(jax.device_get(bridged.step)), self._dir,
                    )
                    from tfde_tpu.observability import flightrec

                    flightrec.record(
                        "ckpt_restore",
                        step=int(jax.device_get(bridged.step)),
                        cross_format=True,
                    )
                    return bridged
                raise ValueError(
                    f"checkpoint step {step} in {self._dir} does not match "
                    f"the current train state's structure — most commonly "
                    f"the optimizer configuration changed since it was "
                    f"written (e.g. a decay mask wraps the opt state). "
                    f"Resume with the original optimizer, or clear the "
                    f"checkpoint directory to restart"
                ) from e
            raise
        log.info("restored checkpoint step %d from %s", step, self._dir)
        from tfde_tpu.observability import flightrec

        flightrec.record("ckpt_restore", step=step)
        return state.replace(
            step=restored["step"],
            params=restored["params"],
            batch_stats=restored["batch_stats"],
            opt_state=restored["opt_state"],
        )

    @staticmethod
    def _note_boot_restore(restored, seconds: float) -> None:
        """Feed the boot ledger's restore accounting: per-top-level-leaf
        bytes of the restored tree plus the restore call's wall become
        ``boot/restore_bandwidth_bps`` — the streamed-restore baseline a
        joining replica's cold start is measured against. Best-effort:
        a ledger failure must never fail a restore."""
        try:
            from tfde_tpu.observability import boot as boot_lib

            leaves = {}
            for name, sub in restored.items():
                nb = sum(int(getattr(x, "nbytes", 0))
                         for x in jax.tree_util.tree_leaves(sub))
                if nb:
                    leaves[str(name)] = nb
            if leaves:
                boot_lib.note_restore(leaves, seconds)
        except Exception:
            log.debug("boot restore accounting failed", exc_info=True)

    @staticmethod
    def _find_packed(node):
        """First ZeRO packed-slot dict (exactly {packed_big, packed_small})
        in an orbax metadata tree, or None. Marks a checkpoint written with
        opt_sharding='shard' (parallel/zero.py)."""
        if isinstance(node, dict):
            if set(node.keys()) == {"packed_big", "packed_small"}:
                return node
            children = node.values()
        elif isinstance(node, (list, tuple)):
            children = node
        else:
            return None
        for child in children:
            found = CheckpointManager._find_packed(child)
            if found is not None:
                return found
        return None

    def _restore_cross_format(self, step, state, abstract):
        """Bridge optimizer-state formats on restore: a checkpoint written
        with opt_sharding='replicated' resumed into a ZeRO-sharded state
        (pack after a replicated restore), one written with 'shard' resumed
        into a replicated state (restore the packed slots, then unpack), or
        one written with 'shard' at a DIFFERENT world size resumed into a
        ZeRO-sharded state (restore under the writer's M-way layout, then
        re-chunk to the live N-way layout — the elastic shrink/grow path,
        both M>N and M<N). All directions are bit-exact — pack/unpack/
        relayout are pure reshapes of the same numbers. Conservative: any
        failure returns None and the direct path's structure-mismatch
        guidance surfaces instead."""
        try:
            from jax.sharding import NamedSharding, PartitionSpec
            from tfde_tpu.parallel import comms as comms_lib
            from tfde_tpu.parallel import zero as zero_lib

            meta = self._item_meta(step)
            saved_packed = self._find_packed(meta["opt_state"])
            layout = getattr(state, "opt_layout", None)
            leaves = jax.tree_util.tree_leaves(state.params)
            if not leaves:
                return None
            psh = leaves[0].sharding
            rep = (NamedSharding(psh.mesh, PartitionSpec())
                   if hasattr(psh, "mesh") else psh)

            def abstract_rep(tree):
                return jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=rep),
                    tree,
                )

            if layout is not None and saved_packed is None:
                # saved replicated -> live sharded: restore the
                # params-congruent slots fully replicated, pack, reshard
                ab_opt = abstract_rep(jax.eval_shape(state.tx.init,
                                                     state.params))
                restored = self._restore_opt_variant(step, abstract, ab_opt)
                opt = zero_lib.pack_opt_state(restored["opt_state"], layout)
            elif layout is None and saved_packed is not None:
                # saved sharded -> live replicated: rebuild the writer's
                # layout from the packed shapes, restore, unpack
                big_shape = tuple(saved_packed["packed_big"].shape)
                cand = zero_lib.build_layout(
                    state.params, comms_lib.CommsConfig(), int(big_shape[0]))
                if (big_shape != (cand.nshards, cand.chunk_big)
                        or tuple(saved_packed["packed_small"].shape)
                        != (cand.nshards, cand.chunk_small)):
                    return None  # non-default comms block/threshold knobs
                ab_opt = abstract_rep(jax.eval_shape(
                    lambda p: state.tx.init(zero_lib.pack_params(p, cand)),
                    state.params,
                ))
                restored = self._restore_opt_variant(step, abstract, ab_opt)
                opt = zero_lib.unpack_opt_state(restored["opt_state"], cand)
            elif layout is not None and saved_packed is not None:
                # saved sharded M-way -> live sharded N-way: reconstruct
                # the writer's layout from the live one (same params, same
                # block; only nshards differs), restore the packed slots
                # replicated under it, then re-chunk to the live layout
                saved_n = int(saved_packed[zero_lib.BIG].shape[0])
                cand = zero_lib.with_nshards(layout, saved_n)
                if (tuple(saved_packed[zero_lib.BIG].shape)
                        != (cand.nshards, cand.chunk_big)
                        or tuple(saved_packed[zero_lib.SMALL].shape)
                        != (cand.nshards, cand.chunk_small)):
                    return None  # different params or comms block knobs
                ab_opt = abstract_rep(jax.eval_shape(
                    lambda p: state.tx.init(zero_lib.pack_params(p, cand)),
                    state.params,
                ))
                restored = self._restore_opt_variant(step, abstract, ab_opt)
                opt = zero_lib.relayout_opt_state(
                    restored["opt_state"], cand, layout)
            else:
                return None
            opt = jax.device_put(
                opt,
                jax.tree_util.tree_map(lambda x: x.sharding, state.opt_state),
            )
            return state.replace(
                step=restored["step"],
                params=restored["params"],
                batch_stats=restored["batch_stats"],
                opt_state=opt,
            )
        except Exception:
            log.debug("cross-format restore attempt failed", exc_info=True)
            return None

    def _restore_opt_variant(self, step, abstract, ab_opt):
        """Restore with the direct path's abstract tree, opt_state swapped
        for the other format's abstract."""
        alt = dict(abstract)
        alt["opt_state"] = ab_opt
        return retry_call(
            self._mngr.restore,
            step,
            args=ocp.args.StandardRestore(alt),
            policy=self._retry,
            what=f"checkpoint restore(step={step}, cross-format)",
            counter="resilience/checkpoint_retries",
        )

    @staticmethod
    def _normalize_structure(tree):
        """Container skeleton of a pytree in orbax-metadata-comparable
        form: namedtuples (optax states) -> {field: ...} dicts (metadata
        loses the namedtuple class), plain tuples/lists -> lists, empty
        containers -> None (metadata collapses e.g. optax.EmptyState() to
        a leaf), every leaf -> None. Verified empirically: a matching
        adamw state normalizes equal to its saved metadata; an
        sgd(momentum) state against an adamw checkpoint does not."""
        n = CheckpointManager._normalize_structure
        if isinstance(tree, tuple) and hasattr(tree, "_fields"):
            return {f: n(v) for f, v in zip(tree._fields, tree)} or None
        if isinstance(tree, dict):
            return {k: n(v) for k, v in tree.items()} or None
        if isinstance(tree, (list, tuple)):
            return [n(v) for v in tree] or None
        return None

    def _packed_geometry_differs(self, step: int, state) -> bool:
        """True when both the checkpoint and the live state hold ZeRO-packed
        optimizer slots but with different chunk geometry — a checkpoint
        written at a different world size. The container skeletons are
        IDENTICAL in that case (same {packed_big, packed_small} dicts, only
        the [N, C] shapes moved), so `_saved_structure_differs` cannot see
        it; this is the trigger that routes the elastic M-way -> N-way
        restore through the cross-format bridge. Conservative like its
        sibling: any failure reading metadata returns False."""
        try:
            from tfde_tpu.parallel import zero as zero_lib

            layout = getattr(state, "opt_layout", None)
            if layout is None:
                return False
            meta = self._item_meta(step)
            saved = self._find_packed(meta["opt_state"])
            if saved is None:
                return False
            return (tuple(saved[zero_lib.BIG].shape)
                    != (layout.nshards, layout.chunk_big)
                    or tuple(saved[zero_lib.SMALL].shape)
                    != (layout.nshards, layout.chunk_small))
        except Exception:
            return False

    def _item_meta(self, step: int):
        """Metadata tree of the saved checkpoint at `step`. The manager's
        own `item_metadata` returns None until a save/restore registered
        the item handler — a fresh manager that has done neither (the
        restart/elastic-restore case) falls back to a standalone
        StandardCheckpointHandler read of the step's item directory."""
        meta = self._mngr.item_metadata(step)
        if meta is None:
            import os

            ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())
            try:
                meta = ckptr.metadata(os.path.join(self._dir, str(step),
                                                   "default"))
            finally:
                ckptr.close()
        # newer orbax wraps the tree in a metadata object; older returns
        # the (dict) tree itself
        return getattr(meta, "tree", meta)

    def _saved_structure_differs(self, step: int, abstract) -> bool:
        """True when the on-disk checkpoint's pytree structure differs from
        the tree we asked to restore into — the condition the optimizer-
        changed guidance in restore_latest is about. Conservative: any
        failure reading metadata returns False (the original error then
        propagates untouched)."""
        try:
            meta = self._item_meta(step)
            return (self._normalize_structure(meta)
                    != self._normalize_structure(abstract))
        except Exception:
            return False

    def close(self) -> None:
        self._mngr.close()

    @staticmethod
    def _tree(state: "TrainState") -> dict:
        return {
            "step": state.step,
            "params": state.params,
            "batch_stats": state.batch_stats,
            "opt_state": state.opt_state,
        }
