"""Pipeline-parallel causal LM — a transformer stack executed through the
collective pipeline (parallel/pipeline.py) over the 'pipe' mesh axis.

Scale-up scope beyond the reference (SURVEY.md §2c: "Pipeline parallel:
absent"). Where GPU frameworks place different *programs* on different
devices and hand-schedule send/recv, the TPU-native formulation keeps one
SPMD program: stage weights live stacked along a leading [num_stages, ...]
axis sharded over 'pipe', and activations hop ranks via `lax.ppermute`
(neighbor ICI traffic). See parallel/pipeline.py for the schedule.

Architecture = GPT arrangement (models/gpt.py): tied embedding/LM head,
learned positions, pre-LN TransformerBlocks, causal attention. The model is
deliberately *mesh-agnostic*: `apply` runs the stage stack through
`pipeline_apply` when the active mesh (parallel/axes.use_axes, set by the
step factories) has a 'pipe' axis of size > 1, and as a plain sequential
scan otherwise — so the same params train on a DP mesh or a pipe mesh, which
is exactly what the pipe-vs-DP numerics test asserts
(tests/test_pipelined_lm.py).

Not an `nn.Module`: the stacked-stage param layout ([S, L, ...] leaves) is
the load-bearing design, and flax's module system fights external param
stacking. Instead the class duck-types `model.init(rng, sample, train=...)`
/ `model.apply(variables, batch, train=..., rngs=...)`, which is all
training/step.py's `init_state` + `make_custom_train_step` consume.

3D (round 3): on a mesh with a >1 'tensor' axis the pipe auto-selects
pipeline_apply's partial-manual mode — stage weights shard over 'pipe' AND
Megatron-split over 'tensor' (PipelineParallelStrategy(tensor=T)), with the
automatic partitioner inserting the TP collectives inside the ring
(dp x pp x tp; tests/test_pipelined_lm.py::test_3d_dp_pp_tp_matches_dp).

pp x sp (round 4): a >1 'seq' axis shards the SEQUENCE inside the
fully-manual pipe — stage attention runs the per-shard ring body
(ops/ring_attention.ring_attention_manual via parallel/axes.manual_seq),
activations shard their seq dim in the pipe specs, and the loss routes
through the full-logit path outside the pipe (a last-stage shifted loss
would misalign at shard boundaries). pp x sp x tp and 1F1B+seq are
refused loudly — see _pipe_mesh / parallel/pipeline.py.

Dropout (round-3, closing VERDICT r2 weak #8's capability cliff vs GPT):
`dropout_rate > 0` threads per-tick keys through the shard_map schedule —
each stage derives fold_in(base, microbatch, global_layer, data_shard) from
the tick's microbatch index (pipeline_apply's 3-arg stage_fn form), its pipe
rank, and its data-shard index, so masks are deterministic per seed and
uncorrelated across microbatches, layers, and shards. Masks are layout-
dependent (a different mesh samples different noise), so exact-numerics
parity tests run at dropout 0, like every framework's.

Loss (round-3, VERDICT r2 weak #8's perf note): `loss_and_metrics` computes
the shifted next-token CE through pipeline_apply's last-stage reduction —
the [M, micro, seq, hidden] full-output psum broadcast at the end of the
pipe is replaced by a 3-scalar psum; use `pipelined_next_token_loss` with
make_custom_train_step to train on that path. `apply` (full logits) keeps
the broadcast, which inference/decoding genuinely needs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from tfde_tpu.models.transformer import TransformerBlock
from tfde_tpu.parallel import axes as axes_lib
from tfde_tpu.parallel.pipeline import pipeline_apply


@dataclasses.dataclass(frozen=True)
class PipelinedLM:
    """Decoder-only LM over [B, S] int ids -> [B, S, vocab] fp32 logits."""

    vocab_size: int = 50257
    hidden_size: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_position: int = 1024
    num_stages: int = 2
    layers_per_stage: int = 6
    microbatches: int = 4
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    fused_qkv: bool = False  # one-GEMM qkv projection (transformer.py)
    remat: Any = False  # False | True/'full' | 'dots' (transformer.remat_policy)
    # pipeline_apply execution mode: None auto-selects — 'auto' (partial-
    # manual shard_map; required for tensor-parallel stage weights, dp x pp
    # x tp) when the mesh has a >1 'tensor' axis, the proven fully-'manual'
    # ring otherwise. Set explicitly to force either.
    pipeline_mode: Optional[str] = None
    # backward schedule for the training loss path: 'gpipe' (AD through the
    # forward ring — activation memory O(M + S) per rank) or '1f1b'
    # (pipeline_train_1f1b: explicit fwd/bwd interleave, memory O(S) with
    # stage-input remat; manual mode only — see parallel/pipeline.py).
    schedule: str = "gpipe"

    @property
    def depth(self) -> int:
        return self.num_stages * self.layers_per_stage

    def _block(self) -> TransformerBlock:
        return TransformerBlock(
            num_heads=self.num_heads,
            head_dim=self.hidden_size // self.num_heads,
            mlp_dim=self.mlp_dim,
            dtype=self.dtype,
            dropout_rate=self.dropout_rate,
            attn_impl=self.attn_impl,
            fused_qkv=self.fused_qkv,
            causal=True,
            norm_style="pre",
        )

    def _dropout_base(self, train: bool, rngs: Optional[dict]):
        """The base dropout key, or None when dropout is inactive. Keys are
        derived as fold_in(base, microbatch, global_layer[, data_shard]) —
        the data-shard fold matters inside shard_map, where flax would
        otherwise draw the SAME mask on every data shard (same key, same
        local shape = correlated dropout across shards). Masks are therefore
        deterministic per seed but layout-dependent; numerical parity tests
        run at dropout 0, like every framework's."""
        if not train or self.dropout_rate <= 0.0 or not rngs:
            return None
        return rngs.get("dropout")

    # -- init ----------------------------------------------------------------
    def init(self, rng, sample_tokens: jax.Array, train: bool = False) -> dict:
        """Returns {'params': {wte, wpe, stages, ln_final}} where every leaf
        under 'stages' is stacked [num_stages, layers_per_stage, ...]."""
        del train
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide by num_heads")
        seq = sample_tokens.shape[1]
        if seq > self.max_position:
            raise ValueError(f"seq {seq} > max_position {self.max_position}")
        k_wte, k_wpe, k_blocks = jax.random.split(rng, 3)

        block = self._block()
        dummy = jnp.zeros((1, seq, self.hidden_size), self.dtype)
        n = self.num_stages * self.layers_per_stage
        block_keys = jax.random.split(k_blocks, n)
        per_layer = jax.vmap(
            lambda k: block.init(k, dummy, None, False)["params"]
        )(block_keys)
        stages = jax.tree_util.tree_map(
            lambda v: v.reshape(
                (self.num_stages, self.layers_per_stage) + v.shape[1:]
            ),
            per_layer,
        )
        params = {
            "wte": jax.random.normal(
                k_wte, (self.vocab_size, self.hidden_size), jnp.float32
            ) * 0.02,
            "wpe": jax.random.normal(
                k_wpe, (self.max_position, self.hidden_size), jnp.float32
            ) * 0.02,
            "stages": stages,
            "ln_final": {
                "scale": jnp.ones((self.hidden_size,), jnp.float32),
                "bias": jnp.zeros((self.hidden_size,), jnp.float32),
            },
        }
        return {"params": params}

    # -- shared pieces -------------------------------------------------------
    def _embed(self, p: dict, tokens: jax.Array) -> jax.Array:
        seq = tokens.shape[1]
        if seq > self.max_position:
            raise ValueError(f"seq {seq} > max_position {self.max_position}")
        x = jnp.take(p["wte"], tokens, axis=0)
        x = x + p["wpe"][None, :seq]
        return x.astype(self.dtype)

    @staticmethod
    def _head(extra: dict, x: jax.Array) -> jax.Array:
        """Final LN in fp32, then the tied LM head (GPT-2 convention).
        extra = {'wte', 'ln_final'}; usable inside the pipe's last-stage
        reduction as well as on the broadcast output."""
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        x32 = (x32 - mean) * jax.lax.rsqrt(var + 1e-6)
        x32 = x32 * extra["ln_final"]["scale"] + extra["ln_final"]["bias"]
        logits = x32.astype(x.dtype) @ extra["wte"].astype(x.dtype).T
        return logits.astype(jnp.float32)

    def _make_layer_fn(self, train: bool, base_key, in_pipe: bool,
                       shard_axes: tuple = (), auto_axes: bool = False,
                       seq_ring: int = 1, manual_axes: tuple = ()):
        """One block application, scanned over a stage's layers. Carries
        (h, mb_idx); per-layer dropout key = fold_in(base, mb, layer) plus,
        inside the fully-manual pipe, the data-shard index (see
        _dropout_base; in auto mode masks are global, no fold needed)."""
        block = self._block()

        def layer(carry, lp_li):
            h, mb = carry
            lp, li = lp_li
            kwargs = {}
            if base_key is not None:
                key = jax.random.fold_in(
                    jax.random.fold_in(base_key, mb), li
                )
                for a in shard_axes:
                    key = jax.random.fold_in(key, jax.lax.axis_index(a))
                kwargs["rngs"] = {"dropout": key}
            if in_pipe and not auto_axes:
                # fully-manual shard_map: every mesh axis is manual, so the
                # blocks' `constrain` annotations (which name full-mesh
                # axes) must degrade to identity here. With a >1 'seq'
                # ring, attention must run the per-shard ring body
                # (pp x sp) — manual_seq flips ops/attention's dispatch.
                with axes_lib.use_axes(None):
                    if seq_ring > 1:
                        with axes_lib.manual_seq(seq_ring, manual_axes):
                            h = block.apply({"params": lp}, h, None, train,
                                            **kwargs)
                    else:
                        h = block.apply({"params": lp}, h, None, train,
                                        **kwargs)
            elif in_pipe:
                # partial-manual (auto) mode: non-pipe axes stay under the
                # automatic partitioner — bind constraints to the abstract
                # mesh so 'tensor'/'data' annotations apply inside the ring
                with axes_lib.use_axes(jax.sharding.get_abstract_mesh()):
                    h = block.apply({"params": lp}, h, None, train, **kwargs)
            else:
                h = block.apply({"params": lp}, h, None, train, **kwargs)
            return (h, mb), None

        from tfde_tpu.models.transformer import remat_policy

        policy = remat_policy(self.remat)
        if policy is not None:
            layer = jax.checkpoint(layer, policy=policy)
        return layer

    def _pipe_mode(self, mesh) -> str:
        if self.pipeline_mode is not None:
            return self.pipeline_mode
        tensor = "tensor" in mesh.axis_names and mesh.shape["tensor"] > 1
        return "auto" if tensor else "manual"

    def _make_stage_fn(self, train: bool, base_key, mesh=None):
        from tfde_tpu.parallel.sharding import data_axes as _data_axes

        auto = mesh is not None and self._pipe_mode(mesh) == "auto"
        seq_ring = self._seq_ring(mesh) if mesh is not None else 1
        shard_axes = _data_axes(mesh) if (mesh is not None and base_key
                                          is not None and not auto) else ()
        if shard_axes and seq_ring > 1:
            shard_axes = shard_axes + ("seq",)  # uncorrelated dropout/shard
        layer = self._make_layer_fn(
            train, base_key, in_pipe=True, shard_axes=shard_axes,
            auto_axes=auto, seq_ring=seq_ring,
            # >1 axes only, matching data_axes/vary conventions: promoting
            # accumulators over a SIZE-1 axis would retype the stage-scan
            # carry mid-loop (caught at dryrun data=1 x pipe=2 x seq=2)
            manual_axes=tuple(
                a for a in mesh.axis_names if mesh.shape[a] > 1
            ) if mesh is not None else (),
        )
        lps = self.layers_per_stage

        def stage_fn(stage_params, h, mb_idx):
            # stage_params: [layers_per_stage, ...] pytree; scan applies the
            # same traced block per layer — compiler-friendly, no unrolling.
            # Global layer index = rank * layers_per_stage + local index.
            rank = jax.lax.axis_index("pipe")
            lis = rank * lps + jnp.arange(lps)
            (h, _), _ = jax.lax.scan(layer, (h, mb_idx), (stage_params, lis))
            return h

        return stage_fn

    def _sequential_stack(
        self, p: dict, x: jax.Array, train: bool, base_key
    ) -> jax.Array:
        """No-pipe fallback. With dropout active, processes the batch in the
        SAME microbatch slices with the SAME (mb, layer) keys as the pipe
        path, so the numerics are identical either way."""
        flat = jax.tree_util.tree_map(
            lambda v: v.reshape((self.depth,) + v.shape[2:]), p["stages"]
        )
        layer = self._make_layer_fn(train, base_key, in_pipe=False)
        lis = jnp.arange(self.depth)
        if base_key is None:
            (x, _), _ = jax.lax.scan(layer, (x, jnp.int32(0)), (flat, lis))
            return x
        m = self.microbatches
        batch = x.shape[0]
        if batch % m:
            raise ValueError(
                f"global batch {batch} must divide by microbatches {m}"
            )
        xm = x.reshape((m, batch // m) + x.shape[1:])

        def per_mb(h, mb):
            (h, _), _ = jax.lax.scan(layer, (h, mb), (flat, lis))
            return h

        xm = jax.vmap(per_mb)(xm, jnp.arange(m))
        return xm.reshape((batch,) + x.shape[1:])

    def _microbatched(self, x: jax.Array) -> jax.Array:
        batch = x.shape[0]
        m = self.microbatches
        if batch % m:
            raise ValueError(
                f"global batch {batch} must divide by microbatches {m}"
            )
        return x.reshape((m, batch // m) + x.shape[1:])

    @staticmethod
    def _seq_ring(mesh) -> int:
        return (mesh.shape["seq"]
                if mesh is not None and "seq" in mesh.axis_names else 1)

    def _pipe_mesh(self):
        mesh = axes_lib.current_mesh()
        if (
            mesh is not None
            and "pipe" in mesh.axis_names
            and mesh.shape["pipe"] > 1
        ):
            if self._seq_ring(mesh) > 1 and self._pipe_mode(mesh) != "manual":
                # pp x sp runs only in the fully-manual ring (the ring
                # body inlines into the same flat manual region); the
                # partial-manual 'tensor' mode would nest manual regions,
                # which does not lower (Shardy, jax 0.9)
                raise ValueError(
                    "pp x sp x tp does not compose: a 'seq' axis needs the "
                    "fully-manual pipe (no 'tensor' axis / "
                    "pipeline_mode='manual') — drop either tensor or seq"
                )
            return mesh
        return None

    # -- apply ---------------------------------------------------------------
    def apply(
        self,
        variables: dict,
        tokens: jax.Array,
        train: bool = False,
        rngs: Optional[dict] = None,
    ) -> jax.Array:
        p = variables["params"]
        batch, seq = tokens.shape
        x = self._embed(p, tokens)
        base_key = self._dropout_base(train, rngs)

        mesh = self._pipe_mesh()
        if mesh is not None:
            xm = self._microbatched(x)
            xm = pipeline_apply(
                self._make_stage_fn(train, base_key, mesh), p["stages"],
                xm, mesh, mode=self._pipe_mode(mesh),
            )
            x = xm.reshape((batch, seq, self.hidden_size))
        else:
            x = self._sequential_stack(p, x, train, base_key)
        return self._head({"wte": p["wte"], "ln_final": p["ln_final"]}, x)

    # -- loss (last-stage reduction) ----------------------------------------
    def loss_and_metrics(
        self,
        variables: dict,
        tokens: jax.Array,
        train: bool = False,
        rngs: Optional[dict] = None,
    ):
        """Shifted next-token CE (gpt.next_token_loss convention) computed
        through the pipe's last-stage reduction: only {loss, correct, count}
        sums cross the ring instead of the full [M, micro, seq, hidden]
        output broadcast. Returns (loss, {'next_token_accuracy': acc})."""
        p = variables["params"]
        base_key = self._dropout_base(train, rngs)
        labels = tokens[:, 1:].astype(jnp.int32)

        mesh = self._pipe_mesh()
        if mesh is None or self._seq_ring(mesh) > 1:
            # no pipe mesh: the sequential fallback. pp x sp: loss on the
            # GLOBAL sequence outside the pipe — the last-stage reduction
            # would shift labels across seq-shard boundaries. Either way
            # the full-logit path computes the exact shifted CE.
            if mesh is not None and self.schedule == "1f1b":
                raise NotImplementedError(
                    "schedule='1f1b' does not compose with a 'seq' axis "
                    "(its loss runs inside the pipe, where the shifted "
                    "next-token loss would misalign at shard boundaries) "
                    "— use schedule='gpipe' for pp x sp"
                )
            logits = self.apply(variables, tokens, train=train, rngs=rngs)
            from tfde_tpu.ops.losses import masked_lm_loss

            loss, acc = masked_lm_loss(logits[:, :-1], labels)
            return loss, {"next_token_accuracy": acc}

        x = self._embed(p, tokens)
        xm = self._microbatched(x)
        labels_m = self._microbatched(labels)
        extra = {"wte": p["wte"], "ln_final": p["ln_final"]}
        head = self._head

        def reduce_fn(extra, outputs, labels_loc):
            # outputs [..., micro_local, seq, H]; labels_loc [...,
            # micro_local, seq-1] — the leading dims are [M] on the GPipe
            # full-buffer reduction and absent on the 1F1B per-microbatch
            # loss, so slicing is ellipsis-based. Per-shard SUMS
            # (the pipeline psums them globally).
            logits = head(extra, outputs)[..., :-1, :]
            import optax

            per_tok = optax.losses.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels_loc
            )
            correct = (jnp.argmax(logits, axis=-1) == labels_loc)
            return {
                "loss_sum": jnp.sum(per_tok),
                "correct_sum": jnp.sum(correct.astype(jnp.float32)),
                "count": jnp.asarray(per_tok.size, jnp.float32),
            }

        if self.schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"schedule must be 'gpipe' or '1f1b', got {self.schedule!r}"
            )
        mode = self._pipe_mode(mesh)
        if self.schedule == "1f1b":
            if mode != "manual":
                raise NotImplementedError(
                    "schedule='1f1b' runs in the fully-manual ring only; "
                    "the partial-manual 'tensor' mode (dp x pp x tp) uses "
                    "AD for its backward — use schedule='gpipe' there"
                )
            red = _sums_1f1b(self, mesh, reduce_fn, train)(
                p["stages"], extra, xm, labels_m, base_key
            )
        else:
            red = pipeline_apply(
                self._make_stage_fn(train, base_key, mesh), p["stages"],
                xm, mesh, reduce_fn=reduce_fn, reduce_aux=labels_m,
                extra_params=extra, mode=mode,
            )
        denom = jnp.maximum(red["count"], 1.0)
        loss = red["loss_sum"] / denom
        acc = red["correct_sum"] / denom
        return loss, {"next_token_accuracy": acc}


def _sums_1f1b(model: "PipelinedLM", mesh, loss_fn, train: bool):
    """custom_vjp around the pipelined loss sums so jax.grad composes with
    the hand-scheduled 1F1B backward (parallel/pipeline.pipeline_train_1f1b):

    - primal (no differentiation, e.g. eval loss): the cheap forward-only
      GPipe pass — identical sums, no gradient work.
    - fwd rule (under jax.grad): ONE 1F1B pass computes the sums AND the
      gradients; the grads ride the residuals.
    - bwd rule: scales the stored grads by the loss_sum cotangent. The
      other sums (count, correct_sum) are shape-constants / argmax metrics
      with zero derivative a.e. — their cotangents are ignored.

    The dropout key is an explicit argument (not a closure): custom_vjp
    functions must not close over tracers, and the key is traced inside a
    jitted train step.
    """
    import numpy as np

    def stage_of(key):
        return model._make_stage_fn(train, key, mesh)

    @jax.custom_vjp
    def sums(stages, extra, xm, labels_m, key):
        return pipeline_apply(
            stage_of(key), stages, xm, mesh, reduce_fn=loss_fn,
            reduce_aux=labels_m, extra_params=extra, mode="manual",
        )

    def fwd(stages, extra, xm, labels_m, key):
        from tfde_tpu.parallel.pipeline import pipeline_train_1f1b

        s, grads = pipeline_train_1f1b(
            stage_of(key), stages, xm, mesh, loss_fn=loss_fn,
            loss_aux=labels_m, extra_params=extra,
        )
        return s, (grads, labels_m, key)

    def bwd(res, ct):
        grads, labels_m, key = res
        scale = ct["loss_sum"]
        sc = lambda t: jax.tree_util.tree_map(
            lambda g: (g * scale).astype(g.dtype), t
        )
        key_ct = (None if key is None
                  else np.zeros(np.shape(key), jax.dtypes.float0))
        return (sc(grads["stages"]), sc(grads["extra"]), sc(grads["x"]),
                np.zeros(labels_m.shape, jax.dtypes.float0), key_ct)

    sums.defvjp(fwd, bwd)
    return sums


def pipelined_next_token_loss(state, params, batch, rng):
    """(loss, metrics) for make_custom_train_step — gpt.next_token_loss's
    pipelined twin, routed through the last-stage reduction so the training
    step never pays the full-logit psum broadcast."""
    (tokens,) = batch if isinstance(batch, tuple) else (batch,)
    model = state.apply_fn.__self__  # PipelinedLM instance (bound method)
    loss, metrics = model.loss_and_metrics(
        {"params": params}, tokens, train=True, rngs={"dropout": rng}
    )
    return loss, metrics


def pipelined_tiny_test(**kw) -> PipelinedLM:
    """CI config for the 8-device CPU mesh (SURVEY.md §4)."""
    defaults = dict(
        vocab_size=97, hidden_size=32, num_heads=4, mlp_dim=64,
        max_position=64, num_stages=2, layers_per_stage=2, microbatches=4,
        dtype=jnp.float32,
    )
    defaults.update(kw)
    return PipelinedLM(**defaults)
