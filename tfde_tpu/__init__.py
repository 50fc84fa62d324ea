"""tfde_tpu — a TPU-native distributed-training framework.

A from-scratch JAX/XLA/pjit framework providing the capabilities of the
reference `lowc1012/tensorflow-distributed-example` (three TF distributed
training recipes on MNIST: multi-worker collective all-reduce, parameter-server
training, and mirrored single-host data parallelism), re-designed TPU-first:

- SPMD over a `jax.sharding.Mesh` (ICI within a slice, DCN across slices)
  instead of NCCL/gRPC collectives.
- `jit`/`pjit`-compiled train steps; gradient aggregation via XLA collectives
  (`lax.psum`) inserted by the partitioner, not hand-written rings.
- Flax modules for the model zoo (reference CNNs plus ResNet-50, ViT-B/16 and
  BERT-base scale configs).
- Per-host sharded input pipelines with on-device double-buffered prefetch
  (the tf.data analog).
- Estimator-style lifecycle: `train_and_evaluate` with eval throttling,
  periodic checkpointing (Orbax, auto-resume), TensorBoard summaries, and a
  serving export artifact (landing per SURVEY.md §7's layer order).

See SURVEY.md at the repo root for the blueprint and reference file:line
citations throughout the docstrings.
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# Sharding-invariant RNG is a framework invariant: params initialized under
# an FSDP/TP sharding must equal the unsharded init, or "numerics identical
# across strategies" dies at step 0.
_jax.config.update("jax_threefry_partitionable", True)


def _place_compile_cache() -> None:
    """Persistent XLA compile cache for every entry point (package import
    is the one place they all pass). JAX_COMPILATION_CACHE_DIR, when set,
    is read by jax itself and the package sets nothing; otherwise the cache
    lives at <checkout>/.jax_cache. The directory is part of the cache key,
    so it is derived from this file's location alone — the same checkout
    always finds its own entries."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    _jax.config.update("jax_compilation_cache_dir",
                       _os.path.join(root, ".jax_cache"))


_place_compile_cache()

# Surface TFDE_* typos (unregistered names in the environment) at import,
# before any knob read silently runs a default the operator didn't ask for.
from tfde_tpu import knobs as _knobs

_knobs.warn_unknown_env()

from tfde_tpu.runtime.mesh import MeshSpec, make_mesh  # noqa: F401
from tfde_tpu.runtime.cluster import ClusterInfo, bootstrap  # noqa: F401
