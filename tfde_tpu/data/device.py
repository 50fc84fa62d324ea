"""Host->device feed: sharded device placement with double-buffered prefetch.

The analog of tf.data's device prefetch plus the distribution-strategy input
splitting (SURVEY.md §2b row 3). Batches come off the host pipeline as numpy;
we place each as a *global* jax.Array laid out by the mesh's batch sharding
and keep `buffer_size` batches in flight so the host copy overlaps the
device step — the overlap that the ≥90 % scaling-efficiency target depends on
(SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import collections
import time
from typing import Iterable, Iterator, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tfde_tpu.data.pipeline import AutoShardPolicy


def local_slice_for_process(global_batch: int) -> Tuple[int, slice]:
    """(per-host batch, this host's slice of a global batch).

    Global-batch accounting per distributed_with_keras.py:13-15: the global
    batch divides evenly across processes; under OFF each host materializes
    the full global batch and takes its slice (dwk:54-57), under DATA each
    host produces only its per-host portion.
    """
    n = jax.process_count()
    if global_batch % n:
        raise ValueError(f"global batch {global_batch} not divisible by {n} processes")
    per = global_batch // n
    i = jax.process_index()
    return per, slice(i * per, (i + 1) * per)


def _to_global(batch, sharding: NamedSharding, policy: AutoShardPolicy):
    def place(x):
        x = np.asarray(x)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        if policy is AutoShardPolicy.OFF:
            _, sl = local_slice_for_process(x.shape[0])
            x = x[sl]
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(place, batch)


def device_prefetch(
    batches: Iterable,
    mesh: Mesh,
    spec: Optional[P] = None,
    buffer_size: int = 2,
    policy: AutoShardPolicy = AutoShardPolicy.DATA,
    background: bool = False,
    wait_metric: Optional[str] = None,
) -> Iterator:
    """Yield global device arrays, keeping `buffer_size` transfers in flight.

    `jax.device_put` is async: enqueueing the next batch's transfer before the
    consumer blocks on the current step gives copy/compute overlap (the
    `prefetch(100)` capability of mnist_keras:145 plus `experimental_prefetch_
    to_device`, without the 100-deep host queue — device HBM holds the window).

    `background=True` moves the host-batch pull AND the device_put into a
    worker thread (a `buffer_size`-deep queue hands finished device arrays
    to the consumer). Use when either blocks the calling thread — a host
    pipeline with real per-batch work, or a link whose device_put is
    effectively synchronous (a high-latency host link): the transfer then
    overlaps the device step even though the consumer never returns to
    Python between steps. Same stream, same order; worker exceptions
    re-raise in the consumer.

    `wait_metric` names an observability histogram (e.g. "train/data_wait")
    that records the seconds the CONSUMER blocks per `next()` — the host
    pull + device_put inline, the queue wait in background mode. This is
    the input-boundness signal goodput accounting classifies as data_wait;
    None (the default) records nothing.
    """
    if wait_metric is None:
        def _rec(dt: float) -> None:
            pass
    else:
        from tfde_tpu.observability import spans

        def _rec(dt: float, _name=wait_metric) -> None:
            spans.record(_name, dt)
    if spec is None:
        from tfde_tpu.parallel.sharding import batch_spec

        spec = batch_spec(mesh)
    sharding = NamedSharding(mesh, spec)

    if background:
        import queue as _queue
        import threading

        q: "_queue.Queue" = _queue.Queue(maxsize=max(1, buffer_size))
        _END = object()

        class _Raise:  # unambiguous error envelope (a batch is never one)
            def __init__(self, e):
                self.e = e

        stop = threading.Event()

        def put(item) -> bool:
            # bounded put that gives up when the consumer is gone — a
            # consumer breaking out of its loop early must not leave the
            # worker blocked forever pinning device arrays
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def worker():
            try:
                for b in batches:
                    if not put(_to_global(b, sharding, policy)):
                        return
                put(_END)
            except BaseException as e:
                put(_Raise(e))

        threading.Thread(target=worker, daemon=True,
                         name="tfde-device-prefetch").start()

        empty_exc = _queue.Empty  # bind the class in the closure: at
        # interpreter shutdown a GC'd generator's finally can run after
        # module teardown has nulled `queue.Empty`

        def gen():
            try:
                while True:
                    t0 = time.perf_counter()
                    item = q.get()
                    _rec(time.perf_counter() - t0)
                    if item is _END:
                        return
                    if isinstance(item, _Raise):
                        raise item.e
                    yield item
            finally:
                # generator close/GC: release the worker and drop any
                # buffered device arrays
                stop.set()
                try:
                    while True:
                        q.get_nowait()
                except empty_exc:
                    pass

        return gen()

    def gen_inline():
        # time between yields IS the consumer's blocking wait in next():
        # the priming fill is charged to the first draw, each refill to
        # the draw it delays
        buf: collections.deque = collections.deque()
        it = iter(batches)
        t0 = time.perf_counter()
        try:
            while len(buf) < max(1, buffer_size):
                buf.append(_to_global(next(it), sharding, policy))
        except StopIteration:
            pass
        while buf:
            out = buf.popleft()
            try:
                buf.append(_to_global(next(it), sharding, policy))
            except StopIteration:
                pass
            _rec(time.perf_counter() - t0)
            yield out
            t0 = time.perf_counter()

    return gen_inline()


def device_resident_feed(
    arrays,
    mesh: Mesh,
    global_batch: int,
    seed: int = 0,
    spec: Optional[P] = None,
    drop_remainder: bool = True,
):
    """Fully ON-DEVICE input pipeline for datasets that fit in HBM: stage
    the arrays once, then every batch is a device-side gather — ZERO
    per-step host->device traffic, the terminal answer to an input-bound
    link.

    Semantics match `Dataset.from_tensor_slices(arrays).shuffle(n, seed)
    .repeat().batch(global_batch, drop_remainder=True)`: a fresh
    Fisher-Yates permutation per epoch (derived on device from `seed` and
    the epoch index), batches crossing epoch boundaries never (each epoch
    truncates to a whole number of batches when drop_remainder — the
    in-memory analog of the streaming loader's per-epoch windows).

    Returns `feed(step) -> batch` — a jitted function of the step index;
    call it with the training step counter. The gather output is sharded
    by the mesh's batch spec, so it drops into the train step exactly
    like a `device_prefetch` batch.
    """
    import jax.numpy as jnp

    if spec is None:
        from tfde_tpu.parallel.sharding import batch_spec

        spec = batch_spec(mesh)
    sharding = NamedSharding(mesh, spec)
    arrays = tuple(np.ascontiguousarray(a) for a in arrays)
    n = arrays[0].shape[0]
    if any(a.shape[0] != n for a in arrays):
        raise ValueError("all arrays must share the leading dimension")
    if not drop_remainder and n % global_batch:
        raise ValueError(
            "device_resident_feed streams whole batches only; use "
            "drop_remainder=True (or a divisible dataset) — a trailing "
            "partial batch would change the compiled shape"
        )
    per_epoch = n // global_batch
    if per_epoch < 1:
        raise ValueError(
            f"global_batch {global_batch} exceeds the dataset size {n}"
        )
    # replicated residency: the gather needs arbitrary rows on every
    # shard's output row, so the source stays whole on each device (the
    # fits-in-HBM contract this feed is for; shard the OUTPUT, not the
    # source)
    dev = tuple(
        jax.device_put(a, NamedSharding(mesh, P())) for a in arrays
    )

    @jax.jit
    def feed(step):
        epoch = step // per_epoch
        within = step % per_epoch
        perm = jax.random.permutation(
            jax.random.fold_in(jax.random.key(seed), epoch), n
        )
        idx = jax.lax.dynamic_slice_in_dim(
            perm, within * global_batch, global_batch
        )
        out = tuple(
            jax.lax.with_sharding_constraint(jnp.take(a, idx, axis=0),
                                             sharding)
            for a in dev
        )
        return out

    return feed
