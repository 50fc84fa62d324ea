"""Input-pipeline determinism and sharding-arithmetic tests (SURVEY.md §4)."""

import numpy as np
import pytest

from tfde_tpu.data.pipeline import Dataset
from tfde_tpu.data import datasets


def _arrays(n=20):
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    y = np.arange(n, dtype=np.int64)
    return x, y


def test_from_tensor_slices_roundtrip():
    x, y = _arrays()
    els = list(Dataset.from_tensor_slices((x, y)))
    assert len(els) == 20
    np.testing.assert_array_equal(els[3][0], x[3])
    assert els[3][1] == 3


def test_batch_vectorized_no_shuffle_keeps_order():
    x, y = _arrays()
    b = list(Dataset.from_tensor_slices((x, y)).batch(8))
    assert len(b) == 3  # 8+8+4, no drop
    np.testing.assert_array_equal(b[0][1], y[:8])
    assert b[2][0].shape[0] == 4


def test_batch_drop_remainder():
    x, y = _arrays()
    b = list(Dataset.from_tensor_slices((x, y)).batch(8, drop_remainder=True))
    assert len(b) == 2


def test_full_shuffle_is_permutation_and_deterministic():
    x, y = _arrays()
    ds = lambda: Dataset.from_tensor_slices((x, y)).shuffle(100, seed=7).batch(20)
    (bx1, by1), = list(ds())
    (bx2, by2), = list(ds())
    np.testing.assert_array_equal(by1, by2)  # deterministic under a seed
    assert sorted(by1.tolist()) == y.tolist()  # a permutation
    assert not np.array_equal(by1, y)  # actually shuffled


def test_windowed_shuffle_semantics():
    x, y = _arrays(200)
    got = [int(e[1]) for e in Dataset.from_tensor_slices((x, y)).shuffle(10, seed=0)]
    assert sorted(got) == y.tolist()
    assert got != y.tolist()
    # windowed: displacement is buffer-bounded in distribution (geometric
    # tail), so check a high percentile rather than the max
    disp = sorted(abs(p - v) for p, v in enumerate(got))
    assert disp[int(len(disp) * 0.9)] <= 40


def test_repeat_infinite_and_counted():
    x, y = _arrays(4)
    it = iter(Dataset.from_tensor_slices((x, y)).repeat().batch(4))
    for _ in range(5):
        next(it)  # infinite stream never raises
    b = list(Dataset.from_tensor_slices((x, y)).repeat(3).batch(4))
    assert len(b) == 3


def test_shuffle_repeat_reshuffles_each_epoch():
    x, y = _arrays(16)
    it = iter(Dataset.from_tensor_slices((x, y)).shuffle(16, seed=3).repeat().batch(16))
    e1, e2 = next(it)[1], next(it)[1]
    assert sorted(e1.tolist()) == sorted(e2.tolist())
    assert not np.array_equal(e1, e2)


def test_map_vectorized_fast_path():
    x, y = _arrays()
    ds = Dataset.from_tensor_slices((x, y)).map(lambda a, b: (a / 2.0, b)).batch(20)
    (bx, by), = list(ds)
    np.testing.assert_allclose(bx, x / 2.0)


def test_shard_partitions_examples():
    x, y = _arrays(10)
    got0 = [int(e[1]) for e in Dataset.from_tensor_slices((x, y)).shard(2, 0)]
    got1 = [int(e[1]) for e in Dataset.from_tensor_slices((x, y)).shard(2, 1)]
    assert got0 == [0, 2, 4, 6, 8]
    assert got1 == [1, 3, 5, 7, 9]


def test_prefetch_transparent():
    x, y = _arrays()
    a = [e[1] for e in Dataset.from_tensor_slices((x, y)).prefetch(4)]
    np.testing.assert_array_equal(np.array(a), y)


def test_cache_materializes():
    calls = []
    x, y = _arrays(5)

    def fn(a, b):
        calls.append(1)
        return a, b

    ds = Dataset.from_tensor_slices((x, y)).map(fn).cache()
    # no fast path for this test: remove slices to force per-element map
    ds._slices = None
    list(ds)
    first = len(calls)
    list(ds)
    assert len(calls) == first  # second pass served from cache


def test_synthetic_mnist_shapes_and_learnability():
    (tx, ty), (ex, ey) = datasets.mnist(flatten=True, n_train=2000, n_test=200)
    assert tx.shape == (2000, 784) and tx.dtype == np.float32
    assert ty.shape == (2000, 1) and ey.shape == (200, 1)
    assert 0.0 <= tx.min() and tx.max() <= 1.0
    # classes must be separable: nearest-class-mean on raw pixels beats chance
    means = np.stack([tx[ty[:, 0] == c].mean(0) for c in range(10)])
    pred = np.argmin(((ex[:, None] - means[None]) ** 2).sum(-1), axis=1)
    assert (pred == ey[:, 0]).mean() > 0.5


def test_repeat_batch_carries_across_epochs():
    """repeat().batch() must never emit per-epoch short batches (tf.data
    semantics): 10 examples repeated, batch 8 -> all batches full-size."""
    x, y = _arrays(10)
    it = iter(Dataset.from_tensor_slices((x, y)).repeat().batch(8))
    seen = [next(it) for _ in range(10)]
    assert all(b[0].shape[0] == 8 for b in seen)
    # every example appears 8*10/10 = 8 times across 80 drawn rows
    counts = np.bincount(np.concatenate([b[1] for b in seen]), minlength=10)
    np.testing.assert_array_equal(counts, np.full(10, 8))


def test_repeat_counted_batch_total():
    x, y = _arrays(10)
    b = list(Dataset.from_tensor_slices((x, y)).repeat(3).batch(8))
    assert [e[0].shape[0] for e in b] == [8, 8, 8, 6]


def test_map_fast_path_rejected_for_non_elementwise_fn():
    x, y = _arrays(8)
    ds = Dataset.from_tensor_slices((x, y)).map(lambda a, b: (a - a.mean(), b))
    (bx, _), = list(ds.batch(8))
    want = np.stack([row - row.mean() for row in x])  # per-element semantics
    np.testing.assert_allclose(bx, want, rtol=1e-6)


def test_unknown_size_repeat_keeps_unknown():
    def gen():
        yield (np.zeros(3),)

    ds = Dataset(gen, None).repeat(3)
    assert ds.size is None


def test_map_after_repeat_keeps_infinite_stream():
    x, y = _arrays(10)
    it = iter(Dataset.from_tensor_slices((x, y)).repeat().map(lambda a, b: (a, b)).batch(4))
    for _ in range(10):  # > one epoch; must not stop
        next(it)


def test_shuffle_then_map_keeps_shuffling():
    x, y = _arrays(20)
    (bx, by), = list(
        Dataset.from_tensor_slices((x, y)).shuffle(20, seed=0)
        .map(lambda a, b: (a, b)).batch(20)
    )
    assert not np.array_equal(by, y)
    assert sorted(by.tolist()) == y.tolist()


def test_repeat_zero_is_empty_both_paths():
    x, y = _arrays(8)
    assert list(Dataset.from_tensor_slices((x, y)).repeat(0).batch(4)) == []
    ds = Dataset.from_tensor_slices((x, y)).repeat(0)
    ds._fast = None  # force iterator path
    assert list(ds.batch(4)) == []


def test_iterator_path_seeded_shuffle_reshuffles_each_epoch():
    x, y = _arrays(20)
    ds = Dataset.from_tensor_slices((x, y)).shuffle(5, seed=0).repeat(2)
    ds._fast = None  # force the windowed iterator path
    got = [int(e[1]) for e in ds]
    assert got[:20] != got[20:]  # epochs differ
    assert sorted(got[:20]) == y.tolist() and sorted(got[20:]) == y.tolist()


def test_prefetch_propagates_upstream_errors():
    def bad_gen(epoch=0):
        yield (np.zeros(2),)
        raise RuntimeError("io error")

    ds = Dataset(bad_gen, None).prefetch(2)
    with pytest.raises(RuntimeError, match="io error"):
        list(ds)


def test_malformed_cluster_env_raises_descriptive():
    import os
    from tfde_tpu.runtime import cluster

    os.environ["TF_CONFIG"] = "{bad"
    try:
        with pytest.raises(ValueError, match="TF_CONFIG"):
            cluster.resolve_cluster()
    finally:
        del os.environ["TF_CONFIG"]
    os.environ["CLUSTER_SPEC"] = "{bad"
    try:
        with pytest.raises(ValueError, match="CLUSTER_SPEC"):
            cluster.resolve_cluster()
    finally:
        del os.environ["CLUSTER_SPEC"]


def test_coordinator_endpoint_derives_offset_port():
    """The jax.distributed coordinator must NOT reuse the cluster spec's
    application port (a leftover TF gRPC server bound there would break
    init): it derives spec+1011, wraps near the range top, respects
    TFDE_COORD_PORT, and defaults when the spec has no port."""
    import os

    from tfde_tpu.runtime.cluster import coordinator_endpoint

    assert coordinator_endpoint("host-a:2222") == "host-a:3233"
    assert coordinator_endpoint("host-a") == "host-a:8476"
    assert coordinator_endpoint("[::1]:2222") == "[::1]:3233"
    assert coordinator_endpoint("[::1]") == "[::1]:8476"
    assert coordinator_endpoint("h:65000") == "h:63989"  # wrap stays valid
    os.environ["TFDE_COORD_PORT"] = "9999"
    try:
        assert coordinator_endpoint("host-a:2222") == "host-a:9999"
    finally:
        del os.environ["TFDE_COORD_PORT"]


def test_download_verifies_checksum(tmp_path, monkeypatch):
    """The opt-in dataset download (reference parity: mnist_keras:207-208
    fetches over the network) must refuse a payload whose sha256 does not
    match, and install a matching one atomically. Exercised hermetically
    via a file:// URL."""
    import hashlib

    from tfde_tpu.data import datasets as ds

    payload = b"not really mnist but bytes all the same"
    src = tmp_path / "src.npz"
    src.write_bytes(payload)
    url = src.as_uri()

    monkeypatch.setitem(
        ds._DOWNLOADS, "mnist",
        {"url": url, "sha256": "0" * 64, "filename": "mnist.npz"},
    )
    with pytest.raises(ValueError, match="checksum mismatch"):
        ds.download("mnist", str(tmp_path / "data"))
    assert not (tmp_path / "data" / "mnist.npz").exists()
    assert not list((tmp_path / "data").glob("*.download"))

    monkeypatch.setitem(
        ds._DOWNLOADS, "mnist",
        {"url": url, "sha256": hashlib.sha256(payload).hexdigest(),
         "filename": "mnist.npz"},
    )
    out = ds.download("mnist", str(tmp_path / "data"))
    assert open(out, "rb").read() == payload
    # idempotent: second call resolves without refetching
    assert ds.download("mnist", str(tmp_path / "data")) == out


def test_download_unknown_dataset():
    from tfde_tpu.data import datasets as ds

    with pytest.raises(ValueError, match="unknown dataset"):
        ds.download("imagenet-22k")


def test_cifar_tarball_conversion(tmp_path):
    """The cifar-10-python tarball converts to the npz layout the loader
    resolves."""
    import pickle
    import tarfile

    from tfde_tpu.data import datasets as ds

    rng = np.random.default_rng(0)

    def batch(n):
        return {
            b"data": rng.integers(0, 256, (n, 3072), dtype=np.uint8),
            b"labels": rng.integers(0, 10, n).tolist(),
        }

    tar = tmp_path / "cifar-10-python.tar.gz"
    with tarfile.open(tar, "w:gz") as tf:
        import io as _io

        for name, n in [("data_batch_1", 20), ("data_batch_2", 20),
                        ("test_batch", 10)]:
            raw = pickle.dumps(batch(n))
            info = tarfile.TarInfo(f"cifar-10-batches-py/{name}")
            info.size = len(raw)
            tf.addfile(info, _io.BytesIO(raw))
    out = tmp_path / "cifar10.npz"
    ds._convert_cifar_tarball(tar, out)
    with np.load(out) as d:
        assert d["x_train"].shape == (40, 32, 32, 3)
        assert d["x_test"].shape == (10, 32, 32, 3)
        assert d["y_train"].shape == (40,)


def test_device_prefetch_background_matches_inline():
    """background=True (worker-thread device_put, the link-overlap mode)
    must yield the same stream in the same order, and surface source
    errors in the consumer."""
    import jax

    from tfde_tpu.data.device import device_prefetch
    from tfde_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"data": 8})
    batches = [
        (np.full((16, 4), i, np.float32), np.full((16, 1), i, np.int32))
        for i in range(6)
    ]
    inline = [jax.device_get(b[0])
              for b in device_prefetch(iter(batches), mesh)]
    bg = [jax.device_get(b[0])
          for b in device_prefetch(iter(batches), mesh, background=True)]
    assert len(inline) == len(bg) == 6
    for a, b in zip(inline, bg):
        np.testing.assert_array_equal(a, b)

    def broken():
        yield batches[0]
        raise RuntimeError("source exploded")

    feed = device_prefetch(broken(), mesh, background=True)
    next(feed)
    with pytest.raises(RuntimeError, match="source exploded"):
        next(feed)


def test_device_resident_feed_semantics():
    """On-device input pipeline: per-epoch permutation exactness,
    determinism per seed, reshuffle across epochs, sharded output."""
    import jax

    from tfde_tpu.data.device import device_resident_feed
    from tfde_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"data": 8})
    n, batch = 48, 16
    x = np.arange(n, dtype=np.int32)
    y = (x * 2).astype(np.float32)
    feed = device_resident_feed((x, y), mesh, batch, seed=3)
    per_epoch = n // batch
    ids = []
    for step in range(2 * per_epoch):
        bx, by = feed(step)
        assert bx.sharding.spec[0] is not None  # batch dim sharded
        np.testing.assert_array_equal(np.asarray(by),
                                      np.asarray(bx) * 2.0)  # rows paired
        ids.extend(np.asarray(bx).tolist())
    assert sorted(ids[:n]) == list(range(n))          # epoch 1 exact
    assert sorted(ids[n:]) == list(range(n))          # epoch 2 exact
    assert ids[:n] != ids[n:]                         # reshuffled
    assert ids[:n] != list(range(n))                  # actually shuffled
    # deterministic per seed
    again = device_resident_feed((x, y), mesh, batch, seed=3)
    np.testing.assert_array_equal(np.asarray(again(1)[0]),
                                  np.asarray(feed(1)[0]))
    # seed moves the order
    other = device_resident_feed((x, y), mesh, batch, seed=4)
    assert not np.array_equal(np.asarray(other(0)[0]),
                              np.asarray(feed(0)[0]))


def test_device_resident_feed_trains():
    """The feed drops into a sharded train step like any batch; loss
    falls with zero per-step host transfer."""
    import jax
    import jax.numpy as jnp
    import optax

    from tfde_tpu.data.device import device_resident_feed
    from tfde_tpu.models.cnn import PlainCNN
    from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy
    from tfde_tpu.training.step import init_state, make_train_step

    rng = np.random.default_rng(0)
    imgs = rng.uniform(0, 0.3, (128, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 4, 128).astype(np.int64)
    for k in range(128):
        q = labels[k]
        imgs[k, (q // 2) * 14 : (q // 2) * 14 + 14,
             (q % 2) * 14 : (q % 2) * 14 + 14] += 0.7
    strat = MultiWorkerMirroredStrategy()
    state, _ = init_state(PlainCNN(num_classes=4),
                          optax.sgd(0.1, momentum=0.9), strat,
                          jnp.zeros((16, 28, 28, 1)))
    step_fn = make_train_step(strat, state)
    feed = device_resident_feed(
        (imgs, labels.reshape(-1, 1)), strat.mesh, 16, seed=0
    )
    key = jax.random.key(0)
    losses = []
    for step in range(40):
        state, m = step_fn(state, feed(step), key)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.6, losses[::10]


def test_device_resident_feed_validation():
    from tfde_tpu.data.device import device_resident_feed
    from tfde_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"data": 8})
    with pytest.raises(ValueError, match="leading dimension"):
        device_resident_feed(
            (np.zeros((8, 2)), np.zeros((6,))), mesh, 4
        )
    with pytest.raises(ValueError, match="drop_remainder"):
        device_resident_feed((np.zeros((10, 2)),), mesh, 4,
                             drop_remainder=False)
    with pytest.raises(ValueError, match="exceeds the dataset"):
        device_resident_feed((np.zeros((8, 2)),), mesh, 16)
