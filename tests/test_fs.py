"""Remote-filesystem working dir: the reference documents --working-dir as a
GCS location (mnist_keras_distributed.py:41-44) and the Estimator machinery
writes events + exports there. These tests drive the same surface against
fsspec's in-memory filesystem (`memory://`) — hermetic stand-in for gs://."""

import json
import os
import struct

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tfde_tpu.export.serving import FinalExporter, export_serving, load_serving
from tfde_tpu.models.cnn import PlainCNN
from tfde_tpu.observability.tensorboard import SummaryWriter, _masked_crc
from tfde_tpu.training.lifecycle import Estimator, RunConfig
from tfde_tpu.utils import fs


@pytest.fixture(autouse=True)
def _clean_memory_fs():
    import fsspec

    mem = fsspec.filesystem("memory")
    mem.store.clear()
    yield
    mem.store.clear()


def test_fs_helpers_on_memory():
    base = "memory://fs-helpers"
    assert fs.is_remote(base) and not fs.is_remote("/tmp/x")
    fs.makedirs(fs.join(base, "sub"))
    fs.write_bytes(fs.join(base, "sub", "a.bin"), b"abc")
    assert fs.exists(fs.join(base, "sub", "a.bin"))
    assert fs.isdir(fs.join(base, "sub"))
    assert fs.listdir(fs.join(base, "sub")) == ["a.bin"]
    with fs.fs_open(fs.join(base, "sub", "a.bin"), "rb") as f:
        assert f.read() == b"abc"


def _read_records(data: bytes):
    """TFRecord stream -> list of event payloads, verifying both crcs."""
    records, off = [], 0
    while off < len(data):
        (length,) = struct.unpack("<Q", data[off:off + 8])
        (len_crc,) = struct.unpack("<I", data[off + 8:off + 12])
        assert len_crc == _masked_crc(data[off:off + 8])
        payload = data[off + 12:off + 12 + length]
        (data_crc,) = struct.unpack(
            "<I", data[off + 12 + length:off + 16 + length]
        )
        assert data_crc == _masked_crc(payload)
        records.append(payload)
        off += 16 + length
    return records


def test_summary_writer_remote_logdir():
    w = SummaryWriter("memory://logs")
    w.scalars(1, {"loss": 0.5})
    w.scalars(2, {"loss": 0.25})
    w.flush()
    assert w.path.startswith("memory://logs/events.out.tfevents.")
    with fs.fs_open(w.path, "rb") as f:
        records = _read_records(f.read())
    # file_version header + 2 scalar events, all crc-valid
    assert len(records) == 3
    w.close()


def test_export_roundtrip_remote():
    import jax

    model = PlainCNN()
    variables = model.init(jax.random.key(0), jnp.zeros((1, 784)), train=False)

    def apply_fn(v, x):
        return model.apply(v, x, train=False)

    out_dir = export_serving(
        apply_fn, variables, (None, 784), "memory://exports"
    )
    assert out_dir.startswith("memory://exports/")
    loaded = load_serving("memory://exports")  # resolves newest timestamp
    x = np.random.default_rng(0).random((3, 784), np.float32)
    probs = loaded.predict(x)
    assert probs.shape == (3, 10)
    np.testing.assert_allclose(probs.sum(axis=-1), 1.0, rtol=1e-5)
    assert loaded.signature["input"]["shape"] == [None, 784]


def test_estimator_remote_model_dir():
    """Full Estimator train + summary + export against a mocked remote
    working dir (checkpointing disabled: Orbax speaks gs:// but not
    memory://; see RunConfig.save_checkpoints_steps)."""
    import jax

    model_dir = "memory://estimator-run"
    est = Estimator(
        PlainCNN(),
        optax.sgd(0.1),
        config=RunConfig(
            model_dir=model_dir,
            save_summary_steps=2,
            log_step_count_steps=2,
            save_checkpoints_steps=None,
        ),
    )
    rng = np.random.default_rng(0)
    images = rng.random((32, 784), np.float32)
    labels = rng.integers(0, 10, (32, 1)).astype(np.int32)

    def input_fn():
        while True:
            yield images, labels

    est.train(input_fn, max_steps=4)
    # events landed remotely
    names = fs.listdir(model_dir)
    events = [n for n in names if n.startswith("events.out.tfevents.")]
    assert events, f"no event file in {names}"

    # export lands under <model_dir>/export/<name>/<timestamp>/
    out = est.export_saved_model(FinalExporter("exporter", (None, 784)))
    assert out.startswith("memory://estimator-run/export/exporter/")
    loaded = load_serving("memory://estimator-run/export/exporter")
    probs = loaded.predict(images[:5])
    assert probs.shape == (5, 10)
    with fs.fs_open(fs.join(out, "signature.json"), "r") as f:
        sig = json.load(f)
    assert sig["framework"] == "tfde_tpu"
    est.close()


def test_a_remote_model_dir_is_never_a_directory_called_memory(
        tmp_path, monkeypatch):
    """`Estimator(model_dir="memory://...")` arms the flight recorder on
    a URL. Whoever dumps it later writes through utils/fs, where the
    events land, and not into `memory:/<run>/debug` under the working
    directory, as it did while the recorder took the URL for a path."""
    from tfde_tpu.observability import flightrec

    monkeypatch.chdir(tmp_path)
    model_dir = "memory://flight-run"
    est = Estimator(
        PlainCNN(), optax.sgd(0.1),
        config=RunConfig(model_dir=model_dir, save_checkpoints_steps=None))
    rng = np.random.default_rng(0)
    batch = (rng.random((8, 784), np.float32),
             rng.integers(0, 10, (8, 1)).astype(np.int32))
    est.train(lambda: iter([batch] * 2), max_steps=1)
    est.close()
    path = flightrec.dump("drill")
    try:
        assert path.startswith("memory://flight-run/debug/flight_")
        assert os.listdir(tmp_path) == []
        kinds = [ev["kind"] for ev in flightrec.load(path)]
        assert "armed" in kinds and kinds[-1] == "dump"
    finally:
        # the process's recorder is left where no later dump writes
        flightrec.default_recorder()._dump_dir = None

