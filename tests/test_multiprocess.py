"""Multi-process distributed tests (SURVEY.md §4: "spawn N local processes
with jax.distributed.initialize — the TF_CONFIG analog"): real OS processes
bootstrap from the reference's CLUSTER_SPEC env contract and form one SPMD
group over loopback, or serve as replicas behind a Router, and the drills
kill some of them.

Children are booted once where drills can share them: `booted_pair` runs
sync-DP, FSDP and the lifecycle's first life in one pair, `replica_pair`
serves the overload drill and then the kill drill. No drill waits longer
for a child than `_WAIT_S`, and every drill reaps its children however it
ends."""

import contextlib
import json
import os
import socket
import subprocess
import sys
import textwrap
import time

import pytest

#: The longest a drill waits for its children, well inside what
#: conftest.py's TEST_LIMIT_S gives the whole test (tests/test_suite_limit.py
#: reads every wait of this file). The slowest child is done in 15 s on an
#: idle box and in 30 s beside a second suite.
_WAIT_S = 120


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(**extra) -> dict:
    """The parent's environment with the checkout importable and no cluster
    spec but the one given."""
    env = dict(os.environ)
    env.pop("TF_CONFIG", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(__file__))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    env.update(extra)
    return env


def _spawn_group(script_path, argv=(), n=2, stderr_files=None):
    """`n` processes of one cluster over loopback, told who they are by the
    reference's CLUSTER_SPEC contract. `stderr_files`: a path a rank, where
    a pipe would lose a hung child's last words."""
    cluster = {"worker": [f"127.0.0.1:{_free_port()}" for _ in range(n)]}
    procs = []
    for i in range(n):
        with contextlib.ExitStack() as opened:
            err = (opened.enter_context(open(stderr_files[i], "w"))
                   if stderr_files else subprocess.PIPE)
            procs.append(subprocess.Popen(
                [sys.executable, str(script_path), *argv],
                env=_child_env(CLUSTER_SPEC=json.dumps(cluster),
                               TASK_INDEX=str(i), JOB_NAME="worker"),
                stdout=subprocess.PIPE, stderr=err, text=True))
    return procs


def _reap(procs) -> None:
    """No child outlives its drill, however the drill ended."""
    for p in procs:
        if p.poll() is None:
            p.kill()
        p.communicate()


_TRAIN_CHILD = textwrap.dedent(
    """
    import hashlib, json, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from tfde_tpu.utils.devices import request_cpu_devices
    request_cpu_devices(1)
    import numpy as np, optax
    from tfde_tpu import bootstrap
    from tfde_tpu.data import Dataset, device_prefetch
    from tfde_tpu.data.device import local_slice_for_process
    from tfde_tpu.data.pipeline import AutoShardPolicy
    from tfde_tpu.export.serving import FinalExporter
    from tfde_tpu.models.cnn import BatchNormCNN, PlainCNN
    from tfde_tpu.parallel.strategies import (
        FSDPStrategy, MultiWorkerMirroredStrategy)
    from tfde_tpu.training.lifecycle import Estimator, RunConfig
    from tfde_tpu.training.step import init_state, make_train_step

    info = bootstrap()
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 2


    def sha(arrays):
        return hashlib.sha256(
            b"".join(np.ascontiguousarray(a).tobytes() for a in arrays)
        ).hexdigest()


    def sync_dp():
        strategy = MultiWorkerMirroredStrategy()
        rng = np.random.default_rng(0)  # same stream on both hosts (OFF)
        images = rng.random((16, 784), np.float32)
        labels = rng.integers(0, 10, (16, 1)).astype(np.int32)
        state, _ = init_state(
            BatchNormCNN(), optax.sgd(0.1), strategy,
            np.zeros((16, 784), np.float32),
        )
        step = make_train_step(strategy, state, donate=False)
        feed = device_prefetch(
            iter([(images, labels)] * 4), strategy.mesh,
            policy=AutoShardPolicy.OFF,
        )
        losses = []
        for batch in feed:
            state, m = step(state, batch, jax.random.key(0))
            losses.append(float(jax.device_get(m["loss"])))
        leaves = jax.tree_util.tree_leaves(jax.device_get(state.params))
        return {"first_loss": losses[0], "last_loss": losses[-1],
                "params_sha": sha(leaves)}


    def fsdp():
        strategy = FSDPStrategy(min_shard_elems=1)  # axis spans both hosts
        rng = np.random.default_rng(0)
        images = rng.random((16, 784), np.float32)
        labels = rng.integers(0, 10, (16, 1)).astype(np.int32)
        state, _ = init_state(PlainCNN(), optax.adam(1e-3), strategy,
                              np.zeros((16, 784), np.float32))
        # params are actually sharded across the two processes
        kernel = state.params["Dense_0"]["kernel"]
        assert kernel.sharding.spec[0] == "fsdp", kernel.sharding.spec
        assert not kernel.is_fully_addressable  # cross-host array
        step = make_train_step(strategy, state, donate=False)
        feed = device_prefetch([(images, labels)] * 3, strategy.mesh,
                               policy=AutoShardPolicy.OFF)
        for batch in feed:
            state, m = step(state, batch, jax.random.key(0))
        # the replicated loss, and the bytes of this process's shards
        return {"loss": float(jax.device_get(m["loss"])),
                "shard_sha": sha(s.data for s in kernel.addressable_shards)}


    def lifecycle(phase, model_dir):
        rng = np.random.default_rng(0)  # same stream on both hosts (OFF)
        X = rng.random((64, 784), np.float32)
        Y = rng.integers(0, 10, (64, 1)).astype(np.int32)
        train_fn = lambda: (
            Dataset.from_tensor_slices((X, Y))
            .shuffle(64, seed=0).repeat().batch(16, drop_remainder=True)
        )
        eval_fn = lambda: Dataset.from_tensor_slices(
            (X[:32], Y[:32])).batch(16)
        cfg = RunConfig(model_dir=model_dir, save_checkpoints_steps=5,
                        save_summary_steps=5)
        est = Estimator(PlainCNN(), optax.sgd(0.1), config=cfg)
        # max_steps is absolute: in the restarted cluster (same model_dir,
        # fresh processes) the completed 10 steps are a no-op...
        state = est.train(train_fn, max_steps=10,
                          shard_policy=AutoShardPolicy.OFF)
        if phase == "resume":
            assert int(jax.device_get(state.step)) == 10, "resume failed"
            # ...and training continues from the checkpoint to 16
            state = est.train(train_fn, max_steps=16,
                              shard_policy=AutoShardPolicy.OFF)
        metrics = est.evaluate(eval_fn)
        export_path = None
        if phase == "resume":
            export_path = est.export_saved_model(
                FinalExporter("exporter", (None, 784))
            )
        est.close()
        per, sl = local_slice_for_process(16)
        return {
            "step": int(jax.device_get(state.step)),
            "loss": metrics["loss"],
            "accuracy": metrics["accuracy"],
            "chief_gating_ok":
                (est._writer() is not None) == (info.process_id == 0),
            "slice": [sl.start, sl.stop],
            "per_host": per,
            "export": export_path,
        }


    # argv: one drill a word, its arguments after colons
    out = {"process_id": info.process_id}
    for word in sys.argv[1:]:
        name, *args = word.split(":")
        out[name] = {"sync_dp": sync_dp, "fsdp": fsdp,
                     "lifecycle": lifecycle}[name](*args)
    print(json.dumps(out))
    """
)


def _run_group(script_path, argv, n=2):
    """Run one cluster to its end; every process's last line of JSON."""
    procs = _spawn_group(script_path, argv, n)
    try:
        results = []
        for p in procs:
            out, err = p.communicate(timeout=_WAIT_S)
            assert p.returncode == 0, f"child failed:\n{err[-3000:]}"
            results.append(json.loads(out.strip().splitlines()[-1]))
        return results
    finally:
        _reap(procs)


@pytest.fixture(scope="module")
def booted_pair(tmp_path_factory):
    """Two real OS processes, booted once, run the drills that need a pair
    and nothing else of it: sync-DP, FSDP, and the lifecycle's first life
    (its second needs a cluster started anew). Returns the script, the
    lifecycle's model_dir and the two processes' results."""
    tmp = tmp_path_factory.mktemp("pair")
    script = tmp / "child_train.py"
    script.write_text(_TRAIN_CHILD)
    model_dir = str(tmp / "run")
    results = _run_group(
        script, ["sync_dp", "fsdp", f"lifecycle:first:{model_dir}"])
    assert {r["process_id"] for r in results} == {0, 1}
    return script, model_dir, results


def test_two_process_sync_dp_agrees(booted_pair):
    _, _, results = booted_pair
    first, second = (r["sync_dp"] for r in results)
    # sync DP: replicated params identical across processes, loss decreased
    assert first["params_sha"] == second["params_sha"]
    assert first["last_loss"] < first["first_loss"]
    assert first["last_loss"] == pytest.approx(second["last_loss"])


def test_two_process_estimator_lifecycle_and_resume(booted_pair):
    """VERDICT r2 #7: the full Estimator lifecycle across 2 real processes —
    train with chief-only summaries, collective checkpointing, eval, restart
    the whole group and resume from the checkpoint, final export; OFF-policy
    host slices reconstruct the global batch."""
    script, model_dir, results = booted_pair
    first = [dict(r["lifecycle"], process_id=r["process_id"])
             for r in results]
    assert all(r["step"] == 10 for r in first)
    assert all(r["chief_gating_ok"] for r in first)
    # sync SPMD: both processes computed identical eval metrics
    assert first[0]["loss"] == pytest.approx(first[1]["loss"])
    assert first[0]["accuracy"] == first[1]["accuracy"]
    # OFF-policy slices tile the global batch exactly (data/device.py)
    slices = sorted(tuple(r["slice"]) for r in first)
    assert slices == [(0, 8), (8, 16)]
    assert all(r["per_host"] == 8 for r in first)
    # checkpoints landed in the shared model_dir
    ckpts = os.listdir(os.path.join(model_dir, "checkpoints"))
    assert any(d.isdigit() for d in ckpts)

    # "kill" the cluster (the first pair has exited) and restart
    resumed = _run_group(script, [f"lifecycle:resume:{model_dir}"])
    assert all(r["lifecycle"]["step"] == 16 for r in resumed)
    assert resumed[0]["lifecycle"]["loss"] == pytest.approx(
        resumed[1]["lifecycle"]["loss"])
    # chief exported; non-chief didn't
    exports = {r["process_id"]: r["lifecycle"]["export"] for r in resumed}
    assert exports[0] is not None and os.path.exists(exports[0])
    assert exports[1] is None


def test_two_process_fsdp_shards_and_agrees(booted_pair):
    """ZeRO/FSDP across two real processes (the DCN-analog layout): params
    shard over the cross-host 'fsdp' axis (not fully addressable anywhere),
    training runs, and both processes agree on the replicated loss."""
    _, _, results = booted_pair
    first, second = (r["fsdp"] for r in results)
    assert first["loss"] == pytest.approx(second["loss"])
    # each host holds a different shard of the same kernel
    assert first["shard_sha"] != second["shard_sha"]


_OBS_CHILD = textwrap.dedent(
    """
    import json, os, sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    from tfde_tpu.utils.devices import request_cpu_devices
    request_cpu_devices(1)
    from tfde_tpu import bootstrap
    from tfde_tpu.observability import aggregate, flightrec, metrics
    from tfde_tpu.observability.exposition import MetricsServer

    model_dir, port_file, stop_file = sys.argv[1:4]
    info = bootstrap()
    assert jax.process_count() == 2

    if info.process_id == 0:
        # chief: /metrics + aggregator; stays up after the worker is killed
        reg = metrics.Registry()
        agg = aggregate.ClusterAggregator(registry=reg, include_local=0,
                                          stale_after=1.5)
        srv = MetricsServer(port=0, host="127.0.0.1", registry=reg,
                            aggregator=agg)
        tmp = port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(srv.port))
        os.replace(tmp, port_file)
        deadline = time.time() + 120
        while not os.path.exists(stop_file) and time.time() < deadline:
            time.sleep(0.05)
        out = agg.rollup()
        print(json.dumps({"process_id": 0,
                          "hosts_stale": out["hosts_stale"],
                          "stale_hosts": out["stale_hosts"]}))
        sys.stdout.flush()
        os._exit(0)  # peer was SIGKILLed: skip jax.distributed teardown
    else:
        # worker: flight recorder armed + metrics pusher, then wait to die
        flightrec.arm(model_dir)
        flightrec.record("worker_alive", pid=os.getpid())
        wreg = metrics.Registry()
        wreg.gauge("train/steps_per_sec").set(21.0)
        wreg.histogram("train/step").observe(0.1)
        deadline = time.time() + 120
        while not os.path.exists(port_file) and time.time() < deadline:
            time.sleep(0.05)
        with open(port_file) as f:
            port = int(f.read())
        pusher = aggregate.MetricsPusher(
            f"http://127.0.0.1:{port}/push", interval=0.25,
            registry=wreg, host=info.process_id)
        time.sleep(120)  # the parent SIGTERMs us here
    """
)


_ELASTIC_CHILD = textwrap.dedent(
    """
    import faulthandler, hashlib, json, os, signal, sys, time
    # a child that hangs says where, into the file the drill reads back,
    # and goes before the drill stops waiting for it
    faulthandler.dump_traceback_later(100, exit=True)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from tfde_tpu.utils.devices import request_cpu_devices
    request_cpu_devices(1)
    import numpy as np, optax
    from tfde_tpu import bootstrap
    from tfde_tpu.data.pipeline import AutoShardPolicy
    from tfde_tpu.models.cnn import PlainCNN
    from tfde_tpu.observability import counters, flightrec, metrics
    from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy
    from tfde_tpu.resilience import (
        ElasticConfig, PeerLossFault, RetryPolicy, Supervisor,
        SupervisorConfig,
    )
    from tfde_tpu.training.lifecycle import Estimator, RunConfig

    mode, model_dir, hb_path, opt_sharding = sys.argv[1:5]
    MAX_STEPS, SAVE_EVERY, KILL_AT = 12, 5, 10
    rng = np.random.default_rng(0)  # same arrays on every host
    X = rng.random((16, 784), np.float32)
    Y = rng.integers(0, 10, (16, 1)).astype(np.int32)

    info = bootstrap()

    if info.num_processes == 2 and info.process_id == 1:
        # a TRUE liveness heartbeat, decoupled from step timing: beating
        # from the training loop itself would conflate "slow step" (ZeRO
        # compile, loaded machine) with "dead peer" and let rank 0 accuse
        # a live rank 1 — SIGKILL stops this thread with the process
        import threading

        def _beat():
            while True:
                with open(hb_path + ".tmp", "w") as f:
                    f.write("alive")
                os.replace(hb_path + ".tmp", hb_path)
                time.sleep(0.25)

        threading.Thread(target=_beat, daemon=True).start()

    def input_fn():
        # every host yields the full GLOBAL batch; OFF policy slices the
        # current process's portion — so the global batch (and with it
        # the loss trajectory) is preserved across a world change with
        # no caller-side re-tuning
        world, rank = jax.process_count(), jax.process_index()
        def gen():
            n = 0
            while True:
                n += 1
                if world == 2 and rank == 1 and n == KILL_AT:
                    # die only once the step-5 save is DONE: on a fast host
                    # steps 6-9 finish before it is. The directory appears
                    # at the chief's rename, and orbax's finalize thread
                    # then closes the save with one more barrier of both
                    # processes (CheckpointManager._finalize ->
                    # _save_progress_tracker.set): a survivor whose peer
                    # died short of it sits in est.close() for orbax's
                    # 600 s before it re-bootstraps. So wait for the
                    # directory (the save has begun), then for this
                    # process's own finalize thread (its last barrier is
                    # behind it, so the survivor's can complete).
                    committed = os.path.join(model_dir, "checkpoints",
                                             str(SAVE_EVERY))
                    deadline = time.time() + 60
                    while (not os.path.isdir(committed)
                           and time.time() < deadline):
                        time.sleep(0.05)
                    estimators[-1]._ckpt.wait()
                    os.kill(os.getpid(), signal.SIGKILL)  # no teardown
                if world == 2 and rank == 0 and n == KILL_AT:
                    # production detection channel, deterministic in-suite:
                    # the peer's heartbeat file goes stale (the analog of
                    # health.note_stale_host's metric-push staleness) --
                    # accuse BEFORE entering the step's collective
                    deadline = time.time() + 60
                    while time.time() < deadline:
                        if time.time() - os.path.getmtime(hb_path) > 2.0:
                            PeerLossFault(
                                rank=1, reason="heartbeat stale",
                            ).fire("input_fn")
                        time.sleep(0.1)
                    raise RuntimeError("peer heartbeat never went stale")
                yield (X, Y)
        return gen()

    estimators = []   # the running one last: rank 1 asks it of its save

    def factory():
        estimators.append(Estimator(
            model=PlainCNN(),
            optimizer=optax.sgd(0.1),
            strategy=MultiWorkerMirroredStrategy(opt_sharding=opt_sharding),
            config=RunConfig(
                model_dir=model_dir,
                save_checkpoints_steps=SAVE_EVERY,
                save_summary_steps=10_000,
                log_step_count_steps=10_000,
            ),
        ))
        return estimators[-1]

    if mode == "elastic":
        sup = Supervisor(factory, SupervisorConfig(
            max_restarts=3,
            restart_policy=RetryPolicy(initial_backoff=0.01, jitter=0.0),
            elastic=ElasticConfig(),
        ))
        state = sup.run(input_fn, MAX_STEPS,
                        shard_policy=AutoShardPolicy.OFF)
        restarts = sup.restarts
        dump = flightrec.dump("elastic_drill")
    else:  # oracle: plain single-process resume from the copied checkpoint
        est = factory()
        state = est.train(input_fn, MAX_STEPS,
                          shard_policy=AutoShardPolicy.OFF)
        est.close()
        restarts, dump = 0, None

    leaves = jax.tree_util.tree_flatten_with_path(
        jax.device_get(state.params))[0]
    h = hashlib.sha256()
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(np.ascontiguousarray(leaf).tobytes())
    print(json.dumps({
        "process_id": info.process_id,
        "step": int(jax.device_get(state.step)),
        "restarts": restarts,
        "world": jax.process_count(),
        "topology_changes": counters.value("resilience/topology_changes"),
        "world_gauge": metrics.gauge("cluster/world_size").value,
        "params_sha": h.hexdigest(),
        "flight_dump": dump,
    }))
    """
)


@pytest.mark.parametrize("opt_sharding", ["replicated", "shard"])
def test_sigkill_peer_elastic_resume(tmp_path, opt_sharding):
    """ISSUE 13 acceptance drill: two REAL processes train sync-DP over
    loopback; rank 1 SIGKILLs itself mid-training (after the step-5
    checkpoint committed). The survivor classifies the loss as TOPOLOGY,
    shrinks the cluster env around the dead rank, re-bootstraps at world
    1, and resumes from the checkpoint to max_steps — with final params
    IDENTICAL to a single-process oracle resumed from the same
    checkpoint (loss-trajectory continuity: OFF-policy hosts feed slices
    of one constant global batch, so the post-resume segment is bit-
    comparable). The 'shard' cell saves 2-way ZeRO-packed optimizer
    state and must restore it at world 1 through the cross-world
    bridge."""
    import glob
    import shutil
    import signal

    from tfde_tpu.observability import flightrec

    script = tmp_path / "child_elastic.py"
    script.write_text(_ELASTIC_CHILD)
    model_dir = str(tmp_path / "run")
    hb_path = str(tmp_path / "hb1")
    # rank 1's heartbeat exists before rank 0 can stat it
    with open(hb_path, "w") as f:
        f.write("0")

    # stderr to files, not pipes: a hung child's log survives the timeout
    # kill and is the only record of where it stuck
    procs = _spawn_group(
        script, ["elastic", model_dir, hb_path, opt_sharding],
        stderr_files=[tmp_path / f"rank{i}.stderr" for i in range(2)])

    def child_err(i):
        return (tmp_path / f"rank{i}.stderr").read_text()[-5000:]

    try:
        # rank 1 dies BY SIGKILL — unannounced, no flight dump, no teardown
        out1, _ = procs[1].communicate(timeout=_WAIT_S)
        assert procs[1].returncode == -signal.SIGKILL, (
            procs[1].returncode, child_err(1))
        # the survivor finishes the run at world 1
        out0, _ = procs[0].communicate(timeout=_WAIT_S)
        assert procs[0].returncode == 0, f"survivor failed:\n{child_err(0)}"
        res = json.loads(out0.strip().splitlines()[-1])
        assert res["step"] == 12
        assert res["restarts"] == 1
        assert res["world"] == 1
        assert res["world_gauge"] == 1
        assert res["topology_changes"] == 1

        # the flight ring tells the whole story
        assert res["flight_dump"] and os.path.exists(res["flight_dump"])
        kinds = [e["kind"] for e in flightrec.load(res["flight_dump"])]
        for kind in ("peer_lost", "env_shrunk", "topology_change",
                     "batch_retune"):
            assert kind in kinds, (kind, kinds)

        # loss-trajectory continuity: a single-process oracle resuming the
        # SAME step-5 checkpoint must land on identical params (prune the
        # later checkpoints the survivor wrote after its re-bootstrap)
        oracle_dir = str(tmp_path / "oracle")
        shutil.copytree(model_dir, oracle_dir)
        ckdir = os.path.join(oracle_dir, "checkpoints")
        steps = sorted(int(d) for d in os.listdir(ckdir) if d.isdigit())
        assert 5 in steps, f"step-5 checkpoint not retained: {steps}"
        for d in steps:
            if d > 5:
                shutil.rmtree(os.path.join(ckdir, str(d)))
        env = _child_env()
        for k in ("CLUSTER_SPEC", "TASK_INDEX", "JOB_NAME",
                  "TFDE_NUM_PROCESSES", "TFDE_PROCESS_ID",
                  "TFDE_COORDINATOR"):
            env.pop(k, None)
        oracle = subprocess.run(
            [sys.executable, str(script),
             "oracle", oracle_dir, hb_path, opt_sharding],
            env=env, capture_output=True, text=True, timeout=_WAIT_S,
        )
        assert oracle.returncode == 0, f"oracle failed:\n{oracle.stderr[-3000:]}"
        ores = json.loads(oracle.stdout.strip().splitlines()[-1])
        assert ores["step"] == 12
        assert ores["params_sha"] == res["params_sha"], (
            "survivor's post-shrink trajectory diverged from the oracle")
    finally:
        _reap(procs)


def test_killed_worker_leaves_flight_file_and_goes_stale(tmp_path):
    """The PR's cluster acceptance: chief /metrics carries the worker's
    host-labelled series; SIGTERM-killing the worker (a) leaves a parseable
    flight_*.jsonl under model_dir/debug and the process dies BY SIGNAL,
    and (b) flips the chief's staleness gauges within ~one push interval."""
    import glob
    import signal
    import urllib.error
    import urllib.request

    from tfde_tpu.observability import flightrec

    script = tmp_path / "child_obs.py"
    script.write_text(_OBS_CHILD)
    model_dir = str(tmp_path / "run")
    port_file = str(tmp_path / "chief_port")
    stop_file = str(tmp_path / "chief_stop")

    procs = _spawn_group(script, [model_dir, port_file, stop_file])
    chief, worker = procs
    try:
        deadline = time.time() + _WAIT_S
        while not os.path.exists(port_file) and time.time() < deadline:
            assert chief.poll() is None, chief.communicate()[1][-3000:]
            time.sleep(0.05)
        with open(port_file) as f:
            url = f"http://127.0.0.1:{int(f.read())}/metrics"

        def scrape():
            return urllib.request.urlopen(url, timeout=5).read().decode()

        body = ""
        while time.time() < deadline:
            body = scrape()
            if 'tfde_train_steps_per_sec{host="1"} 21.0' in body:
                break
            time.sleep(0.1)
        # the worker's pushed snapshot shows up host-labelled, and live
        assert 'tfde_train_steps_per_sec{host="1"} 21.0' in body
        assert 'tfde_cluster_host_up{host="1"} 1' in body

        worker.send_signal(signal.SIGTERM)
        worker.wait(timeout=60)
        # the flight hook dumped, then chained to SIG_DFL: death BY SIGNAL
        assert worker.returncode == -signal.SIGTERM, worker.returncode
        files = glob.glob(os.path.join(model_dir, "debug",
                                       "flight_*.jsonl"))
        assert files, "killed worker left no flight file"
        kinds = [e["kind"] for e in flightrec.load(files[0])]
        assert "worker_alive" in kinds and "sigterm" in kinds
        assert kinds[-1] == "dump"

        while time.time() < deadline:
            body = scrape()
            if 'tfde_cluster_host_up{host="1"} 0' in body:
                break
            time.sleep(0.2)
        assert 'tfde_cluster_host_up{host="1"} 0' in body
        assert "tfde_cluster_hosts_stale 1" in body

        with open(stop_file, "w") as f:
            f.write("x")
        out, err = chief.communicate(timeout=60)
        assert chief.returncode == 0, err[-3000:]
        res = json.loads(out.strip().splitlines()[-1])
        assert res["hosts_stale"] == 1 and res["stale_hosts"] == [1]
    finally:
        _reap(procs)


_REPLICA_CHILD = textwrap.dedent(
    """
    import os, pickle, sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    from tfde_tpu.utils.devices import request_cpu_devices
    request_cpu_devices(1)
    import jax.numpy as jnp
    import numpy as np
    from tfde_tpu.inference.router import ReplicaServer
    from tfde_tpu.inference.server import ContinuousBatcher
    from tfde_tpu.models.gpt import gpt_tiny_test
    from tfde_tpu.observability import boot as boot_lib

    rid, port_file = int(sys.argv[1]), sys.argv[2]
    push_url = sys.argv[3] or None   # "" -> no metrics pusher
    model_dir = sys.argv[4] if len(sys.argv) > 4 else None
    hold_file = sys.argv[5] if len(sys.argv) > 5 else ""
    led = boot_lib.current()   # init phase backdates to process birth
    led.begin("init")
    model = gpt_tiny_test()
    params = model.init(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    # a real (tiny) checkpoint round-trip so the restore phase and its
    # bandwidth gauge carry measured numbers in the drill
    ckpt = port_file + ".ckpt"
    with open(ckpt, "wb") as f:
        pickle.dump(jax.device_get(params), f)
    led.begin("restore")
    t0 = time.perf_counter()
    with open(ckpt, "rb") as f:
        params = pickle.load(f)
    led.note_restore_leaf(
        "params",
        sum(x.nbytes for x in jax.tree_util.tree_leaves(params)),
        max(time.perf_counter() - t0, 1e-9))
    os.remove(ckpt)
    led.begin("compile")
    gate_file = os.environ.get("DRILL_GATE_FILE", "")

    class _Batcher(ContinuousBatcher):
        # the overload drill holds the step loop by a file: while it
        # exists the loop sees an idle batcher, so submits queue up to
        # the cap and nothing runs
        @property
        def idle(self):
            return ((bool(gate_file) and os.path.exists(gate_file))
                    or super().idle)

    b = _Batcher(model, params, kv_quant="fp", batch_size=2, max_len=64)
    rng = np.random.default_rng(rid)
    for ln in (4, 6):   # warm the compiles before announcing the port
        b.submit(rng.integers(1, 90, ln), 6)
    b.run()
    led.begin("warmup")
    b.submit(rng.integers(1, 90, 4), 4)
    b.run()
    srv = ReplicaServer(b, replica_id=rid, push_url=push_url,
                        push_interval=0.3, model_dir=model_dir,
                        boot_ledger=led).start()

    def announce():
        with open(port_file + ".tmp", "w") as f:
            f.write(str(srv.port))
        os.replace(port_file + ".tmp", port_file)

    if hold_file:
        # joining-replica drill: announce while still warming so the
        # router can observe a not-ready boot; become ready only when
        # the parent releases the hold (the wait is warmup wall)
        announce()
        while not os.path.exists(hold_file):
            time.sleep(0.05)
        led.ready()
    else:
        led.ready()
        announce()
    while True:
        time.sleep(60)   # the parent SIGKILLs replica 0, SIGTERMs 1
    """
)


def _spawn_replica(script, rid, port_file, *argv, **env):
    """One replica process on one CPU device (the parent's XLA_FLAGS ask
    for 8)."""
    env = _child_env(JAX_PLATFORMS="cpu", **env)
    env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, str(script), str(rid), port_file, *argv],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _announced_urls(procs, port_files) -> list:
    """The replicas' URLs, once every one wrote its port."""
    deadline = time.time() + _WAIT_S
    while not all(os.path.exists(p) for p in port_files):
        for p in procs:
            assert p.poll() is None, p.communicate()[1][-3000:]
        assert time.time() < deadline, "children never announced ports"
        time.sleep(0.1)
    urls = []
    for pf in port_files:
        with open(pf) as f:
            urls.append(f"http://127.0.0.1:{int(f.read())}")
    return urls


@pytest.fixture(scope="module")
def replica_pair(tmp_path_factory):
    """Two REAL replica processes, booted once for the two drills that put
    a Router before them, with what both drills ask of a replica: a queue
    cap of 2 and the file that holds the step loops (the overload drill),
    tracing, the usage journal and metric pushes to a chief aggregator in
    this process (the kill drill). The kill drill SIGKILLs replica 0, so
    it stands last in this file."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tfde_tpu.inference.decode import generate
    from tfde_tpu.models.gpt import gpt_tiny_test
    from tfde_tpu.observability.aggregate import ClusterAggregator
    from tfde_tpu.observability.exposition import serve_metrics

    model = gpt_tiny_test()
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]

    def solo(prompt, n):
        toks, lengths = generate(
            model, params,
            jnp.asarray(np.asarray(prompt)[None, :], jnp.int32),
            max_new_tokens=n,
        )
        return np.asarray(toks)[0, len(prompt) : int(lengths[0])].tolist()

    tmp = tmp_path_factory.mktemp("replicas")
    script = tmp / "child_replica.py"
    script.write_text(_REPLICA_CHILD)
    port_files = [str(tmp / f"port{i}") for i in range(2)]
    gate = str(tmp / "gate")
    queue_cap = 2
    agg = ClusterAggregator(stale_after=3.0)
    ms = serve_metrics(host="127.0.0.1", aggregator=agg)
    procs = []
    try:
        for i in range(2):
            procs.append(_spawn_replica(
                script, i, port_files[i],
                f"http://127.0.0.1:{ms.port}/push", str(tmp / f"rep{i}"),
                TFDE_TRACE="on",        # replicas record their rings
                TFDE_USAGE_LOG="on",    # journal per-request usage
                # a tight queue cap per replica, so load overflows into
                # 429s instead of unbounded queueing, and the file that
                # holds both step loops
                TFDE_ADMIT_MAX_QUEUE=str(queue_cap), DRILL_GATE_FILE=gate,
                TFDE_ADMIT_MAX_QUEUED_TOKENS="",
                TFDE_ADMIT_TTFT_DEADLINE_MS=""))
        yield types.SimpleNamespace(
            procs=procs, urls=_announced_urls(procs, port_files), agg=agg,
            ms=ms, gate=gate, queue_cap=queue_cap, script=script, tmp=tmp,
            solo=solo)
    finally:
        ms.close()
        _reap(procs)


def test_open_loop_poisson_overload_drill(replica_pair):
    """The PR-14 acceptance drill: two REAL capped replica processes
    (TFDE_ADMIT_MAX_QUEUE from env) behind the Router. An open-loop
    Poisson arrival stream: every request must end in exactly one of
    three orderly ways — completed with tokens greedy-bit-identical to
    solo generate(), rejected with a well-formed 429 + Retry-After, or
    deadline-shed in-band — with zero in-flight drops and admitted p99
    TTFT holding near the unloaded baseline. Then overflow by counts the
    drill sets: with both step loops held, the cluster holds 2 replicas x
    cap 2 requests and refuses every one offered beyond them. (How many
    of the Poisson stream are refused depends on how fast the replicas
    are beside the sender, so nothing is asserted on it: the tiny model
    answers a request in 12 ms and absorbed a stream offered at twice a
    capacity estimated from one request at a time.)"""
    import threading
    import urllib.error

    import numpy as np

    from tfde_tpu.inference.router import Router, request_generate
    from tfde_tpu.observability import metrics

    solo, gate, queue_cap = (replica_pair.solo, replica_pair.gate,
                             replica_pair.queue_cap)
    assert all(p.poll() is None for p in replica_pair.procs)
    metrics.default_registry().reset("router/")
    router = Router(replica_pair.urls).start()
    try:
        rng = np.random.default_rng(14)
        budget = 6
        prompts = [rng.integers(1, 90, int(ln)).tolist()
                   for ln in rng.integers(4, 7, 28)]
        want = [solo(p, budget) for p in prompts]

        # -- phase 1: unloaded baseline ---------------------------------
        base_ttfts = []
        t0 = time.perf_counter()
        for p, w in zip(prompts[:6], want[:6]):
            out = request_generate(router.url, p, budget)
            assert out["tokens"] == w
            base_ttfts.append(out["ttft_s"])
        base_elapsed = time.perf_counter() - t0
        base_p99 = float(np.percentile(base_ttfts, 99))
        svc_rate = 6.0 / base_elapsed      # req/s at concurrency 1

        # -- phase 2: open-loop Poisson, eight times the rate of one
        # request at a time ----------------------------------------------
        offered = 2.0 * svc_rate * 4.0
        arrivals = np.cumsum(rng.exponential(1.0 / offered,
                                             len(prompts) - 6))
        results = [None] * len(arrivals)
        classes = ["interactive", "batch", "best_effort"]
        # admitted interactive work gets a TTFT deadline generous enough
        # that only genuinely stuck requests shed
        dl_ms = max(2000.0, base_p99 * 1e3 * 20.0)

        def fire(into, k, prompt, at, **kw):
            time.sleep(max(0.0, at - (time.perf_counter() - t_load)))
            try:
                out = request_generate(
                    router.url, prompt, budget, timeout=_WAIT_S,
                    priority=classes[k % 3], **kw)
                into[k] = ("ok", out)
            except urllib.error.HTTPError as e:
                body = e.read().decode(errors="replace")
                into[k] = ("http", e.code,
                           e.headers.get("Retry-After"), body)
            except RuntimeError as e:
                into[k] = ("runtime", str(e))
            except Exception as e:   # anything else is a dropped request
                into[k] = ("drop", repr(e))

        def start(into, at_times, **kw):
            threads = [
                threading.Thread(target=fire, daemon=True, kwargs=kw,
                                 args=(into, k, prompts[6 + k], at))
                for k, at in enumerate(at_times)
            ]
            for t in threads:
                t.start()
            return threads

        def finish(threads):
            for t in threads:
                t.join(timeout=_WAIT_S)
                assert not t.is_alive(), "drill request never finished"

        def sort_out(outcomes):
            completed, rejected, shed = [], [], []
            for k, res in enumerate(outcomes):
                assert res is not None, f"request {k} vanished"
                kind = res[0]
                if kind == "ok":
                    out = res[1]
                    # greedy bit-identity survives overload for every
                    # admitted request
                    assert out["tokens"] == want[6 + k], f"request {k}"
                    completed.append(out)
                elif kind == "http":
                    _, code, retry_after, body = res
                    assert code == 429, res
                    assert retry_after is not None \
                        and int(retry_after) >= 1
                    parsed = json.loads(body)
                    assert parsed.get("retriable", True) in (True,)
                    assert float(parsed["retry_after_s"]) > 0
                    rejected.append(parsed)
                elif kind == "runtime":
                    assert "deadline_shed" in res[1], res
                    shed.append(res)
                else:
                    raise AssertionError(f"in-flight drop: {res}")
            return completed, rejected, shed

        t_load = time.perf_counter()
        finish(start(results, arrivals, ttft_deadline_ms=dl_ms))
        completed, _, _ = sort_out(results)
        assert completed, results
        # admitted latency holds: p99 TTFT within 1.5x the unloaded
        # baseline plus absolute slack for CI scheduling noise
        adm_p99 = float(np.percentile(
            [o["ttft_s"] for o in completed], 99))
        assert adm_p99 <= 1.5 * base_p99 + 0.75, (adm_p99, base_p99)

        # -- phase 3: overflow, by counts the drill sets -----------------
        # With both step loops held nothing leaves a queue and no row
        # fills, so the cluster holds 2 x queue_cap requests and not one
        # more: of 12 offered at once, at least 8 come back 429 while the
        # loops are held, and none completes before they run again.
        held_room, n_offered = 2 * queue_cap, 12
        held = [None] * n_offered
        with open(gate, "w"):
            pass
        t_load = time.perf_counter()
        threads = start(held, [0.0] * n_offered)
        deadline = time.time() + _WAIT_S
        while sum(r is not None for r in held) < n_offered - held_room:
            assert time.time() < deadline, held
            time.sleep(0.02)
        assert all(r is None or r[0] == "http" for r in held), held
        os.remove(gate)
        finish(threads)
        completed, rejected, shed = sort_out(held)
        assert len(rejected) >= n_offered - held_room, held
        assert 1 <= len(completed) <= held_room and not shed, held

        # recovery: once the wave passes, the cluster admits again and
        # still decodes solo-correct
        time.sleep(0.5)
        out = request_generate(router.url, prompts[0], budget)
        assert out["tokens"] == want[0]
    finally:
        router.close()


def test_killed_replica_drains_to_survivor(replica_pair, tmp_path):
    """The PR's serving acceptance drill, in-suite: two REAL replica
    processes behind the Router; SIGKILL one mid-service and verify the
    next sessions re-route to the survivor with solo-correct outputs,
    the router's flight ring dumps the `replica_down` story, and the
    chief aggregator's host-up gauge flips when the dead replica's
    metric pushes go stale. Tracing rides along (children spawn with
    TFDE_TRACE=on): the re-routed request's stitched waterfall must show
    BOTH replicas in the routing story and the survivor's serve events,
    and the replica_down flight record must cross-reference the traces
    stranded on the dead replica. Boot observability closes the loop: a
    REPLACEMENT replica then rejoins, serves zero requests before its
    readiness state is `ready`, and its boot-phase decomposition must
    sum to the birth->ready wall, with the first token no later after
    `ready` than the drill waited for it."""
    import glob
    import signal
    import urllib.error
    import urllib.request

    import numpy as np

    from tfde_tpu.inference.router import Router, request_generate
    from tfde_tpu.observability import flightrec, metrics
    from tfde_tpu.observability import trace as reqtrace

    solo, agg, ms, script = (replica_pair.solo, replica_pair.agg,
                             replica_pair.ms, replica_pair.script)
    procs, urls = replica_pair.procs, replica_pair.urls
    assert all(p.poll() is None for p in procs)
    router_dir = str(tmp_path / "router")
    reg = metrics.default_registry()
    reg.reset("router/")

    rejoiner, router, router2 = None, None, None
    # the parent's ring carries the router half of the stitched waterfall
    trace_was_on = reqtrace.active()
    if not trace_was_on:
        reqtrace.enable()
    try:
        deadline = time.time() + _WAIT_S
        router = Router(urls, aggregator=agg, model_dir=router_dir).start()

        # prompts of 9 tokens: the other drill before this pair sends 4-6,
        # so the usage journal's records of this drill are told apart
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 90, 9).tolist() for _ in range(4)]
        # sequential requests tie on outstanding tokens -> replica 0
        pre = [request_generate(router.url, p, 6) for p in prompts[:2]]
        assert all(o["replica"] == 0 for o in pre)
        for o, p in zip(pre, prompts):
            assert o["tokens"] == solo(p, 6)

        scrape_url = f"http://127.0.0.1:{ms.port}/metrics"

        def scrape():
            return urllib.request.urlopen(
                scrape_url, timeout=5).read().decode()

        while ('tfde_cluster_host_up{host="0"} 1' not in scrape()
               and time.time() < deadline):
            time.sleep(0.1)

        os.kill(procs[0].pid, signal.SIGKILL)
        procs[0].wait(timeout=60)

        # queued/new sessions re-route and still decode solo-correct
        out = request_generate(router.url, prompts[2], 6)
        assert out["replica"] == 1 and out["tokens"] == solo(prompts[2], 6)
        rerouted_tid = out["trace"]
        assert rerouted_tid, "router did not return a trace id"
        assert reg.get("router/reroutes").value >= 1
        assert reg.get("router/replicas_lost").value >= 1
        tab = {row["replica"]: row for row in router.table()}
        assert tab[0]["up"] is False and tab[1]["up"] is True
        # the survivor keeps serving fresh sessions
        out = request_generate(router.url, prompts[3], 6)
        assert out["replica"] == 1 and out["tokens"] == solo(prompts[3], 6)

        # the dead replica can't dump its own ring (SIGKILL) — the
        # router's ring carries the routing-side story
        files = glob.glob(os.path.join(router_dir, "debug",
                                       "flight_*.jsonl"))
        assert files, "router left no flight dump for the lost replica"
        flight = flightrec.load(sorted(files)[-1])
        # the post-mortem cross-reference: the down record names the
        # traces that were in flight on the dead replica. The newest one:
        # the ring is the process's, and an earlier file of this xdist
        # worker (test_router.py) may have left a `replica_down` in it
        downs = [e for e in flight if e["kind"] == "replica_down"]
        assert downs, [e["kind"] for e in flight]
        assert rerouted_tid in downs[-1].get("traces", [])

        # the re-routed request's stitched waterfall: ONE trace holding
        # the router's both attempts (0, then the reroute to 1) and the
        # survivor's serving events — the dead replica's ring died with
        # it, which is exactly the post-mortem shape
        body = json.loads(urllib.request.urlopen(
            router.url + f"/trace/{rerouted_tid}", timeout=5).read())
        evs = body["events"]
        assert "router" in body["procs"]
        assert "replica1" in body["procs"]
        attempts = [e["replica"] for e in evs
                    if e["name"] == "router/attempt"]
        assert 0 in attempts and 1 in attempts
        names = [e["name"] for e in evs]
        assert "serve/queued" in names        # survivor admitted it
        assert "serve/first_token" in names
        assert "serve/stream_out" in names
        assert "router/done" in names
        # SLO layer rode the same requests: /replicas embeds the summary
        rep_body = json.loads(urllib.request.urlopen(
            router.url + "/replicas", timeout=5).read())
        assert rep_body["slo"]["ttft_requests"] >= 3
        assert rep_body["slo"]["ttft_attainment"] is not None

        # capacity rode the same pushes: /replicas carries the per-
        # replica kv table and the chief rollup folds the fleet's
        # waste/headroom — the survivor's slab is visible end to end
        assert rep_body["kv"]["1"]["allocated_bytes"] > 0
        assert rep_body["kv"]["1"]["headroom_rows"] is not None
        roll = agg.rollup()
        assert "kv_waste_frac" in roll and 0.0 <= roll["kv_waste_frac"] <= 1.0
        assert roll["kv_headroom_rows"] >= 0

        # both replicas journaled per-request usage to their model_dir —
        # replica 0's records survived the SIGKILL because the log
        # flushes at finish, and the warmup requests (pre-arm) are
        # absent, so each file holds exactly this drill's two requests
        for i in (0, 1):
            uf = os.path.join(str(replica_pair.tmp / f"rep{i}"),
                              "metrics", "usage_0.jsonl")
            assert os.path.exists(uf), f"replica {i} left no usage journal"
            with open(uf) as f:
                recs = [json.loads(ln) for ln in f]
            recs = [r for r in recs if r["prompt_tokens"] == 9]
            assert len(recs) == 2, (i, recs)
            assert all(r["generated_tokens"] == 6 for r in recs)
            assert all(r["outcome"] == "ok" for r in recs)
            assert all(r["kv_token_seconds"] > 0 for r in recs)

        # host-up flips once the dead replica's pushes go stale
        body = scrape()
        while ('tfde_cluster_host_up{host="0"} 0' not in body
               and time.time() < deadline):
            time.sleep(0.2)
            body = scrape()
        assert 'tfde_cluster_host_up{host="0"} 0' in body
        assert 'tfde_cluster_host_up{host="1"} 1' in body

        # -- the rejoin drill: replica 0 comes back as a NEW process
        # that announces its port while still warming (hold file), so
        # the parent can observe the not-ready boot from outside. The
        # acceptance bars: it serves ZERO requests before `ready`, its
        # boot ledger arrives complete over /load and /replicas, and
        # the phase decomposition sums to the wall from process birth
        # to its first served token within 5%.
        hold = str(tmp_path / "hold2")
        port2 = str(tmp_path / "port2")
        rejoiner = _spawn_replica(
            script, 2, port2, "", str(tmp_path / "rep2"), hold,
            TFDE_TRACE="on", TFDE_USAGE_LOG="on")
        deadline = time.time() + _WAIT_S
        (url2,) = _announced_urls([rejoiner], [port2])
        # a fresh router epoch over [survivor, rejoiner]; no aggregator —
        # the old host ids would not line up with the new replica indices
        router2 = Router([urls[1], url2]).start()
        router2._load_ttl = 0.05   # age snapshots fast: tight ready flip
        # while the rejoiner warms, everything lands on the survivor...
        outs = [request_generate(router2.url, prompts[0], 6)
                for _ in range(3)]
        assert all(o["replica"] == 0 for o in outs)
        boot_blk = json.loads(urllib.request.urlopen(
            router2.url + "/replicas", timeout=5).read())["boot"]["1"]
        assert boot_blk["state"] in ("starting", "restoring",
                                     "compiling", "warming")
        assert boot_blk["time_to_ready_s"] is None
        # ...and the gate is hard: with the survivor drained the router
        # 503s rather than placing on the not-ready rejoiner
        urllib.request.urlopen(urllib.request.Request(
            router2.url + "/drain",
            data=json.dumps({"replica": 0}).encode(),
            headers={"Content-Type": "application/json"}), timeout=5)
        with pytest.raises(urllib.error.HTTPError):
            request_generate(router2.url, prompts[0], 6)
        load2 = json.loads(urllib.request.urlopen(
            url2 + "/load", timeout=5).read())
        assert load2["boot"]["ttft_from_birth_ms"] is None  # zero served
        # release the hold: the rejoiner flips ready and takes traffic
        released = time.monotonic()
        with open(hold, "w"):
            pass
        out2 = None
        while out2 is None and time.time() < deadline:
            try:
                out2 = request_generate(router2.url, prompts[0], 6)
            except urllib.error.HTTPError:
                time.sleep(0.05)
        assert out2 is not None, "rejoiner never became placeable"
        answered = time.monotonic()
        assert out2["replica"] == 1
        assert out2["tokens"] == solo(prompts[0], 6)
        # the complete cold-start ledger, phase by phase
        snap = json.loads(urllib.request.urlopen(
            url2 + "/load", timeout=5).read())["boot"]
        assert snap["state"] == "ready"
        for ph in ("init", "restore", "compile", "warmup"):
            assert snap["phases"].get(ph, 0.0) > 0.0, (ph, snap)
        assert snap["restore"]["bytes"] > 0
        assert snap["restore"]["bandwidth_bps"] > 0
        assert snap["time_to_ready_s"] > 0
        # the acceptance identity, cross-process: the phases tile the wall
        # from process birth to `ready` (to the ledger's rounding), and
        # what lies between `ready` and the first served token is the
        # placement latency alone: the rejoiner turned ready after the
        # hold was released and served its token before this drill held
        # the answer. (On an idle box that is 5 % of the wall; a loaded
        # one stretches it, so it is held to what the drill itself waited.)
        ttft_s = snap["ttft_from_birth_ms"] / 1e3
        assert abs(sum(snap["phases"].values())
                   - snap["time_to_ready_s"]) <= 0.01, snap
        assert 0 <= ttft_s - snap["time_to_ready_s"] \
            <= answered - released + 0.01, (snap, answered - released)
    finally:
        if not trace_was_on:
            reqtrace.disable()
        if router2 is not None:
            router2.close()
        if router is not None:
            router.close()
        if rejoiner is not None:
            _reap([rejoiner])
