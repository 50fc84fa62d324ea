"""Cluster bootstrap — the TPU-native analog of the reference's TF_CONFIG path.

The reference synthesizes a ``TF_CONFIG`` env var from ``CLUSTER_SPEC`` /
``TASK_INDEX`` / ``JOB_NAME`` (mnist_keras_distributed.py:221-233) and relies on
TensorFlow's gRPC runtime to wire up ps/master/worker roles with per-role device
filters (mnist_keras_distributed.py:165-189).

On TPU there is no parameter-server data plane: every process is an equal SPMD
participant and the runtime is `jax.distributed` over DCN, with XLA collectives
over ICI inside a slice. This module therefore:

- accepts the *same environment contract* as the reference
  (``CLUSTER_SPEC``/``TASK_INDEX``/``JOB_NAME``, or a pre-built ``TF_CONFIG``),
  plus the native ``TFDE_COORDINATOR``/``TFDE_NUM_PROCESSES``/``TFDE_PROCESS_ID``
  variables and JAX's own defaults;
- maps roles onto SPMD ranks: ``master``/``chief`` -> process 0, ``worker`` i ->
  process i (+1 when a master exists), ``ps`` entries are *dropped* — their
  capability (sharded variable hosting) is provided synchronously by ZeRO-style
  optimizer-state sharding (see parallel/strategies.py, and SURVEY.md §7 "hard
  parts" for the documented async->sync semantic change);
- calls ``jax.distributed.initialize`` exactly once when a multi-process
  cluster is configured.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Optional

from tfde_tpu import knobs

log = logging.getLogger(__name__)

_INITIALIZED = False
#: the ClusterInfo the last bootstrap() resolved — what the running
#: process group was actually built from. The elastic layer diffs a fresh
#: resolve_cluster() against this to detect a scheduler that rewrote the
#: spec (TF_CONFIG / TFDE_*) between supervisor attempts.
_LAST_INFO: Optional["ClusterInfo"] = None


@dataclasses.dataclass(frozen=True)
class ClusterInfo:
    """Resolved identity of this process within the training cluster."""

    num_processes: int
    process_id: int
    coordinator_address: Optional[str]
    job_type: str  # 'chief' | 'worker' | 'local'
    task_index: int

    @property
    def is_chief(self) -> bool:
        """Chief = process 0, the reference's `worker 0` / `master` role.

        The reference gates TensorBoard launch and export on worker 0
        (mnist_keras_distributed.py:277-280); we gate all host-side side
        effects (checkpoint writes, event files, export) the same way.
        """
        return self.process_id == 0

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def _parse_tf_config() -> Optional[dict]:
    """Parse TF_CONFIG if present — reference contract at mnist_keras:165-189."""
    raw = os.environ.get("TF_CONFIG")
    if not raw:
        return None
    try:
        cfg = json.loads(raw)
    except json.JSONDecodeError as e:
        # Fail loudly: silently degrading would fan a configured N-host job
        # out into N independent single-host jobs.
        raise ValueError(f"TF_CONFIG is set but is not valid JSON: {e}") from e
    if "cluster" not in cfg:
        return None
    return cfg


def _synthesize_tf_config() -> Optional[dict]:
    """CLUSTER_SPEC/TASK_INDEX/JOB_NAME -> TF_CONFIG dict.

    Mirrors mnist_keras_distributed.py:221-233, including writing the
    synthesized TF_CONFIG back into the environment, but fixes the reference's
    ``NameError`` when CLUSTER_SPEC is unset with ``job_type`` used later
    (mnist_keras:224-225 vs :278) by always returning a well-defined config.
    """
    raw = os.environ.get("CLUSTER_SPEC")
    if not raw:
        return None
    try:
        cluster_spec = json.loads(raw)
    except json.JSONDecodeError as e:
        raise ValueError(f"CLUSTER_SPEC is set but is not valid JSON: {e}") from e
    job_index = int(os.environ.get("TASK_INDEX", "0"))
    job_type = os.environ.get("JOB_NAME", "worker")
    cfg = {"cluster": cluster_spec, "task": {"type": job_type, "index": job_index}}
    os.environ["TF_CONFIG"] = json.dumps(cfg)
    log.info("Distribution enabled: %s", os.environ["TF_CONFIG"])
    return cfg


def _rank_from_tf_config(cfg: dict) -> tuple[int, int, str, int, Optional[str]]:
    """Map a TF_CONFIG cluster onto SPMD ranks.

    ps tasks are dropped (no PS data plane on TPU — see module docstring);
    chief/master is rank 0; workers follow in index order.
    Returns (num_processes, process_id, job_type, task_index, coordinator).
    """
    cluster = cfg["cluster"]
    task = cfg.get("task", {"type": "worker", "index": 0})
    job_type = task.get("type", "worker")
    task_index = int(task.get("index", 0))

    chief_hosts = cluster.get("chief", []) or cluster.get("master", [])
    worker_hosts = cluster.get("worker", [])
    ps_hosts = cluster.get("ps", [])
    if ps_hosts:
        log.info(
            "Cluster spec lists %d ps tasks; TPU build provides their "
            "capability via sharded optimizer state (sync DP), ps processes "
            "are not ranked. See SURVEY.md §7.",
            len(ps_hosts),
        )

    ranked_hosts = list(chief_hosts) + list(worker_hosts)
    num_processes = max(len(ranked_hosts), 1)

    if job_type in ("chief", "master"):
        process_id = 0
        norm_type = "chief"
    elif job_type == "worker":
        process_id = len(chief_hosts) + task_index
        norm_type = "chief" if (not chief_hosts and task_index == 0) else "worker"
    elif job_type == "ps":
        raise RuntimeError(
            "This process was launched with JOB_NAME=ps. The TPU-native build "
            "has no parameter-server role: run only chief/worker tasks and the "
            "optimizer state will be sharded across them (ZeRO-style). "
            "See SURVEY.md §7."
        )
    else:
        process_id = task_index
        norm_type = job_type

    # Coordinator = first ranked host, on a port derived from its service port
    # (the jax.distributed service is a separate listener from any app port).
    coordinator = ranked_hosts[0] if ranked_hosts else None
    return num_processes, process_id, norm_type, task_index, coordinator


def resolve_cluster() -> ClusterInfo:
    """Resolve cluster identity from the environment without side effects."""
    # Native contract takes precedence.
    if os.environ.get("TFDE_NUM_PROCESSES"):
        # knobs.env_int warn-fallbacks on garbage: an unparseable world
        # size drops to the TF_CONFIG path instead of crashing bootstrap
        num = knobs.env_int("TFDE_NUM_PROCESSES")
        if num is not None:
            pid = knobs.env_int("TFDE_PROCESS_ID", 0)
            coord = knobs.env_str("TFDE_COORDINATOR")
            return ClusterInfo(num, pid, coord,
                               "chief" if pid == 0 else "worker", pid)

    cfg = _parse_tf_config() or _synthesize_tf_config()
    if cfg is None:
        log.info("Distribution is not enabled")  # mnist_keras:233
        return ClusterInfo(1, 0, None, "local", 0)

    num, pid, job_type, task_index, coord = _rank_from_tf_config(cfg)
    return ClusterInfo(num, pid, coord, job_type, task_index)


def coordinator_endpoint(coord: str, default_port: int = 8476) -> str:
    """host[:port] from the cluster spec -> the jax.distributed coordinator
    endpoint.

    The spec port belongs to the application's own service (in a genuine
    TF_CONFIG migration, the TF gRPC server — a leftover process bound to
    it would make init fail), so the coordinator listens on a DERIVED
    port: spec port + 1011, wrapped to stay in range. Deterministic, so
    every process computes the same endpoint from the same spec.
    `TFDE_COORD_PORT` overrides when the derived port is also taken.
    """
    tail = coord.rsplit("]")[-1]  # IPv6-bracket aware
    if ":" in tail:
        host, spec_port = coord.rsplit(":", 1)
        derived = int(spec_port) + 1011
        if derived > 65535:
            derived = int(spec_port) - 1011
    else:
        host, derived = coord, default_port
    port = knobs.env_int("TFDE_COORD_PORT", int(derived))
    return f"{host}:{port}"


def metrics_push_url(info: Optional[ClusterInfo] = None,
                     port: Optional[int] = None) -> Optional[str]:
    """Where a non-chief host pushes metric snapshots
    (observability/aggregate.MetricsPusher), derived from the same spec
    that placed the chief:

    - ``TFDE_METRICS_PUSH_URL`` wins outright (explicit endpoint —
      required when the chief's server fell back to an ephemeral port);
    - else the coordinator's *host* + ``TFDE_METRICS_PORT``/`port` — the
      chief runs next to the jax.distributed coordinator, and its metrics
      server listens on the port every process already agrees on.

    Returns None when neither is derivable (single-process, or no fixed
    metrics port configured) — callers treat that as "pushing disabled".
    """
    env = knobs.env_str("TFDE_METRICS_PUSH_URL")
    if env:
        return env
    if port is None:
        port = knobs.env_int("TFDE_METRICS_PORT")
    if not port:  # None or 0 (ephemeral): workers can't guess the binding
        return None
    info = info or resolve_cluster()
    if not info.is_distributed or not info.coordinator_address:
        return None
    coord = info.coordinator_address
    tail = coord.rsplit("]")[-1]  # IPv6-bracket aware, like coordinator_endpoint
    host = coord.rsplit(":", 1)[0] if ":" in tail else coord
    return f"http://{host}:{port}/push"


def last_info() -> Optional[ClusterInfo]:
    """The ClusterInfo the last `bootstrap()` call resolved (None before
    the first bootstrap). This is the *running* topology, as opposed to
    `resolve_cluster()` which re-reads the environment fresh."""
    return _LAST_INFO


def initialized() -> bool:
    """True while a `jax.distributed` runtime this module started is up."""
    return _INITIALIZED


#: runtime clients/services abandoned by an elastic teardown — once a peer
#: died, neither can be shut down or destroyed without terminating the
#: survivor, so they are made immortal (permanent incref) and listed here
#: for introspection; the OS reclaims them at process exit
_ZOMBIE_CLIENTS: list = []


#: heartbeat window under which the coordination service never declares a
#: task dead on its own: peer-death detection belongs to the resilience
#: layer (health staleness -> elastic.note_peer_lost, collective errors),
#: which can actually survive it — the stock runtime's reaction to a dead
#: peer is LOG(FATAL) in every process, the exact opposite of elastic
#: training. ~12 days: effectively never, without integer-overflow risk.
_HEARTBEAT_TIMEOUT_S = 1_000_000


def shutdown(abandon: bool = False) -> None:
    """Tear down the distributed runtime so `bootstrap()` can run again —
    the first half of an elastic re-bootstrap (resilience/elastic.py).
    Safe when nothing was initialized; failures during teardown are logged
    and swallowed.

    `abandon=True` is the peer-is-dead path: the graceful shutdown
    protocol runs a cluster-wide barrier that can never complete once a
    task died (and the stock runtime LOG(FATAL)s the surviving process
    when it fails). Worse, ANY teardown of the old runtime is fatal: the
    client's error-polling thread reacts to its poll RPC being cancelled
    — which both `service.shutdown()` and client destruction cause — by
    terminating the process (client.h: "Terminating process because the
    JAX distributed service detected fatal errors"), and the Python
    `missed_heartbeat_callback` escape hatch crashes with std::bad_cast
    on this jaxlib (no Status caster). So abandoning PARKS the old
    client and service in a module-level zombie list — alive but
    disowned, their threads quiescent under the long heartbeat window —
    and the re-bootstrap moves to a fresh coordination port (see
    elastic.shrink_env) instead of re-binding the abandoned one."""
    global _INITIALIZED
    if not _INITIALIZED:
        return
    import jax

    if abandon:
        # jax has no public handle on the runtime's client and service; a
        # jax that moves them fails this import loudly, not into a fallback
        from jax._src import distributed as jdist

        try:
            state = jdist.global_state
            client, service = state.client, state.service
            state.client = None
            state.service = None
            state.preemption_sync_manager = None
            # back to the class defaults: backend factories consult these
            # (e.g. the CPU client wires gloo collectives through
            # global_state.client) and stale world numbers would make a
            # post-shrink world-1 backend demand a client we just parked
            state.process_id = 0
            state.num_processes = 1
            state.coordinator_address = None
            import ctypes

            for obj in (client, service):
                if obj is None:
                    continue
                # immortal, not merely parked: interpreter teardown would
                # otherwise run the destructors in arbitrary order, and a
                # dying service cancels the client's outstanding poll RPC
                # — which the poll thread answers with LOG(FATAL). The OS
                # reclaims both at process exit.
                ctypes.pythonapi.Py_IncRef(ctypes.py_object(obj))
                _ZOMBIE_CLIENTS.append(obj)
            log.warning(
                "abandoned the distributed runtime of the old topology "
                "(client%s parked; a dead peer makes any teardown fatal)",
                "+service" if service is not None else "")
        except Exception:
            log.warning("abandon-teardown failed (continuing)",
                        exc_info=True)
    else:
        try:
            jax.distributed.shutdown()
        except Exception:
            # a dead peer/coordinator makes the farewell barrier fail —
            # that is exactly the situation an elastic teardown is for
            log.warning("jax.distributed.shutdown failed (continuing "
                        "teardown)", exc_info=True)
    _INITIALIZED = False
    from tfde_tpu.observability import flightrec

    flightrec.record("distributed_shutdown", abandoned=bool(abandon))


def bootstrap(coordinator_port: int = 8476, force: bool = False) -> ClusterInfo:
    """Resolve the cluster and initialize `jax.distributed` if multi-process.

    The TPU-native analog of the reference's cluster bootstrap + gRPC session
    construction (mnist_keras_distributed.py:221-233 + 165-189). Safe to call
    multiple times; initialization happens once. `force=True` is the
    re-entrant path (elastic re-bootstrap after a topology change): it
    tears down any prior runtime via `shutdown()` and re-initializes from
    a FRESH read of the environment — the caller (resilience/elastic.py)
    is responsible for having rewritten the env to the surviving hosts.
    """
    global _INITIALIZED, _LAST_INFO
    if force:
        shutdown()
    info = resolve_cluster()
    if info.is_distributed and not _INITIALIZED:
        import jax

        coord = info.coordinator_address
        if coord:
            coord = coordinator_endpoint(coord, coordinator_port)
        log.info(
            "jax.distributed.initialize(coordinator=%s, num_processes=%d, process_id=%d)",
            coord, info.num_processes, info.process_id,
        )
        # Pod bring-up is racy by nature: workers start before the
        # coordinator listens, DNS lags the scheduler. initialize() surfaces
        # that as RuntimeError (grpc deadline) — retried under the
        # operator's TFDE_RETRY_* policy with RuntimeError added, since a
        # worker that gives up on first connect strands the whole slice.
        import dataclasses as _dc

        from tfde_tpu.resilience.policy import policy_from_env, retry_call

        base = policy_from_env()
        policy = _dc.replace(
            base, retryable=tuple(base.retryable) + (RuntimeError,)
        )
        retry_call(
            jax.distributed.initialize,
            coordinator_address=coord,
            num_processes=info.num_processes,
            process_id=info.process_id,
            heartbeat_timeout_seconds=_HEARTBEAT_TIMEOUT_S,
            policy=policy,
            what="jax.distributed.initialize",
            counter="resilience/bootstrap_retries",
        )
        _INITIALIZED = True
        from tfde_tpu.observability import flightrec

        flightrec.record(
            "bootstrap", num_processes=info.num_processes,
            process_id=info.process_id, coordinator=coord,
        )
    _LAST_INFO = info
    from tfde_tpu.observability import metrics

    metrics.gauge("cluster/world_size").set(info.num_processes)
    return info
