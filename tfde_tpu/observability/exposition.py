"""Exposition: render a metric snapshot for operators.

Four surfaces off the one registry (metrics.py):

- **Prometheus text** (`to_prometheus_text`): the scrape format, classic
  histograms included (`_bucket{le="..."}` / `_sum` / `_count`).
  `parse_prometheus_text` is the inverse — used by tests to prove the
  round-trip and by anyone who wants the numbers back out of a scrape.
- **HTTP /metrics** (`MetricsServer`): a tiny threaded endpoint the chief
  (or the inference server) runs; `/metrics` serves Prometheus text,
  `/metrics.json` the flattened snapshot, `/healthz` liveness.
- **JSONL event log** (`JsonlMetricsLog`): append-structured snapshots
  under `<model_dir>/metrics/` — the post-hoc analysis surface (works on
  remote model_dirs through utils/fs, like the TensorBoard writer).
- **TensorBoard bridge** (`export_to_tensorboard`): the flattened snapshot
  as scalars through the existing SummaryWriter, so ops metrics land next
  to the training curves.

Metric names are slash-namespaced internally ("train/data_wait");
Prometheus names sanitize to ``tfde_train_data_wait``.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from typing import Dict, Optional

from tfde_tpu.observability import metrics
from tfde_tpu.utils import fs

log = logging.getLogger(__name__)

PROM_PREFIX = "tfde_"
PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

_INVALID = re.compile(r"[^a-zA-Z0-9_:]")


def prom_name(name: str, prefix: str = PROM_PREFIX) -> str:
    """'train/data_wait' -> 'tfde_train_data_wait' (Prometheus charset)."""
    out = _INVALID.sub("_", f"{prefix}{name}")
    if out[0].isdigit():
        out = f"_{out}"
    return out


def _fmt(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    return repr(float(v))


def to_prometheus_text(snapshot: Optional[Dict[str, dict]] = None,
                       registry: Optional[metrics.Registry] = None,
                       prefix: str = PROM_PREFIX) -> str:
    """Render a `Registry.snapshot()` (or the registry's current state) as
    Prometheus text exposition format. Counters get the conventional
    `_total` suffix; histograms render classic cumulative buckets."""
    if snapshot is None:
        snapshot = (registry or metrics.default_registry()).snapshot()
    lines = []
    for name in sorted(snapshot):
        data = snapshot[name]
        kind = data["type"]
        pname = prom_name(name, prefix)
        if kind == "counter":
            pname = f"{pname}_total"
            lines.append(f"# TYPE {pname} counter")
            lines.append(f"{pname} {_fmt(data['value'])}")
        elif kind == "gauge":
            lines.append(f"# TYPE {pname} gauge")
            lines.append(f"{pname} {_fmt(data['value'])}")
        else:  # histogram
            lines.append(f"# TYPE {pname} histogram")
            cum = 0
            for le, cum in data["buckets"]:
                lines.append(f'{pname}_bucket{{le="{_fmt(le)}"}} {cum}')
            lines.append(f'{pname}_bucket{{le="+Inf"}} {data["count"]}')
            lines.append(f"{pname}_sum {_fmt(data['sum'])}")
            lines.append(f"{pname}_count {data['count']}")
    return "\n".join(lines) + "\n"


def parse_prometheus_text(text: str) -> Dict[str, dict]:
    """Inverse of `to_prometheus_text` for the families it emits. Returns
    {prom_name: {"type": ..., "value": float}} for counters (name keeps its
    `_total` suffix) and gauges, and {"type": "histogram", "buckets":
    [(le, cum)], "sum": float, "count": int} for histograms."""
    types: Dict[str, str] = {}
    out: Dict[str, dict] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        name_part, _, val_part = line.rpartition(" ")
        value = float(val_part)
        if "{" in name_part:
            base, _, rest = name_part.partition("{")
            labels = rest.rstrip("}")
            if base.endswith("_bucket"):
                hname = base[: -len("_bucket")]
                h = out.setdefault(
                    hname, {"type": "histogram", "buckets": [],
                            "sum": 0.0, "count": 0})
                m = re.search(r'le="([^"]+)"', labels)
                le = float(m.group(1)) if m.group(1) != "+Inf" else float("inf")
                h["buckets"].append((le, int(value)))
            continue
        if name_part.endswith("_sum") and name_part[: -4] in out:
            out[name_part[: -4]]["sum"] = value
        elif name_part.endswith("_count") and name_part[: -6] in out:
            out[name_part[: -6]]["count"] = int(value)
        else:
            out[name_part] = {"type": types.get(name_part, "untyped"),
                              "value": value}
    # the +Inf bucket duplicates _count; drop it for a clean comparison
    for h in out.values():
        if h.get("type") == "histogram":
            h["buckets"] = [(le, c) for le, c in h["buckets"]
                            if le != float("inf")]
    return out


# -- JSONL event log ---------------------------------------------------------
class JsonlMetricsLog:
    """Append-only JSONL snapshots under `<model_dir>/metrics/`.

    Each `write(step)` appends one line::

        {"ts": <unix>, "step": N, "metrics": {flattened snapshot}}

    Local paths append through a held file handle; remote paths
    (gs://, memory://) buffer and rewrite the object on flush — the same
    trade the TensorBoard writer makes (remote stores have no append)."""

    def __init__(self, model_dir: str,
                 registry: Optional[metrics.Registry] = None):
        self._reg = registry or metrics.default_registry()
        d = fs.join(model_dir, "metrics")
        fs.makedirs(d)
        fname = f"metrics-{int(time.time())}-{os.getpid()}.jsonl"
        self.path = fs.join(d, fname)
        self._remote = fs.is_remote(self.path)
        self._buf: list = []
        self._f = None if self._remote else open(self.path, "a")
        self._lock = threading.Lock()

    def write(self, step: int, extra: Optional[Dict[str, float]] = None) -> None:
        flat = metrics.flatten_snapshot(self._reg.snapshot())
        if extra:
            flat.update(extra)
        rec = {"ts": time.time(), "step": int(step), "metrics": flat}
        # exemplar linking (trace.py): when request tracing is on, each
        # snapshot line carries the trace ids of the slowest latency
        # observations so a post-mortem can jump from a bad percentile
        # straight to the offending waterfalls
        from tfde_tpu.observability import trace as _trace

        ex = _trace.exemplars()
        if ex:
            rec["exemplars"] = ex
        line = json.dumps(rec, sort_keys=True)
        with self._lock:
            if self._f is not None:
                self._f.write(line + "\n")
            else:
                self._buf.append(line)

    def flush(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.flush()
            elif self._buf:
                fs.write_bytes(self.path,
                               ("\n".join(self._buf) + "\n").encode())

    def close(self) -> None:
        self.flush()
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# -- TensorBoard bridge ------------------------------------------------------
def export_to_tensorboard(writer, step: int,
                          registry: Optional[metrics.Registry] = None,
                          prefix: str = "") -> Dict[str, float]:
    """Write the flattened snapshot (optionally filtered to names under
    `prefix`) as scalars at `step`. `writer` may be None (non-chief) —
    then this is only the snapshot read. Returns what was (or would be)
    written."""
    reg = registry or metrics.default_registry()
    flat = {k: v for k, v in metrics.flatten_snapshot(reg.snapshot()).items()
            if k.startswith(prefix)}
    if writer is not None and flat:
        writer.scalars(step, flat)
    return flat


# -- HTTP /metrics endpoint --------------------------------------------------
class MetricsServer:
    """Chief-only scrape endpoint: `/metrics` (Prometheus text),
    `/metrics.json` (flattened snapshot), `/healthz`. Runs a
    ThreadingHTTPServer in a daemon thread; `port=0` binds an ephemeral
    port (read it back from `.port`, as the tests do).

    With an `aggregator` (observability/aggregate.ClusterAggregator)
    attached, `POST /push` ingests worker snapshots and `/metrics` appends
    the aggregator's host-labelled series + cluster rollups — each scrape
    re-runs the rollup, so a dead host's staleness gauge flips even though
    it will never push again."""

    def __init__(self, port: int = 0, host: str = "0.0.0.0",
                 registry: Optional[metrics.Registry] = None,
                 aggregator=None):
        import http.server

        reg = registry or metrics.default_registry()
        self._reg = reg
        self.aggregator = aggregator
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            server_version = "tfde-metrics"

            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 (http.server API)
                try:
                    agg = outer.aggregator
                    if self.path.split("?")[0] == "/metrics":
                        if agg is not None:
                            agg.rollup()  # staleness flips on scrape too
                        body = to_prometheus_text(registry=reg)
                        if agg is not None:
                            body += agg.prometheus_text()
                        self._send(200, body.encode(), PROM_CONTENT_TYPE)
                    elif self.path.split("?")[0] == "/metrics.json":
                        if agg is not None:
                            agg.rollup()
                        flat = metrics.flatten_snapshot(reg.snapshot())
                        body = json.dumps(flat, sort_keys=True).encode()
                        self._send(200, body, "application/json")
                    elif self.path.split("?")[0] == "/healthz":
                        self._send(200, b"ok\n", "text/plain")
                    else:
                        self._send(404, b"not found\n", "text/plain")
                except BrokenPipeError:  # scraper went away mid-response
                    pass

            def do_POST(self):  # noqa: N802 (http.server API)
                try:
                    agg = outer.aggregator
                    if self.path.split("?")[0] != "/push" or agg is None:
                        self._send(404, b"not found\n", "text/plain")
                        return
                    n = int(self.headers.get("Content-Length", 0))
                    try:
                        payload = json.loads(self.rfile.read(n))
                        agg.ingest(payload)
                    except (ValueError, KeyError, TypeError) as e:
                        self._send(400, f"bad push: {e}\n".encode(),
                                   "text/plain")
                        return
                    # the response doubles as the chief->worker command
                    # channel: a pending coordinated-profile broadcast is
                    # delivered (once per host) in the push reply
                    reply: dict = {"ok": True}
                    pending = getattr(agg, "pending_profile", None)
                    if pending is not None:
                        cmd = pending(int(payload.get("host", -1)))
                        if cmd:
                            reply["profile"] = cmd
                    self._send(200, json.dumps(reply).encode(),
                               "application/json")
                except BrokenPipeError:
                    pass

            def log_message(self, fmt, *args):  # scrapes are not log lines
                log.debug("metrics server: " + fmt, *args)

        try:
            self._httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        except OSError as e:
            if port == 0:
                raise
            # A configured port that is already bound (a stale process, a
            # port-sharing collision on one box) must not crash chief
            # startup — fall back to an ephemeral port and say so loudly.
            log.warning(
                "metrics port %d unavailable (%s); falling back to an "
                "ephemeral port — read it back from MetricsServer.port",
                port, e,
            )
            self._httpd = http.server.ThreadingHTTPServer((host, 0), Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="tfde-metrics-server",
        )
        self._thread.start()
        log.info("metrics server listening on %s:%d", host, self.port)

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


def serve_metrics(port: int = 0, host: str = "0.0.0.0",
                  registry: Optional[metrics.Registry] = None,
                  aggregator=None) -> MetricsServer:
    """Convenience: start a MetricsServer over the default registry — the
    one-liner an inference deployment calls next to its batcher."""
    return MetricsServer(port=port, host=host, registry=registry,
                         aggregator=aggregator)
