"""Observability: the unified metrics-and-tracing layer.

- metrics.py     process-wide registry (counters/gauges/histograms)
- spans.py       phase timers feeding the histograms (+ XProf regions)
- goodput.py     wall-clock classification -> goodput fraction
- exposition.py  Prometheus text, JSONL logs, /metrics HTTP, TB bridge
- tensorboard.py event-file SummaryWriter
- profiler.py    jax.profiler trace windows
- counters.py    legacy counter API (shim over metrics.py)
- trace.py       per-request distributed tracing ring (TFDE_TRACE)
- slo.py         TTFT/TPOT SLO attainment + burn-rate gauges
- flightrec.py   crash-dump flight recorder ring
- aggregate.py   cross-host metric aggregation + trace stitching
- memwatch.py    measured memory ledger (mem/*, TFDE_MEMWATCH)
- recompile.py   jit-cache-miss sentinel and set-up ledger (compile/*, setup())
"""

from tfde_tpu.observability.tensorboard import SummaryWriter  # noqa: F401
from tfde_tpu.observability.profiler import profile_trace  # noqa: F401
from tfde_tpu.observability import counters  # noqa: F401
from tfde_tpu.observability import metrics  # noqa: F401
from tfde_tpu.observability import spans  # noqa: F401
from tfde_tpu.observability.spans import span  # noqa: F401
from tfde_tpu.observability.goodput import GoodputLedger  # noqa: F401
from tfde_tpu.observability.exposition import (  # noqa: F401
    JsonlMetricsLog,
    MetricsServer,
    serve_metrics,
    to_prometheus_text,
)
from tfde_tpu.observability import trace  # noqa: F401
from tfde_tpu.observability.slo import SLOTracker  # noqa: F401
from tfde_tpu.observability import memwatch  # noqa: F401
from tfde_tpu.observability import recompile  # noqa: F401
