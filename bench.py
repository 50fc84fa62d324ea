"""Re-export of one function, not a benchmark (that is `benchmarks/run.py`
under `BENCHMARK.json`). `tests/benchmarks/test_benchmark_flops.py` pins
`benchmarks/lib/flops.py` to `bench.gpt_train_flops_per_token` and only a
`benchmark` PR may edit it: that PR points the pin at
`tfde_tpu.ops.roofline` and deletes this file (ROADMAP Design 7)."""
from tfde_tpu.ops.roofline import gpt_train_flops_per_token  # noqa: F401
