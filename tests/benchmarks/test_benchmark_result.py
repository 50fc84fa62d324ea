"""The last line has exactly the contract's keys, and the command refuses a
machine without the chips."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib import result
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import median, percentile


class _Device:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 123}


def test_last_line_has_exactly_the_keys():
    device = result.device_block([_Device()], 13958643712)
    line = json.loads(result.result_line(
        True, 400, 0, {"ttft_p50_ms": (212.4071, "ms"),
                       "setup_s": (95.3127, "s")}, device))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"]["ttft_p50_ms"] == {"value": 212.4071, "unit": "ms"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 13958643712}


def test_traced_line_adds_busy_window_and_breakdown():
    device = result.device_block([_Device()] * 4, 1, busy_s=2.5, window_s=3.0)
    ops = [[f"op{i}", 1.0 / (i + 1)] for i in range(14)]
    line = json.loads(result.result_line(
        False, 3, 1, {"device_idle_pct.train": (16.7, "%")}, device,
        {"device_ops": ops, "idle_gaps": [["serving/admit", 0.2]]}))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["device"]["busy_s"] == 2.5 and line["device"]["count"] == 4
    assert len(line["breakdown"]["device_ops"]) == 10
    assert line["breakdown"]["idle_gaps"] == [["serving/admit", 0.2]]
    assert line["correct"] is False and line["failed"] == 1


def test_memory_peak_is_the_fullest_chip():
    class Other(_Device):
        def memory_stats(self):
            return {"peak_bytes_in_use": 456}

    class Silent(_Device):
        def memory_stats(self):
            return None

    assert result.memory_peak_bytes([_Device(), Other(), Silent()]) == 456


def test_peaks_table_is_exact_and_has_no_default():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v5")
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_percentile_matches_numpy():
    import numpy as np

    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 50, 95, 100):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert median(xs) == pytest.approx(np.median(xs))


def _run_on_cpu(cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "gpt2m-train-s4096-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_command_refuses_a_cpu():
    done = _run_on_cpu(manifest_lib.REPO_ROOT)
    assert done.returncode != 0
    assert _no_result(done.stdout)
    assert "needs 1 TPU chip" in done.stderr


def test_command_refuses_where_only_the_benchmark_is(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(manifest_lib.REPO_ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(manifest_lib.REPO_ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run_on_cpu(root)
    assert done.returncode != 0
    assert _no_result(done.stdout)
