"""Was it the host? A run that reads far off says so itself.

A thread sleeps `period` seconds at a time through the window and notes how
late it wakes. If the window's longest step comes with a wake-up as late,
the whole process stood still (the machine was taken away, or a call held
the interpreter); if the watcher kept time, the main thread was waiting for
the device. The kernel's own counts over the window go beside it: seconds
stolen from the machine, involuntary context switches, major page faults.
Printed on the `[notes]` line, read by no metric.
"""

from __future__ import annotations

import os
import resource
import threading
import time


def _machine_steal_s() -> float:
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return float("nan")


class HostWatch:
    def __init__(self, period: float = 0.02):
        self._period = period
        self._halt = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._late = []          # (seconds late, seconds after start)

    def _watch(self) -> None:
        while not self._halt.is_set():
            t0 = time.perf_counter()
            time.sleep(self._period)
            late = time.perf_counter() - t0 - self._period
            if late > 0.05:
                self._late.append((late, t0 - self._t_start))

    def start(self) -> None:
        self._t_start = time.perf_counter()
        self._usage = resource.getrusage(resource.RUSAGE_SELF)
        self._steal = _machine_steal_s()
        self._thread.start()

    def stop(self) -> dict:
        self._halt.set()
        self._thread.join()
        usage = resource.getrusage(resource.RUSAGE_SELF)
        worst = max(self._late, default=(0.0, None))
        return {
            "watcher_late_max_s": worst[0], "watcher_late_at_s": worst[1],
            "watcher_late_over_50ms": len(self._late),
            "machine_steal_s": _machine_steal_s() - self._steal,
            "involuntary_switches": usage.ru_nivcsw - self._usage.ru_nivcsw,
            "major_faults": usage.ru_majflt - self._usage.ru_majflt,
        }
