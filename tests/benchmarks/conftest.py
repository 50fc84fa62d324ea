"""What the benchmark's tests share."""

import os

import pytest

from benchmarks.lib import reduce_trace

SMALL_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "small_trace.textproto")


class RecordedTrace:
    """Stands where run.py's WindowTracer stands: the CPU's profile holds
    no device plane, so the summary is the recorded fixture's."""

    started = stopped = False

    def start(self):
        self.started = True

    def stop(self):
        self.stopped = True

    def summary(self, chips):
        return reduce_trace.reduce(reduce_trace.load(SMALL_TRACE),
                                   chips=chips)


@pytest.fixture
def recorded_trace(monkeypatch):
    """A traced run on the CPU: the recorded trace, and the v5e's peaks
    under the CPU's name (the table rightly has no entry for it)."""
    from benchmarks.lib import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return RecordedTrace()
