"""The traced run: a few seconds of the steady window under jax.profiler,
with the program's host spans switched on so that idle gaps carry a name.
Measured runs (`--trace 0`) never come here."""

from __future__ import annotations

import os
import shutil

from benchmarks.lib import reduce_trace


class WindowTracer:
    """start() ... stop() around part of the window; summary() afterwards.

    `span_switch(on)` turns the program's own TraceAnnotations on and off
    (for this program `observability.spans.set_trace_active`)."""

    def __init__(self, out_dir: str, span_switch=None):
        self.out_dir = out_dir
        self._switch = span_switch or (lambda on: None)
        self._annotation = None
        self.started = False
        self.stopped = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # spans only: the interpreter's
        options.host_tracer_level = 2     # own events would drown them
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self._switch(True)
        self._annotation = jax.profiler.TraceAnnotation(
            reduce_trace.WINDOW_ANNOTATION)
        self._annotation.__enter__()
        self.started = True

    def stop(self) -> None:
        import jax

        if not self.started or self.stopped:
            return
        self._annotation.__exit__(None, None, None)
        self._switch(False)
        jax.profiler.stop_trace()
        self.stopped = True

    def summary(self, chips: int) -> dict:
        """The reduced trace; the profile itself is deleted."""
        profile = reduce_trace.load(reduce_trace.find_xplane(self.out_dir))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return reduce_trace.reduce(profile, chips=chips)
