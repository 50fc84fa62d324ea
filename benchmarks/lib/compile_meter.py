"""Seconds jax spent lowering, compiling and reading the persistent cache,
and how many programs the backend compiled, from jax.monitoring (a copy of
`chip_smoke.py::CompileMeter`). `compile_s` moves `setup_s`;
`backend_compiles` taken before and after the window counts compilations
inside it."""

from __future__ import annotations


class CompileMeter:
    _DURATIONS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )
    _COUNTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.backend_compiles = 0
        self.counts = {name: 0 for name in self._COUNTS.values()}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event in self._DURATIONS:
            self.compile_s += seconds
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        name = self._COUNTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s,
                "backend_compiles": self.backend_compiles, **self.counts}
