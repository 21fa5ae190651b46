"""Host spans and set-up counters of the served path.

`span` is the one way the serving code marks a stretch of host work: a
`jax.profiler.TraceAnnotation`, the profiler's own host event, which lands
on the device trace's clock when a profile is being taken and costs about
a microsecond when none is.

No profiler runs during set-up, so set-up is counted in memory instead:

* `phase(name)` adds the seconds of a named set-up phase (the weights'
  init, their quantization, their placement in the simulated DRAM pool);
* `jax.monitoring` listeners, installed once at import, count JAX's own
  compile events (trace to jaxpr, lowering to MLIR, backend compile, and
  the persistent cache's retrievals, which happen inside a backend
  compile) with their seconds, and the wall seconds the compile stages'
  union covers — a nested trace of an inner jitted function counts once.

`snapshot()` returns both as plain data; two snapshots subtract to what
happened between them.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

#: the compile events counted, by their `jax.monitoring` names
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class _Cover:
    """Seconds covered by intervals that arrive as they end, where one
    that starts no later than the last contains it (an outer trace and
    the inner traces it made)."""

    def __init__(self):
        self.spans: list = []       # disjoint [start, end], in order
        self.total = 0.0

    def add(self, start: float, end: float) -> None:
        while self.spans and self.spans[-1][0] >= start:
            a, b = self.spans.pop()
            self.total -= b - a
        if self.spans and self.spans[-1][1] >= start:
            last = self.spans[-1]
            self.total += max(last[1], end) - last[1]
            last[1] = max(last[1], end)
        else:
            self.spans.append([start, end])
            self.total += end - start


_lock = threading.Lock()
_phases: dict = {}          # name -> [count, seconds]
_compiles: dict = {}        # event -> [count, seconds]
_compile_cover = _Cover()


def span(name: str, **args):
    """A host span named `name`, with `args` shown beside it in the trace."""
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def phase(name: str):
    """Count the enclosed seconds under set-up phase `name`."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        with _lock:
            c = _phases.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += dt


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event in COMPILE_EVENTS:
        with _lock:
            c = _compiles.setdefault(event, [0, 0.0])
            c[0] += 1
            c[1] += seconds


def _on_time_span(event: str, start: float, end: float, **_kw) -> None:
    # the three compile stages report spans; a cache retrieval, inside its
    # backend compile, reports only its duration
    if event in COMPILE_EVENTS:
        with _lock:
            _compile_cover.add(start, end)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_time_span_listener(_on_time_span)


def snapshot() -> dict:
    """{"phases": {name: {"count", "s"}}, "compiles": {event: {"count",
    "s"}}, "compile_wall_s": seconds of compile work, nesting counted
    once}."""
    with _lock:
        return {
            "phases": {k: {"count": n, "s": s}
                       for k, (n, s) in _phases.items()},
            "compiles": {k: {"count": n, "s": s}
                         for k, (n, s) in _compiles.items()},
            "compile_wall_s": _compile_cover.total,
        }
