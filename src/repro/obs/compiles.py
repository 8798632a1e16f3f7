"""A process-wide counter of the seconds JAX spends building programs.

JAX reports its own compile stages through `jax.monitoring`; `install()`
registers one listener (once per process, however often it is called)
that sums them:

  trace_s       /jax/core/compile/jaxpr_trace_duration
  lower_s       /jax/core/compile/jaxpr_to_mlir_module_duration
  compile_s     /jax/core/compile/backend_compile_duration, which wraps
                the persistent cache's lookup, so it covers loads too
  cache_load_s  /jax/compilation_cache/cache_retrieval_time_sec (inside
                compile_s)

A jit traced inside another jit's trace reports its own span inside its
parent's; a span that lies inside one already counted is left out, so
`trace_s` is wall time spent tracing. `snapshot()` returns the sums and
`total_s` (trace + lower + compile); two snapshots' difference is what
happened between them. The cost is one dictionary add per event, and
nothing per step.
"""
from __future__ import annotations

import threading

from jax import monitoring

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
SPANS = {TRACE: "trace_s", LOWER: "lower_s", COMPILE: "compile_s"}
KEYS = ("trace_s", "lower_s", "compile_s", "cache_load_s", "compiles",
        "cache_loads")

_lock = threading.Lock()
_sums = dict.fromkeys(KEYS, 0.0)
_open: dict = {}           # event -> [(start, end)] of spans counted, newest last
_installed = False


def _on_span(event: str, start: float, end: float, **_) -> None:
    key = SPANS.get(event)
    if key is None:
        return
    with _lock:
        spans = _open.setdefault(event, [])
        inside = 0.0
        while spans and spans[-1][0] >= start and spans[-1][1] <= end:
            s, e = spans.pop()       # a nested span, now covered by this one
            inside += e - s
        spans.append((start, end))
        del spans[:-64]
        _sums[key] += (end - start) - inside
        if event == COMPILE:
            _sums["compiles"] += 1


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == CACHE_LOAD:
        with _lock:
            _sums["cache_load_s"] += seconds
            _sums["cache_loads"] += 1


def install() -> None:
    """Start counting; later calls do nothing."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    """The sums so far, and `total_s` = trace + lower + compile."""
    with _lock:
        out = dict(_sums)
    out["total_s"] = out["trace_s"] + out["lower_s"] + out["compile_s"]
    return out


def since(before: dict) -> dict:
    """What was counted after the snapshot `before`."""
    now = snapshot()
    return {k: now[k] - before[k] for k in now}
