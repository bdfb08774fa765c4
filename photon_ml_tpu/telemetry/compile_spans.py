"""JAX's compile phases as spans of the program's tracer.

Every time JAX traces a function to a jaxpr, lowers a jaxpr to an MLIR
module or hands a module to the backend (a compile, or a load from the
persistent cache where that holds the program) it reports the phase's
duration through ``jax.monitoring``, with the function's name. The listeners
here turn each report into a finished span ``jit/trace``, ``jit/lower`` or
``jit/backend`` on the tracer's own clock, parented to whatever span was open
on the calling thread: the compile time of a step, seen from inside the
program, under the work that caused it. A hit or miss of the persistent cache becomes a
zero-length span ``jit/cache`` and bumps ``jit.cache.hits`` /
``jit.cache.misses`` (JAX reports neither for a program that compiles in
under the cache's minimum compile time: it is compiled every time).

The listeners are registered once, by the first ``enable_tracing()``, and
return at once while the tracer is off. Only events that end while it is on
are recorded. The events nest (a ``jax.jit`` traced inside another, and every
``jnp`` function called during a trace or a lowering, reports its own): of
one thread's spans of one phase the tracer keeps the outermost, so that they
do not flood it. Phases still nest in each other (a kernel body traced inside
a lowering, an eager compile inside a trace) and threads overlap: a reader
that wants a time takes ``telemetry.union_seconds`` of the spans it picks.
"""
from __future__ import annotations

import time

from photon_ml_tpu.telemetry.metrics import get_registry
from photon_ml_tpu.telemetry.span import _CURRENT, get_tracer

__all__ = ["register"]

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": True,
    "/jax/compilation_cache/cache_misses": False,
}
_registered = False


def _under() -> str:
    parent = _CURRENT.get()
    return "" if parent is None else parent.path


def _on_duration(event: str, duration_secs: float, fun_name: str = "", **_) -> None:
    tracer = get_tracer()
    if not tracer.enabled:
        return
    phase = _PHASES.get(event)
    if phase is None:
        return
    end = time.perf_counter()
    tracer.add_interval(
        f"jit/{phase}", end - duration_secs, end, absorb_nested=True,
        fun_name=str(fun_name), phase=phase, under=_under(),
    )


def _on_event(event: str, **_) -> None:
    tracer = get_tracer()
    if not tracer.enabled:
        return
    hit = _CACHE.get(event)
    if hit is None:
        return
    now = time.perf_counter()
    tracer.add_interval("jit/cache", now, now, hit=hit, under=_under())
    get_registry().count("jit.cache.hits" if hit else "jit.cache.misses")


def register() -> None:
    """Register the two listeners with ``jax.monitoring`` (idempotent)."""
    global _registered
    if _registered:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _registered = True
