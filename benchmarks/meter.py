"""Host-side meters: JAX's own compile events, and the device's memory.

``CompileMeter`` is chip_smoke.py's (PR 21), copied so that the yardstick
lives with the benchmark.
"""

from __future__ import annotations

import threading
import time


class CompileMeter:
    """Trace, lowering and backend-compile seconds as JAX reports them
    (jax.monitoring), stamped with the host clock so they can be cut by
    window, plus the persistent cache's hits and misses."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.events = []  # (perf_counter at end, seconds, is_backend_compile)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self._DURATIONS:
            self.events.append(
                (time.perf_counter(), seconds, event == self._DURATIONS[2])
            )

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def window(self, start: float, end: float):
        """(compile seconds, programs compiled) inside [start, end]."""
        inside = [e for e in self.events if start <= e[0] <= end]
        return sum(e[1] for e in inside), sum(1 for e in inside if e[2])

    def intervals(self, start: float, end: float):
        """[(start, end)] of every compile event that ended inside the window."""
        return [(e[0] - e[1], e[0]) for e in self.events if start <= e[0] <= end]


class MemoryMeter:
    """The fullest device's memory over a run.

    ``memory_stats()`` keeps two high-water marks: ``peak_bytes_in_use`` (live
    buffers) and ``peak_bytes_reserved`` (what the runtime sets aside at a
    program's load for its temporaries: a solver's while-loop carry, such as
    an L-BFGS history, lives there and never shows in ``bytes_in_use``;
    ``memcheck.py`` is the experiment). The two marks may date from different
    moments, so their sum can read over the true peak. ``peak_bytes`` is the
    most ``bytes_in_use + bytes_reserved`` that one reading showed together
    (bytes held at one instant), or ``peak_bytes_in_use`` where that is more:
    it can read under the true peak, never over it.

    Readings are taken by a thread, every 50 ms, during set-up only (the
    warm-up step is the same work as a window step, and a reservation lasts
    only while its program is loaded, which a step's end may not see), then
    once at every step's end and at the window's close: in the measured window
    nothing runs but the program and its one driving thread. Everything is 0
    where the backend reports nothing (the CPU)."""

    PERIOD_S = 0.05

    def __init__(self):
        import jax

        self.devices = jax.local_devices()
        self.instant = 0       # largest in_use + reserved of one reading
        self.readings = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, name="memory-meter", daemon=True)

    def read(self) -> None:
        for d in self.devices:
            stats = d.memory_stats() or {}
            self.instant = max(self.instant, int(stats.get("bytes_in_use", 0))
                               + int(stats.get("bytes_reserved", 0)))
        self.readings += 1

    def _poll(self) -> None:
        while not self._stop.wait(self.PERIOD_S):
            self.read()

    def start(self) -> "MemoryMeter":
        self._thread.start()
        return self

    def end_of_setup(self) -> None:
        """Ends the thread; from here on ``read()`` is called by hand."""
        self._stop.set()
        self._thread.join()

    def result(self) -> dict:
        """A last reading, and the readings in bytes."""
        self.end_of_setup()
        self.read()
        in_use = reserved = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            in_use = max(in_use, int(stats.get("peak_bytes_in_use", 0)))
            reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
        return {
            "peak_bytes": max(in_use, self.instant),
            "peak_bytes_in_use": in_use,
            "peak_bytes_reserved": reserved,
            "peak_instant_in_use_plus_reserved": self.instant,
            "readings": self.readings,
        }
