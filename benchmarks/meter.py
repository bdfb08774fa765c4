"""Host-side meter: the device's memory over a run. (Compile time is read
from the program's own ``jit/*`` spans: layer_metrics/_compile.py.)"""

from __future__ import annotations

import threading


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
