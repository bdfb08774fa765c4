"""The measured window, as every training driver keeps it.

A driver makes its warm-up steps (set-up) and then whole steps until
``seconds`` have passed since the first window step began. The window is one
interval on the host clock: from the start of the first step after the last
warm-up step to the end of the step during which the time ran out. Compiles
and stalls inside it stay inside it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional


@dataclasses.dataclass
class Window:
    seconds: float
    on_start: Optional[Callable[[], None]] = None   # runs in set-up
    on_end: Optional[Callable[[], None]] = None     # runs after the window
    on_step: Optional[Callable[[], None]] = None    # after every step, warm-up too
    start: Optional[float] = None
    step_ends: List[float] = dataclasses.field(default_factory=list)

    def warmup_step_done(self) -> None:
        if self.on_step is not None:
            self.on_step()

    def warmed_up(self) -> None:
        """Call when the last warm-up step has ended (after its
        ``warmup_step_done``); the window starts here."""
        if self.on_start is not None:
            self.on_start()
        self.start = time.perf_counter()

    def step_done(self) -> bool:
        """Call at the end of every window step; True when the window is
        over."""
        now = time.perf_counter()
        self.step_ends.append(now)
        if self.on_step is not None:
            self.on_step()
        over = now - self.start >= self.seconds
        if over and self.on_end is not None:
            self.on_end()
        return over

    @property
    def steps(self) -> int:
        return len(self.step_ends)

    @property
    def end(self) -> float:
        return self.step_ends[-1]

    @property
    def length(self) -> float:
        return self.end - self.start

    @property
    def step_seconds(self) -> List[float]:
        ends = [self.start] + self.step_ends
        return [b - a for a, b in zip(ends, ends[1:])]

    def train_step_s(self) -> dict:
        """The training drivers' end-to-end metric: the whole window over
        the whole steps in it."""
        return {"train_step_s": {"value": self.length / self.steps, "unit": "s/step"}}
