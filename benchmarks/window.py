"""The window: a slice hook that times whole generations.

`launch_boundary` calls the installed hook at the end of every launch,
after that generation was journaled. The first call ends set-up and
starts the window. Every later call closes the window if the next
generation could not finish inside it, always after at least one whole
generation: it stamps the end and asks the sweep to drain, and the same
boundary raises `SweepInterrupted`. The window is a whole number of
generations and never longer than `seconds` unless one generation is.
"""

from __future__ import annotations

import time


class Window:
    def __init__(self, seconds: float, request, clock=time.time, on_open=None):
        self.seconds = float(seconds)
        self._request = request
        self._clock = clock
        self._on_open = on_open  # runs at the first boundary, inside set-up
        self.stamps: list = []  # epoch seconds of every boundary seen
        self.closed = False

    def hook(self, stage: str) -> None:
        if self.closed:
            return
        if not self.stamps and self._on_open is not None:
            self._on_open()
        now = self._clock()
        self.stamps.append(now)
        if len(self.stamps) < 2:
            return
        elapsed = now - self.stamps[0]
        last = now - self.stamps[-2]
        if elapsed + last > self.seconds:
            self.closed = True
            self._request()

    @property
    def start(self):
        return self.stamps[0] if self.stamps else None

    @property
    def end(self):
        return self.stamps[-1] if self.closed else None

    @property
    def generations(self) -> int:
        """Whole generations inside the closed window."""
        return len(self.stamps) - 1 if self.closed else 0

    def periods(self) -> list:
        """Boundary-to-boundary seconds of each window generation."""
        return [b - a for a, b in zip(self.stamps, self.stamps[1:])]
