"""Window sums of the counters the program records with
``jax.monitoring.record_scalar`` (names starting ``/cdfl/``).

``listen()`` registers one listener for the process, which keeps every
such scalar with the host-clock instant it was recorded at; a reader
sums a name over ``[start, end]`` of the window. A reader calls
``listen()`` when it is imported: ``spec.resolve`` imports a cell's
readers before set-up, so the listener hears the whole run. A program
that records no such counter leaves every sum at 0.
"""
from __future__ import annotations

import time

PREFIX = "/cdfl/"


class Scalars:
    def __init__(self):
        self.events = []        # [(perf_counter instant, name, value)]

    def __call__(self, event, value, **kw):
        if event.startswith(PREFIX):
            self.events.append((time.perf_counter(), event, float(value)))

    def total(self, name: str, lo: float, hi: float) -> float:
        return sum(v for t, n, v in self.events
                   if n == name and lo <= t <= hi)


_SCALARS: Scalars | None = None


def listen() -> Scalars:
    """The process's listener, registered on the first call."""
    global _SCALARS
    if _SCALARS is None:
        import jax
        _SCALARS = Scalars()
        jax.monitoring.register_scalar_listener(_SCALARS)
    return _SCALARS
