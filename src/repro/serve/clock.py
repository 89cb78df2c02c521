"""Injectable clocks for the serve runtime.

Every serve component reads time through one of these objects instead of
the ``time`` module, so the whole server — batching deadlines, SLO
estimates, sliding-window metrics, trace replay — runs identically under
the real clock and under a test-controlled manual clock (the
same trick ``tests/test_scenarios.py`` plays on the scenario runtime, made
first-class here because the router's correctness *is* its timing).
"""

from __future__ import annotations

import time


class SystemClock:
    """The real clock: ``perf_counter``, the source ``obs.timer`` reads, so
    the router's stamps and the tracer's spans share one clock."""

    def now(self) -> float:
        return time.perf_counter()

    def sleep(self, seconds: float) -> None:
        if seconds > 0:
            time.sleep(seconds)


class ManualClock:
    """Deterministic clock: time moves only when told to.

    ``sleep`` advances instead of blocking, so trace replay under a
    ManualClock is an exact discrete-event simulation — every latency the
    metrics report is reproducible arithmetic, not wall-clock noise.
    """

    def __init__(self, start: float = 0.0):
        self.t = float(start)

    def now(self) -> float:
        return self.t

    def sleep(self, seconds: float) -> None:
        # typed, not a bare assert: sleeping a negative duration would
        # silently run time backwards under ``python -O``
        if seconds < 0:
            raise ValueError(f"cannot sleep a negative duration: {seconds}")
        self.t += seconds

    def advance(self, seconds: float) -> None:
        if seconds < 0:
            raise ValueError(f"cannot advance time backwards: {seconds}")
        self.t += seconds
