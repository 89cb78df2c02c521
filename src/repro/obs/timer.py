"""The one injectable timer every measurement in ``src/repro`` reads.

Raw ``time.time()`` / ``time.perf_counter()`` calls used to be scattered
through ``deploy/scenarios.py``, ``deploy/executor.py`` and the
launch/serving shims — each one a place a deterministic test could
not reach. This module is now the single point of truth (enforced by
``scripts/check_no_raw_clock.py``): everything times itself through
``obs.timer.now()``, and a test swaps the process-wide timer for a manual
clock (the ``serve/clock.py`` pattern, made global):

    from repro.obs import timer
    with timer.fake(ManualClock()) as clock:
        ...            # every now()/sleep() in repro reads the fake

The only two files allowed to touch the ``time`` module directly are this
one and ``repro/serve/clock.py`` (whose clock *objects* plug in here).

``now()`` is a monotonic high-resolution stamp for measuring durations;
``walltime()`` is the epoch stamp for provenance metadata (checkpoint
manifests, bench artifacts) — the two must never be mixed.
"""

from __future__ import annotations

import contextlib
import time as _time
from typing import Iterator, Optional


class PerfTimer:
    """The real timer: ``perf_counter`` durations, real sleeps."""

    def now(self) -> float:
        return _time.perf_counter()

    def sleep(self, seconds: float) -> None:
        if seconds > 0:
            _time.sleep(seconds)

    def walltime(self) -> float:
        return _time.time()


_TIMER: object = PerfTimer()


def get_timer() -> object:
    return _TIMER


def set_timer(timer: Optional[object]) -> object:
    """Install a timer object (``now()``/``sleep()``); returns the previous
    one so callers can restore it. ``None`` restores the real timer."""
    global _TIMER
    old = _TIMER
    _TIMER = timer if timer is not None else PerfTimer()
    return old


@contextlib.contextmanager
def fake(timer: object) -> Iterator[object]:
    """Scoped timer swap: install ``timer`` for the block, restore after.
    The fixture-shaped entry point for deterministic-clock tests."""
    old = set_timer(timer)
    try:
        yield timer
    finally:
        set_timer(old)


def now() -> float:
    """Monotonic seconds from the installed timer (durations only)."""
    return _TIMER.now()


def sleep(seconds: float) -> None:
    _TIMER.sleep(seconds)


def walltime() -> float:
    """Epoch seconds (provenance stamps). Falls back to the real clock when
    the installed timer has no ``walltime`` (manual clocks measure
    durations, not dates)."""
    wt = getattr(_TIMER, "walltime", None)
    return wt() if wt is not None else _time.time()
