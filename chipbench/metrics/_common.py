"""Arithmetic shared by the metric reducers in this directory."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (linear between order statistics); None for no
    values."""
    import numpy as np

    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if values.size else None


def router_waits_ms(spans) -> List[float]:
    """Per request: arrival to the submission of its wave, in ms. A
    ``request`` span (arrival -> completion) is paired with the ``wave``
    span (submission -> completion) of its lane that ends at the same
    stamp: the router stamps every request of a wave with the wave's
    completion."""
    waves: Dict[tuple, float] = {}
    for s in spans:
        if s.name == "wave":
            waves[(s.tid, s.t1)] = s.t0
    out = []
    for s in spans:
        if s.name == "request" and not (s.args or {}).get("shed"):
            t0 = waves.get((s.tid, s.t1))
            if t0 is not None:
                out.append((t0 - s.t0) * 1e3)
    return out


def wave_ms(spans) -> List[float]:
    return [(s.t1 - s.t0) * 1e3 for s in spans if s.name == "wave"]


def median(values) -> Optional[float]:
    return float(statistics.median(values)) if values else None


def idle_share_pct(ctx) -> Optional[float]:
    """Percent of the measured window in which no operation ran on the
    device, mean over the chips used. The profiler slows the host, so its
    window serves fewer samples a second than the measured one: the device's
    busy time per sample comes from the profiled window (the profiler does
    not slow device operations) and is paced at the measured window's
    rate."""
    if ctx.device is None or ctx.device["samples"] <= 0 or ctx.window_s <= 0:
        return None
    busy_per_sample = ctx.device["busy_s"] / ctx.device["samples"]
    return 100.0 * (1.0 - busy_per_sample * ctx.samples / ctx.window_s)
