"""The one traffic generator: it reads a mix's parameters from
``traffic/<name>.json`` and makes the cell's inputs and arrivals from the
seed.

A mix file holds:

    loop            "closed": a query of ``query_samples`` requests is sent
                    at once, and the next when all of it has returned
                    (MLPerf SingleStream with 1, Offline with more);
                    "open": requests arrive on a schedule whatever the
                    server does (MLPerf Server).
    arrivals        open loop only: "poisson" with ``rate_per_s``, or
                    "mmpp" with ``rates_per_s`` (one per state) and
                    ``dwell_s`` (mean time in a state).
    distinct_rows   the inputs: this many distinct rows of signed codes,
                    uniform over the input layer's range; request i gets
                    row i mod distinct_rows.
    check_rows      how many served answers the correctness check compares.
    serving         the router and engine the mix is served with:
                    ``engine`` ("sync" | "async"), ``max_inflight``,
                    ``wave`` (rows per wave), ``max_wait_ms`` and
                    ``replicas`` (one per chip).

Poisson arrivals are drawn as a fixed count, ``rate_per_s * seconds``, at
uniform times in the window (a Poisson process given its count), so every
seed offers the same work in another order. The MMPP generator is
``serve/traffic.py``'s ``mmpp_trace``, copied so that the yardstick stays
fixed when the program changes.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

LOOPS = ("closed", "open")
ENGINES = ("sync", "async")

#: independent random streams of one seed
INPUTS, ARRIVALS, CHECK = 0, 1, 2


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def validate(t: Dict) -> Dict:
    if t.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}: {t}")
    s = t["serving"]
    if s.get("engine") not in ENGINES:
        raise ValueError(f"serving engine must be one of {ENGINES}: {s}")
    for k in ("wave", "max_inflight", "replicas"):
        if int(s.get(k, 0)) < 1:
            raise ValueError(f"serving {k} must be >= 1: {s}")
    if t["loop"] == "closed" and int(t.get("query_samples", 0)) < 1:
        raise ValueError(f"closed loop needs query_samples >= 1: {t}")
    if t["loop"] == "open" and t.get("arrivals") not in ("poisson", "mmpp"):
        raise ValueError(f"open loop needs arrivals poisson|mmpp: {t}")
    if int(t.get("distinct_rows", 0)) < 1 or int(t.get("check_rows", 0)) < 1:
        raise ValueError(f"distinct_rows and check_rows must be >= 1: {t}")
    return t


def input_rows(t: Dict, shape: Sequence[int], input_bits: int,
               seed: int) -> np.ndarray:
    """The distinct input rows: signed codes uniform in
    [-(2^(b-1) - 1), 2^(b-1) - 1], int32 as the program takes them."""
    hi = 2 ** (input_bits - 1) - 1
    return rng(seed, INPUTS).integers(
        -hi, hi + 1, (int(t["distinct_rows"]),) + tuple(shape),
        dtype=np.int32)


def arrivals(t: Dict, seconds: float, seed: int) -> np.ndarray:
    """Arrival times in [0, seconds) of an open-loop mix, sorted."""
    r = rng(seed, ARRIVALS)
    if t["arrivals"] == "poisson":
        n = int(round(float(t["rate_per_s"]) * seconds))
        return np.sort(r.uniform(0.0, seconds, n))
    return mmpp(t["rates_per_s"], float(t["dwell_s"]), seconds, r)


def mmpp(rates: Sequence[float], dwell_s: float, seconds: float,
         r: np.random.Generator) -> np.ndarray:
    """Markov-modulated Poisson arrivals: cycle through rate states with
    an exponential dwell in each, Poisson arrivals at the state's rate
    while dwelling (``serve/traffic.py``'s ``mmpp_trace``, cut at
    ``seconds`` instead of a count)."""
    rates = [float(q) for q in rates]
    if not rates or any(q <= 0 for q in rates) or dwell_s <= 0:
        raise ValueError(f"mmpp needs positive rates and dwell: {rates}, "
                         f"{dwell_s}")
    out, t, state = [], 0.0, 0
    while t < seconds:
        dwell = r.exponential(dwell_s)
        tt = t + r.exponential(1.0 / rates[state])
        while tt < min(t + dwell, seconds):
            out.append(tt)
            tt += r.exponential(1.0 / rates[state])
        t += dwell
        state = (state + 1) % len(rates)
    return np.asarray(out)
