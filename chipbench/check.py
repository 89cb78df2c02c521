"""The comparison that decides ``correct``.

A sample of the served answers, drawn from the seed, is compared with the
configuration's plain reference on the same input rows. Two numbers are
compared, each with its limit from ``limits/<workload>.json``:

    rows_off_share  the share of sampled answers whose logits differ from
                    the reference's by more than 1e-4 of (1 + the row's
                    largest reference logit), float32's rounding of the
                    program's exact head being some 1e-7 of it;
    unanswered      requests of the window that never got an answer.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

#: how far a logit may sit from the reference's before its row is "off"
ROW_TOL = 1e-4


def rows_off_share(served: np.ndarray, ref: np.ndarray) -> float:
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape:
        return 1.0
    tol = ROW_TOL * (1.0 + np.max(np.abs(ref), axis=1))
    off = ~(np.max(np.abs(served - ref), axis=1) <= tol)   # NaN is off
    return float(np.mean(off))


def sample(results: Dict[int, np.ndarray], k: int,
           rng: np.random.Generator):
    """Up to ``k`` answered request ids, drawn from ``rng``, sorted."""
    uids = np.asarray(sorted(results))
    if len(uids) > k:
        uids = np.sort(rng.choice(uids, size=k, replace=False))
    return [int(u) for u in uids]


def compare(mod, cfg: Dict, params, rows: np.ndarray,
            results: Dict[int, np.ndarray], index: Dict[int, int],
            n_rows: int, limits: Dict, rng: np.random.Generator, k: int,
            served_override=None) -> Dict:
    """The numbers of one run beside their limits.

    ``results`` maps request id -> served logits, ``index`` request id ->
    the request's position (request i was sent ``rows[i % n_rows]``).
    ``served_override`` (control and fault readings) maps the sampled
    inputs to the answers put in the program's place."""
    uids = sample(results, k, rng)
    x = rows[[index[u] % n_rows for u in uids]] if uids else rows[:0]
    ref = mod.reference(cfg, params, x)
    if served_override is not None:
        served = served_override(x)
    elif uids:
        served = np.stack([results[u] for u in uids])
    else:
        served = np.zeros((0,) + ref.shape[1:])
    value = rows_off_share(served, ref) if uids else 1.0
    return {
        "rows_off_share": {"value": value,
                           "limit": float(limits["rows_off_share"]["limit"]),
                           "rows": len(uids)},
        "unanswered": {"value": int(len(index) - len(results)),
                       "limit": int(limits["unanswered"]["limit"])},
    }


def passed(numbers: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in numbers.values())
