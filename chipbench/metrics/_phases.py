"""Per-wave sums of the program's phase spans, for the phase reducers.

The router gives every wave an id: its ``wave`` span carries it as
``args["wave"]``, and so does each phase span recorded while the router
submitted, waited on or settled that wave (``replica.put``,
``executor.pad``, ``executor.launch``, ``wave.wait``, ``router.fetch``).
Children pair with their wave by that id, never by time: under an async
engine waves overlap. A program whose spans carry no such id gives
nothing to read.
"""

from __future__ import annotations

from typing import Dict, List, Sequence


def _wave_id(span):
    return (span.args or {}).get("wave")


def per_wave_ms(spans, names: Sequence[str]) -> List[float]:
    """For each completed wave (a ``wave`` span with an id) that has a
    phase span named in ``names``: those spans' summed duration, in ms."""
    waves = {_wave_id(s) for s in spans if s.name == "wave"}
    waves.discard(None)
    out: Dict[object, float] = {}
    for s in spans:
        if s.name in names:
            w = _wave_id(s)
            if w in waves:
                out[w] = out.get(w, 0.0) + (s.t1 - s.t0) * 1e3
    return list(out.values())
