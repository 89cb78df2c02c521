"""Median over waves of the ``host_segments`` arg of the ``executor.launch``
span: the non-compiled segments each wave crossed, 0 when the whole
schedule runs as compiled programs. Paired with its wave by id, as
``_phases`` pairs the phase spans; nothing to read where the program's
launch span carries no such arg."""

from chipbench.metrics._common import median
from chipbench.metrics._phases import _wave_id


def reduce(ctx):
    waves = {_wave_id(s) for s in ctx.spans if s.name == "wave"}
    waves.discard(None)
    per_wave = {}
    for s in ctx.spans:
        n = (s.args or {}).get("host_segments")
        if s.name == "executor.launch" and n is not None \
                and _wave_id(s) in waves:
            per_wave[_wave_id(s)] = n
    return median(list(per_wave.values()))
