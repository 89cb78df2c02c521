"""Readings that set a cell's correctness limits, in one process on the
chip: for each seed the program's numbers, the lower-precision control's
(the reference with its 8-bit input codes requantized to 4 bits, put in the
program's place on the same sampled inputs), and on the first seeds the
numbers of the program with its timed path broken.

    python3 -m chipbench.control --workload kws-singlestream --seconds 10 \
        --seeds 11 12 13 --faults 3

Prints one JSON line per reading and a summary line: the largest sound
reading (the lower end of a limit), the smallest control reading (its
upper end) and each fault's readings.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from typing import Callable, Dict

import numpy as np

from chipbench import run, spec


def _half_left_out(x):
    x = np.array(x)
    x[x.shape[0] // 2:] = 0
    return x


#: faults a served cell can have, planted where the wave is produced:
#: name -> (transform of the wave's input rows, transform of its output)
FAULTS: Dict[str, tuple] = {
    "half_left_out": (_half_left_out, None),
    "answer_altered": (None, lambda y: y.at[:, 0].add(1.0)),
}


def break_pool(pool, fault: str) -> Callable[[], None]:
    """Plant ``fault`` in every replica's ``submit_wave``; returns the
    function that takes it out again."""
    before, after = FAULTS[fault]
    saved = []
    for r in pool.replicas:
        inner = r.model.submit_wave
        saved.append((r.model, inner))

        def submit_wave(x, valid=None, micro_batch=None, inner=inner):
            if before is not None:
                x = before(x)
            y, mask = inner(x, valid=valid, micro_batch=micro_batch)
            return (after(y) if after is not None else y), mask

        r.model.submit_wave = submit_wave

    def restore():
        for model, inner in saved:
            model.submit_wave = inner

    return restore


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", type=int, default=0,
                    help="plant each fault on this many of the seeds")
    args = ap.parse_args(argv)
    run.use_checkout_cache()
    bench = spec.load_benchmark()
    try:
        devices = run.require_chips(int(spec.workload(
            bench, args.workload)["chips"]))
    except run.NoChip as e:
        print(f"chipbench.control: {e}", file=sys.stderr)
        return 2
    sound, control, faults = [], [], {f: [] for f in FAULTS}
    for i, seed in enumerate(args.seeds):
        t0 = time.monotonic()
        state = run.prepare(bench, args.workload, seed, devices)
        t1 = time.monotonic()
        w = run.window(state, args.seconds, False, None)
        nums = run.judge(state, w)
        ctrl = run.judge(state, w, served_override=functools.partial(
            state.mod.reference, state.cfg, state.params, input_bits=4))
        rec = {"seed": seed, "prepare_s": t1 - t0,
               "served": w.samples, "program": nums, "control": ctrl,
               "correct": all(c["value"] <= c["limit"]
                              for c in nums.values())}
        sound.append(nums["rows_off_share"]["value"])
        control.append(ctrl["rows_off_share"]["value"])
        if i < args.faults:
            for f in FAULTS:
                restore = break_pool(state.pool, f)
                fw = run.window(state, args.seconds, False, None)
                restore()
                rec[f] = run.judge(state, fw)
                faults[f].append(rec[f]["rows_off_share"]["value"])
        rec["seconds"] = time.monotonic() - t0
        print(json.dumps(rec), flush=True)
        del state, w
        gc.collect()
    print(json.dumps({"summary": args.workload, "seeds": args.seeds,
                      "lower": max(sound), "upper": min(control),
                      "sound": sound, "control": control,
                      "faults": faults}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
