"""Reduce a profiler trace to device metrics: busy and idle time, time per
device operation and per kernel, and the idle gaps labelled by what the
host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per device operation, named by its HLO instruction
(``%conv_threshold.13 = s32[...] custom-call(...), custom_call_target=
"tpu_custom_call"`` for a Pallas kernel). Host threads are lines of the
plane ``/host:CPU``; the harness's ``jax.profiler.TraceAnnotation`` spans
(``bench.*``) sit on the thread that drives the window. Device and host
events share one clock.

``load`` turns a file into plain tuples and ``reduce`` does the arithmetic,
so the reduction is tested on synthesised events without a chip.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: (start_ns, end_ns, name)
Event = Tuple[float, float, str]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"^%([^\s=]+) = ")
_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event (``conv_threshold.13``);
    the event name itself where it is not an HLO instruction."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name[:80]


def kernel_name(event_name: str) -> Optional[str]:
    """The kernel a device op event runs, as the trace names it today: the
    instruction name of a Pallas custom call without its numeric suffix
    (``conv_threshold``, ``mlp_megakernel``); None for other ops."""
    if not _KERNEL.search(event_name):
        return None
    return re.sub(r"\.\d+$", "", op_name(event_name))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Tuple[Dict[int, List[Event]], List[Event]]:
    """(device ops per chip, events of the host thread holding the window
    span) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                if any(name == WINDOW_SPAN for _, _, name in evs):
                    host = evs
    return chips, host


def merge(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window_of(host: Sequence[Event]) -> Tuple[float, float]:
    spans = [(a, b) for a, b, name in host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, "
                         f"found {len(spans)}")
    return spans[0]


def timeline(host: Sequence[Event], lo: float, hi: float
             ) -> List[Event]:
    """What the host thread was doing over [lo, hi]: disjoint segments,
    each labelled by the innermost event running in it ("none" where no
    event runs). Events of one thread nest, so a stack sweep finds the
    innermost one."""
    evs = sorted((a, -b, n) for a, b, n in host if b > lo and a < hi)
    segs: List[Event] = []
    stack: List[Tuple[float, str]] = []
    t = lo

    def emit(upto):
        nonlocal t
        if upto > t:
            segs.append((t, upto, stack[-1][1] if stack else "none"))
            t = upto

    for a, neg_b, name in evs:
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(max(a, lo))
        stack.append((min(-neg_b, hi), name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return segs


def _idle_by_label(busy: List[Tuple[float, float]], segs: List[Event],
                   lo: float, hi: float) -> Dict[str, float]:
    """Seconds of device idle time in [lo, hi] under each host label."""
    gaps, edge = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    out: Dict[str, float] = collections.defaultdict(float)
    i = 0
    for ga, gb in gaps:
        while i < len(segs) and segs[i][1] <= ga:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < gb:
            a, b = max(segs[j][0], ga), min(segs[j][1], gb)
            if b > a:
                out[segs[j][2]] += (b - a) / 1e9
            j += 1
    return out


def reduce(chips: Dict[int, List[Event]], host: Sequence[Event],
           top: int = 10) -> Dict:
    """Device metrics over the window span.

    ``busy_s`` is the union of device-op intervals inside the window,
    averaged over the chips; ``idle_share`` is 1 - busy / window.
    ``kernel_s`` sums device time per kernel and ``op_s`` per device op
    over all chips; ``kernel_calls`` counts kernel events. ``idle_gaps``
    sums the idle time of chip 0 (the lowest-numbered chip) by the
    innermost host event running at each gap's midpoint.
    """
    lo, hi = window_of(host)
    window_ns = hi - lo
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    busy = {}
    kernel_s: Dict[str, float] = collections.defaultdict(float)
    kernel_calls: Dict[str, int] = collections.defaultdict(int)
    op_s: Dict[str, float] = collections.defaultdict(float)
    for chip, evs in chips.items():
        inside = [(max(a, lo), min(b, hi), n) for a, b, n in evs
                  if b > lo and a < hi]
        busy[chip] = sum(b - a for a, b in
                         merge([(a, b) for a, b, _ in inside], lo, hi))
        for a, b, n in inside:
            op_s[op_name(n)] += (b - a) / 1e9
            k = kernel_name(n)
            if k is not None:
                kernel_s[k] += (b - a) / 1e9
                kernel_calls[k] += 1
    first = min(chips)
    gaps = _idle_by_label(merge([(a, b) for a, b, _ in chips[first]], lo, hi),
                          timeline(host, lo, hi), lo, hi)
    busy_s = sum(busy.values()) / len(busy) / 1e9
    window_s = window_ns / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_chip": {c: v / 1e9 for c, v in sorted(busy.items())},
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": dict(kernel_s),
        "kernel_calls": dict(kernel_calls),
        "op_s": dict(op_s),
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                op_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, s] for n, s in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]],
        },
    }
