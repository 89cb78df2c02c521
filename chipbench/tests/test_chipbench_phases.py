"""The wave-phase reducers on synthesised spans, and the program's phases
in a CPU profiler trace read back by ``trace.load``/``timeline`` (no
chip)."""

import tempfile
import types

import numpy as np
import pytest

from chipbench import spec, trace as trace_mod
from chipbench.metrics._phases import per_wave_ms

PHASES = ("replica.put", "executor.pad", "executor.launch", "wave.wait",
          "router.fetch")
READS = {
    "stage_in_ms.stream": ("replica.put", "executor.pad"),
    "stage_in_ms.offline": ("replica.put", "executor.pad"),
    "launch_ms.stream": ("executor.launch",),
    "fetch_ms.stream": ("router.fetch",),
    "fetch_ms.offline": ("router.fetch",),
    "wait_ms.offline": ("wave.wait",),
}


def _span(name, t0_ms, t1_ms, wave=None):
    return types.SimpleNamespace(
        name=name, t0=t0_ms / 1e3, t1=t1_ms / 1e3,
        args=None if wave is None else {"wave": wave})


def _async_waves():
    """Three overlapping waves of an async engine, as a span window
    records them (children interleave in time across waves): wave k's
    put, pad, launch, wait and fetch last 1+k, 2+k, 3+k, 4+k, 5+k ms;
    wave 4 completed with no children; wave 9 failed (children, no
    wave span)."""
    spans = []
    for k, (w, t0) in enumerate(((1, 0.0), (2, 3.0), (3, 6.0))):
        t = t0
        for i, name in enumerate(PHASES[:3]):
            spans.append(_span(name, t, t + 1 + k + i, wave=w))
            t += 1 + k + i
        # wait and fetch after the next wave was submitted
        tw = t + 20.0
        spans.append(_span("wave.wait", tw, tw + 4 + k, wave=w))
        spans.append(_span("router.fetch", tw + 4 + k, tw + 9 + 2 * k,
                           wave=w))
        spans.append(_span("wave", t0, tw + 4 + k, wave=w))
        spans.append(_span("request", t0 - 1.0, tw + 4 + k))
    spans.append(_span("wave", 40.0, 41.0, wave=4))
    spans.append(_span("executor.pad", 50.0, 90.0, wave=9))
    spans.append(_span("wave.wait", 50.0, 90.0, wave=9))
    return sorted(spans, key=lambda s: s.t0)


def test_per_wave_sums_pair_children_by_wave_id():
    spans = _async_waves()
    assert sorted(per_wave_ms(spans, ("replica.put", "executor.pad"))) \
        == pytest.approx([3.0, 5.0, 7.0])
    assert sorted(per_wave_ms(spans, ("wave.wait",))) \
        == pytest.approx([4.0, 5.0, 6.0])
    assert sorted(per_wave_ms(spans, ("router.fetch",))) \
        == pytest.approx([5.0, 6.0, 7.0])


@pytest.mark.parametrize("metric", sorted(READS))
def test_phase_reducer_reads_the_median_wave(metric):
    expected = {"stage_in_ms.stream": 5.0, "stage_in_ms.offline": 5.0,
                "launch_ms.stream": 4.0, "fetch_ms.stream": 6.0,
                "fetch_ms.offline": 6.0, "wait_ms.offline": 5.0}
    ctx = types.SimpleNamespace(spans=_async_waves())
    assert spec.reducer(metric).reduce(ctx) == pytest.approx(
        expected[metric])


@pytest.mark.parametrize("metric", sorted(READS))
def test_phase_reducer_with_nothing_to_read_returns_none(metric):
    # no spans; waves without children; children without ids (a program
    # that records no parent links)
    bare = [_span("wave", 0.0, 3.0, wave=1), _span("wave", 1.0, 4.0)]
    unlinked = [_span(name, 0.0, 1.0) for name in PHASES]
    for spans in ([], bare, bare + unlinked):
        ctx = types.SimpleNamespace(spans=spans)
        assert spec.reducer(metric).reduce(ctx) is None


def test_every_new_metric_is_listed_with_its_cell():
    bench = spec.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for metric in READS:
        m = per_layer[metric]
        assert m["source"] == "program_span"
        cell = ("kws-singlestream" if metric.endswith(".stream")
                else "cnv-offline")
        assert m["workloads"] == [cell]


def test_a_cpu_profile_of_one_served_wave_shows_the_phases(tmp_path):
    """One real ``compile_graph`` wave through the router under the JAX
    profiler, inside a ``bench.window`` annotation: the host line that
    holds the window labels the wave's host time by the program's
    phases, and each ``wave`` annotation carries the id the tracer's
    ``wave`` span has."""
    import jax
    from jax.profiler import ProfileData

    from repro.core.qir import export_qmlp
    from repro.deploy import compile_graph
    from repro.models.tiny import KWSMLP
    from repro.obs import Tracer
    from repro.serve import ReplicaPool, Router, RouterConfig

    model = KWSMLP(width=16)
    params = model.init(jax.random.PRNGKey(0))
    hidden_defs, _ = model.layers()
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"])
    cm = compile_graph(graph, in_scale=1.0 / 127.0, use_pallas=False)
    pool = ReplicaPool(cm, devices=[jax.devices("cpu")[0]])
    rows = np.random.default_rng(0).integers(
        -127, 128, (4, 490)).astype(np.int32)
    jax.block_until_ready(cm.submit_wave(rows, micro_batch=4)[0])
    tracer = Tracer()
    router = Router({"m": pool}, RouterConfig(micro_batch=4),
                    tracer=tracer)

    prof_dir = tempfile.mkdtemp(dir=tmp_path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(prof_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        reqs = [router.submit("m", r) for r in rows]
    jax.profiler.stop_trace()
    assert all(r.result is not None for r in reqs)

    path = trace_mod.find_xplane(prof_dir)
    _, host = trace_mod.load(path)
    lo, hi = trace_mod.window_of(host)
    labels = {name for _, _, name in trace_mod.timeline(host, lo, hi)}
    assert labels >= set(PHASES) | {"wave"}, labels

    (wave_span,) = tracer.spans(name="wave")
    ids = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "wave":
                    ids |= {v for k, v in e.stats if k == "wave_id"}
    assert ids == {wave_span.args["wave"]}
