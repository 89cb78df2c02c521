"""The comparison that decides ``correct``, at sizes a test run holds (no
chip): the lower-precision control fails it, and so does a run of the
harness whose timed path is broken underneath."""

import functools

import jax
import numpy as np
import pytest

from chipbench import check, control, run, spec

SMALL = {
    "kws": {"in_dim": 64, "hidden_size": 32},
    "cnv": {"channels": [8, 8, 16, 16, 16, 16], "fc": [32, 32]},
}
TRAFFIC = {"distinct_rows": 96, "check_rows": 64}
WAVE = {"singlestream": 8, "server": 8, "offline": 16}
#: an open-loop Poisson mix, so the generator's open loop is driven too;
#: no cell of BENCHMARK.json offers one yet
SERVER = {"loop": "open", "arrivals": "poisson", "rate_per_s": 400,
          "serving": {"engine": "async", "max_inflight": 2,
                      "max_wait_ms": 2.0, "replicas": 1}}


@pytest.fixture
def small(monkeypatch):
    """Shrink the widths and the mixes, and run the program's kernels in
    the Pallas interpreter on the CPU."""
    import repro.compile_cache
    import repro.deploy as deploy

    config, mix, limits = spec.config, spec.traffic, spec.limits

    def small_config(name):
        return dict(config(name), **SMALL[name])

    def small_traffic(name):
        t = dict(SERVER if name == "server" else mix(name), **TRAFFIC)
        t["serving"] = dict(t["serving"], wave=WAVE[name])
        if t["loop"] == "closed" and t["query_samples"] > 1:
            t["query_samples"] = 32
        return t

    def test_limits(workload):
        return limits("kws-singlestream" if workload == "kws-server"
                      else workload)

    monkeypatch.setattr(spec, "config", small_config)
    monkeypatch.setattr(spec, "traffic", small_traffic)
    monkeypatch.setattr(spec, "limits", test_limits)
    # the test process keeps JAX's default (no persistent cache)
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)
    monkeypatch.setattr(deploy, "compile_graph", functools.partial(
        deploy.compile_graph, use_pallas=True, interpret=True))


def _bench():
    """BENCHMARK.json's cells, plus a KWS cell of the open-loop ``SERVER``
    mix under ``kws-singlestream``'s limits."""
    bench = spec.load_benchmark()
    if all(w["name"] != "kws-server" for w in bench["workloads"]):
        bench["workloads"].append({"name": "kws-server", "config": "kws",
                                   "traffic": "server", "chips": 1})
    return bench


def _window(workload, seed=2 ** 31 + 7):
    bench = _bench()
    state = run.prepare(bench, workload, seed, jax.devices())
    return state, run.window(state, 0.3, False, None)


@pytest.mark.parametrize("workload", ["kws-singlestream", "cnv-offline"])
def test_program_passes_and_the_int4_control_fails(small, workload):
    state, w = _window(workload)
    sound = run.judge(state, w)
    assert check.passed(sound), sound
    control = run.judge(state, w, served_override=functools.partial(
        state.mod.reference, state.cfg, state.params, input_bits=4))
    assert not check.passed(control), control
    assert control["rows_off_share"]["value"] > \
        3 * max(sound["rows_off_share"]["value"], 1e-3)


@pytest.mark.parametrize("workload", ["kws-server", "cnv-offline"])
@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_a_broken_timed_path_reads_not_correct(small, monkeypatch,
                                               workload, fault):
    build = run.build_pool

    def broken(*a, **k):
        pool = build(*a, **k)
        control.break_pool(pool, fault)
        return pool

    monkeypatch.setattr(run, "build_pool", broken)
    out = run.run_cell(_bench(), workload, 11, 0.3, False, jax.devices())
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["rows_off_share"]["value"] > 0.3


def test_rows_off_share_counts_rows_past_the_tolerance():
    ref = np.array([[100.0, 1.0], [0.0, 0.0], [5.0, 5.0]])
    served = ref + np.array([[0.005, 0.0], [1e-5, 0.0], [0.0, 0.01]])
    # tolerances: 1e-4 * 101, 1e-4 * 1, 1e-4 * 6
    assert check.rows_off_share(served, ref) == pytest.approx(1 / 3)
    served[1, 0] = np.nan
    assert check.rows_off_share(served, ref) == pytest.approx(2 / 3)
