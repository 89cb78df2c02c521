"""The ``resnet8-offline`` cell's files at sizes a test run holds (no chip):
its plain reference stands alone, the program passes the comparison that
decides ``correct`` while the int4 control and the planted faults fail it,
and its two new per-layer reducers read what the program records."""

import functools
import json
import os
import subprocess
import sys
import types

import jax
import pytest

from chipbench import check, control, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "resnet8-offline"
#: a ResNet-8 of 8x8 inputs and widths 4/8/16 (its parameter counts as
#: ``build_graph`` checks them)
SMALL = {"in_hw": 8, "filters": [4, 8, 16], "n_params": 5370,
         "n_weights": 5036}


@pytest.fixture
def small(monkeypatch):
    """Shrink the widths and the mix, and run the program's kernels in the
    Pallas interpreter on the CPU."""
    import repro.compile_cache
    import repro.deploy as deploy

    config, mix = spec.config, spec.traffic

    def small_traffic(name):
        t = dict(mix(name), distinct_rows=48, check_rows=32,
                 query_samples=32)
        t["serving"] = dict(t["serving"], wave=16)
        return t

    monkeypatch.setattr(spec, "config",
                        lambda name: dict(config(name), **SMALL))
    monkeypatch.setattr(spec, "traffic", small_traffic)
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)
    monkeypatch.setattr(deploy, "compile_graph", functools.partial(
        deploy.compile_graph, use_pallas=True, interpret=True))


def test_the_small_counts_are_the_models():
    from repro.models.tiny import ResNet8

    m = ResNet8(filters=tuple(SMALL["filters"]), in_hw=SMALL["in_hw"])
    assert (m.n_params(), m.n_weights()) == (SMALL["n_params"],
                                             SMALL["n_weights"])


def test_the_reference_imports_nothing_of_the_program():
    """In a fresh interpreter with only the harness on the path, the
    reference runs on NumPy weights and loads no module of ``repro``."""
    code = f"""
import sys, json
import numpy as np
sys.path.insert(0, {ROOT!r})
from chipbench import spec
cfg = dict(spec.config("resnet8"), **{json.dumps(SMALL)})
mod = spec.config_module("resnet8")
rng = np.random.default_rng(0)
def conv(k, cin, cout):
    return {{"w": rng.uniform(-0.01, 0.05, (k, k, cin, cout)),
            "b": 0.1 * rng.standard_normal(cout)}}
f = cfg["filters"]
params = {{"stem": conv(3, 3, f[0]), "blocks": [
    {{"conv1": conv(3, f[0], f[0]), "conv2": conv(3, f[0], f[0])}},
    {{"conv1": conv(3, f[0], f[1]), "conv2": conv(3, f[1], f[1]),
     "proj": conv(1, f[0], f[1])}},
    {{"conv1": conv(3, f[1], f[2]), "conv2": conv(3, f[2], f[2]),
     "proj": conv(1, f[1], f[2])}}],
    "head": {{"w": rng.standard_normal((f[2], 10)), "b": np.zeros(10)}}}}
x = rng.integers(-127, 128, (3, 8, 8, 3))
y = mod.reference(cfg, params, x)
assert y.shape == (3, 10) and np.all(np.isfinite(y))
assert not [m for m in sys.modules if m.split(".")[0] == "repro"]
print("ok")
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_program_passes_and_the_int4_control_fails(small):
    state = run.prepare(spec.load_benchmark(), CELL, 2 ** 31 + 15,
                        jax.devices())
    assert state.pool.replicas[0].model.schedule.n_join == 3
    w = run.window(state, 0.3, False, None)
    sound = run.judge(state, w)
    assert check.passed(sound), sound
    ctrl = run.judge(state, w, served_override=functools.partial(
        state.mod.reference, state.cfg, state.params, input_bits=4))
    assert not check.passed(ctrl), ctrl
    assert ctrl["rows_off_share"]["value"] > 0.5


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_a_broken_timed_path_reads_not_correct(small, monkeypatch, fault):
    build = run.build_pool

    def broken(*a, **k):
        pool = build(*a, **k)
        control.break_pool(pool, fault)
        return pool

    monkeypatch.setattr(run, "build_pool", broken)
    out = run.run_cell(spec.load_benchmark(), CELL, 11, 0.3, False,
                       jax.devices())
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["rows_off_share"]["value"] > 0.3


def _span(name, wave=None, **args):
    if wave is not None:
        args["wave"] = wave
    return types.SimpleNamespace(name=name, t0=0.0, t1=1.0,
                                 args=args or None)


def test_host_segments_reads_the_median_launch_by_wave_id():
    spans = [_span("wave", wave=w) for w in (1, 2, 3)]
    spans += [_span("executor.launch", wave=1, host_segments=0),
              _span("executor.launch", wave=2, host_segments=2),
              _span("executor.launch", wave=3, host_segments=0),
              _span("executor.launch", wave=9, host_segments=5)]
    ctx = types.SimpleNamespace(spans=spans)
    assert spec.reducer("host_segments.offline").reduce(ctx) == 0


def test_host_segments_with_nothing_to_read_returns_none():
    """A program whose launch span carries no ``host_segments`` (the parent
    of this cell's PR) gives nothing, and the line leaves the metric out."""
    for spans in ([], [_span("wave", wave=1),
                       _span("executor.launch", wave=1)]):
        ctx = types.SimpleNamespace(spans=spans)
        assert spec.reducer("host_segments.offline").reduce(ctx) is None


def test_the_join_roofline_reads_only_the_join_kernel():
    """``conv_threshold_add_roofline`` sums the join layers of the
    configuration over the join kernel's device time; the conv kernel's
    time and layers are the other reducer's. Both stay under 100% at the
    chip's peaks for any time at least the least one."""
    from chipbench import cost, peaks

    cfg = spec.config("resnet8")
    layers = spec.config_module("resnet8").layers(cfg)
    pk = peaks.peaks("TPU v5 lite")
    joins = [x for x in layers if x["kernel"] == "conv_threshold_add"]
    least = cost.roofline_s(joins, 256, 1, pk["int8_ops"],
                            pk["hbm_bytes_per_s"])["least_s"]
    ctx = types.SimpleNamespace(
        layers=layers, peaks=pk,
        device={"samples": 256,
                "kernel_s": {"conv_threshold_add": 10 * least,
                             "conv_threshold": 1.0},
                "kernel_calls": {"conv_threshold_add": 3,
                                 "conv_threshold": 4}})
    got = spec.reducer("conv_threshold_add_roofline").reduce(ctx)
    assert got == pytest.approx(10.0)
    assert [x["name"] for x in joins] == ["b1_conv2", "b2_conv2",
                                          "b3_conv2"]


def test_the_cell_is_declared_with_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = spec.workload(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "resnet8", "offline", 1)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("conv_threshold_add_roofline", "host_segments.offline"):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "samples_per_s"
    for name in ("device_idle_share.offline", "conv_threshold_roofline",
                 "mfu.offline"):
        assert per_layer[name]["workloads"] == ["cnv-offline", CELL]
