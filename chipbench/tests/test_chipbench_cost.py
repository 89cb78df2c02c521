"""Operation and byte counts, peaks, and the per-layer reducers (no chip)."""

import types

import numpy as np
import pytest

from chipbench import cost, peaks, spec
from chipbench.metrics._common import router_waits_ms


def _layers(name):
    return spec.config_module(name).layers(spec.config(name))


def test_op_counts_reproduce_model_cost_flops():
    from repro.models.tiny import CNVModel, KWSMLP

    assert cost.ops_per_sample(_layers("kws")) == 519_168 \
        == KWSMLP().cost().flops
    assert cost.ops_per_sample(_layers("cnv")) == 118_922_752 \
        == CNVModel().cost().flops


def test_weight_counts_match_the_published_ones():
    for name, n in (("kws", 259_584), ("cnv", 1_542_848)):
        assert sum(layer["weights"] for layer in _layers(name)) \
            == spec.config(name)["n_weights"] == n


def test_conv_roofline_counts_bits_at_their_defined_widths():
    layers = _layers("cnv")
    convs = [layer for layer in layers if layer["kernel"] == "conv_threshold"]
    assert [c["name"] for c in convs] == [f"conv{i}" for i in range(6)]
    first = convs[0]
    # 8-bit input codes in, 1-bit codes out, 1-bit weights
    assert first["act_bytes"] == (32 * 32 * 3 * 8 + 30 * 30 * 64) / 8
    assert first["weight_bytes"] == 9 * 3 * 64 / 8
    # output sizes 30, 28, 12, 10, 3, 1 (pools after convs 1 and 3)
    assert [c["ops"] // (2 * c["weights"]) for c in convs] == [
        900, 784, 144, 100, 9, 1]
    r = cost.roofline_s(convs, samples=256, calls=1, peak_ops=393e12,
                        peak_bytes_per_s=819e9)
    ops = 256 * cost.ops_per_sample(convs)
    assert r["ops"] == ops
    assert r["bound"] == "compute"
    assert r["least_s"] == pytest.approx(ops / 393e12)
    assert r["bytes"] == 256 * sum(c["act_bytes"] for c in convs) \
        + sum(c["weight_bytes"] for c in convs)


def test_peaks_know_v5e_and_refuse_an_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def _router_spans():
    """A SyncEngine router on a manual clock: a lone request waits out the
    2 ms deadline, then a full wave of 4 leaves at once; every wave takes
    3 ms of scripted service."""
    from repro.obs import Tracer
    from repro.serve import Router, RouterConfig, SyncEngine
    from repro.serve.clock import ManualClock
    from repro.serve.sim import ScriptedWaveModel

    clock = ManualClock()
    tracer = Tracer(clock=clock)
    model = ScriptedWaveModel(clock, service_s=0.003, micro_batch=4)
    router = Router({"m": model}, RouterConfig(micro_batch=4,
                                               max_wait_ms=2.0),
                    clock=clock, tracer=tracer, engine=SyncEngine())
    router.submit("m", np.ones(3))                 # t = 0
    clock.advance(0.002)
    router.step()                                  # deadline flush
    clock.advance(0.005)                           # t = 0.010
    for _ in range(4):
        router.submit("m", np.ones(3))             # full wave, no wait
    return tracer.spans()


def test_router_wait_and_wave_ms_reduce_from_router_spans():
    spans = _router_spans()
    ctx = types.SimpleNamespace(spans=spans)
    waits = sorted(router_waits_ms(spans))
    assert waits == pytest.approx([0, 0, 0, 0, 2.0])
    assert spec.reducer("router_wait_ms.stream").reduce(ctx) == 0.0
    assert spec.reducer("wave_ms.stream").reduce(ctx) == pytest.approx(3.0)


def test_idle_share_paces_profiled_busy_time_at_the_measured_rate():
    # the profiled window served 100 samples with 0.5 s of device time;
    # the measured window serves 400 in 4 s: 2 s busy, half the window
    ctx = types.SimpleNamespace(samples=400, window_s=4.0,
                                device={"busy_s": 0.5, "samples": 100})
    for name in ("device_idle_share.offline", "device_idle_share.stream"):
        assert spec.reducer(name).reduce(ctx) == pytest.approx(50.0)


def test_reducers_with_nothing_to_read_return_none():
    ctx = types.SimpleNamespace(spans=[], device=None, peaks=None,
                                cfg=spec.config("kws"),
                                layers=_layers("kws"), samples=0,
                                window_s=1.0, chips=1)
    for name in ("router_wait_ms.stream", "wave_ms.stream",
                 "device_idle_share.stream", "conv_threshold_roofline",
                 "mfu.offline"):
        assert spec.reducer(name).reduce(ctx) is None, name
