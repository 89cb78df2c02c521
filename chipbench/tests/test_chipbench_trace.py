"""The trace reduction on synthesised profiler events (no chip)."""

import pytest

from chipbench import trace

MS = 1e6  # ns

KERNEL = ('%conv_threshold.13 = s32[256,36,28,64] custom-call(s32[256]), '
          'custom_call_target="tpu_custom_call"')
MEGA = ('%mlp_megakernel.1 = s32[32,256] custom-call(bf16[32,490]), '
        'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.11 = f32[1,16,12] fusion(f32[16,12] %fusion)"


def host(window=(0, 100 * MS), extra=()):
    return [(window[0], window[1], "bench.window"), *extra]


def test_idle_share_is_the_interval_union_over_the_window():
    # overlapping ops count once; an op straddling the window edge is
    # clipped to it
    ops = [(-5 * MS, 5 * MS, FUSION), (10 * MS, 30 * MS, KERNEL),
           (20 * MS, 40 * MS, FUSION), (95 * MS, 120 * MS, MEGA)]
    r = trace.reduce({0: ops}, host())
    busy = 5 + 30 + 5
    assert r["busy_s"] == pytest.approx(busy / 1e3)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_share"] == pytest.approx(1 - busy / 100)


def test_busy_time_is_averaged_over_chips():
    r = trace.reduce({0: [(0, 50 * MS, FUSION)], 1: [(0, 10 * MS, FUSION)]},
                     host())
    assert r["busy_s_per_chip"] == {0: pytest.approx(0.05),
                                    1: pytest.approx(0.01)}
    assert r["idle_share"] == pytest.approx(1 - 0.03 / 0.1)


def test_kernel_time_sums_pallas_custom_calls_by_name():
    ops = [(0, 2 * MS, KERNEL), (3 * MS, 4 * MS, KERNEL.replace(
        "conv_threshold.13", "conv_threshold.14")),
        (5 * MS, 8 * MS, MEGA), (9 * MS, 10 * MS, FUSION)]
    r = trace.reduce({0: ops}, host())
    assert r["kernel_s"] == {"conv_threshold": pytest.approx(0.003),
                             "mlp_megakernel": pytest.approx(0.003)}
    assert r["kernel_calls"] == {"conv_threshold": 2, "mlp_megakernel": 1}
    assert trace.kernel_name(FUSION) is None
    assert trace.op_name(KERNEL) == "conv_threshold.13"


def test_breakdown_lists_top_ops_and_idle_gaps_by_host_activity():
    ops = [(10 * MS, 20 * MS, KERNEL), (60 * MS, 65 * MS, FUSION)]
    extra = [(0, 30 * MS, "bench.step"), (30 * MS, 100 * MS, "bench.reap"),
             (40 * MS, 50 * MS, "DevicePut")]
    r = trace.reduce({0: ops}, host(extra=extra))
    ops_ = dict(r["breakdown"]["device_ops"])
    assert ops_ == {"conv_threshold.13": pytest.approx(0.01),
                    "fusion.11": pytest.approx(0.005)}
    gaps = dict(r["breakdown"]["idle_gaps"])
    # idle [0,10) and [20,30) under step, [30,40) [50,60) [65,100) under
    # reap, [40,50) under DevicePut, the innermost event there
    assert gaps == {"bench.step": pytest.approx(0.02),
                    "DevicePut": pytest.approx(0.01),
                    "bench.reap": pytest.approx(0.055)}
    assert [n for n, _ in r["breakdown"]["idle_gaps"]][0] == "bench.reap"


def test_timeline_labels_each_instant_by_the_innermost_event():
    host = [(0, 100, "bench.window"), (10, 50, "bench.step"),
            (20, 30, "DevicePut"), (60, 120, "bench.reap")]
    assert trace.timeline(host, 0, 100) == [
        (0, 10, "bench.window"), (10, 20, "bench.step"),
        (20, 30, "DevicePut"), (30, 50, "bench.step"),
        (50, 60, "bench.window"), (60, 100, "bench.reap")]


def test_breakdown_keeps_at_most_ten_entries():
    ops = [(i * MS, i * MS + 0.5 * MS, f"%op{i} = f32[] add()")
           for i in range(30)]
    r = trace.reduce({0: ops}, host())
    assert len(r["breakdown"]["device_ops"]) == 10


def test_a_trace_without_the_window_span_or_a_chip_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({0: []}, [(0, 1, "bench.step")])
    with pytest.raises(ValueError):
        trace.reduce({}, host())


def test_merge_clips_and_joins():
    assert trace.merge([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 10) == [
        (1, 4), (5, 8), (9, 10)]
