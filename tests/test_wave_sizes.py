"""Wave size is a schedule, never a result; the SLO service model builds
from the executor alone.

``streaming_compiled`` and ``submit_wave`` run the same segment programs at
any micro-batch: at every wave size from 1 to 64, and for a wave of one
row, half full and full, they must give ``offline``'s outputs. The models
are the benchmark's KWS at its published widths and ResNet-8 at its
published channels on 8x8 inputs, on the XLA path.

``ServiceModel.from_compiled`` and ``measure_wave_service_s`` probe with the
executor's own zero batch (``CompiledTinyModel.zero_batch``) on every golden
model and on ResNet-8.
"""

import functools
import os

import numpy as np
import pytest

from repro.core.qir import Graph
from repro.deploy import compile_graph
from repro.serve import ServiceModel, measure_wave_service_s
from test_kernel_blocking import config_graph

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
WAVE_SIZES = (1, 2, 4, 8, 16, 32, 64)

#: (configuration, input size or None for the published one, input shape)
MODELS = {"kws": (None, (490,)), "resnet8": (8, (8, 8, 3))}


@functools.lru_cache(maxsize=None)
def _served(name):
    """(executor, input codes, offline outputs) of a benchmark model."""
    in_hw, shape = MODELS[name]
    graph, in_scale = config_graph(name, in_hw)
    cm = compile_graph(graph, in_scale=in_scale, use_pallas=False)
    x = np.random.default_rng(19).integers(-127, 128, (70,) + shape
                                            ).astype(np.int32)
    return cm, x, np.asarray(cm.offline(x))


@pytest.mark.parametrize("mb", WAVE_SIZES)
@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_wave_size_matches_offline(name, mb):
    """``streaming_compiled`` over 70 rows (a ragged last micro-batch) and
    ``submit_wave`` filled with 1, mb/2 and mb rows equal ``offline``."""
    cm, x, want = _served(name)
    y, stats = cm.streaming_compiled(x, micro_batch=mb)
    assert stats.micro_batch == mb and stats.n_micro == -(-70 // mb)
    np.testing.assert_array_equal(np.asarray(y), want,
                                  err_msg=f"{name} streaming mb={mb}")
    for fill in sorted({1, max(mb // 2, 1), mb}):
        y, mask = cm.submit_wave(x[:fill], micro_batch=mb)
        assert int(mask.sum()) == fill and mask.shape == (mb,)
        np.testing.assert_array_equal(
            np.asarray(y)[mask], want[:fill],
            err_msg=f"{name} wave of {fill}/{mb}")


def _golden_model(name):
    graph = Graph.load(os.path.join(GOLDEN_DIR, f"{name}.qir.json"))
    return compile_graph(graph, in_scale=graph.meta["in_scale"],
                         use_pallas=False)


@pytest.mark.parametrize("name", ["kws", "ad", "ic", "cnv", "resnet8"])
def test_service_model_builds_from_the_executor(name):
    """The service model calibrates from the executor's zero batch, and
    one measured wave pins it."""
    cm = _golden_model(name) if name != "resnet8" else _served(name)[0]
    zero = cm.zero_batch(3)
    s0 = cm.schedule.stages[0]
    assert zero.shape[0] == 3 and zero.size == 3 * s0.in_dim
    assert not np.asarray(zero).any()
    model = ServiceModel.from_compiled(cm, probe_batch=4)
    assert model.calibration["probe_batch"] == 4
    assert [w[0] for w in model.works] == [s.name for s in
                                           cm.schedule.stages]
    assert model.sec_per_cycle > 0
    mb = cm.default_micro_batch
    measured = measure_wave_service_s(cm, mb, iters=2)
    pinned = model.recalibrated(measured, mb)
    assert pinned.wave_service_s(mb) == pytest.approx(measured)
