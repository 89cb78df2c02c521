"""A wave's input crosses to the device once.

``Replica.submit`` hands the router's stacked host rows to the model;
``CompiledTinyModel.submit_wave`` pads them on the host (a full wave of
valid rows is put as it is) and places the ``(1, micro_batch, ...)``
wave on its device with one explicit ``device_put``. Nothing pulls the
rows back (``executor.pad`` pads, ``replica.put`` is the one put), and
no implicit host-to-device transfer happens on the way: the waves below
run under ``jax.transfer_guard_host_to_device("disallow")``.
``ReplicaPool`` makes each replica's device and its model's agree, since
the model decides where a wave lands.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.deploy import compile_graph
from repro.obs import Tracer
from repro.serve import ReplicaPool

from tests.test_serve import MODELS, _assert_rows_equal, _load

MB = 8
GARBAGE = 99                     # what an invalid row carries in


@pytest.fixture(scope="module")
def kws_small():
    from repro.core.qir import export_qmlp
    from repro.models.tiny import KWSMLP

    model = KWSMLP(width=16)
    params = model.init(jax.random.PRNGKey(0))
    hidden_defs, _ = model.layers()
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"])
    return lambda: compile_graph(graph, in_scale=1.0 / 127.0,
                                 use_pallas=False)


@pytest.fixture
def put_spy(monkeypatch):
    """Every ``jax.device_put`` call's host input and target device."""
    calls = []
    real = jax.device_put

    def spy(x, device=None, *a, **kw):
        calls.append((np.array(x), device))
        return real(x, device, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    return calls


def _wave(fill, invalid, seed=0):
    """``fill`` rows of codes; with ``invalid``, every even row is invalid
    and carries ``GARBAGE``."""
    x = np.random.default_rng(seed).integers(-127, 128, (fill, 490)) \
        .astype(np.int32)
    if not invalid:
        return x, None
    valid = np.arange(fill) % 2 == 1
    x[~valid] = GARBAGE
    return x, valid


@pytest.mark.parametrize("invalid", [False, True], ids=["valid", "holes"])
@pytest.mark.parametrize("fill", [1, MB // 2, MB])
def test_one_device_put_per_wave(kws_small, put_spy, fill, invalid):
    cm = kws_small()
    dev = jax.devices("cpu")[0]
    replica = ReplicaPool(cm, devices=[dev]).replicas[0]
    x, valid = _wave(fill, invalid)
    replica.submit(x, valid=valid, micro_batch=MB).wait()   # compile
    put_spy.clear()
    with jax.transfer_guard_host_to_device("disallow"):
        y, mask = replica.submit(x, valid=valid, micro_batch=MB).wait()
    assert len(put_spy) == 1
    staged, where = put_spy[0]
    assert staged.shape == (1, MB, 490) and where == dev
    assert y.devices() == {dev}
    want = np.ones(fill, bool) if valid is None else valid
    assert mask.tolist() == want.tolist() + [False] * (MB - fill)
    # invalid and padded rows reach the device as zero codes
    assert not staged[0][~mask].any()
    np.testing.assert_array_equal(staged[0][mask], x[want])
    _assert_rows_equal(np.asarray(y)[mask],
                       np.asarray(cm.offline(jnp.asarray(x[want]))),
                       f"fill {fill}")


@pytest.mark.parametrize("name", MODELS)
def test_full_wave_with_holes_is_bit_exact(name):
    """A full wave whose invalid rows carry garbage: the valid rows match
    ``offline`` and the garbage never reaches the device."""
    graph, x = _load(name)
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False)
    n = min(4, x.shape[0])
    x = np.asarray(x[:n]).copy()
    y_off = np.asarray(cm.offline(jnp.asarray(x)))
    y, mask = cm.submit_wave(x, micro_batch=n)
    _assert_rows_equal(np.asarray(y)[mask], y_off, f"{name} full wave")
    valid = np.arange(n) % 2 == 1
    x[~valid] = GARBAGE
    y, mask = cm.submit_wave(x, valid=valid, micro_batch=n)
    assert mask.tolist() == valid.tolist()
    _assert_rows_equal(np.asarray(y)[mask], y_off[valid],
                       f"{name} wave with holes")
    zeros = np.zeros_like(x[:1])
    _assert_rows_equal(np.asarray(y)[~mask][:1],
                       np.asarray(cm.offline(jnp.asarray(zeros))),
                       f"{name} invalid row ran as zero codes")


@pytest.mark.parametrize("arrives", ["host", "device"])
@pytest.mark.parametrize("fill,invalid", [(MB, False), (MB // 2, True)],
                         ids=["full", "partial-holes"])
def test_pad_span_reads_the_staging(kws_small, fill, invalid, arrives):
    cm = kws_small()
    tracer = Tracer()
    cm.set_tracer(tracer)
    x, valid = _wave(fill, invalid)
    rows = jnp.asarray(x) if arrives == "device" else x
    y, mask = cm.submit_wave(rows, valid=valid, micro_batch=MB)
    (pad,) = tracer.spans(name="executor.pad")
    (put,) = tracer.spans(name="replica.put")
    assert pad.t1 <= put.t0
    assert pad.args == {"rows": int(mask.sum()), "pad_rows": MB - fill,
                        "put_bytes": MB * 490 * 4,
                        "pulled_back": arrives == "device"}
    # the device array is still served right, pull-back and all
    _assert_rows_equal(np.asarray(y)[mask],
                       np.asarray(cm.offline(jnp.asarray(x[mask[:fill]]))),
                       "pad span wave")


def test_pool_adopts_the_replica_device_for_an_unpinned_model(kws_small):
    cm = kws_small()
    assert cm.device is None
    dev = jax.devices("cpu")[0]
    ReplicaPool(cm, devices=[dev])
    assert cm.device == dev
    # a model pinned to the replica's own device is kept as it is
    pool = ReplicaPool(cm, devices=[dev])
    assert pool.replicas[0].model.device == dev


class _Pinned:
    """A model that places its own waves, pinned to another device."""

    default_micro_batch = 4
    device = "another-device"

    def submit_wave(self, x, valid=None, micro_batch=None):
        raise AssertionError("never served")


def test_pool_refuses_a_model_pinned_elsewhere():
    with pytest.raises(ValueError, match="pinned"):
        ReplicaPool(_Pinned(), devices=[jax.devices("cpu")[0]])
    # a replica without a device leaves the model's placement alone
    pool = ReplicaPool(_Pinned(), devices=[None])
    assert pool.replicas[0].model.device == "another-device"
