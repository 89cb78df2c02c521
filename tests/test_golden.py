"""Golden-file regression: compiled-path bit-exactness can't silently drift.

``tests/golden/`` holds frozen QIR exports + expected per-stage outputs for
small instances of all four Table-1 model families (see
``tests/golden/generate.py``). Everything here recompiles the *frozen*
graph — no RNG, no training — so any change to the streamliner, the
lowering, or the executors that perturbs a single integer fails loudly:

  * every integer stage output must match the fixture bit for bit, under
    BOTH conv lowerings (direct fused kernel and im2col fallback);
  * the conv models must also reproduce the live unfused ``Graph.run``
    interpreter exactly (the po2 export contract, ties included);
  * the streaming (FIFO-pipelined) executor must equal offline;
  * the Pallas kernel path (interpret mode on CPU) must produce the same
    integers.
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.qir import Graph
from repro.deploy import FusedConvThresholdStage, compile_graph

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
MODELS = ("kws", "ad", "ic", "cnv")


def _load(name):
    graph = Graph.load(os.path.join(GOLDEN_DIR, f"{name}.qir.json"))
    data = np.load(os.path.join(GOLDEN_DIR, f"{name}.golden.npz"))
    stages = [data[k] for k in sorted(data.files) if k.startswith("stage_")]
    return graph, data["x"], stages


def _assert_stage_match(got, want, label):
    got = np.asarray(got)
    if np.issubdtype(want.dtype, np.integer):
        np.testing.assert_array_equal(got, want, err_msg=label)
    else:
        # float head logits: affine of exact integers; allow fp assoc drift
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=label)


@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize("lowering", ["direct", "im2col"])
def test_golden_stage_outputs_bit_exact(name, lowering):
    graph, x, want_stages = _load(name)
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False, conv_lowering=lowering)
    outs = cm.stage_outputs(jnp.asarray(x))
    assert len(outs) == len(want_stages)
    for i, (got, want) in enumerate(zip(outs, want_stages)):
        _assert_stage_match(got, want, f"{name}[{lowering}] stage {i}")


@pytest.mark.parametrize("name", ("ic", "cnv"))
def test_golden_conv_models_match_live_graph_run(name):
    """The frozen conv exports still reproduce the unfused per-node
    interpreter bit for bit — the compiled path and Graph.run can't drift
    apart without this failing."""
    graph, x, want_stages = _load(name)
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False, conv_lowering="direct")
    quant_outs = [n.outputs[0] for n in graph.nodes if n.op == "Quant"]
    probe = Graph(nodes=graph.nodes, initializers=graph.initializers,
                  inputs=graph.inputs,
                  outputs=list(graph.outputs) + quant_outs,
                  meta=graph.meta)
    run = probe.run(
        {"x": np.asarray(x, np.float32) * graph.meta["in_scale"]})
    k = 0
    for s, want in zip(cm.schedule.stages, want_stages):
        if isinstance(s, FusedConvThresholdStage):
            np.testing.assert_array_equal(
                want.reshape(run[quant_outs[k]].shape) * s.stage.out_scale,
                run[quant_outs[k]])
            k += 1
    np.testing.assert_allclose(want_stages[-1], run["logits"],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ("kws", "ad"))
def test_golden_mlps_bit_exact_vs_streamlined_float_reference(name):
    """MLP exports carry float weights (quantized at lowering), so their
    exactness oracle is the streamlined float reference chain
    (``core.streamline.float_ref_dense``, half-up semantics) rebuilt from
    the frozen initializers: every integer stage must match it bit for
    bit, and the head logits to float tolerance."""
    from repro.core.streamline import float_ref_dense

    graph, x, want_stages = _load(name)
    init = graph.initializers
    h = jnp.asarray(x, jnp.float32) * graph.meta["in_scale"]
    denses = [n for n in graph.nodes if n.op == "Dense" and n.name != "head"]
    quants = [n for n in graph.nodes if n.op == "Quant"]
    assert len(denses) == len(quants) == len(want_stages) - 1
    for i, (dn, qn) in enumerate(zip(denses, quants)):
        params = {"w": jnp.asarray(init[f"w{i}"]),
                  "b": jnp.asarray(init[f"b{i}"])}
        if f"gamma{i}" in init:
            params.update({k: jnp.asarray(init[f"{k}{i}"])
                           for k in ("gamma", "beta", "mu", "sigma2")})
        s_out = float(qn.attrs["scale"])
        h_int = float_ref_dense(params, h,
                                weight_bits=dn.attrs["weight_bits"],
                                act_bits=qn.quant.bits, s_out=s_out)
        np.testing.assert_array_equal(np.asarray(h_int), want_stages[i],
                                      err_msg=f"{name} stage {i}")
        h = h_int.astype(jnp.float32) * s_out
    logits = (np.asarray(h) @ init["w_head"] + init["b_head"])
    np.testing.assert_allclose(want_stages[-1], logits,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", MODELS)
def test_golden_streaming_matches_frozen_offline(name):
    graph, x, want_stages = _load(name)
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False)
    y_str, stats = cm.streaming(jnp.asarray(x), micro_batch=2)
    _assert_stage_match(y_str, want_stages[-1], f"{name} streaming")
    assert len(stats.fifo_depths) == len(cm.schedule.stages) + 1


def test_golden_ic_pallas_kernel_path_bit_exact():
    """The fused direct-conv Pallas kernel (interpret mode) reproduces the
    frozen integers on the conv-heaviest golden."""
    graph, x, want_stages = _load("ic")
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=True, interpret=True,
                       conv_lowering="direct")
    outs = cm.stage_outputs(jnp.asarray(x[:2]))
    for i, (got, want) in enumerate(zip(outs, want_stages)):
        _assert_stage_match(got, want[:2], f"ic[pallas] stage {i}")


# ---------------------------------------------------------------------------
# megakernel dispatch: {staged, megakernel} x {offline, streaming, waves}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ("kws", "ad"))
@pytest.mark.parametrize("mode", ["staged", "megakernel"])
def test_golden_mlp_dispatch_modes_bit_exact(name, mode):
    """Both segment dispatch modes reproduce the frozen logits across every
    executor entry point: the whole-network-resident megakernel
    (``docs/megakernel.md``) is integer-exact against the per-stage path
    because threshold counting is order-free."""
    graph, x, want_stages = _load(name)
    want = want_stages[-1]
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False, megakernel=(mode == "megakernel"))
    if mode == "megakernel":
        assert cm._mega_plans, f"{name}: planner admitted no megakernel run"
    else:
        assert not cm._mega_plans
    xj = jnp.asarray(x)
    _assert_stage_match(cm.offline(xj), want, f"{name}[{mode}] offline")
    y_str, stats = cm.streaming_compiled(xj, micro_batch=2)
    _assert_stage_match(y_str, want, f"{name}[{mode}] streaming_compiled")
    assert bool(stats.megakernel) == (mode == "megakernel")
    # submit_wave: a partially filled wave with an explicit valid mask —
    # padding rows must not perturb the real queries
    valid = np.array([True, False, True])
    y_w, mask = cm.submit_wave(x[:3], valid=valid, micro_batch=4)
    assert mask.tolist() == [True, False, True, False]
    _assert_stage_match(np.asarray(y_w)[mask], want[:3][valid],
                        f"{name}[{mode}] submit_wave")


@pytest.mark.parametrize("name", ("kws", "ad"))
def test_golden_mlp_megakernel_pallas_interpret_bit_exact(name):
    """The actual Pallas megakernel program (interpret mode on CPU) — not
    just the straight-line XLA fallback — reproduces the frozen integers."""
    graph, x, want_stages = _load(name)
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=True, interpret=True, megakernel=True)
    assert cm._mega_plans
    _assert_stage_match(cm.offline(jnp.asarray(x)), want_stages[-1],
                        f"{name}[pallas-mega] offline")


@pytest.mark.parametrize("name", ("kws", "ad"))
def test_golden_mlp_megakernel_fallback_when_budget_rejects(name):
    """Force-requesting the megakernel under a VMEM budget too small for
    the segment's weights+banks+tiles falls back to the staged path — and
    the outputs stay frozen-exact (the fallback IS the reference)."""
    graph, x, want_stages = _load(name)
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False, megakernel=True)
    assert cm._mega_plans
    cm.set_megakernel(True, budget_bytes=64)
    assert cm._mega_plans == {}
    xj = jnp.asarray(x)
    _assert_stage_match(cm.offline(xj), want_stages[-1],
                        f"{name}[fallback] offline")
    y_str, stats = cm.streaming_compiled(xj, micro_batch=2)
    _assert_stage_match(y_str, want_stages[-1], f"{name}[fallback] streaming")
    assert not stats.megakernel
    # restoring the default budget re-admits the plan
    cm.set_megakernel(True)
    assert cm._mega_plans
