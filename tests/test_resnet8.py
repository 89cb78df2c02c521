"""ResNet-8 on the integer serving path: the residual join kernel, the QIR
``Add`` and ``AvgPool`` ops, lowering a DAG to one compiled schedule, and
the served path against ``Graph.run`` and a plain float32 reference.

The model under test is a small ResNet-8 (8x8 inputs, widths 4/8/16) with
seeded BN-folded weights of positive mean, so every layer's 8-bit codes
stay spread (a zero-mean net is all zeros by its second stack under the
export's worst-case-reach activation scales). The published widths are
lowered, not run: lowering is cheap, and the chip smoke test and the
benchmark run them.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qir import Graph, Node, QuantSpec, eval_node, export_qcnn
from repro.deploy import compile_graph
from repro.core.streamline import ThresholdDense
from repro.deploy.lower import (ConvAddThresholdStage, ConvGeom, FlattenStage,
                                FloatHeadStage, FusedConvThresholdStage,
                                IntSumPoolStage, RefChainStage, im2col,
                                lower_graph)
from repro.kernels import ops
from repro.models.tiny import ResNet8
from repro.obs import Tracer
from test_conv_threshold_kernel import (FORMS, affine_bank,
                                        assert_meets_every_boundary,
                                        boundary_case, proven_affine)

SMALL = ResNet8(filters=(4, 8, 16), in_hw=8)


def _folded_params(model, seed):
    """BN-folded conv weights ~ U(-l/4, 7l/4), l = 1/fan_in; biases
    ~ N(0, 0.1^2); a float head."""
    rng = np.random.default_rng(seed)

    def conv(k, cin, cout):
        lim = 1.0 / (k * k * cin)
        return {"w": rng.uniform(-0.25 * lim, 1.75 * lim,
                                 (k, k, cin, cout)).astype(np.float32),
                "b": (0.1 * rng.standard_normal(cout)).astype(np.float32)}

    f = model.filters
    params = {"stem": conv(3, model.in_ch, f[0]), "blocks": [],
              "head": {"w": rng.uniform(-0.5, 0.5, (f[-1], model.n_classes)
                                        ).astype(np.float32),
                       "b": (0.1 * rng.standard_normal(model.n_classes)
                             ).astype(np.float32)}}
    for (c1, c2, proj) in model.residual_blocks():
        blk = {"conv1": conv(3, c1.in_ch, c1.out_ch),
               "conv2": conv(3, c2.in_ch, c2.out_ch)}
        if proj is not None:
            blk["proj"] = conv(1, proj.in_ch, proj.out_ch)
        params["blocks"].append(blk)
    return params


@pytest.fixture(scope="module")
def small():
    graph = export_qcnn(SMALL, _folded_params(SMALL, 1))
    x = np.random.default_rng(2).integers(-127, 128, (6, 8, 8, 3)).astype(
        np.int32)
    return graph, x


def _run_with_codes(graph, x):
    """``Graph.run`` on input codes, every Quant input and output kept;
    returns (outputs, the Quant nodes)."""
    quants = [n for n in graph.nodes if n.op == "Quant"]
    probe = copy.deepcopy(graph)
    probe.outputs = list(graph.outputs) + [v for n in quants
                                           for v in (n.inputs[0],
                                                     n.outputs[0])]
    return probe.run({"x": x.astype(np.float32)
                      * graph.meta["in_scale"]}), quants


# -- the join kernel ----------------------------------------------------------

def _count(total, thresholds):
    return jnp.sum(total[..., None] >= thresholds, axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("lowering", ["direct", "im2col"])
@pytest.mark.parametrize("operand,x_offset", [("bf16", 0), ("int8", 128)])
@pytest.mark.parametrize("proj,skip_stride", [(False, 1), (True, 2)])
def test_conv_threshold_add_is_bit_exact(proj, skip_stride, operand,
                                         x_offset, lowering):
    """The join kernel against a jnp reference, ties included: the main
    3x3 SAME conv of 8-bit codes, plus the identity codes or a 1x1
    projection of a block input twice the size, each times its grid
    ratio, counted against a bank drawn from the reference's own sums (so
    ``>=`` meets equality). ``im2col`` runs the patch matrix through the
    same kernel as a 1x1 conv."""
    rng = np.random.default_rng(7)
    n, hw, c, f, cs = 2, 6, 5, 7, 3
    x = rng.integers(0, 256, (n, hw, hw, c)).astype(np.int32)
    w = rng.integers(-127, 128, (9 * c, f)).astype(np.int8)
    mul = np.stack([2 ** rng.integers(0, 3, f),
                    2 ** rng.integers(0, 5, f)]).astype(np.int32)
    if proj:
        skip = rng.integers(0, 256, (n, hw * skip_stride, hw * skip_stride,
                                     cs)).astype(np.int32)
        w_skip = rng.integers(-127, 128, (cs, f)).astype(np.int8)
        acc_skip = (skip[:, ::skip_stride, ::skip_stride].astype(np.int64)
                    @ w_skip.astype(np.int64))
    else:
        skip = rng.integers(0, 256, (n, hw, hw, f)).astype(np.int32)
        w_skip, acc_skip = None, skip.astype(np.int64)
    cols = np.asarray(im2col(jnp.asarray(x), 3, 1, "SAME"), np.int64)
    acc = cols @ w.astype(np.int64)
    total = acc * mul[0] + acc_skip * mul[1]
    thr = np.sort(rng.choice(total.reshape(-1, f), 9, axis=0).T, axis=1)
    want = _count(jnp.asarray(total), jnp.asarray(thr[None, None, None]))
    x_in, kernel, padding = jnp.asarray(x), 3, "SAME"
    if lowering == "im2col":
        x_in, kernel, padding = jnp.asarray(cols, jnp.int32), 1, "VALID"
    got = ops.conv_threshold_add(
        x_in, jnp.asarray(w), jnp.asarray(skip),
        None if w_skip is None else jnp.asarray(w_skip), jnp.asarray(mul),
        jnp.asarray(thr, jnp.int32), kernel=kernel, stride=1,
        padding=padding, out_h=hw, out_w=hw, skip_stride=skip_stride,
        block_h=4, operand=operand, x_offset=x_offset, skip_operand=operand,
        skip_offset=x_offset, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("operand,x_offset", FORMS)
@pytest.mark.parametrize("proj,skip_stride", [(False, 1), (True, 2)])
def test_join_affine_path_is_bit_exact_on_every_boundary(
        proj, skip_stride, operand, x_offset):
    """The join kernel ending its rows in the proven affine gives the codes
    of its bank count (the same kernel without it), of a NumPy count of
    the int64 total and of the join stage's ``apply_ref``: identity codes
    and a 1x1 stride-2 projection, both MXU forms (the offsets of both
    branches added back before the affine), every boundary a tie on
    channel 0, whose skip branch is zero, and codes clipped at 0 and at
    255."""
    rng = np.random.default_rng(160 + skip_stride)
    n, hw = 2, 16
    x, w2d, grid, bias, s_out = boundary_case(rng, n, hw, 1)
    f = w2d.shape[1]
    mul = np.array([[1, 2, 1, 1], [1, 4, 1, 2]], np.int32)
    if proj:
        cs = 5
        skip = rng.integers(0, 256, (n, hw * skip_stride, hw * skip_stride,
                                     cs)).astype(np.int32)
        w_skip = rng.integers(-3, 4, (cs, f)).astype(np.int8)
        w_skip[:, 0] = 0
        acc_skip = (skip[:, ::skip_stride, ::skip_stride].astype(np.int64)
                    @ w_skip.astype(np.int64))
        skip_reach = np.sum(np.abs(w_skip.astype(np.int64)), axis=0) * 255
    else:
        skip = rng.integers(0, 256, (n, hw, hw, f)).astype(np.int32)
        skip[..., 0] = 0
        w_skip, acc_skip = None, skip.astype(np.int64)
        skip_reach = np.full(f, 255)
    cols = np.asarray(im2col(jnp.asarray(x), 3, 1, "SAME"), np.int64)
    total = (cols @ w2d.astype(np.int64)) * mul[0] + acc_skip * mul[1]
    reach = (np.sum(np.abs(w2d.astype(np.int64)), axis=0) * 255 * mul[0]
             + skip_reach * mul[1])
    affine = proven_affine(grid, bias, s_out, reach)
    bank = affine_bank(grid, bias, s_out)
    want = np.asarray(_count(jnp.asarray(total), jnp.asarray(bank)))
    assert_meets_every_boundary(total.astype(np.float64), grid, bias, s_out,
                                bank, want)
    args = (jnp.asarray(x), jnp.asarray(w2d), jnp.asarray(skip),
            None if w_skip is None else jnp.asarray(w_skip),
            jnp.asarray(mul), jnp.asarray(bank))
    kw = dict(kernel=3, stride=1, padding="SAME", out_h=hw, out_w=hw,
              skip_stride=skip_stride, block_h=4, operand=operand,
              x_offset=x_offset, skip_operand=operand, skip_offset=x_offset,
              interpret=True)
    np.testing.assert_array_equal(
        np.asarray(ops.conv_threshold_add(*args, **kw)), want)
    np.testing.assert_array_equal(np.asarray(ops.conv_threshold_add(
        *args, affine=affine, **kw)), want)
    td = ThresholdDense(w_int=jnp.asarray(w2d), thresholds=jnp.asarray(bank),
                        out_scale=s_out, act_bits=8, weight_bits=8)
    stage = ConvAddThresholdStage(
        name="join", stage=td,
        geom=ConvGeom(3, 1, "SAME", hw, hw, 3, hw, hw, f), in_scale=1.0,
        affine=affine, skip_from=0,
        w_skip=None if w_skip is None else jnp.asarray(w_skip),
        skip_stride=skip_stride, mul=mul)
    assert stage.affine_on_kernel
    for got in (stage.apply_ref(jnp.asarray(x), jnp.asarray(skip)),
                stage.apply_kernel(jnp.asarray(x), jnp.asarray(skip),
                                   interpret=True)):
        np.testing.assert_array_equal(np.asarray(got), want)


# -- QIR ops ------------------------------------------------------------------

def _op_graph(op, attrs):
    ins = ["a", "b"] if op == "Add" else ["a"]
    return Graph(nodes=[Node(op, "n", ins, ["y"], attrs=attrs)],
                 inputs=ins, outputs=["y"])


@pytest.mark.parametrize("op,attrs", [
    ("Add", {}),
    ("AvgPool", {"window": 4, "stride": 4, "padding": "VALID"}),
    ("AvgPool", {"window": 2, "stride": 2}),
])
def test_add_and_avgpool_in_eval_node_and_graph_run(op, attrs):
    """``Add`` sums its two inputs; ``AvgPool`` is the window mean (NHWC).
    ``eval_node`` and ``Graph.run`` agree, also after a JSON round trip."""
    rng = np.random.default_rng(3)
    a = rng.standard_normal((2, 4, 4, 3)).astype(np.float32)
    b = rng.standard_normal((2, 4, 4, 3)).astype(np.float32)
    if op == "Add":
        want = a + b
    else:
        win = attrs["window"]
        want = a.reshape(2, 4 // win, win, 4 // win, win, 3).mean(axis=(2, 4))
    g = _op_graph(op, attrs)
    feeds = {"a": a, "b": b} if op == "Add" else {"a": a}
    node_y = eval_node(g.nodes[0], [jnp.asarray(feeds[i])
                                    for i in g.nodes[0].inputs])
    np.testing.assert_allclose(np.asarray(node_y), want, rtol=1e-6)
    for graph in (g, Graph.from_json(g.to_json())):
        np.testing.assert_allclose(graph.run(feeds)["y"], want, rtol=1e-6)


def test_export_has_joins_pool_and_a_dag(small):
    """The export is a DAG: each block's input feeds its first conv and its
    ``Add``; one activation quantizer per join, after the add."""
    graph, _ = small
    ops_ = [n.op for n in graph.nodes]
    assert ops_.count("Add") == 3 and ops_.count("AvgPool") == 1
    assert ops_.count("Quant") == 7
    readers = {}
    for n in graph.nodes:
        for v in n.inputs:
            readers[v] = readers.get(v, 0) + 1
    assert readers["stem_q"] == 2          # b1_conv1 and the identity add
    add = next(n for n in graph.nodes if n.name == "b1_conv2_add")
    assert add.inputs == ["b1_conv2_acc", "stem_q"]
    g2 = Graph.from_json(graph.to_json())
    assert [n.op for n in g2.nodes] == ops_


# -- lowering -----------------------------------------------------------------

def test_lower_published_resnet8_has_no_fallback():
    """At its published widths the schedule is four conv stages, three
    joins (skip of the stem, of stack 1 and of stack 2), the sum pool,
    the flatten and the float head; no ``RefChainStage``, no host
    segment."""
    model = ResNet8()
    graph = export_qcnn(model, _folded_params(model, 4))
    sched = lower_graph(graph, in_scale=graph.meta["in_scale"])
    kinds = [type(s) for s in sched.stages]
    assert kinds == ([FusedConvThresholdStage] * 2 + [ConvAddThresholdStage]
                     + [FusedConvThresholdStage, ConvAddThresholdStage] * 2
                     + [IntSumPoolStage, FlattenStage, FloatHeadStage])
    assert not any(isinstance(s, RefChainStage) for s in sched.stages)
    assert [s.skip_from for s in sched.stages
            if isinstance(s, ConvAddThresholdStage)] == [0, 2, 4]
    assert sched.n_join == 3 and sched.host_segments == 0
    assert sched.meta["host_segments"] == 0
    assert "3 join, 0 host segments" in sched.describe()
    pool = sched.stages[7]
    assert pool.out_scale == sched.stages[6].out_scale / 64
    assert sum(s.macs for s in sched.stages[:7]) == \
        model.cost().flops / 2 - 640


def test_an_op_with_no_lowering_still_falls_back_and_is_counted():
    """A chain with an op the matcher has no lowering for (``Mul``) still
    runs: the rest becomes one ``RefChainStage``, which the schedule
    counts as a host segment in ``describe()`` and ``meta``, and the wave's
    launch span reports."""
    rng = np.random.default_rng(5)
    g = Graph(inputs=["x"], outputs=["y"], initializers={
        "w": rng.standard_normal((4, 3)).astype(np.float32),
        "b": np.zeros(3, np.float32),
        "k": np.float32(0.5)})
    g.nodes = [
        Node("Dense", "fc", ["x", "w", "b"], ["h"]),
        Node("Relu", "relu", ["h"], ["r"]),
        Node("Quant", "q", ["r"], ["c"], attrs={"scale": 0.25},
             quant=QuantSpec(bits=4, signed=False)),
        Node("Mul", "mul", ["c", "k"], ["y"]),
    ]
    sched = lower_graph(g, in_scale=1 / 8)
    assert isinstance(sched.stages[-1], RefChainStage)
    assert sched.host_segments == sched.meta["host_segments"] == 1
    assert "1 host segments" in sched.describe()
    tr = Tracer()
    cm = compile_graph(g, in_scale=1 / 8, use_pallas=False, tracer=tr)
    x = rng.integers(-7, 8, (3, 4)).astype(np.int32)
    y, mask = cm.submit_wave(x, micro_batch=4)
    np.testing.assert_array_equal(np.asarray(y)[mask],
                                  np.asarray(cm.offline(x)))
    launch = [s for s in tr.spans() if s.name == "executor.launch"]
    assert launch[-1].args["host_segments"] == 1
    assert launch[-1].args["skip_bytes"] == 0


def test_a_fallback_that_would_need_a_skip_value_is_refused(small):
    """Where the op with no lowering lies inside a residual block, the
    interpreter from there on would need the block input, an earlier
    stage's value: lowering says so rather than building a schedule that
    cannot run."""
    graph, _ = small
    g = copy.deepcopy(graph)
    add = next(n for n in g.nodes if n.name == "b1_conv2_add")
    add.op = "Sub"
    with pytest.raises(NotImplementedError, match="stem_q"):
        lower_graph(g, in_scale=g.meta["in_scale"])


# -- the served path ----------------------------------------------------------

def _plain_reference(graph, x):
    """The exported graph's arithmetic in plain float32 jnp: convs by
    ``lax.conv`` at highest precision, the skip sum, half-up activation
    quantization, the window mean and the head."""
    ini, nodes = graph.initializers, graph.nodes
    env = {"x": jnp.asarray(x, jnp.float32) * graph.meta["in_scale"]}
    with jax.default_matmul_precision("highest"):
        for n in nodes:
            v = [env[i] if i in env else jnp.asarray(ini[i])
                 for i in n.inputs]
            if n.op == "Conv2D":
                s = n.attrs["stride"]
                y = jax.lax.conv_general_dilated(
                    v[0], v[1], (s, s), n.attrs["padding"],
                    dimension_numbers=("NHWC", "HWIO", "NHWC")) + v[2]
            elif n.op == "Add":
                y = v[0] + v[1]
            elif n.op == "Relu":
                y = jnp.maximum(v[0], 0.0)
            elif n.op == "Quant":
                s = n.attrs["scale"]
                y = jnp.clip(jnp.floor(v[0] / s + 0.5), 0, 255) * s
            elif n.op == "AvgPool":
                y = jnp.mean(v[0], axis=(1, 2), keepdims=True)
            elif n.op == "Flatten":
                y = v[0].reshape(v[0].shape[0], -1)
            else:
                y = v[0] @ v[1] + v[2]
            env[n.outputs[0]] = y
    return np.asarray(env["logits"])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_served_resnet8_is_bit_exact_against_graph_run(small, use_pallas):
    """Every stage's codes equal ``Graph.run``'s, ties included (half-up
    ties do occur here), through ``stage_outputs``, ``offline``,
    ``submit_wave`` (a partly filled wave) and both streaming paths; the
    logits equal ``Graph.run``'s within float32 rounding of its head.
    Against the plain float32 reference the logits agree to 1e-5: the
    codes are the same integers, and the only rounding left is float32's
    in the head's sums (at most a few ulps of logits of order 1)."""
    graph, x = small
    run, quants = _run_with_codes(graph, x)
    ties = sum(int(np.sum(np.mod(run[q.inputs[0]] / q.attrs["scale"], 1)
                          == 0.5)) for q in quants)
    assert ties > 0
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=use_pallas, interpret=use_pallas)
    outs = cm.stage_outputs(x)
    k = 0
    for i, (s, out) in enumerate(zip(cm.schedule.stages, outs)):
        if isinstance(s, ConvAddThresholdStage):
            # the int32 im2col reference of a join gives the same codes
            np.testing.assert_array_equal(
                np.asarray(s.apply_ref(outs[i - 1], outs[s.skip_from])),
                np.asarray(out))
        if isinstance(s, FusedConvThresholdStage):
            np.testing.assert_array_equal(
                np.asarray(out) * s.stage.out_scale,
                run[quants[k].outputs[0]])
            assert np.asarray(out).max() > 0
            k += 1
    assert k == 7
    y = np.asarray(cm.offline(x))
    np.testing.assert_allclose(y, run["logits"], rtol=1e-5, atol=1e-6)
    yw, mask = cm.submit_wave(x[:5], micro_batch=8)
    np.testing.assert_array_equal(np.asarray(yw)[mask], y[:5])
    for yy in (cm.streaming_host(x, micro_batch=2)[0],
               cm.streaming_compiled(x, micro_batch=4)[0]):
        np.testing.assert_array_equal(np.asarray(yy), y)
    np.testing.assert_allclose(y, _plain_reference(graph, x), rtol=1e-5,
                               atol=1e-6)


def test_launch_span_reports_skip_bytes():
    """The wave's ``executor.launch`` span carries ``host_segments`` (0:
    the schedule compiles whole) and ``skip_bytes``: the int32 skip values
    the wave's program keeps live (stem, stack 1 and stack 2 outputs) for
    every row of the wave."""
    graph = export_qcnn(SMALL, _folded_params(SMALL, 1))
    tr = Tracer()
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=False, tracer=tr)
    cm.submit_wave(np.zeros((3, 8, 8, 3), np.int32), micro_batch=4)
    args = [s for s in tr.spans() if s.name == "executor.launch"][-1].args
    per_row = 4 * (8 * 8 * 4 + 8 * 8 * 4 + 4 * 4 * 8)
    assert args["host_segments"] == 0
    assert args["skip_bytes"] == 4 * per_row


@pytest.mark.parametrize("use_pallas", [False, True])
def test_launch_span_reports_affine_stages(use_pallas):
    """``executor.launch`` carries ``affine_stages``: the 7 threshold
    stages (4 convs, 3 joins) whose kernel ends its rows in the O(1)
    affine; 0 where the model runs the XLA path, which runs no kernel."""
    graph = export_qcnn(SMALL, _folded_params(SMALL, 1))
    tr = Tracer()
    cm = compile_graph(graph, in_scale=graph.meta["in_scale"],
                       use_pallas=use_pallas, interpret=use_pallas,
                       tracer=tr)
    assert cm.schedule.affine_stages == 7
    cm.submit_wave(np.zeros((3, 8, 8, 3), np.int32), micro_batch=4)
    if not use_pallas:
        cm.streaming_compiled(np.zeros((3, 8, 8, 3), np.int32),
                              micro_batch=4)
    spans = [s for s in tr.spans() if s.name == "executor.launch"]
    assert spans and all(s.args["affine_stages"] == (7 if use_pallas else 0)
                         for s in spans)


@pytest.mark.parametrize("name,want", [("resnet8", 7), ("cnv", 0),
                                       ("kws", 0)])
def test_benchmark_schedules_count_their_affine_stages(name, want):
    """The benchmark's configurations at their published widths, from
    seeded weights: every ResNet-8 threshold stage (S = 255) proves the
    affine; no CNV (S = 1) or KWS (S = 7) stage does."""
    import importlib
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    mod = importlib.import_module(f"chipbench.configs.{name}")
    with open(os.path.join(root, "chipbench", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    params = jax.jit(lambda k: mod.make_params(cfg, k))(
        jax.random.PRNGKey(3000016001))
    graph, in_scale = mod.build_graph(cfg, params)
    sched = lower_graph(graph, in_scale=in_scale)
    assert sched.affine_stages == want
    assert all(s.affine_on_kernel == (want > 0) for s in sched.stages
               if isinstance(s, FusedConvThresholdStage))


# -- the model ----------------------------------------------------------------

def test_resnet8_counts_match_the_reference_model():
    """78,666 parameters (77,360 weights) and 12,501,632 MACs a sample, as
    mlcommons/tiny's ``resnet_v1_eembc``; the benchmark's layer list counts
    the same operations."""
    import importlib.util
    import os

    model = ResNet8()
    assert model.n_params() == 78666 and model.n_weights() == 77360
    assert model.cost().flops / 2 == 12501632
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "resnet8_cfg", os.path.join(root, "chipbench", "configs",
                                    "resnet8.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import json
    import sys

    sys.path.insert(0, root)
    from chipbench import cost

    with open(os.path.join(root, "chipbench", "configs",
                           "resnet8.json")) as f:
        cfg = json.load(f)
    assert cost.ops_per_sample(mod.layers(cfg)) / 2 == 12501632


def test_resnet8_qat_forward_trains_its_batch_norm():
    """The QAT forward: logits of the right shape, BN statistics updated in
    train mode and left alone in eval mode."""
    params = SMALL.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).uniform(-1, 1, (4, 8, 8, 3)),
                    jnp.float32)
    logits, new = SMALL.apply(params, x, train=True)
    assert logits.shape == (4, 10) and bool(jnp.all(jnp.isfinite(logits)))
    assert not np.allclose(new["stem"]["mu"], params["stem"]["mu"])
    _, same = SMALL.apply(params, x, train=False)
    np.testing.assert_array_equal(same["stem"]["mu"], params["stem"]["mu"])
