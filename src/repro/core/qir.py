"""QIR — a QONNX-style interchange format for arbitrary-precision QNNs.

The paper's C8: hls4ml and FINN exchange quantized models through QONNX, an
ONNX extension whose key addition is a ``Quant(bitwidth, scale, zero_point,
signed, narrow)`` node. QIR is the same idea as a minimal, dependency-free
JSON graph so the training flow (core/qlayers) and the deployment flow
(core/streamline + kernels/) share one artifact:

  train (QAT)  --export-->  QIR json  --import-->  streamline/deploy

Supported ops: Dense, Conv2D, MaxPool, AvgPool, Flatten, BatchNorm, Relu,
Quant, Add, MultiThreshold, TopK, Mul. Weights live in ``initializers``
(name -> ndarray, stored base64 in JSON). A value may have more than one
consumer (a residual block's input feeds its first conv and its ``Add``),
so a graph is a DAG in topological node order, not only a chain.

Quant node semantics (attrs select the flavor):
  * default             — dynamic min-max IntQuantizer (the QAT fake-quant)
  * ``attrs["scale"]``  — fixed-grid unsigned quant with half-up rounding,
    value = clip(floor(x/s + 0.5), 0, 2^bits - 1) * s. This is the form the
    conv exporter emits: the scale is frozen at export so the deployed
    integer thresholds (core/streamline.py) reproduce it bit-exactly.
  * ``attrs["bipolar"]``— FINN's bipolar activation in unipolar encoding:
    value = [x >= 0] in {0, 1} standing for sign(x) in {-1, +1}. Layers
    consuming it carry export-folded weights (w' = 2w, b' = b - sum(w)) so
    the graph stays affine in the 0/1 codes.
"""

from __future__ import annotations

import base64
import dataclasses
import io
import json
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class QuantSpec:
    bits: int = 8
    signed: bool = True
    narrow: bool = False
    po2_scale: bool = False

    def to_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


@dataclasses.dataclass
class Node:
    op: str
    name: str
    inputs: List[str]
    outputs: List[str]
    attrs: Dict = dataclasses.field(default_factory=dict)
    quant: Optional[QuantSpec] = None

    def to_dict(self):
        d = {
            "op": self.op,
            "name": self.name,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": self.attrs,
        }
        if self.quant is not None:
            d["quant"] = self.quant.to_dict()
        return d

    @classmethod
    def from_dict(cls, d):
        q = QuantSpec.from_dict(d["quant"]) if "quant" in d else None
        return cls(d["op"], d["name"], d["inputs"], d["outputs"], d.get("attrs", {}), q)


def _enc(a: np.ndarray) -> Dict:
    buf = io.BytesIO()
    np.save(buf, np.asarray(a), allow_pickle=False)
    return {"b64": base64.b64encode(buf.getvalue()).decode("ascii")}


def _dec(d: Dict) -> np.ndarray:
    return np.load(io.BytesIO(base64.b64decode(d["b64"])), allow_pickle=False)


@dataclasses.dataclass
class Graph:
    nodes: List[Node] = dataclasses.field(default_factory=list)
    initializers: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    inputs: List[str] = dataclasses.field(default_factory=list)
    outputs: List[str] = dataclasses.field(default_factory=list)
    meta: Dict = dataclasses.field(default_factory=dict)

    # -- serialization ----------------------------------------------------
    def to_json(self) -> str:
        return json.dumps(
            {
                "nodes": [n.to_dict() for n in self.nodes],
                "initializers": {k: _enc(v) for k, v in self.initializers.items()},
                "inputs": self.inputs,
                "outputs": self.outputs,
                "meta": self.meta,
            }
        )

    @classmethod
    def from_json(cls, s: str) -> "Graph":
        d = json.loads(s)
        return cls(
            nodes=[Node.from_dict(n) for n in d["nodes"]],
            initializers={k: _dec(v) for k, v in d["initializers"].items()},
            inputs=d["inputs"],
            outputs=d["outputs"],
            meta=d.get("meta", {}),
        )

    def save(self, path: str):
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path: str) -> "Graph":
        with open(path) as f:
            return cls.from_json(f.read())

    # -- execution (reference interpreter) --------------------------------
    def run(self, feeds: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        import jax.numpy as jnp

        env: Dict[str, np.ndarray] = dict(self.initializers)
        env.update(feeds)
        for node in self.nodes:
            x = [jnp.asarray(env[i]) for i in node.inputs]
            env[node.outputs[0]] = np.asarray(eval_node(node, x))
        return {o: env[o] for o in self.outputs}


# ---------------------------------------------------------------------------
# single-node evaluation (shared by Graph.run and repro.deploy's fallback)
# ---------------------------------------------------------------------------

def eval_node(node: Node, x: List):
    """Evaluate one QIR node on already-fetched (jnp) input values.

    Traceable — the deploy fallback stage calls this inside jit; Graph.run
    wraps it eagerly per node.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.quantizers import IntQuantizer
    from repro.core.streamline import multi_threshold

    if node.op == "Dense":
        y = x[0] @ x[1]
        if len(x) > 2:
            y = y + x[2]
    elif node.op == "Conv2D":
        stride = int(node.attrs.get("stride", 1))
        y = jax.lax.conv_general_dilated(
            x[0], x[1],
            window_strides=(stride, stride),
            padding=node.attrs.get("padding", "SAME"),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if len(x) > 2:
            y = y + x[2]
    elif node.op == "MaxPool":
        win = int(node.attrs.get("window", 2))
        stride = int(node.attrs.get("stride", win))
        init = (jnp.iinfo(x[0].dtype).min
                if jnp.issubdtype(x[0].dtype, jnp.integer) else -jnp.inf)
        y = jax.lax.reduce_window(
            x[0], init, jax.lax.max, (1, win, win, 1), (1, stride, stride, 1),
            node.attrs.get("padding", "VALID"))
    elif node.op == "AvgPool":
        win = int(node.attrs.get("window", 2))
        stride = int(node.attrs.get("stride", win))
        y = jax.lax.reduce_window(
            x[0], jnp.zeros((), x[0].dtype), jax.lax.add, (1, win, win, 1),
            (1, stride, stride, 1), node.attrs.get("padding", "VALID")
        ) / (win * win)
    elif node.op == "Add":
        y = x[0] + x[1]
    elif node.op == "Flatten":
        y = x[0].reshape(x[0].shape[0], -1)
    elif node.op == "Relu":
        y = jnp.maximum(x[0], 0)
    elif node.op == "BatchNorm":
        xx, gamma, beta, mu, var = x
        eps = node.attrs.get("eps", 1e-3)
        y = gamma * (xx - mu) / jnp.sqrt(var + eps) + beta
    elif node.op == "Quant":
        if node.attrs.get("bipolar"):
            # unipolar encoding of the bipolar sign activation: [x >= 0]
            y = (x[0] >= 0).astype(jnp.float32)
        elif node.attrs.get("scale") is not None:
            s = float(node.attrs["scale"])
            qmax = 2 ** node.quant.bits - 1
            y = jnp.clip(jnp.floor(x[0] / s + 0.5), 0, qmax) * s
        else:
            q = IntQuantizer(
                bits=node.quant.bits,
                signed=node.quant.signed,
                narrow=node.quant.narrow,
            )
            y = q(x[0])
    elif node.op == "MultiThreshold":
        y = multi_threshold(x[0].astype(jnp.int32), jnp.asarray(x[1]))
    elif node.op == "TopK":
        y = jnp.argmax(x[0], axis=-1)
    elif node.op == "Mul":
        y = x[0] * x[1]
    else:
        raise NotImplementedError(f"QIR op {node.op}")
    return y


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def export_qmlp(layer_defs, params_list, head_params, meta=None,
                freeze_scales: bool = False,
                in_scale: float = 1.0 / 127.0,
                bn_eps: float = 1e-3) -> Graph:
    """Export a QDense/QDenseBatchNorm stack + linear head to QIR.

    With ``freeze_scales`` the activation Quant nodes carry the po2 scale
    the streamliner would pick (chained from ``in_scale``), so the unfused
    ``Graph.run`` reference uses the same half-up deployment grid as the
    compiled integer schedule instead of dynamic min-max fake-quant — the
    compiled-vs-unfused parity then holds at the decision level. ``bn_eps``
    must match the value later passed to ``lower_graph`` so the BN fold
    behind the frozen scales stays in lockstep with the deployed thresholds.
    """
    g = Graph(inputs=["x"], outputs=["logits"], meta=meta or {})
    prev = "x"
    scale = in_scale
    for i, (ld, p) in enumerate(zip(layer_defs, params_list)):
        wname, bname = f"w{i}", f"b{i}"
        g.initializers[wname] = np.asarray(p["w"])
        g.initializers[bname] = np.asarray(p["b"])
        out = f"h{i}_fc"
        g.nodes.append(
            Node(
                "Dense",
                f"dense{i}",
                [prev, wname, bname],
                [out],
                attrs={"weight_bits": getattr(ld, "weight_bits", 8)},
            )
        )
        prev = out
        if "gamma" in p:
            for stat in ("gamma", "beta", "mu", "sigma2"):
                g.initializers[f"{stat}{i}"] = np.asarray(p[stat])
            out = f"h{i}_bn"
            g.nodes.append(
                Node(
                    "BatchNorm",
                    f"bn{i}",
                    [prev, f"gamma{i}", f"beta{i}", f"mu{i}", f"sigma2{i}"],
                    [out],
                )
            )
            prev = out
        out = f"h{i}_relu"
        g.nodes.append(Node("Relu", f"relu{i}", [prev], [out]))
        prev = out
        out = f"h{i}_q"
        attrs = {}
        if freeze_scales:
            from repro.core.streamline import _fold_affine, choose_act_scale

            import jax.numpy as jnp

            k_f, b_f = _fold_affine(
                {k: jnp.asarray(v) for k, v in p.items()}, bn_eps)
            s_out = choose_act_scale(k_f, b_f, in_scale=scale,
                                     act_bits=ld.act_bits)
            attrs["scale"] = s_out
            scale = s_out
        g.nodes.append(
            Node(
                "Quant",
                f"quant{i}",
                [prev],
                [out],
                attrs=attrs,
                quant=QuantSpec(bits=ld.act_bits,
                                signed=not freeze_scales),
            )
        )
        prev = out
    g.initializers["w_head"] = np.asarray(head_params["w"])
    g.initializers["b_head"] = np.asarray(head_params["b"])
    g.nodes.append(Node("Dense", "head", [prev, "w_head", "b_head"], ["logits"]))
    return g


def _conv_out_hw(h: int, w: int, k: int, stride: int, padding: str):
    if padding == "SAME":
        return -(-h // stride), -(-w // stride)
    return (h - k) // stride + 1, (w - k) // stride + 1


def export_qcnn(model, params, in_scale: float = 1.0 / 128.0, meta=None,
                calibrate=None) -> Graph:
    """Export a conv model (``ICModel``, ``CNVModel`` or ``ResNet8``) to QIR.

    Mirrors ``export_qmlp`` for the spatial models: every conv layer becomes a
    ``Conv2D -> [Relu] -> Quant`` chain with per-layer ``QuantSpec``s, plus
    ``MaxPool``/``Flatten`` nodes where the architecture has them. Two export
    decisions make the graph *exactly* streamlinable (the lowered integer
    schedule reproduces ``Graph.run`` bit for bit, ties included):

      * weights are stored fake-quantized with power-of-two per-channel
        scales (recorded via ``attrs["w_scale"]``) and biases snapped to the
        integer-accumulator grid, so with a po2 ``in_scale`` every float in
        the reference interpreter is an exact multiple of a po2 step;
      * the binary CNV is exported in FINN's unipolar form: activations are
        ``[x >= 0]`` codes in {0, 1} and downstream weights are folded as
        ``w' = 2w, b' = b - sum(w)`` so arithmetic stays affine in the codes
        (its ``meta["in_scale"]`` is 1.0 — input codes are the values).

    ``ResNet8`` exports its residual joins as ``Conv2D [Conv2D] -> Add ->
    Relu -> Quant`` (``_export_resnet``): both branches' biases sit on the
    finer of the two accumulator grids, so the sum stays exact too.

    ``in_scale`` is the float value of one step of the 8-bit input image
    (ignored for CNV); keep it a power of two for the exactness guarantee.
    ``calibrate`` (optional, multi-bit models) is a batch of integer input
    codes used to measure real post-ReLU activation ranges; without it the
    per-layer scales come from the worst-case reach bound, which wastes most
    of the code range and costs accuracy (post-training static calibration
    is what the hls4ml flow does with its profiling pass).
    """
    if hasattr(model, "residual_blocks"):
        return _export_resnet(model, params, in_scale, meta)
    if getattr(model, "weight_bits", 8) == 1 and hasattr(model, "channels"):
        return _export_cnv(model, params, meta)
    if hasattr(model, "conv_layers"):
        return _export_ic(model, params, in_scale, meta, calibrate)
    raise TypeError(f"no QIR conv exporter for {type(model).__name__}")


def _export_ic(model, params, in_scale: float, meta, calibrate=None) -> Graph:
    import jax
    import jax.numpy as jnp

    from repro.core.quantizers import IntQuantizer, quantize_po2
    from repro.core.streamline import choose_act_scale

    g = Graph(inputs=["x"], outputs=["logits"],
              meta=dict(meta or {}, model=type(model).__name__,
                        in_scale=in_scale))
    convs = model.conv_layers()
    h, w, cin = model.in_hw, model.in_hw, model.in_ch
    scale, in_qmax = in_scale, 127          # signed 8-bit input codes
    hcal = (None if calibrate is None
            else jnp.asarray(calibrate, jnp.float32) * in_scale)
    prev = "x"
    for i, (ld, p) in enumerate(zip(convs, params["convs"])):
        wk = np.asarray(p["w"], np.float32)             # (k, k, cin, f)
        wq = IntQuantizer(bits=ld.weight_bits, signed=True, narrow=True,
                          axis=0, po2=True)
        w_int, s_w = wq.quantize_int(jnp.asarray(wk.reshape(-1, ld.out_ch)))
        s_w = np.asarray(s_w, np.float32).reshape(-1)   # (f,) po2
        w_hat = (np.asarray(w_int, np.float32) * s_w).reshape(wk.shape)
        grid = s_w * scale                              # accumulator step
        b_q = np.asarray(np.round(np.asarray(p["b"]) / grid) * grid,
                         np.float32)
        oh, ow = _conv_out_hw(h, w, ld.kernel, ld.stride, ld.padding)
        g.initializers[f"cw{i}"] = w_hat
        g.initializers[f"cb{i}"] = b_q
        g.initializers[f"cws{i}"] = s_w
        g.nodes.append(Node(
            "Conv2D", f"conv{i}", [prev, f"cw{i}", f"cb{i}"], [f"c{i}_conv"],
            attrs={"kernel": ld.kernel, "stride": ld.stride,
                   "padding": ld.padding, "weight_bits": ld.weight_bits,
                   "w_scale": f"cws{i}",
                   "in_shape": [h, w, cin], "out_shape": [oh, ow, ld.out_ch]}))
        g.nodes.append(Node("Relu", f"relu{i}", [f"c{i}_conv"], [f"c{i}_relu"]))
        qmax_out = 2 ** ld.act_bits - 1
        if hcal is not None:
            y = jax.lax.conv_general_dilated(
                hcal, jnp.asarray(w_hat), (ld.stride, ld.stride), ld.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + jnp.asarray(b_q)
            r = jnp.maximum(y, 0)
            s_out = float(quantize_po2(
                jnp.maximum(jnp.max(r), 1e-8) / qmax_out))
            hcal = jnp.clip(jnp.floor(r / s_out + 0.5), 0, qmax_out) * s_out
        else:
            s_out = choose_act_scale(
                jnp.asarray(w_hat.reshape(-1, ld.out_ch)), jnp.asarray(b_q),
                in_scale=scale, act_bits=ld.act_bits, in_qmax=in_qmax)
        g.nodes.append(Node(
            "Quant", f"quant{i}", [f"c{i}_relu"], [f"c{i}_q"],
            attrs={"scale": s_out},
            quant=QuantSpec(bits=ld.act_bits, signed=False)))
        prev = f"c{i}_q"
        scale, in_qmax = s_out, 2 ** ld.act_bits - 1
        h, w, cin = oh, ow, ld.out_ch
    g.nodes.append(Node("Flatten", "flatten", [prev], ["flat"],
                        attrs={"in_shape": [h, w, cin]}))
    wq_head = IntQuantizer(bits=model.weight_bits, axis=0)
    g.initializers["w_head"] = np.asarray(
        wq_head(jnp.asarray(params["head"]["w"])), np.float32)
    g.initializers["b_head"] = np.asarray(params["head"]["b"], np.float32)
    g.nodes.append(Node("Dense", "head", ["flat", "w_head", "b_head"],
                        ["logits"]))
    return g


def _export_cnv(model, params, meta) -> Graph:
    g = Graph(inputs=["x"], outputs=["logits"],
              meta=dict(meta or {}, model=type(model).__name__,
                        in_scale=1.0))
    convs = model.conv_layers()
    h, w, cin = model.in_hw, model.in_hw, model.in_ch
    prev = "x"
    for i, (ld, p) in enumerate(zip(convs, params["convs"])):
        sgn = np.where(np.asarray(p["w"]) >= 0, 1.0, -1.0).astype(np.float32)
        if i == 0:
            wk, b_q = sgn, None       # signed input codes: plain +-1 taps
        else:
            wk = 2.0 * sgn            # unipolar folding: x = 2q - 1
            b_q = -np.sum(sgn, axis=(0, 1, 2)).astype(np.float32)
        oh, ow = _conv_out_hw(h, w, ld.kernel, ld.stride, ld.padding)
        g.initializers[f"cw{i}"] = wk
        g.initializers[f"cws{i}"] = np.ones((ld.out_ch,), np.float32)
        ins = [prev, f"cw{i}"]
        if b_q is not None:
            g.initializers[f"cb{i}"] = b_q
            ins.append(f"cb{i}")
        g.nodes.append(Node(
            "Conv2D", f"conv{i}", ins, [f"c{i}_conv"],
            attrs={"kernel": ld.kernel, "stride": ld.stride,
                   "padding": ld.padding, "weight_bits": 1,
                   "w_scale": f"cws{i}",
                   "in_shape": [h, w, cin], "out_shape": [oh, ow, ld.out_ch]}))
        g.nodes.append(Node("Quant", f"sign{i}", [f"c{i}_conv"], [f"c{i}_q"],
                            attrs={"bipolar": True},
                            quant=QuantSpec(bits=1, signed=False)))
        prev = f"c{i}_q"
        h, w, cin = oh, ow, ld.out_ch
        if i in model.pool_after:
            g.nodes.append(Node(
                "MaxPool", f"pool{i}", [prev], [f"p{i}"],
                attrs={"window": 2, "stride": 2, "padding": "VALID",
                       "in_shape": [h, w, cin],
                       "out_shape": [h // 2, w // 2, cin]}))
            prev = f"p{i}"
            h, w = h // 2, w // 2
    g.nodes.append(Node("Flatten", "flatten", [prev], ["flat"],
                        attrs={"in_shape": [h, w, cin]}))
    prev = "flat"
    dims = [h * w * cin, *model.fc, model.n_classes]
    for j, p in enumerate(params["fcs"]):
        sgn = np.where(np.asarray(p["w"]) >= 0, 1.0, -1.0).astype(np.float32)
        g.initializers[f"fw{j}"] = 2.0 * sgn
        g.initializers[f"fb{j}"] = -np.sum(sgn, axis=0).astype(np.float32)
        last = j == len(params["fcs"]) - 1
        out = "logits" if last else f"f{j}_fc"
        attrs = {"weight_bits": 1}
        if not last:
            g.initializers[f"fws{j}"] = np.ones((dims[j + 1],), np.float32)
            attrs["w_scale"] = f"fws{j}"
        g.nodes.append(Node("Dense", f"fc{j}", [prev, f"fw{j}", f"fb{j}"],
                            [out], attrs=attrs))
        if not last:
            g.nodes.append(Node("Quant", f"fsign{j}", [out], [f"f{j}_q"],
                                attrs={"bipolar": True},
                                quant=QuantSpec(bits=1, signed=False)))
            prev = f"f{j}_q"
    return g


def _export_resnet(model, params, in_scale: float, meta) -> Graph:
    """Export ``models.tiny.ResNet8`` (the MLPerf Tiny IC reference).

    BN folds into each conv (hls4ml's ``QConv2DBatchnorm``) before the
    po2 per-channel weight quant; params without BN statistics are taken
    as already folded. A plain conv is ``Conv2D -> Relu -> Quant``. A
    block's second conv is a join: ``Conv2D(main) [-> Conv2D(1x1 proj of
    the block input)] -> Add(main, skip) -> Relu -> Quant``, one activation
    quantizer after the add and none on either branch. The two branches'
    accumulator grids (``s_w * s_in`` per channel, ``s_in`` for an identity
    skip) are powers of two; both biases are snapped to the finer of them,
    so ``Graph.run``'s float sum is the integer sum on that grid, exactly.
    Activation scales come from the worst-case reach bound, the join's
    from both branches' reach. Then a global ``AvgPool``, ``Flatten`` and
    the float ``Dense`` head.
    """
    import jax.numpy as jnp

    from repro.core.quantizers import IntQuantizer, quantize_po2
    from repro.core.streamline import _fold_affine_conv, choose_act_scale

    g = Graph(inputs=["x"], outputs=["logits"],
              meta=dict(meta or {}, model=type(model).__name__,
                        in_scale=in_scale))
    qa = 2 ** model.act_bits - 1
    # value name -> (scale, largest code, (h, w, c))
    vals = {"x": (in_scale, 127, (model.in_hw, model.in_hw, model.in_ch))}

    def fold_quant(layer, p):
        k, b = _fold_affine_conv({n: jnp.asarray(v) for n, v in p.items()},
                                 model.bn_eps)
        k = np.asarray(k, np.float32)
        wq = IntQuantizer(bits=layer.weight_bits, signed=True, narrow=True,
                          axis=0, po2=True)
        w_int, s_w = wq.quantize_int(jnp.asarray(k.reshape(-1, k.shape[-1])))
        s_w = np.asarray(s_w, np.float32).reshape(-1)
        w_hat = (np.asarray(w_int, np.float32) * s_w).reshape(k.shape)
        return w_hat, np.asarray(b, np.float32), s_w

    def conv(name, layer, src, w_hat, b_q, s_w):
        h, w, cin = vals[src][2]
        oh, ow = _conv_out_hw(h, w, layer.kernel, layer.stride,
                              layer.padding)
        g.initializers[f"{name}_w"] = w_hat
        g.initializers[f"{name}_b"] = b_q
        g.initializers[f"{name}_ws"] = s_w
        g.nodes.append(Node(
            "Conv2D", name, [src, f"{name}_w", f"{name}_b"], [f"{name}_acc"],
            attrs={"kernel": layer.kernel, "stride": layer.stride,
                   "padding": layer.padding,
                   "weight_bits": layer.weight_bits, "w_scale": f"{name}_ws",
                   "in_shape": [h, w, cin],
                   "out_shape": [oh, ow, layer.out_ch]}))
        return f"{name}_acc", (oh, ow, layer.out_ch)

    def act(name, src, s_out, shape):
        g.nodes.append(Node("Relu", f"{name}_relu", [src], [f"{name}_r"]))
        g.nodes.append(Node("Quant", f"{name}_quant", [f"{name}_r"],
                            [f"{name}_q"], attrs={"scale": s_out},
                            quant=QuantSpec(bits=model.act_bits,
                                            signed=False)))
        vals[f"{name}_q"] = (s_out, qa, shape)
        return f"{name}_q"

    def plain(name, layer, p, src):
        s_in, qmax_in, _ = vals[src]
        w_hat, b, s_w = fold_quant(layer, p)
        grid = s_w * s_in
        b_q = (np.round(b / grid) * grid).astype(np.float32)
        out, shape = conv(name, layer, src, w_hat, b_q, s_w)
        s_out = choose_act_scale(
            jnp.asarray(w_hat.reshape(-1, layer.out_ch)), jnp.asarray(b_q),
            in_scale=s_in, act_bits=model.act_bits, in_qmax=qmax_in)
        return act(name, out, s_out, shape)

    def join(name, layer, p, src, proj, p_proj, block_in):
        s_x, qmax_x, _ = vals[src]
        s_u, qmax_u, _ = vals[block_in]
        w_hat, b, s_w = fold_quant(layer, p)
        g_main = s_w.astype(np.float64) * s_x
        reach = (np.sum(np.abs(w_hat.reshape(-1, layer.out_ch)), axis=0,
                        dtype=np.float64) * s_x * qmax_x)
        if proj is None:
            g_skip = np.full_like(g_main, s_u)
            reach = reach + s_u * qmax_u
        else:
            wp_hat, bp, sp_w = fold_quant(proj, p_proj)
            g_skip = sp_w.astype(np.float64) * s_u
            reach = reach + (np.sum(np.abs(wp_hat.reshape(-1, proj.out_ch)),
                                    axis=0, dtype=np.float64) * s_u * qmax_u)
        grid = np.minimum(g_main, g_skip)   # the finer po2 grid
        b_q = (np.round(b / grid) * grid).astype(np.float32)
        main, shape = conv(name, layer, src, w_hat, b_q, s_w)
        bias = b_q.astype(np.float64)
        if proj is None:
            skip = block_in
        else:
            bp_q = (np.round(bp / grid) * grid).astype(np.float32)
            skip, _ = conv(f"{name}_proj", proj, block_in, wp_hat, bp_q, sp_w)
            bias = bias + bp_q
        g.nodes.append(Node("Add", f"{name}_add", [main, skip],
                            [f"{name}_sum"]))
        reach = float(np.max(reach + np.abs(bias)))
        s_out = float(quantize_po2(max(reach, 1e-8) / qa))
        return act(name, f"{name}_sum", s_out, shape)

    h = plain("stem", model.stem(), params["stem"], "x")
    for k, (c1, c2, proj) in enumerate(model.residual_blocks()):
        p = params["blocks"][k]
        mid = plain(f"b{k + 1}_conv1", c1, p["conv1"], h)
        h = join(f"b{k + 1}_conv2", c2, p["conv2"], mid, proj,
                 p.get("proj"), h)
    hh, ww, c = vals[h][2]
    g.nodes.append(Node("AvgPool", "pool", [h], ["pooled"],
                        attrs={"window": hh, "stride": hh, "padding": "VALID",
                               "in_shape": [hh, ww, c],
                               "out_shape": [1, 1, c]}))
    g.nodes.append(Node("Flatten", "flatten", ["pooled"], ["flat"],
                        attrs={"in_shape": [1, 1, c]}))
    g.initializers["w_head"] = np.asarray(params["head"]["w"], np.float32)
    g.initializers["b_head"] = np.asarray(params["head"]["b"], np.float32)
    g.nodes.append(Node("Dense", "head", ["flat", "w_head", "b_head"],
                        ["logits"]))
    return g
