"""The paper's four MLPerf Tiny submission models, in JAX with QAT.

Table 1 of the paper:
  IC  (hls4ml) : 8-12 bit CNN, 58 115 params, 83.5% acc   -> ``ICModel``
  IC  (FINN)   : 1-bit CNV-W1A1, 1 542 848 params, 84.5%  -> ``CNVModel``
  AD  (hls4ml) : 6-12 bit autoencoder, 22 285 params      -> ``ADAutoencoder``
  KWS (FINN)   : 3-bit MLP, 259 584 params, 82.5%         -> ``KWSMLP``

and the MLPerf Tiny image-classification reference model every IC
submission is measured against (Banbury et al., arXiv:2106.07597):
  IC  (ResNet-8): 8-bit QAT, 78 666 params, 12 501 632 MACs -> ``ResNet8``

Parameter-count notes: CNV reproduces the paper count exactly (1 542 848).
The KWS MLP (490-256-256-256-12, no biases in the paper's count) matches
259 584 weights exactly. The IC and AD architectures follow the paper's
stated layer structure; where the prose is ambiguous the benchmark reports
our exact count next to the paper's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.bops import ModelCost, conv_cost, dense_cost
from repro.core.qlayers import (QConv2D, QConv2DBatchNorm, QDense,
                                QDenseBatchNorm)
from repro.core.quantizers import (BinaryQuantizer, FixedPointQuantizer,
                                   IntQuantizer)


# ---------------------------------------------------------------------------
# AD: autoencoder (hls4ml, 6-12 bit)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ADAutoencoder:
    """128 -> [72 72] -> 8 -> [72 72] -> 128; QDenseBatchNorm + ReLU hidden
    stages (paper §3.3: 5 hidden layers, width 72, downsampled 128-dim input)."""

    in_dim: int = 128
    width: int = 72
    bottleneck: int = 8
    weight_bits: int = 8
    act_bits: int = 8
    use_bn: bool = True

    @property
    def dims(self) -> List[int]:
        return [self.in_dim, self.width, self.width, self.bottleneck,
                self.width, self.width, self.in_dim]

    def layers(self):
        hidden = []
        ds = self.dims
        for i in range(len(ds) - 2):
            cls = QDenseBatchNorm if self.use_bn else QDense
            kw = {} if self.use_bn else {"relu": True}
            hidden.append(cls(ds[i], ds[i + 1], weight_bits=self.weight_bits,
                              act_bits=self.act_bits, **kw))
        head = QDense(ds[-2], ds[-1], weight_bits=self.weight_bits,
                      act_bits=32, relu=False)
        return hidden, head

    def init(self, key):
        hidden, head = self.layers()
        keys = jax.random.split(key, len(hidden) + 1)
        return {
            "hidden": [l.init(k) for l, k in zip(hidden, keys[:-1])],
            "head": head.init(keys[-1]),
        }

    def apply(self, params, x, train: bool = True):
        """Returns (recon, new_params) — BN stats update in train mode."""
        hidden, head = self.layers()
        new_hidden = []
        h = x
        for l, p in zip(hidden, params["hidden"]):
            if isinstance(l, QDenseBatchNorm):
                h, p = l.apply(p, h, train=train)
            else:
                h = l.apply(p, h, train=train)
            new_hidden.append(p)
        recon = head.apply(params["head"], h, train=train)
        return recon, {"hidden": new_hidden, "head": params["head"]}

    def anomaly_score(self, params, x):
        recon, _ = self.apply(params, x, train=False)
        return jnp.mean(jnp.square(recon - x), axis=-1)

    def cost(self) -> ModelCost:
        ds = self.dims
        ls = [dense_cost(f"fc{i}", ds[i], ds[i + 1], self.act_bits, self.weight_bits)
              for i in range(len(ds) - 1)]
        return ModelCost(ls)

    def n_params(self) -> int:
        hidden, head = self.layers()
        return sum(l.n_params() for l in hidden) + head.n_params()


# ---------------------------------------------------------------------------
# KWS: 3-bit MLP (FINN)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KWSMLP:
    """490 (10 MFCC x 49 frames) -> 256 x3 (BN+ReLU) -> 12. 3-bit W/A,
    8-bit input (paper §3.4). Weight count 490*256+256*256*2+256*12=259 584."""

    in_dim: int = 490
    width: int = 256
    n_classes: int = 12
    weight_bits: int = 3
    act_bits: int = 3

    def layers(self):
        dims = [self.in_dim, self.width, self.width, self.width]
        hidden = [QDenseBatchNorm(dims[i], dims[i + 1], weight_bits=self.weight_bits,
                                  act_bits=self.act_bits) for i in range(3)]
        head = QDense(self.width, self.n_classes, weight_bits=self.weight_bits,
                      act_bits=32, relu=False)
        return hidden, head

    def init(self, key):
        hidden, head = self.layers()
        keys = jax.random.split(key, 4)
        return {"hidden": [l.init(k) for l, k in zip(hidden, keys[:3])],
                "head": head.init(keys[3])}

    def apply(self, params, x, train: bool = True):
        hidden, head = self.layers()
        new_hidden = []
        h = x
        for l, p in zip(hidden, params["hidden"]):
            h, p = l.apply(p, h, train=train)
            new_hidden.append(p)
        logits = head.apply(params["head"], h, train=train)
        return logits, {"hidden": new_hidden, "head": params["head"]}

    def cost(self) -> ModelCost:
        dims = [self.in_dim, self.width, self.width, self.width, self.n_classes]
        return ModelCost([
            dense_cost(f"fc{i}", dims[i], dims[i + 1], self.act_bits, self.weight_bits,
                       bias=False)
            for i in range(4)
        ])

    def n_weights(self) -> int:
        dims = [self.in_dim, self.width, self.width, self.width, self.n_classes]
        return sum(dims[i] * dims[i + 1] for i in range(4))


# ---------------------------------------------------------------------------
# IC: hls4ml v0.7 CNN (2-stack, no skips)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ICModel:
    """Paper §3.1.1 v0.7 model: 5 convs (32,4,32,32,4 filters; kernels
    1,4,4,4,4; strides 1,1,1,4,1) + dense head; fixed-point 8 total / 2
    integer bits (QKeras quantized_bits(8,2))."""

    filters: Tuple[int, ...] = (32, 4, 32, 32, 4)
    kernels: Tuple[int, ...] = (1, 4, 4, 4, 4)
    strides: Tuple[int, ...] = (1, 1, 1, 4, 1)
    n_classes: int = 10
    weight_bits: int = 8
    act_bits: int = 8
    in_hw: int = 32
    in_ch: int = 3

    def conv_layers(self):
        convs, cin = [], self.in_ch
        for f, k, s in zip(self.filters, self.kernels, self.strides):
            convs.append(QConv2D(cin, f, kernel=k, stride=s, padding="SAME",
                                 weight_bits=self.weight_bits,
                                 act_bits=self.act_bits, relu=True))
            cin = f
        return convs

    def feature_hw(self) -> int:
        hw = self.in_hw
        for s in self.strides:
            hw = -(-hw // s)  # ceil for SAME padding
        return hw

    def init(self, key):
        convs = self.conv_layers()
        keys = jax.random.split(key, len(convs) + 1)
        flat = self.feature_hw() ** 2 * self.filters[-1]
        head = QDense(flat, self.n_classes, weight_bits=self.weight_bits,
                      act_bits=32, relu=False)
        return {"convs": [c.init(k) for c, k in zip(convs, keys[:-1])],
                "head": head.init(keys[-1])}

    def apply(self, params, x, train: bool = True):
        convs = self.conv_layers()
        h = x
        for c, p in zip(convs, params["convs"]):
            h = c.apply(p, h, train=train)
        h = h.reshape(h.shape[0], -1)
        flat = self.feature_hw() ** 2 * self.filters[-1]
        head = QDense(flat, self.n_classes, weight_bits=self.weight_bits,
                      act_bits=32, relu=False)
        return head.apply(params["head"], h, train=train)

    def cost(self) -> ModelCost:
        ls, cin, hw = [], self.in_ch, self.in_hw
        for i, (f, k, s) in enumerate(zip(self.filters, self.kernels, self.strides)):
            hw = -(-hw // s)
            ls.append(conv_cost(f"conv{i}", cin, f, k, hw, hw,
                                self.act_bits, self.weight_bits))
            cin = f
        flat = hw * hw * self.filters[-1]
        ls.append(dense_cost("head", flat, self.n_classes,
                             self.act_bits, self.weight_bits))
        return ModelCost(ls)


# ---------------------------------------------------------------------------
# IC: CNV-W1A1 (FINN binary VGG)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CNVModel:
    """CNV-W1A1 (Umuroglu et al. 2017): 3 conv blocks (64,64 / 128,128 /
    256,256 3x3 VALID convs + 2x2 maxpool after the first two blocks... per
    the original: pool after each of the first two blocks and after none of
    the last) then FC 512, 512, 10. Binary W/A except 8-bit input layer.
    Weight count = 1 542 848 exactly (paper Table 1)."""

    channels: Tuple[int, ...] = (64, 64, 128, 128, 256, 256)
    fc: Tuple[int, ...] = (512, 512)
    n_classes: int = 10
    weight_bits: int = 1
    act_bits: int = 1
    in_hw: int = 32
    in_ch: int = 3
    pool_after: Tuple[int, ...] = (1, 3)  # 2x2 maxpool after these convs

    def conv_layers(self):
        convs, cin = [], self.in_ch
        for i, ch in enumerate(self.channels):
            # input layer consumes 8-bit images; the rest are binary
            convs.append(QConv2D(cin, ch, kernel=3, stride=1, padding="VALID",
                                 weight_bits=self.weight_bits,
                                 act_bits=8 if i == 0 else self.act_bits,
                                 weight_kind="binary", relu=False, use_bias=False))
            cin = ch
        return convs

    def init(self, key):
        convs = self.conv_layers()
        keys = jax.random.split(key, len(convs) + len(self.fc) + 1)
        params = {"convs": [c.init(k) for c, k in zip(convs, keys[: len(convs)])]}
        dims = [self.channels[-1], *self.fc, self.n_classes]
        fcs = []
        for i in range(len(dims) - 1):
            fc = QDense(dims[i], dims[i + 1], weight_bits=self.weight_bits,
                        act_bits=self.act_bits if i < len(dims) - 2 else 32,
                        weight_kind="binary", use_bias=False)
            fcs.append(fc.init(keys[len(convs) + i]))
        params["fcs"] = fcs
        return params

    def apply(self, params, x, train: bool = True):
        convs = self.conv_layers()
        h = x
        from repro.core.quantizers import ste_sign

        for i, (c, p) in enumerate(zip(convs, params["convs"])):
            h = c.apply(p, h, train=train)
            h = ste_sign(h)  # binary activation
            if i in self.pool_after:  # maxpool after blocks 1 and 2
                h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                          (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        h = h.reshape(h.shape[0], -1)
        dims = [h.shape[-1], *self.fc, self.n_classes]
        for i, p in enumerate(params["fcs"]):
            fc = QDense(dims[i], dims[i + 1], weight_bits=self.weight_bits,
                        act_bits=32, weight_kind="binary", use_bias=False)
            h = fc.apply(p, h, train=train)
            if i < len(params["fcs"]) - 1:
                h = ste_sign(h)
        return h

    def n_weights(self) -> int:
        total, cin, hw = 0, self.in_ch, self.in_hw
        for i, ch in enumerate(self.channels):
            total += 3 * 3 * cin * ch
            cin = ch
        total += self.channels[-1] * self.fc[0]
        total += self.fc[0] * self.fc[1]
        total += self.fc[1] * self.n_classes
        return total

    def cost(self) -> ModelCost:
        ls, cin, hw = [], self.in_ch, self.in_hw
        for i, ch in enumerate(self.channels):
            hw = hw - 2  # VALID 3x3
            ls.append(conv_cost(f"conv{i}", cin, ch, 3, hw, hw,
                                8 if i == 0 else 1, 1, bias=False))
            if i in self.pool_after:
                hw //= 2
            cin = ch
        dims = [self.channels[-1], *self.fc, self.n_classes]
        for i in range(len(dims) - 1):
            ls.append(dense_cost(f"fc{i}", dims[i], dims[i + 1], 1, 1, bias=False))
        return ModelCost(ls)


# ---------------------------------------------------------------------------
# IC: ResNet-8 (the MLPerf Tiny reference, mlcommons/tiny resnet_v1_eembc)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ResNet8:
    """mlcommons/tiny ``resnet_v1_eembc``: a 3x3 stem conv (16), then three
    residual stacks of two 3x3 convs each (16, 32, 64 filters; the last two
    stacks start with stride 2 and add a 1x1 stride-2 projection of the
    block input, with no BN on that branch; the first adds the identity),
    a ReLU after each add, 8x8 average pooling and a Dense(10) head.
    QAT with 8-bit weights and activations as the hls4ml IC model; every
    BN folds into its conv (``QConv2DBatchNorm``). 78 666 parameters
    (77 360 weights), 12 501 632 MACs per 32x32x3 sample."""

    filters: Tuple[int, ...] = (16, 32, 64)
    n_classes: int = 10
    weight_bits: int = 8
    act_bits: int = 8
    in_hw: int = 32
    in_ch: int = 3
    bn_eps: float = 1e-3

    def _bn_conv(self, cin, cout, stride=1):
        return QConv2DBatchNorm(cin, cout, kernel=3, stride=stride,
                                padding="SAME", weight_bits=self.weight_bits,
                                act_bits=self.act_bits, eps=self.bn_eps)

    def stem(self) -> QConv2DBatchNorm:
        return self._bn_conv(self.in_ch, self.filters[0])

    def residual_blocks(self):
        """Per stack: (conv1, conv2, projection or None for identity)."""
        out, cin = [], self.filters[0]
        for k, f in enumerate(self.filters):
            stride = 1 if k == 0 else 2
            proj = None
            if stride != 1 or cin != f:
                proj = QConv2D(cin, f, kernel=1, stride=stride,
                               padding="SAME", weight_bits=self.weight_bits,
                               act_bits=32)
            out.append((self._bn_conv(cin, f, stride), self._bn_conv(f, f),
                        proj))
            cin = f
        return out

    def head(self) -> QDense:
        return QDense(self.filters[-1], self.n_classes, weight_bits=32,
                      act_bits=32)

    def init(self, key):
        blocks = self.residual_blocks()
        keys = jax.random.split(key, 3 * len(blocks) + 2)
        params = {"stem": self.stem().init(keys[0]), "blocks": [],
                  "head": self.head().init(keys[-1])}
        for k, (c1, c2, proj) in enumerate(blocks):
            p = {"conv1": c1.init(keys[3 * k + 1]),
                 "conv2": c2.init(keys[3 * k + 2])}
            if proj is not None:
                p["proj"] = proj.init(keys[3 * k + 3])
            params["blocks"].append(p)
        return params

    def apply(self, params, x, train: bool = True):
        """Returns (logits, new_params); BN statistics update in train
        mode."""
        aq = IntQuantizer(bits=self.act_bits, signed=False)

        def act(y):
            return aq(jax.nn.relu(y))

        h, stem = self.stem().apply(params["stem"], x, train=train)
        h = act(h)
        new_blocks = []
        for (c1, c2, proj), p in zip(self.residual_blocks(),
                                     params["blocks"]):
            y, p1 = c1.apply(p["conv1"], h, train=train)
            y, p2 = c2.apply(p["conv2"], act(y), train=train)
            skip = h if proj is None else proj.apply(p["proj"], h,
                                                     train=train)
            h = act(y + skip)
            new_blocks.append(dict(p, conv1=p1, conv2=p2))
        logits = self.head().apply(params["head"], jnp.mean(h, axis=(1, 2)),
                                   train=train)
        return logits, dict(params, stem=stem, blocks=new_blocks)

    def _layers(self):
        """(name, k, cin, cout, out_hw, in_bits) of every conv."""
        out, hw = [], self.in_hw
        out.append(("stem", 3, self.in_ch, self.filters[0], hw, 8))
        cin = self.filters[0]
        for k, (c1, c2, proj) in enumerate(self.residual_blocks()):
            hw = -(-hw // c1.stride)
            out.append((f"b{k + 1}_conv1", 3, cin, c1.out_ch, hw,
                        self.act_bits))
            out.append((f"b{k + 1}_conv2", 3, c1.out_ch, c2.out_ch, hw,
                        self.act_bits))
            if proj is not None:
                out.append((f"b{k + 1}_proj", 1, cin, proj.out_ch, hw,
                            self.act_bits))
            cin = c2.out_ch
        return out

    def cost(self) -> ModelCost:
        ls = [conv_cost(name, cin, cout, k, hw, hw, b_a, self.weight_bits)
              for name, k, cin, cout, hw, b_a in self._layers()]
        ls.append(dense_cost("head", self.filters[-1], self.n_classes,
                             self.act_bits, self.weight_bits))
        return ModelCost(ls)

    def n_weights(self) -> int:
        return (sum(k * k * cin * cout
                    for _, k, cin, cout, _, _ in self._layers())
                + self.filters[-1] * self.n_classes)

    def n_params(self) -> int:
        blocks = self.residual_blocks()
        return (self.stem().n_params() + self.head().n_params()
                + sum(c1.n_params() + c2.n_params()
                      + (proj.n_params() if proj is not None else 0)
                      for c1, c2, proj in blocks))
