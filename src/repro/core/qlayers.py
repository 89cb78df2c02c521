"""Quantization-aware layers (QDense, QConv2D, QDenseBatchNorm,
QConv2DBatchNorm).

These are the paper's building blocks, expressed as pure init/apply pairs
(params are plain pytrees — no flax dependency):

  * ``QDense``          - FC layer with weight/activation quantizers attached.
  * ``QConv2D``         - NHWC conv with the same quantizer hooks.
  * ``QDenseBatchNorm`` - the paper's §3.3.1 contribution: BN folded into the
                          FC kernel *during training* (Eqs. 3-4), so the
                          deployed layer is a single affine:
                             k_folded = v * k_FC
                             b_folded = v * (b_FC - mu) + beta,
                          v = gamma / sqrt(sigma^2 + eps).
  * ``QConv2DBatchNorm``- the same fold for a conv (hls4ml's
                          ``QConv2DBatchnorm``), per output channel.

The deployment ("streamlined") path of each layer produces integer-only
arithmetic via core/streamline.py and runs on the fused Pallas kernel
(kernels/qmatmul.py) when enabled.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantizers import IntQuantizer, make_quantizer

Params = Any


def _init_dense(key, in_dim, out_dim, dtype=jnp.float32):
    wkey, _ = jax.random.split(key)
    limit = (6.0 / (in_dim + out_dim)) ** 0.5  # glorot uniform, like QKeras
    w = jax.random.uniform(wkey, (in_dim, out_dim), dtype, -limit, limit)
    b = jnp.zeros((out_dim,), dtype)
    return {"w": w, "b": b}


@dataclasses.dataclass(frozen=True)
class QDense:
    in_dim: int
    out_dim: int
    weight_bits: int = 8
    act_bits: int = 8
    weight_kind: str = "int"
    act_kind: str = "int"
    use_bias: bool = True
    relu: bool = False  # merged ReLU (paper §3.1.3)

    def init(self, key, dtype=jnp.float32) -> Params:
        return _init_dense(key, self.in_dim, self.out_dim, dtype)

    @property
    def wq(self):
        return make_quantizer(self.weight_bits, self.weight_kind, axis=0)

    @property
    def aq(self):
        return make_quantizer(self.act_bits, self.act_kind)

    def apply(self, params: Params, x, train: bool = True):
        w = params["w"]
        if self.wq is not None:
            w = self.wq(w)
        y = x @ w
        if self.use_bias:
            y = y + params["b"]
        if self.relu:
            y = jax.nn.relu(y)
        if self.aq is not None:
            y = self.aq(y)
        return y

    def n_params(self) -> int:
        return self.in_dim * self.out_dim + (self.out_dim if self.use_bias else 0)


@dataclasses.dataclass(frozen=True)
class QDenseBatchNorm:
    """FC + BN folded during the forward pass (paper Eqs. 3-4).

    Training keeps separate (k_FC, b_FC, gamma, beta, mu, sigma2); every
    forward computes the folded kernel and quantizes *the folded kernel*, so
    train-time arithmetic matches the deployed integer layer exactly — this is
    why the paper's Table 4 "With folding" row changes AUC.
    """

    in_dim: int
    out_dim: int
    weight_bits: int = 8
    act_bits: int = 8
    relu: bool = True
    momentum: float = 0.99
    eps: float = 1e-3

    def init(self, key, dtype=jnp.float32) -> Params:
        p = _init_dense(key, self.in_dim, self.out_dim, dtype)
        p.update(
            gamma=jnp.ones((self.out_dim,), dtype),
            beta=jnp.zeros((self.out_dim,), dtype),
            mu=jnp.zeros((self.out_dim,), dtype),
            sigma2=jnp.ones((self.out_dim,), dtype),
        )
        return p

    @property
    def wq(self):
        return make_quantizer(self.weight_bits, "int", axis=0)

    @property
    def aq(self):
        return make_quantizer(self.act_bits, "int")

    def fold(self, params: Params) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Eqs. 3-4: returns (k_folded, b_folded)."""
        v = params["gamma"] / jnp.sqrt(params["sigma2"] + self.eps)
        k_folded = params["w"] * v[None, :]
        b_folded = v * (params["b"] - params["mu"]) + params["beta"]
        return k_folded, b_folded

    def apply(self, params: Params, x, train: bool = True):
        """Returns (y, new_params) in train mode; (y, params) in eval mode."""
        if train:
            # batch statistics over all leading axes
            y_fc = x @ params["w"] + params["b"]
            red = tuple(range(y_fc.ndim - 1))
            mu_b = jnp.mean(y_fc, axis=red)
            var_b = jnp.var(y_fc, axis=red)
            m = self.momentum
            params = dict(
                params,
                mu=m * params["mu"] + (1 - m) * jax.lax.stop_gradient(mu_b),
                sigma2=m * params["sigma2"] + (1 - m) * jax.lax.stop_gradient(var_b),
            )
            # fold with *batch* stats so training sees the deployed arithmetic
            v = params["gamma"] / jnp.sqrt(var_b + self.eps)
            k_folded = params["w"] * v[None, :]
            b_folded = v * (params["b"] - mu_b) + params["beta"]
        else:
            k_folded, b_folded = self.fold(params)

        if self.wq is not None:
            k_folded = self.wq(k_folded)
        y = x @ k_folded + b_folded
        if self.relu:
            y = jax.nn.relu(y)
        if self.aq is not None:
            y = self.aq(y)
        return y, params

    def n_params(self) -> int:
        return self.in_dim * self.out_dim + 5 * self.out_dim


@dataclasses.dataclass(frozen=True)
class QConv2D:
    """NHWC conv with quantizer hooks + optional merged ReLU."""

    in_ch: int
    out_ch: int
    kernel: int = 3
    stride: int = 1
    padding: str = "SAME"
    weight_bits: int = 8
    act_bits: int = 8
    weight_kind: str = "int"
    relu: bool = False
    use_bias: bool = True

    def init(self, key, dtype=jnp.float32) -> Params:
        fan_in = self.in_ch * self.kernel * self.kernel
        fan_out = self.out_ch * self.kernel * self.kernel
        limit = (6.0 / (fan_in + fan_out)) ** 0.5
        w = jax.random.uniform(
            key, (self.kernel, self.kernel, self.in_ch, self.out_ch), dtype, -limit, limit
        )
        return {"w": w, "b": jnp.zeros((self.out_ch,), dtype)}

    @property
    def wq(self):
        return make_quantizer(self.weight_bits, self.weight_kind, axis=(0, 1, 2))

    @property
    def aq(self):
        return make_quantizer(self.act_bits, "int")

    def apply(self, params: Params, x, train: bool = True):
        w = params["w"]
        if self.wq is not None:
            # per-output-channel scale over (kh, kw, cin)
            q = IntQuantizer(bits=self.weight_bits, signed=True, narrow=True)
            qmax = q.qmax
            amax = jnp.max(jnp.abs(w), axis=(0, 1, 2), keepdims=True)
            s = jax.lax.stop_gradient(jnp.maximum(amax, 1e-8) / qmax)
            from repro.core.quantizers import ste_clip, ste_round

            w = ste_clip(ste_round(w / s), float(q.qmin), float(q.qmax)) * s
        y = jax.lax.conv_general_dilated(
            x, w,
            window_strides=(self.stride, self.stride),
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["b"]
        if self.relu:
            y = jax.nn.relu(y)
        if self.aq is not None:
            y = self.aq(y)
        return y

    def n_params(self) -> int:
        return self.kernel * self.kernel * self.in_ch * self.out_ch + (
            self.out_ch if self.use_bias else 0
        )


@dataclasses.dataclass(frozen=True)
class QConv2DBatchNorm:
    """NHWC conv + BN folded during the forward pass, per output channel
    (hls4ml's ``QConv2DBatchnorm``; Eqs. 3-4 of ``QDenseBatchNorm``).

    The folded kernel is fake-quantized per output channel with a po2
    scale, the grid the QIR export deploys. ``apply`` returns the folded
    pre-activation: the caller places the ReLU and activation quantizer,
    which a residual block puts after its add.
    """

    in_ch: int
    out_ch: int
    kernel: int = 3
    stride: int = 1
    padding: str = "SAME"
    weight_bits: int = 8
    act_bits: int = 8
    momentum: float = 0.99
    eps: float = 1e-3

    def init(self, key, dtype=jnp.float32) -> Params:
        fan_in = self.in_ch * self.kernel * self.kernel
        w = jax.random.normal(
            key, (self.kernel, self.kernel, self.in_ch, self.out_ch),
            dtype) * (2.0 / fan_in) ** 0.5                 # he_normal
        c = self.out_ch
        return {"w": w, "b": jnp.zeros((c,), dtype),
                "gamma": jnp.ones((c,), dtype), "beta": jnp.zeros((c,), dtype),
                "mu": jnp.zeros((c,), dtype), "sigma2": jnp.ones((c,), dtype)}

    def _conv(self, x, w):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(self.stride, self.stride),
            padding=self.padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def apply(self, params: Params, x, train: bool = True):
        """Returns (y, new_params); BN statistics update in train mode."""
        if train:
            y0 = self._conv(x, params["w"]) + params["b"]
            mu_b = jnp.mean(y0, axis=(0, 1, 2))
            var_b = jnp.var(y0, axis=(0, 1, 2))
            m = self.momentum
            params = dict(
                params,
                mu=m * params["mu"] + (1 - m) * jax.lax.stop_gradient(mu_b),
                sigma2=(m * params["sigma2"]
                        + (1 - m) * jax.lax.stop_gradient(var_b)))
        else:
            mu_b, var_b = params["mu"], params["sigma2"]
        v = params["gamma"] / jnp.sqrt(var_b + self.eps)
        k = params["w"] * v
        b = v * (params["b"] - mu_b) + params["beta"]
        if self.weight_bits < 32:
            k = IntQuantizer(bits=self.weight_bits, signed=True, narrow=True,
                             axis=(0, 1, 2), po2=True)(k)
        return self._conv(x, k) + b, params

    def n_params(self) -> int:
        return (self.kernel * self.kernel * self.in_ch * self.out_ch
                + 5 * self.out_ch)
