"""CNV-W1A1 (FINN's binary VGG: six 3x3 convs, FC 512, 512, 10): weights
from the seed, the program's export of them, and the plain reference.

The reference is the BinaryNet forward pass in NumPy and imports nothing of
the program: every weight is its sign (+1 for w >= 0, else -1); the input
layer takes the signed 8-bit codes as values; every later activation is
the sign of its accumulator (+1 for acc >= 0); a 2x2 max-pool follows the
convs in ``pool_after``; the logits are the last accumulator. Every value
is an integer below 2^24, so float32 BLAS computes it exactly.
"""

from __future__ import annotations

import numpy as np


def input_shape(cfg):
    return (cfg["in_hw"], cfg["in_hw"], cfg["in_ch"])


def layers(cfg):
    """The served layers with the kernel that serves each: the VALID convs
    (a 2x2 max-pool after those in ``pool_after``) in the conv-threshold
    kernel, the hidden FC stages in the MLP megakernel, then the head."""
    from chipbench import cost

    k, hw, cin = cfg["kernel"], cfg["in_hw"], cfg["in_ch"]
    out = []
    for i, cout in enumerate(cfg["channels"]):
        oh = hw - k + 1
        out.append(cost.conv(
            f"conv{i}", "conv_threshold", k, cin, cout, hw, oh,
            in_bits=cfg["input_bits"] if i == 0 else cfg["act_bits"],
            out_bits=cfg["act_bits"], w_bits=cfg["weight_bits"]))
        hw = oh // 2 if i in cfg["pool_after"] else oh
        cin = cout
    dims = [hw * hw * cin, *cfg["fc"], cfg["n_classes"]]
    for j in range(len(dims) - 1):
        head = j == len(dims) - 2
        out.append(cost.dense(
            f"fc{j}", "float_head" if head else "mlp_megakernel",
            dims[j], dims[j + 1], in_bits=cfg["act_bits"],
            out_bits=32 if head else cfg["act_bits"],
            w_bits=cfg["weight_bits"]))
    return out


def make_params(cfg, key):
    """The float parameters, random from ``key`` (traced under ``jit``)."""
    import jax
    import jax.numpy as jnp

    k, cin = cfg["kernel"], cfg["in_ch"]
    dims_fc = [cfg["channels"][-1], *cfg["fc"], cfg["n_classes"]]
    keys = jax.random.split(key, len(cfg["channels"]) + len(dims_fc) - 1)
    convs = []
    for i, cout in enumerate(cfg["channels"]):
        convs.append({"w": jax.random.uniform(keys[i], (k, k, cin, cout),
                                              jnp.float32, -1.0, 1.0),
                      "b": jnp.zeros((cout,), jnp.float32)})
        cin = cout
    fcs = []
    for j in range(len(dims_fc) - 1):
        fcs.append({"w": jax.random.uniform(
            keys[len(convs) + j], (dims_fc[j], dims_fc[j + 1]), jnp.float32,
            -1.0, 1.0),
            "b": jnp.zeros((dims_fc[j + 1],), jnp.float32)})
    return {"convs": convs, "fcs": fcs}


def build_graph(cfg, params):
    """The program's export of ``params``: (QIR graph, input scale)."""
    from repro.core.qir import export_qcnn
    from repro.models.tiny import CNVModel

    model = CNVModel(channels=tuple(cfg["channels"]), fc=tuple(cfg["fc"]),
                     n_classes=cfg["n_classes"],
                     weight_bits=cfg["weight_bits"],
                     act_bits=cfg["act_bits"], in_hw=cfg["in_hw"],
                     in_ch=cfg["in_ch"], pool_after=tuple(cfg["pool_after"]))
    graph = export_qcnn(model, params)
    return graph, graph.meta["in_scale"]


def _sign(a):
    return np.where(a >= 0, 1.0, -1.0).astype(np.float32)


def _conv_valid(x, w):
    """(n, h, w, c) x (k, k, c, o) -> (n, h-k+1, w-k+1, o), by im2col."""
    k = w.shape[0]
    n, h, wd, c = x.shape
    oh, ow = h - k + 1, wd - k + 1
    cols = np.lib.stride_tricks.sliding_window_view(x, (k, k), axis=(1, 2))
    cols = cols.transpose(0, 1, 2, 4, 5, 3).reshape(n * oh * ow, k * k * c)
    return (cols @ w.reshape(k * k * c, -1)).reshape(n, oh, ow, -1)


def _pool2(x):
    n, h, w, c = x.shape
    return x[:, :h // 2 * 2, :w // 2 * 2].reshape(
        n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def reference(cfg, params, x, input_bits=None, block=32):
    """Logits (float64) for signed input codes ``x`` (n, hw, hw, ch), in
    blocks of ``block`` rows.

    ``input_bits`` below the configuration's 8 requantizes the input codes
    to that many bits first: the lower-precision control."""
    x = np.asarray(x, np.float32)
    bits = cfg["input_bits"] if input_bits is None else input_bits
    if bits < cfg["input_bits"]:
        step = 2.0 ** (cfg["input_bits"] - bits)
        lim = 2.0 ** (bits - 1)
        x = (np.clip(np.round(x / step), -lim, lim - 1) * step).astype(
            np.float32)
    conv_w = [_sign(np.asarray(p["w"])) for p in params["convs"]]
    fc_w = [_sign(np.asarray(p["w"])) for p in params["fcs"]]
    out = []
    for s in range(0, x.shape[0], block):
        h = x[s:s + block]
        for i, w in enumerate(conv_w):
            h = _sign(_conv_valid(h, w))
            if i in cfg["pool_after"]:
                h = _pool2(h)
        h = h.reshape(h.shape[0], -1)
        for j, w in enumerate(fc_w):
            h = h @ w
            if j < len(fc_w) - 1:
                h = _sign(h)
        out.append(h.astype(np.float64))
    return np.concatenate(out)
