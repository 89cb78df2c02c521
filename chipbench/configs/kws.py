"""KWS (FINN's 3-bit MLP, 490 -> 256 x 3 -> 12): weights from the seed, the
program's export of them, and the plain reference.

The reference implements the served model's arithmetic from its definition
in float64 NumPy and imports nothing of the program: per hidden layer, batch
norm folded into the dense kernel (paper Eqs. 3-4), the kernel quantized per
output channel to 3-bit narrow codes (scale max|k| / 3, round half to even),
the pre-activation ``acc * s_w * s_in + b`` quantized half-up onto the
unsigned 3-bit grid of a power-of-two scale chosen from the layer's reach
(``sum|k| * s_in * 3 + |b|`` over 7, rounded to a power of two), then the
float head on the last codes.
"""

from __future__ import annotations

import numpy as np


def input_shape(cfg):
    return (cfg["in_dim"],)


def layers(cfg):
    """The served layers with the kernel that serves each: the hidden
    threshold stages in the MLP megakernel, then the float head."""
    from chipbench import cost

    dims = ([cfg["in_dim"]] + [cfg["hidden_size"]] * cfg["num_hidden_layers"]
            + [cfg["n_classes"]])
    out = []
    for i in range(len(dims) - 1):
        head = i == len(dims) - 2
        out.append(cost.dense(
            f"fc{i}", "float_head" if head else "mlp_megakernel",
            dims[i], dims[i + 1],
            in_bits=cfg["input_bits"] if i == 0 else cfg["act_bits"],
            out_bits=32 if head else cfg["act_bits"],
            w_bits=32 if head else cfg["weight_bits"]))
    return out


def make_params(cfg, key):
    """The float parameters, random from ``key`` (traced under ``jit``).

    Hidden weights have a positive mean: the export picks each layer's
    activation scale from its worst-case reach (``sum|k|``), and against
    that scale a zero-mean random layer's pre-activations shrink by
    sqrt(fan-in), so by the second layer every code is 0 and the logits
    no longer depend on the input. With the mean at half the range every
    layer's codes spread over the grid, as a trained network's do."""
    import jax
    import jax.numpy as jnp

    dims = [cfg["in_dim"]] + [cfg["hidden_size"]] * cfg["num_hidden_layers"]
    keys = jax.random.split(key, 6 * (len(dims) - 1) + 2)
    u = jax.random.uniform
    n = jax.random.normal
    hidden = []
    for i in range(len(dims) - 1):
        k = keys[6 * i:6 * i + 6]
        din, dout = dims[i], dims[i + 1]
        lim = (6.0 / (din + dout)) ** 0.5
        hidden.append({
            "w": u(k[0], (din, dout), jnp.float32, -0.5 * lim, 1.5 * lim),
            "b": 0.1 * n(k[1], (dout,), jnp.float32),
            "gamma": u(k[2], (dout,), jnp.float32, 0.5, 1.5),
            "beta": 0.1 * n(k[3], (dout,), jnp.float32),
            "mu": 0.1 * n(k[4], (dout,), jnp.float32),
            "sigma2": u(k[5], (dout,), jnp.float32, 0.5, 2.0),
        })
    h, c = cfg["hidden_size"], cfg["n_classes"]
    lim = (6.0 / (h + c)) ** 0.5
    head = {"w": u(keys[-2], (h, c), jnp.float32, -lim, lim),
            "b": 0.1 * n(keys[-1], (c,), jnp.float32)}
    return {"hidden": hidden, "head": head}


def build_graph(cfg, params):
    """The program's export of ``params``: (QIR graph, input scale)."""
    from repro.core.qir import export_qmlp
    from repro.models.tiny import KWSMLP

    model = KWSMLP(in_dim=cfg["in_dim"], width=cfg["hidden_size"],
                   n_classes=cfg["n_classes"],
                   weight_bits=cfg["weight_bits"], act_bits=cfg["act_bits"])
    hidden_defs, _ = model.layers()
    if len(hidden_defs) != cfg["num_hidden_layers"]:
        raise ValueError(f"KWSMLP has {len(hidden_defs)} hidden layers, "
                         f"the configuration {cfg['num_hidden_layers']}")
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"],
                        meta={"model": "KWSMLP"}, freeze_scales=True,
                        in_scale=cfg["in_scale"], bn_eps=cfg["bn_eps"])
    return graph, cfg["in_scale"]


def _po2(s):
    return 2.0 ** np.round(np.log2(np.clip(s, 2.0 ** -24, 2.0 ** 24)))


def reference(cfg, params, x, input_bits=None):
    """Logits (float64) for signed input codes ``x`` (n, in_dim).

    ``input_bits`` below the configuration's 8 requantizes the input codes
    to that many bits first: the lower-precision control."""
    codes = np.asarray(x, np.float64)
    bits = cfg["input_bits"] if input_bits is None else input_bits
    if bits < cfg["input_bits"]:
        step = 2.0 ** (cfg["input_bits"] - bits)
        lim = 2.0 ** (bits - 1)
        codes = np.clip(np.round(codes / step), -lim, lim - 1) * step
    s_in = float(cfg["in_scale"])
    qw = 2 ** (cfg["weight_bits"] - 1) - 1          # narrow signed codes
    qa = 2 ** cfg["act_bits"] - 1                   # unsigned act codes
    reach_codes = 2 ** (cfg["act_bits"] - 1) - 1
    for p in params["hidden"]:
        p = {k: np.asarray(v, np.float64) for k, v in p.items()}
        v = p["gamma"] / np.sqrt(p["sigma2"] + cfg["bn_eps"])
        k = p["w"] * v[None, :]
        b = v * (p["b"] - p["mu"]) + p["beta"]
        s_w = np.maximum(np.max(np.abs(k), axis=0), 1e-8) / qw
        w_int = np.clip(np.round(k / s_w), -qw, qw)
        reach = np.max(np.sum(np.abs(k), axis=0) * s_in * reach_codes
                       + np.abs(b))
        s_out = float(_po2(max(reach, 1e-8) / qa))
        y = (codes @ w_int) * (s_w * s_in) + b
        codes = np.clip(np.floor(y / s_out + 0.5), 0, qa)
        s_in = s_out
    w = np.asarray(params["head"]["w"], np.float64)
    b = np.asarray(params["head"]["b"], np.float64)
    return (codes * s_in) @ w + b
