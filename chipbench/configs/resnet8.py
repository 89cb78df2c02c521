"""ResNet-8 (the MLPerf Tiny image-classification reference: a 3x3 stem
conv, three residual stacks of 16, 32, 64 filters, 8x8 average pooling,
Dense(10)): weights from the seed, the program's export of them, and the
plain reference.

The reference implements the served model's arithmetic from its definition
in float64 NumPy and imports nothing of the program. The seeded weights are
already BN-folded. Per conv, the kernel is quantized per output channel to
narrow 8-bit codes on a power-of-two scale (``po2(max|w| / 127)``, round
half to even). A plain conv's pre-activation is ``acc * s_w * s_in + b``,
with ``b`` snapped to the accumulator grid ``s_w * s_in``. A residual
join's is the sum of its main conv's and its skip branch's (the block
input, or its 1x1 projection), with both biases snapped to the finer of
the two branch grids. Each is quantized half-up onto the unsigned 8-bit
grid of a power-of-two scale chosen from the layer's reach (``sum|w| *
s_in * q_max + |b|``, summed over both branches of a join, over 255,
rounded to a power of two); then the mean of the last codes over the 8x8
map meets the float head. Every value is a multiple of a power-of-two
step far inside float64's 53 bits, so the integer arithmetic is exact.
"""

from __future__ import annotations

import numpy as np


def input_shape(cfg):
    return (cfg["in_hw"], cfg["in_hw"], cfg["in_ch"])


def _blocks(cfg):
    """Per stack: (output width, filters, input channels, stride, whether
    the skip branch is a projection)."""
    out, hw, cin = [], cfg["in_hw"], cfg["filters"][0]
    for k, f in enumerate(cfg["filters"]):
        stride = 1 if k == 0 else 2
        hw = -(-hw // stride)
        out.append((hw, f, cin, stride, stride != 1 or cin != f))
        cin = f
    return out


def layers(cfg):
    """The served layers with the kernel that serves each: the stem and
    each stack's first conv on the conv-threshold kernel; each join (the
    stack's second conv, its 1x1 projection where it has one, and the
    read of the skip operand) on the join kernel; then the head."""
    from chipbench import cost

    a, w = cfg["act_bits"], cfg["weight_bits"]
    hw, f0 = cfg["in_hw"], cfg["filters"][0]
    out = [cost.conv("stem", "conv_threshold", 3, cfg["in_ch"], f0, hw, hw,
                     in_bits=cfg["input_bits"], out_bits=a, w_bits=w)]
    for k, (ohw, f, cin, stride, proj) in enumerate(_blocks(cfg)):
        out.append(cost.conv(f"b{k + 1}_conv1", "conv_threshold", 3, cin, f,
                             ohw * stride, ohw, in_bits=a, out_bits=a,
                             w_bits=w))
        join = cost.conv(f"b{k + 1}_conv2", "conv_threshold_add", 3, f, f,
                         ohw, ohw, in_bits=a, out_bits=a, w_bits=w)
        if proj:
            # the projection reads only the pixels at its stride
            p = cost.conv(f"b{k + 1}_proj", "conv_threshold_add", 1, cin, f,
                          ohw, ohw, in_bits=a, out_bits=0, w_bits=w)
            for key in ("weights", "ops", "weight_bytes", "act_bytes"):
                join[key] += p[key]
        else:
            join["act_bytes"] += ohw * ohw * f * a / 8     # identity codes
        out.append(join)
    out.append(cost.dense("head", "float_head", cfg["filters"][-1],
                          cfg["n_classes"], in_bits=a, out_bits=32,
                          w_bits=32))
    return out


def make_params(cfg, key):
    """The float parameters, BN already folded, random from ``key``
    (traced under ``jit``).

    Conv weights have a positive mean: the export picks each layer's
    activation scale from its worst-case reach (``sum|w|``), and against
    that scale a zero-mean random layer's pre-activations shrink by
    sqrt(fan-in), so by the second stack every code is 0 and the logits no
    longer depend on the input. With the mean at 3/8 of the range every
    layer's codes spread over the grid, as a trained network's do."""
    import jax
    import jax.numpy as jnp

    blocks = _blocks(cfg)
    keys = iter(jax.random.split(key, 2 + 6 * len(blocks) + 2))

    def conv(k, cin, cout):
        lim = 1.0 / (k * k * cin)
        return {"w": jax.random.uniform(next(keys), (k, k, cin, cout),
                                        jnp.float32, -0.25 * lim, 1.75 * lim),
                "b": 0.1 * jax.random.normal(next(keys), (cout,),
                                             jnp.float32)}

    params = {"stem": conv(3, cfg["in_ch"], cfg["filters"][0]),
              "blocks": []}
    for _, f, cin, _, proj in blocks:
        p = {"conv1": conv(3, cin, f), "conv2": conv(3, f, f)}
        if proj:
            p["proj"] = conv(1, cin, f)
        params["blocks"].append(p)
    f, c = cfg["filters"][-1], cfg["n_classes"]
    lim = (6.0 / (f + c)) ** 0.5
    params["head"] = {
        "w": jax.random.uniform(next(keys), (f, c), jnp.float32, -lim, lim),
        "b": 0.1 * jax.random.normal(next(keys), (c,), jnp.float32)}
    return params


def build_graph(cfg, params):
    """The program's export of ``params``: (QIR graph, input scale)."""
    from repro.core.qir import export_qcnn
    from repro.models.tiny import ResNet8

    model = ResNet8(filters=tuple(cfg["filters"]),
                    n_classes=cfg["n_classes"],
                    weight_bits=cfg["weight_bits"], act_bits=cfg["act_bits"],
                    in_hw=cfg["in_hw"], in_ch=cfg["in_ch"])
    if (model.n_params(), model.n_weights()) != (cfg["n_params"],
                                                 cfg["n_weights"]):
        raise ValueError(f"ResNet8 has {model.n_params()} parameters and "
                         f"{model.n_weights()} weights, the configuration "
                         f"{cfg['n_params']} and {cfg['n_weights']}")
    graph = export_qcnn(model, params, in_scale=cfg["in_scale"])
    return graph, cfg["in_scale"]


def _po2(s):
    return 2.0 ** np.round(np.log2(np.clip(s, 2.0 ** -24, 2.0 ** 24)))


def _quant_w(w, bits):
    """Narrow signed codes (k, k, cin, f) and the po2 scale (f,)."""
    q = 2 ** (bits - 1) - 1
    w = np.asarray(w, np.float64)
    s = _po2(np.maximum(np.max(np.abs(w.reshape(-1, w.shape[-1])), axis=0),
                        1e-8) / q)
    return np.clip(np.round(w / s), -q, q), s


def _conv(x, w, stride):
    """SAME conv of (n, h, w, c) by (k, k, c, f), by im2col (float64)."""
    k = w.shape[0]
    n, h, wd, c = x.shape
    oh, ow = -(-h // stride), -(-wd // stride)
    ph = max((oh - 1) * stride + k - h, 0)
    pw = max((ow - 1) * stride + k - wd, 0)
    x = np.pad(x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2),
                   (0, 0)))
    cols = np.lib.stride_tricks.sliding_window_view(x, (k, k), axis=(1, 2))
    cols = cols[:, ::stride, ::stride][:, :oh, :ow]
    cols = cols.transpose(0, 1, 2, 4, 5, 3).reshape(n * oh * ow, k * k * c)
    return (cols @ w.reshape(k * k * c, -1)).reshape(n, oh, ow, -1)


def _act(y, s_out, qa):
    return np.clip(np.floor(y / s_out + 0.5), 0, qa)


def reference(cfg, params, x, input_bits=None, block=32):
    """Logits (float64) for signed input codes ``x`` (n, hw, hw, ch), in
    blocks of ``block`` rows.

    ``input_bits`` below the configuration's 8 requantizes the input codes
    to that many bits first: the lower-precision control."""
    x = np.asarray(x, np.float64)
    bits = cfg["input_bits"] if input_bits is None else input_bits
    if bits < cfg["input_bits"]:
        step = 2.0 ** (cfg["input_bits"] - bits)
        lim = 2.0 ** (bits - 1)
        x = np.clip(np.round(x / step), -lim, lim - 1) * step
    wb, qa = cfg["weight_bits"], 2 ** cfg["act_bits"] - 1

    def plain(codes, s_in, q_in, layer, stride):
        w, s_w = _quant_w(layer["w"], wb)
        g = s_w * s_in
        b = np.round(np.asarray(layer["b"], np.float64) / g) * g
        reach = np.max(np.sum(np.abs(w * s_w).reshape(-1, w.shape[-1]),
                              axis=0) * s_in * q_in + np.abs(b))
        s_out = float(_po2(max(reach, 1e-8) / qa))
        return _act(_conv(codes, w, stride) * g + b, s_out, qa), s_out

    def join(codes, s_x, u, s_u, blk):
        w, s_w = _quant_w(blk["conv2"]["w"], wb)
        f = w.shape[-1]
        g_main = s_w * s_x
        reach = (np.sum(np.abs(w * s_w).reshape(-1, f), axis=0) * s_x * qa)
        if "proj" in blk:
            wp, s_p = _quant_w(blk["proj"]["w"], wb)
            g_skip = s_p * s_u
            reach = reach + (np.sum(np.abs(wp * s_p).reshape(-1, f), axis=0)
                             * s_u * qa)
        else:
            g_skip = np.full(f, s_u)
            reach = reach + s_u * qa
        grid = np.minimum(g_main, g_skip)
        b = np.round(np.asarray(blk["conv2"]["b"], np.float64) / grid) * grid
        if "proj" in blk:
            b_p = (np.round(np.asarray(blk["proj"]["b"], np.float64) / grid)
                   * grid)
            skip = _conv(u, wp, 2) * g_skip + b_p
            b_all = b + b_p
        else:
            skip = u * s_u
            b_all = b
        s_out = float(_po2(max(np.max(reach + np.abs(b_all)), 1e-8) / qa))
        y = _conv(codes, w, 1) * g_main + b + skip
        return _act(y, s_out, qa), s_out

    w_h = np.asarray(params["head"]["w"], np.float64)
    b_h = np.asarray(params["head"]["b"], np.float64)
    out = []
    for s0 in range(0, x.shape[0], block):
        h, s_h = plain(x[s0:s0 + block], cfg["in_scale"], 127, params["stem"], 1)
        for blk, (_, _, _, stride, _) in zip(params["blocks"], _blocks(cfg)):
            mid, s_mid = plain(h, s_h, qa, blk["conv1"], stride)
            h, s_h = join(mid, s_mid, h, s_h, blk)
        pooled = np.mean(h * s_h, axis=(1, 2))
        out.append(pooled @ w_h + b_h)
    return np.concatenate(out)
