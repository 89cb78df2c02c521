"""Operations and bytes a model needs, computed from its shapes alone — the
yardstick of the roofline and MFU metrics.

Each configuration's module (``configs/<config>.py``) lists its layers with
``layers(cfg)``, built here by ``conv`` and ``dense`` from the shapes and
bit widths its configuration defines; each layer names the kernel that
serves it. This module does only the arithmetic over such lists, so a new
configuration brings its own layer list and no edit here.

Operations count a multiply and an add as two, as ``core/bops.py``'s
``ModelCost.flops`` does (KWS 519,168 and CNV 118,922,752 per sample).
Bytes count each code and weight at the bit width its configuration
defines (1 bit for CNV's binary layers, 8 for its input layer) and each
activation read or written once, so a change of operand form or packing in
the program cannot push a roofline share past 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence


def conv(name: str, kernel: str, k: int, cin: int, cout: int, in_hw: int,
         out_hw: int, in_bits: int, out_bits: int, w_bits: int,
         groups: int = 1) -> Dict:
    """A square ``k`` x ``k`` convolution over a square ``in_hw`` input to
    a square ``out_hw`` output (stride and padding are in the two sizes)."""
    weights = k * k * (cin // groups) * cout
    return {"name": name, "kernel": kernel, "weights": weights,
            "ops": 2 * weights * out_hw * out_hw,
            "act_bytes": (in_hw * in_hw * cin * in_bits
                          + out_hw * out_hw * cout * out_bits) / 8,
            "weight_bytes": weights * w_bits / 8}


def dense(name: str, kernel: str, n_in: int, n_out: int, in_bits: int,
          out_bits: int, w_bits: int) -> Dict:
    """A dense layer of ``n_in`` x ``n_out`` weights."""
    weights = n_in * n_out
    return {"name": name, "kernel": kernel, "weights": weights,
            "ops": 2 * weights,
            "act_bytes": (n_in * in_bits + n_out * out_bits) / 8,
            "weight_bytes": weights * w_bits / 8}


def ops_per_sample(layers: Sequence[Dict]) -> int:
    """Operations of ``layers`` for one sample."""
    return sum(layer["ops"] for layer in layers)


def roofline_s(layers: Sequence[Dict], samples: int, calls: float,
               peak_ops: float, peak_bytes_per_s: float) -> Dict:
    """Least time the chip could take for ``samples`` samples through
    ``layers`` in ``calls`` waves, each wave reading every weight once: the
    larger of operations over the peak rate and bytes over the memory
    bandwidth, with the bound that sets it."""
    ops = samples * ops_per_sample(layers)
    nbytes = (samples * sum(layer["act_bytes"] for layer in layers)
              + calls * sum(layer["weight_bytes"] for layer in layers))
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes_per_s
    return {"ops": ops, "bytes": nbytes, "least_s": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}
