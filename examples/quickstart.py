"""Quickstart: the paper's codesign workflow on one page.

1. Build a quantized model (QAT, arbitrary bit width)      [C1]
2. Train it on synthetic data                              [C9]
3. Fold BN + merge ReLU (training-time fusion)             [C3]
4. Streamline to an integer-only threshold graph           [C2]
5. Execute the deployed graph on the fused Pallas kernel   [C4]
6. Report BOPs / weight-memory / roofline latency          [C7]

Run: PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bops import ModelCost, dense_cost
from repro.core.codesign import deploy_report, train_tiny
from repro.core.qlayers import QDense, QDenseBatchNorm
from repro.core.streamline import streamline_mlp
from repro.kernels import ops

# --- 1. a 4-bit MLP classifier (QDense+BN stages, merged ReLU) -------------
DIMS, N_CLASSES, BITS = [32, 24, 16], 4, 4
layers = [QDenseBatchNorm(DIMS[i], DIMS[i + 1], weight_bits=BITS,
                          act_bits=BITS) for i in range(len(DIMS) - 1)]
head = QDense(DIMS[-1], N_CLASSES, weight_bits=32, act_bits=32)

key = jax.random.PRNGKey(0)
params = {"hidden": [l.init(k) for l, k in zip(layers, jax.random.split(key, 2))],
          "head": head.init(jax.random.fold_in(key, 9))}

# --- 2. train on a synthetic 4-class problem --------------------------------
protos = jax.random.normal(jax.random.PRNGKey(7), (N_CLASSES, DIMS[0])) * 2


def make_batch(step):
    k = jax.random.PRNGKey(step)
    y = jax.random.randint(k, (64,), 0, N_CLASSES)
    x = protos[y] + 0.5 * jax.random.normal(jax.random.fold_in(k, 1),
                                            (64, DIMS[0]))
    return x, y


def forward(ps, x, train=False):
    h, new_hidden = x, []
    for l, p in zip(layers, ps["hidden"]):
        h, p = l.apply(p, h, train=train)
        new_hidden.append(p)
    return head.apply(ps["head"], h, train=train), new_hidden


def loss_fn(ps, batch):
    x, y = batch
    logits, _ = forward(ps, x)
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, y[:, None], 1)[:, 0])


params, losses = train_tiny(loss_fn, params, make_batch, steps=150, lr=3e-3)
print(f"[2] QAT training: loss {losses[0]:.3f} -> {losses[-1]:.3f}")

# --- 3. BN statistics warm-up (the fold uses running stats) -----------------
for s in range(5):
    x, _ = make_batch(500 + s)
    _, params["hidden"] = forward(params, x, train=True)

# --- 4. streamline: float graph -> integer thresholds -----------------------
IN_SCALE = 0.1
smlp = streamline_mlp(layers, params["hidden"], IN_SCALE, params["head"])
print(f"[4] streamlined: {len(smlp.stages)} integer threshold stages, "
      f"out_scales={[f'{s.out_scale:.4f}' for s in smlp.stages]}")

# --- 5. run the deployed graph, once in jnp and once on the Pallas kernel ---
x, y = make_batch(9_999)
x_int = jnp.clip(jnp.round(x / IN_SCALE), -127, 127).astype(jnp.int8)

h = x_int.astype(jnp.int32)
on_cpu = jax.default_backend() != "tpu"   # the Pallas interpreter off-chip
for st in smlp.stages:
    h = ops.threshold_matmul(h.astype(jnp.int8), st.w_int, st.thresholds,
                             block_m=32, block_n=8, block_k=8,
                             interpret=on_cpu)
logits = (h.astype(jnp.float32) @ smlp.head_w * smlp.stages[-1].out_scale
          + smlp.head_b)
acc_kernel = float((jnp.argmax(logits, -1) == y).mean())
acc_float = float((jnp.argmax(forward(params, x)[0], -1) == y).mean())
print(f"[5] accuracy: float QAT graph {acc_float:.1%} | "
      f"integer Pallas deployment {acc_kernel:.1%}")

# --- 6. hardware cost report -------------------------------------------------
cost = ModelCost([dense_cost(f"fc{i}", DIMS[i], DIMS[i + 1], BITS, BITS)
                  for i in range(len(DIMS) - 1)]
                 + [dense_cost("head", DIMS[-1], N_CLASSES, 8, 8)])
rep = deploy_report(cost, batch=1, bits=BITS)
print(f"[6] BOPs={cost.bops:.2e}  WM={cost.wm_bits} bits  "
      f"roofline latency={rep['latency_us']:.2f}us ({rep['bound']}-bound)  "
      f"energy={rep['energy_uJ']:.2f}uJ")
