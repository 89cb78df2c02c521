"""The serving path's bring-up contract, checked on the CPU.

* the exact MXU operand form each fused stage picks (``mxu_form``);
* the exact float head (``head_limbs`` / ``FloatHeadStage``);
* device binding: each replica's executor and its waves live on the device
  the pool built it for;
* the compile cache directory is placed from outside the program;
* ``launch.serve --stack tiny`` serves every Table-1 family;
* ``chip_smoke.py`` refuses to run without a TPU.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.deploy import compile_graph
from repro.deploy.lower import FloatHeadStage, head_limbs, mxu_form

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# exact operand forms
# ---------------------------------------------------------------------------

def test_mxu_form_prefers_bf16_inside_the_exact_bound():
    w = np.full((490, 256), 3, np.int8)             # KWS-like 3-bit weights
    assert mxu_form(w, 8, in_signed=True) == ("bf16", 0)
    assert mxu_form(w, 3, in_signed=False) == ("bf16", 0)


@pytest.mark.parametrize("in_bits,signed,offset", [
    (8, False, 128),     # unsigned 8-bit codes overflow int8: shift them
    (8, True, 0),        # signed input codes fit int8 as they are
    (7, False, 0),       # 7-bit codes fit int8 as they are
])
def test_mxu_form_falls_back_to_int8_past_the_bound(in_bits, signed,
                                                    offset):
    w = np.full((2048, 8), 127, np.int8)     # sum |w| * 2^bits > 2^24
    assert mxu_form(w, in_bits, in_signed=signed) == ("int8", offset)


def test_mxu_form_refuses_what_no_form_holds_exactly():
    w = np.full((1024, 8), 300, np.int32)           # past int8 and bf16
    with pytest.raises(ValueError, match="no exact MXU operand form"):
        mxu_form(w, 8, in_signed=False)


# ---------------------------------------------------------------------------
# exact float head
# ---------------------------------------------------------------------------

def _head(w, b, in_scale=0.25, in_bits=3):
    return FloatHeadStage(name="head", w=jnp.asarray(w), b=jnp.asarray(b),
                          in_dim=w.shape[0], out_dim=w.shape[1],
                          in_scale=in_scale, in_bits=in_bits)


def test_head_limbs_reconstruct_the_weights():
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((256, 12)) * 0.1).astype(np.float32)
    limbs, bits, step = head_limbs(w, 256, 3)
    assert np.abs(limbs).max() <= 1 << (bits - 1)   # exact in bf16
    base = 2.0 ** (bits * np.arange(len(limbs)))
    rebuilt = np.tensordot(base, limbs.astype(np.float64), 1) * step
    colmax = np.abs(w).max(axis=0)
    assert np.all(np.abs(rebuilt - w) <= colmax * 2.0 ** -24)


def test_float_head_is_exact_on_integer_weights():
    """CNV's sign head: integer weights and biases give integer logits."""
    rng = np.random.default_rng(1)
    w = rng.choice([-2.0, 2.0], (512, 10)).astype(np.float32)
    b = rng.integers(-300, 300, 10).astype(np.float32)
    x = rng.integers(0, 2, (8, 512)).astype(np.int32)
    head = _head(w, b, in_scale=1.0, in_bits=1)
    np.testing.assert_array_equal(np.asarray(head.apply_ref(x)), x @ w + b)


def test_float_head_is_the_same_in_every_program():
    """Same logits eager, under jit, and fused after other work — the
    property that lets every execution path agree bit for bit."""
    rng = np.random.default_rng(2)
    w = (rng.standard_normal((256, 12)) * 0.05).astype(np.float32)
    b = (rng.standard_normal(12) * 0.1).astype(np.float32)
    x = jnp.asarray(rng.integers(0, 8, (16, 256)), jnp.int32)
    head = _head(w, b)
    eager = np.asarray(head.apply_ref(x))
    jitted = np.asarray(jax.jit(head.apply_ref)(x))
    fused = np.asarray(jax.jit(lambda v: head.apply_ref(jnp.minimum(v, 7)))(x))
    np.testing.assert_array_equal(jitted, eager)
    np.testing.assert_array_equal(fused, eager)
    ref = np.asarray(x, np.float64) @ w * 0.25 + b
    np.testing.assert_allclose(eager, ref, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# device binding
# ---------------------------------------------------------------------------

def _kws_small():
    from tests.test_serve import _load

    graph, x = _load("kws")
    return graph, graph.meta["in_scale"], x


def test_replica_pool_builds_each_executor_on_its_device():
    from repro.serve import ReplicaPool

    graph, in_scale, x = _kws_small()
    dev = jax.devices()[0]
    pool = ReplicaPool(
        factory=lambda: compile_graph(graph, in_scale=in_scale),
        devices=[dev, dev])
    for r in pool.replicas:
        assert r.model.device == dev
        y, mask = r.submit(x[:3], micro_batch=4).wait()
        assert y.devices() == {dev} and mask.tolist() == [1, 1, 1, 0]


def test_executor_outside_a_device_context_uses_the_default():
    graph, in_scale, x = _kws_small()
    cm = compile_graph(graph, in_scale=in_scale)
    assert cm.device is None
    y, _ = cm.submit_wave(x[:2], micro_batch=2)
    assert y.devices() == {jax.devices()[0]}


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import os, sys, jax, jax.numpy as jnp
from repro import compile_cache
compile_cache.REPO_CACHE_DIR = sys.argv[1]
print(compile_cache.enable_compile_cache())
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lives_where_it_is_placed(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set, entries appear there and not at
    the in-repo default; without it, at the default."""
    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(repo_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True).stdout.split()
    used, unused = (env_dir, repo_dir) if from_env else (repo_dir, env_dir)
    assert out[-1] == str(used)
    assert used.is_dir() and any(used.iterdir())
    assert not unused.exists()


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["kws", "ad", "ic", "cnv"])
def test_launch_serve_tiny_serves_every_family(monkeypatch, name):
    import repro.compile_cache
    from repro.launch.serve import main

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "")
    res = main(["--stack", "tiny", "--tiny-model", name, "--autotune", "off",
                "--requests", "24"])
    assert res["served"] == res["n"] == 24


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
