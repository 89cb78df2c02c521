"""The harness finds every cell's files by name, registers new files with
no edit, and refuses to run without a TPU (no chip needed)."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from chipbench import cost, peaks, spec, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_resolves_its_files_by_name(bench):
    for w in bench["workloads"]:
        cfg = spec.config(w["config"])
        assert cfg["name"] == w["config"]
        mod = spec.config_module(w["config"])
        for fn in ("input_shape", "make_params", "build_graph", "reference"):
            assert callable(getattr(mod, fn)), (w["name"], fn)
        traffic.validate(spec.traffic(w["traffic"]))
        lim = spec.limits(w["name"])
        assert set(lim) == {"rows_off_share", "unanswered"}
        for trace in (False, True):
            metrics = spec.metrics_for(bench, w["name"], trace)
            assert metrics, (w["name"], trace)
            for m in metrics:
                assert callable(spec.reducer(m["name"]).reduce)


def test_benchmark_json_keeps_the_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    names = [x["name"] for x in bench["configs"] + bench["workloads"]
             + bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        moves = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moves.get("workloads", cells), (m["name"], w)
    for w in bench["workloads"]:
        reported = [m for m in bench["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert len(reported) >= 2, w["name"]
        assert spec.metrics_for(bench, w["name"], trace=True)


NEW_FAMILY = {"name": "toy", "family": "toy", "in_hw": 16, "in_ch": 4,
              "out_ch": 8, "stride": 2, "n_classes": 5}

#: a configuration module of a family the harness has never seen: its own
#: layer list (a strided conv on the conv-threshold kernel, then a head)
NEW_FAMILY_PY = """
from chipbench import cost


def input_shape(cfg):
    return (cfg["in_hw"], cfg["in_hw"], cfg["in_ch"])


def layers(cfg):
    out_hw = (cfg["in_hw"] - 3) // cfg["stride"] + 1
    return [cost.conv("conv0", "conv_threshold", 3, cfg["in_ch"],
                      cfg["out_ch"], cfg["in_hw"], out_hw, 8, 1, 1),
            cost.dense("fc0", "float_head", out_hw * out_hw * cfg["out_ch"],
                       cfg["n_classes"], 1, 32, 1)]
"""


def test_a_new_file_registers_without_editing_the_harness(tmp_path,
                                                          monkeypatch):
    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (here / "metrics" / "new_metric.py").write_text(
        "def reduce(ctx):\n    return 42.0\n")
    mix = dict(spec.traffic("offline"), query_samples=123)
    (here / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    cfg = dict(spec.config("kws"), name="kws_copy")
    (here / "configs" / "kws_copy.json").write_text(json.dumps(cfg))
    shutil.copy(here / "configs" / "kws.py", here / "configs" / "kws_copy.py")
    (here / "configs" / "toy.json").write_text(json.dumps(NEW_FAMILY))
    (here / "configs" / "toy.py").write_text(NEW_FAMILY_PY)
    monkeypatch.setattr(spec, "HERE", str(here))
    assert spec.reducer("new_metric").reduce(None) == 42.0
    assert traffic.validate(spec.traffic("new_mix"))["query_samples"] == 123
    assert spec.config("kws_copy")["name"] == "kws_copy"
    assert spec.config_module("kws_copy").input_shape(cfg) == (490,)

    # a new family's metrics come from its own layer list
    toy = spec.config("toy")
    layers = spec.config_module("toy").layers(toy)
    conv_ops = 2 * 9 * 4 * 8 * 7 * 7
    assert cost.ops_per_sample(layers) == conv_ops + 2 * 7 * 7 * 8 * 5
    peak = peaks.peaks("TPU v5 lite")
    ctx = types.SimpleNamespace(
        cfg=toy, layers=layers, samples=1000, window_s=2.0, chips=1,
        peaks=peak, spans=[],
        device={"kernel_s": {"conv_threshold": 1e-3},
                "kernel_calls": {"conv_threshold": 10}, "idle_share": 0.5,
                "samples": 1000})
    least = max(1000 * conv_ops / peak["int8_ops"],
                (1000 * layers[0]["act_bytes"] + 10 * layers[0]
                 ["weight_bytes"]) / peak["hbm_bytes_per_s"])
    assert spec.reducer("conv_threshold_roofline").reduce(ctx) \
        == pytest.approx(100 * least / 1e-3)
    assert spec.reducer("mfu.offline").reduce(ctx) is not None


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "kws-singlestream", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_the_command_refuses_to_run_without_a_tpu():
    p = _run(spec.ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
