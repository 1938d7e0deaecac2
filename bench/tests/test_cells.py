"""A cell is data: a traffic file and a ``workloads`` entry are found by
name with no edit to the harness; every metric of ``BENCHMARK.json`` has
its arithmetic or its reader; unknown chips have no peaks."""
from __future__ import annotations

import json
import shutil

import pytest

import run
from cell import ROOT, CellError, load_cell
from peaks import UnknownDevice, peaks_for


def test_every_benchmark_cell_loads():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = load_cell(w["name"])
        assert c.config["name"] == w["config"]
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert c.per_layer


def test_a_new_cell_is_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench/configs")
    shutil.copytree(ROOT / "bench" / "traffic", tmp_path / "bench/traffic")
    steady = {"loop": "open", "rate_rps": 6.0,
              "phases": [{"seconds": 5, "rate_mult": 0.8}],
              "prompt": {"median": 160, "sigma": 0.8, "min": 16, "max": 1024},
              "output": {"median": 64, "sigma": 0.7, "min": 8, "max": 256}}
    (tmp_path / "bench/traffic/steady-chat.json").write_text(
        json.dumps(steady))
    bf16 = json.loads((tmp_path / "bench/configs/zamba2-1.2b-nf4.json")
                      .read_text())
    bf16.update(name="zamba2-1.2b-bf16", decode_nf4=[])
    bf16["serving"].pop("quant")
    (tmp_path / "bench/configs/zamba2-1.2b-bf16.json").write_text(
        json.dumps(bf16))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "zamba2-1.2b-bf16",
        "file": "bench/configs/zamba2-1.2b-bf16.json",
        "source": "https://huggingface.co/Zyphra/Zamba2-1.2B",
        "reduced": [], "why": "the hybrid in bf16"})
    spec["workloads"].append({"name": "zamba2.steady-chat",
                              "config": "zamba2-1.2b-bf16",
                              "traffic": "steady-chat", "chips": 1,
                              "why": "Poisson with no bursts"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = load_cell("zamba2.steady-chat", root=tmp_path)
    assert c.traffic["phases"] == [{"seconds": 5, "rate_mult": 0.8}]
    assert c.config["name"] == "zamba2-1.2b-bf16"
    assert "quant" not in c.config["serving"]
    assert "itl_p95_ms" in [m["name"] for m in c.end_to_end]
    with pytest.raises(CellError):
        load_cell("zamba2.nothing", root=tmp_path)


def test_every_per_layer_metric_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        mod = run.load_reader(m["name"])
        assert mod.LAYER == m["layer"] and mod.UNIT == m["unit"]
        assert mod.MOVES == m["moves"] and m["moves"] in e2e


def test_unknown_device_has_no_peaks():
    assert peaks_for("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")


def test_no_tpu_no_result():
    """Off a TPU the command exits non-zero and prints no result."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "zamba2-nf4.batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 3, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr
