"""A cell is data: a configuration, a traffic file, the reference and
work modules a configuration names and a ``workloads`` entry are found by
name with no edit to the harness; every metric of ``BENCHMARK.json`` has
its arithmetic or its reader; unknown chips have no peaks."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest
import tiny

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
        assert callable(c.Reference) and callable(c.decode_step)


def _checkout(root):
    """``BENCHMARK.json`` and the harness under ``root``, as a checkout
    holds them (without the harness's own tests)."""
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))


def test_a_new_cell_is_found_by_name(tmp_path):
    _checkout(tmp_path)
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


#: DeepSeek-V2-Lite's layer kinds (one dense layer, then MLA with routed
#: and shared experts) at a few dozen widths, served paged in bf16
DEEPSEEK_TINY = {
    "name": "deepseek-v2-lite-16b-tiny", "arch": "deepseek-v2-lite-16b",
    "reference": "stub_mla_moe", "work": "stub_mla_moe",
    "model": {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "d_ff": 32, "vocab_size": 256,
              "head_dim": 16, "dtype": "bfloat16",
              "moe": {"num_experts": 8, "num_shared": 2, "top_k": 2,
                      "d_expert": 32, "first_dense": 1, "dense_ff": 128},
              "mla": {"kv_lora_rank": 32, "q_lora_rank": 0,
                      "qk_nope_dim": 16, "qk_rope_dim": 16, "v_dim": 16}},
    "serving": {"max_batch": 4, "max_seq": 128, "paged": True,
                "block_size": 8, "prefill_bucket": 32, "prefill_chunk": 32},
    "check": {"sample_tokens": 64, "max_requests": 6, "min_tokens": 8,
              "max_logit_gap": 0.5},
}

#: a stand-in reference: every served token lies 0.25 below the best
STUB_REFERENCE = """
import numpy as np


class Reference:
    def __init__(self, dims, params, *, seq_len, decode_nf4=(),
                 low_precision=False):
        self.vocab = dims["vocab_size"]

    def logits(self, prompt, served):
        out = np.zeros((len(served), self.vocab), np.float32)
        out[np.arange(len(served)), served] = -0.25
        return out
"""

#: a stand-in work count: the output head's FLOPs alone (it imports
#: nothing shared, so what runs is the file under the tmp root)
STUB_WORK = """
from types import SimpleNamespace


def decode_step(model, family, rows, keys, frozen=frozenset()):
    return SimpleNamespace(
        flops=2 * rows * model["d_model"] * model["vocab_size"])
"""


def _add_deepseek_cell(root, config):
    (root / "bench/configs/deepseek-tiny.json").write_text(
        json.dumps(config))
    (root / "bench/traffic/tiny-closed.json").write_text(
        json.dumps(tiny.CLOSED))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": config["name"], "file": "bench/configs/deepseek-tiny.json",
        "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
        "reduced": [], "why": "latent attention, routed and shared experts"})
    spec["workloads"].append({"name": "deepseek.tiny-closed",
                              "config": config["name"],
                              "traffic": "tiny-closed", "chips": 1,
                              "why": "4 closed-loop clients"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_moe_mla_config_is_new_files_only(tmp_path):
    """DeepSeek-V2-Lite's layer kinds run through ``run_cell`` to a result
    line from new files and entries alone: its configuration, a traffic
    file, and the reference and work modules the configuration names."""
    _checkout(tmp_path)
    copied = _files(tmp_path)
    _add_deepseek_cell(tmp_path, DEEPSEEK_TINY)
    (tmp_path / "bench/reference/stub_mla_moe.py").write_text(STUB_REFERENCE)
    (tmp_path / "bench/work/stub_mla_moe.py").write_text(STUB_WORK)
    cell = load_cell("deepseek.tiny-closed", root=tmp_path)
    assert cell.Reference.__module__ == "bench_reference_stub_mla_moe"
    assert cell.decode_step.__module__ == "bench_work_stub_mla_moe"
    line, _ = run.run_cell(cell, 2**31 + 7, 1.5, False, check_device=False)
    assert line["correct"], line["checks"]
    # the gap comes from the stub the configuration names
    assert line["checks"]["max_logit_gap"]["value"] == 0.25
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "tokens_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    w = cell.decode_step(cell.config["model"], "moe", 3, 40, frozenset())
    assert w.flops == 3 * 2 * 64 * 256
    # no copied file changed; BENCHMARK.json only gained two entries
    now = _files(tmp_path)
    old = json.loads(copied.pop(Path("BENCHMARK.json")))
    new = json.loads(now[Path("BENCHMARK.json")])
    assert {k: now[k] for k in copied} == copied
    for key in ("configs", "workloads"):
        assert new[key][:-1] == old[key]
    assert {k: v for k, v in new.items() if k not in ("configs", "workloads")
            } == {k: v for k, v in old.items()
                  if k not in ("configs", "workloads")}


@pytest.mark.parametrize("key,body,error", [
    ("reference", None, "bench/reference/absent.py"),
    ("work", None, "bench/work/absent.py"),
    ("reference", "", "bench/reference/absent.py, .* exposes no Reference"),
    ("work", "Reference = 1", "bench/work/absent.py, .* exposes no "
     "decode_step"),
])
def test_a_missing_named_module_is_a_cell_error(tmp_path, monkeypatch, key,
                                                body, error):
    """A configuration that names a module which is not there, or which
    does not expose what its key asks for, is a ``CellError`` at load
    time and exit 2."""
    _checkout(tmp_path)
    _add_deepseek_cell(tmp_path, dict(DEEPSEEK_TINY, **{key: "absent"}))
    (tmp_path / "bench/reference/stub_mla_moe.py").write_text(STUB_REFERENCE)
    (tmp_path / "bench/work/stub_mla_moe.py").write_text(STUB_WORK)
    if body is not None:
        (tmp_path / "bench" / key / "absent.py").write_text(body)
    with pytest.raises(CellError, match=error):
        load_cell("deepseek.tiny-closed", root=tmp_path)
    monkeypatch.setattr(run, "load_cell",
                        lambda name: load_cell(name, root=tmp_path))
    assert run.main(["--workload", "deepseek.tiny-closed", "--seed", "1",
                     "--seconds", "1"]) == 2


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
