"""``chip_smoke.py``: its serve-and-check path at reduced size on the CPU,
its checks, and its refusal to report a result off the chip."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from dataclasses import replace

import pytest

from repro.models.registry import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reduced_report(smoke):
    cfg = get_config(smoke.ARCH).reduced()
    lines = []
    return cfg, smoke.serve_and_check(cfg, log=lines.append), lines


def test_serve_and_check_passes_at_reduced_size(smoke, reduced_report):
    """Both phases serve every request in full, and the first tokens of
    bf16 and nf4 agree (the check would have raised otherwise)."""
    cfg, report, lines = reduced_report
    assert report["param_bytes"] > 0
    assert set(report["phases"]) == {"bf16", "nf4"}
    for phase in report["phases"].values():
        toks = phase["tokens"]
        assert len(toks) == len(smoke.PROMPT_LENS)
        assert all(len(t) == smoke.MAX_NEW for t in toks)
        assert all(0 <= x < cfg.vocab_size for t in toks for x in t)
        assert phase["setup_s"] >= 0 and phase["wall_s"] > 0
    firsts = {k: [t[0] for t in v["tokens"]]
              for k, v in report["phases"].items()}
    assert firsts["bf16"] == firsts["nf4"]
    assert any("not a performance result" in line for line in lines)


def test_smoke_prompts_share_one_prefill_bucket(smoke):
    from repro.serve.config import EngineConfig
    bucket = EngineConfig().prefill_bucket
    cfg = get_config(smoke.ARCH).reduced()
    reqs = smoke.make_requests(cfg)
    assert len({-(-len(r.prompt) // bucket) for r in reqs}) == 1
    assert all(r.max_new == smoke.MAX_NEW for r in reqs)


def test_check_run_rejects_bad_runs(smoke, reduced_report):
    """Every check raises on the fault it guards."""
    cfg, report, _ = reduced_report
    reqs = smoke.make_requests(cfg)
    for r, toks in zip(reqs, report["phases"]["bf16"]["tokens"]):
        r.out, r.done = list(toks), True
    streams = [list(r.out) for r in reqs]
    smoke.check_run("ok", reqs, streams, cfg.vocab_size)

    def fails(reqs_, streams_, vocab=cfg.vocab_size):
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_run("bad", reqs_, streams_, vocab)

    fails(reqs, streams[:-1])                                 # lost stream
    fails(reqs, [s[:-1] for s in streams])                    # stream != out
    fails(reqs, streams, vocab=min(min(r.out) for r in reqs))  # out of vocab
    short = [replace(r, out=r.out[:-1]) for r in reqs]
    fails(short, [list(r.out) for r in short])                # too few
    unfinished = [replace(r, done=False) for r in reqs]
    fails(unfinished, streams)                                # not done


def _run_smoke(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], capture_output=True,
                          text=True, cwd=cwd, env=env, timeout=300)


def _says_ok(stdout):
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_smoke_refuses_without_a_tpu():
    r = _run_smoke(SMOKE, ROOT)
    assert r.returncode != 0
    assert not _says_ok(r.stdout)
    assert "needs a TPU" in r.stderr


def test_smoke_refuses_without_the_program(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    r = _run_smoke(str(alone), str(tmp_path))
    assert r.returncode != 0
    assert not _says_ok(r.stdout)
    assert "no repro package" in r.stderr
