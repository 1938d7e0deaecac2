"""``correct`` on a whole run at a tiny size on the CPU (the look for a
chip skipped): sound runs pass; the control and each fault a serving
cell can have fail."""
from __future__ import annotations

import pytest
import tiny

import run


def _run(cell, seed=2**31 + 99, control=False):
    line, _ = run.run_cell(cell, seed, 1.5, False, control=control,
                           check_device=False)
    return line


@pytest.mark.parametrize("config,traffic", [
    (tiny.MAMBA2, tiny.OPEN), (tiny.ZAMBA2_NF4, tiny.CLOSED)])
def test_sound_run_is_correct_and_control_is_not(config, traffic):
    line = _run(tiny.cell(config, traffic))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert list(line)[-1] == "checks"
    sound = line["checks"]["max_logit_gap"]["value"]
    control = _run(tiny.cell(config, traffic), control=True)
    assert not control["correct"], control["checks"]
    c = control["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"] and c["value"] >= 3 * sound


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.serve.engine import Engine
    step = Engine._decode_impl

    def frozen(self, params, tokens, caches, *a):
        toks, _ = step(self, params, tokens, caches, *a)
        return toks, caches                 # the step forgets its state
    monkeypatch.setattr(Engine, "_decode_impl", frozen)
    line = _run(tiny.cell(tiny.ZAMBA2_NF4, tiny.CLOSED))
    assert not line["correct"], line["checks"]


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import Engine
    emit = Engine._emit
    seen = [0]

    def altered(self, req, tok):
        seen[0] += 1
        if seen[0] % 5 == 0:
            tok = (tok + 1) % self.cfg.vocab_size
        return emit(self, req, tok)
    monkeypatch.setattr(Engine, "_emit", altered)
    line = _run(tiny.cell(tiny.MAMBA2, tiny.OPEN))
    assert not line["correct"], line["checks"]
