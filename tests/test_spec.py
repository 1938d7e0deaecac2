"""Speculative decoding: token-identity on every family + lifecycle edges.

The load-bearing pins:
  * speculative greedy output is TOKEN-IDENTICAL to non-speculative greedy
    for attention, SSM, and hybrid configs, both proposers, dense and
    paged substrates (the verifier's argmax IS the plain tick's argmax —
    drafts only change how many of them land per tick);
  * the accept/rollback machinery composes with the rest of the request
    lifecycle: preempt and cancel fired from an ``on_token`` callback
    mid-window, prefix-cache warm admissions, and the per-request
    acceptance accounting at retirement.
"""
import jax
import numpy as np
import pytest

from repro.models.registry import get_config, get_model
from repro.serve.config import EngineConfig
from repro.serve.engine import Engine, Request
from repro.serve.spec import NGramProposer, _prompt_lookup, accept_length


def _setup(arch="yi-9b", **over):
    cfg = get_config(arch).reduced(dtype="float32", attn_impl="full", **over)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, params


def _prompts(cfg, lens=(5, 11, 3)):
    rng = np.random.default_rng(0)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _serve(cfg, params, prompts, max_new=8, **knobs):
    eng = Engine(cfg, params, EngineConfig(max_batch=3, max_seq=48, **knobs))
    reqs = [Request(rid=i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    assert eng.serve(reqs)["done"]
    return [r.out for r in reqs], eng


# --- host-side helpers -------------------------------------------------
def test_accept_length():
    assert accept_length([], [7]) == 0
    assert accept_length([4, 5], [4, 5, 6]) == 2
    assert accept_length([4, 9], [4, 5, 6]) == 1
    # agreements after the first mismatch are conditioned on a wrong
    # prefix and must not count
    assert accept_length([9, 5], [4, 5, 6]) == 0


def test_prompt_lookup():
    # longest suffix n-gram wins, most recent earlier occurrence
    assert _prompt_lookup([1, 2, 3, 9, 1, 2, 3], 2, 3, 1) == [9, 1]
    # budget caps the continuation
    assert _prompt_lookup([1, 2, 3, 9, 1, 2, 3], 1, 3, 1) == [9]
    # a match flush with the suffix has no continuation: back off to a
    # shorter n-gram rather than return nothing
    assert _prompt_lookup([5, 1, 2, 5, 9, 1, 2], 2, 3, 1) == [5, 9]
    # nothing repeats: no draft
    assert _prompt_lookup([1, 2, 3, 4], 3, 3, 1) == []


def test_ngram_proposer_respects_budget():
    prop = NGramProposer()
    req = Request(rid=0, prompt=[1, 2, 3, 1, 2], max_new=8)
    req.out = [3]
    drafts = prop.propose([req, None], [2, 4])
    assert drafts[1] == []
    assert len(drafts[0]) <= 2


# --- config surface ----------------------------------------------------
def test_spec_config_validation():
    from repro.serve.sampling import SamplingConfig
    with pytest.raises(ValueError, match="spec must be one of"):
        EngineConfig(spec="medusa")
    with pytest.raises(ValueError, match="spec_k"):
        EngineConfig(spec="ngram", spec_k=0)
    with pytest.raises(ValueError, match="greedy-only"):
        EngineConfig(spec="ngram",
                     sampling=SamplingConfig(mode="temperature",
                                             temperature=0.7))
    # greedy sampling (explicit or default) composes fine
    EngineConfig(spec="ngram", sampling=SamplingConfig(mode="greedy"))
    EngineConfig(spec="self_lut", spec_k=2)


# --- token identity, per family ----------------------------------------
@pytest.mark.parametrize("arch,paged", [
    ("yi-9b", False),          # attention, dense slab
    ("yi-9b", True),           # attention, paged pool
    ("mamba2-1.3b", False),    # pure SSM (recurrent re-commit path)
    ("zamba2-1.2b", False),    # hybrid, dense
    ("zamba2-1.2b", True),     # hybrid, split substrate
])
@pytest.mark.parametrize("mode", ["ngram", "self_lut"])
def test_spec_token_identity(arch, paged, mode):
    cfg, params = _setup(arch)
    prompts = _prompts(cfg)
    base, _ = _serve(cfg, params, prompts, paged=paged)
    out, eng = _serve(cfg, params, prompts, paged=paged, spec=mode)
    assert out == base
    m = eng.metrics
    assert m.spec_accepted + m.spec_rejected == m.spec_drafted
    if mode == "self_lut":
        # the LUT draft tree always proposes a full window
        assert m.spec_drafted > 0 and m.spec_ticks > 0


@pytest.mark.parametrize("mode", ["ngram", "self_lut"])
def test_spec_drafts_are_accepted(mode):
    """On periodic prompts both proposers land some drafts: acceptance is
    a rate in (0, 1] and the accounting reconciles.  Drafts that never
    match the verifier would mean the proposer or the accept scan broke
    (token identity alone cannot see it: the verifier's correction still
    lands every token)."""
    cfg, params = _setup()
    prompts = [[7, 8, 9, 10] * 4, [3, 5] * 6, [11, 12, 13] * 3]
    base, _ = _serve(cfg, params, prompts)
    out, eng = _serve(cfg, params, prompts, spec=mode)
    assert out == base
    m = eng.metrics
    assert 0 < m.spec_accepted <= m.spec_drafted
    assert m.spec_accepted + m.spec_rejected == m.spec_drafted
    assert 0.0 < m.summary(len(prompts))["spec_acceptance"] <= 1.0


def test_spec_identity_moe_mla():
    """DeepSeek MLA attention + capacity-routed MoE: the verify window
    groups MoE dispatch by column so routing competition matches the
    plain per-tick fold."""
    cfg, params = _setup("deepseek-v2-lite-16b")
    prompts = _prompts(cfg)
    base, _ = _serve(cfg, params, prompts)
    out, eng = _serve(cfg, params, prompts, spec="self_lut")
    assert out == base
    assert eng.metrics.spec_drafted > 0


def test_spec_metrics_and_obs():
    cfg, params = _setup()
    prompts = _prompts(cfg)
    _, eng = _serve(cfg, params, prompts, spec="self_lut", trace=True)
    m = eng.metrics
    s = m.summary(3)
    assert s["spec_ticks"] == m.spec_ticks > 0
    assert 0.0 <= s["spec_acceptance"] <= 1.0
    assert s["spec_acceptance"] == m.spec_accepted / m.spec_drafted
    dump = eng.registry.dump()
    assert dump["engine_spec_accepted_per_window"]["series"]
    assert dump["engine_spec_tokens_per_request"]["series"]
    assert dump["engine_info"]["series"]
    (k, v), = dump["engine_info"]["series"].items()
    assert "spec" in k and "self_lut" in k
    # the per-request histogram observes once per kind per retired request
    series = dump["engine_spec_tokens_per_request"]["series"]
    counts = {k: s["count"] for k, s in series.items()}
    assert all(c == len(prompts) for c in counts.values()), counts
    names = {e.name for e in eng.tracer.events()}
    assert {"draft", "decode_inputs", "verify", "verify_wait",
            "emit"} <= names


def test_spec_tokens_not_double_counted():
    """decode_tokens counts every emitted token exactly once (accepted
    drafts + corrections), so tok/s math stays honest under speculation."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    out, eng = _serve(cfg, params, prompts, spec="self_lut")
    # prefill emits token 0; every later token comes from exactly one
    # spec-tick emission (accepted draft or correction)
    assert eng.metrics.decode_tokens == sum(len(o) - 1 for o in out)


# --- lifecycle edges under speculation ----------------------------------
def test_spec_cancel_from_callback_mid_window():
    """An on_token callback on one request cancels ANOTHER active request
    mid-spec-tick: the cancelled row's teardown must not be undone by the
    remainder of the emit loop (no rollback/positions writes on a freed
    slot), and the survivor must finish token-identical."""
    cfg, params = _setup()
    prompts = _prompts(cfg, lens=(5, 7))
    base, _ = _serve(cfg, params, prompts[:1], max_new=8, spec="self_lut")

    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq=48,
                                           spec="self_lut"))
    r0 = Request(rid=0, prompt=list(prompts[0]), max_new=8)
    r1 = Request(rid=1, prompt=list(prompts[1]), max_new=8)
    fired = []

    def kill_r1(tok):
        if len(r0.out) == 3 and not fired:
            fired.append(True)
            assert eng.cancel(r1)

    h0 = eng.submit(r0, on_token=kill_r1)
    h1 = eng.submit(r1)
    assert h0 and h1
    for _ in range(64):
        if r0.done and r1.done:
            break
        eng.step()
    assert r0.done and r1.done and r1.cancelled
    assert r0.out == base[0]
    assert eng.metrics.cancelled == 1


def test_spec_preempt_racing_mid_verify():
    """Preempting an active request from a callback mid-spec-tick frees
    its slot inside the emit loop; the requeued request re-prefills its
    extended prompt and the continued stream is token-identical to never
    having been preempted (same pin the plain engine carries)."""
    cfg, params = _setup()
    prompts = _prompts(cfg, lens=(5, 7))
    base, _ = _serve(cfg, params, prompts, max_new=8, spec="self_lut")

    eng = Engine(cfg, params, EngineConfig(max_batch=3, max_seq=48,
                                           spec="self_lut"))
    r0 = Request(rid=0, prompt=list(prompts[0]), max_new=8)
    r1 = Request(rid=1, prompt=list(prompts[1]), max_new=8)
    fired = []

    def kick_r1(tok):
        if len(r0.out) == 3 and not fired:
            fired.append(True)
            eng.preempt(r1)

    h0 = eng.submit(r0, on_token=kick_r1)
    h1 = eng.submit(r1)
    assert h0 and h1
    for _ in range(64):
        if r0.done and r1.done:
            break
        eng.step()
    assert r0.done and r1.done
    assert fired and eng.metrics.preemptions == 1
    assert [r0.out, r1.out] == base


def test_spec_cancel_during_draft_window():
    """A request cancelled between submit and its first spec tick (i.e.
    while the proposer would still draft for it) is skipped cleanly."""
    cfg, params = _setup()
    prompts = _prompts(cfg, lens=(5, 7))
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq=48,
                                           spec="self_lut"))
    r0 = Request(rid=0, prompt=list(prompts[0]), max_new=8)
    r1 = Request(rid=1, prompt=list(prompts[1]), max_new=8)
    assert eng.submit(r0) and eng.submit(r1)
    assert eng.cancel(r1)
    for _ in range(32):
        if r0.done:
            break
        eng.step()
    base, _ = _serve(cfg, params, prompts[:1], max_new=8, spec="self_lut")
    assert r0.done and r0.out == base[0]


def test_spec_prefix_cache_warm_equals_cold():
    """spec x paged x prefix-cache: a warm admission sharing a cached
    prefix must stream token-identically to its own cold run."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    shared = rng.integers(1, cfg.vocab_size, 24).tolist()
    p_a = shared + rng.integers(1, cfg.vocab_size, 4).tolist()
    p_b = shared + rng.integers(1, cfg.vocab_size, 6).tolist()

    def run(prompts, **kw):
        eng = Engine(cfg, params, EngineConfig(
            max_batch=2, max_seq=64, paged=True, prefix_cache=True,
            spec="self_lut", **kw))
        outs = []
        for i, p in enumerate(prompts):
            req = Request(rid=i, prompt=list(p), max_new=6)
            assert eng.serve([req])["done"]
            outs.append(req.out)
        return outs, eng

    cold_a, _ = run([p_a])
    cold_b, _ = run([p_b])
    warm, eng = run([p_a, p_b])
    assert warm == [cold_a[0], cold_b[0]]
    assert eng.metrics.prefix_hits >= 1


def test_spec_max_new_one_never_spec_ticks():
    """max_new=1 requests finish at admission; the spec path must not
    draft for (or emit beyond) them."""
    cfg, params = _setup()
    prompts = _prompts(cfg)
    base, _ = _serve(cfg, params, prompts, max_new=1)
    out, eng = _serve(cfg, params, prompts, max_new=1, spec="self_lut")
    assert out == base and all(len(o) == 1 for o in out)
    assert eng.metrics.spec_ticks == 0
