"""The float32 reference against the engine at a tiny size on the CPU:
prefill by batched and by chunked admission, decode through the cached
SSM/conv state and (hybrid) the paged shared-attention KV, and the frozen
NF4 decode tree."""
from __future__ import annotations

import jax
import numpy as np
import pytest
import tiny

import check
import weights
from e2e import Record
from reference.mamba2_hybrid import Reference
from run import model_config, reference_dims

#: bf16 program against the f32 reference at these widths reads about
#: 0.02-0.03 (two tiny configurations, several seeds)
GAP = 0.1


def _serve(config, seed, prompt_lens, max_new=12):
    from repro.models.registry import get_model
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request
    cfg = model_config(config)
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, seed)
    eng = Engine(cfg, params, EngineConfig(**config["serving"]))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                               n).tolist(), max_new=max_new)
            for i, n in enumerate(prompt_lens)]
    eng.serve(reqs)
    recs = [Record(rid=r.rid, due=0.0, prompt=tuple(r.prompt),
                   max_new=max_new, tokens=list(r.out)) for r in reqs]
    return cfg, params, recs


# prompts under one chunk (batched bucket prefill) and over it (chunked)
LENS = (5, 17, 31, 45, 70)


@pytest.mark.parametrize("name", ["mamba2", "zamba2_nf4", "zamba2_bf16"])
def test_engine_agrees_with_reference(name):
    config = {"mamba2": tiny.MAMBA2, "zamba2_nf4": tiny.ZAMBA2_NF4,
              "zamba2_bf16": tiny.ZAMBA2_NF4}[name]
    config = dict(config)
    if name == "zamba2_bf16":
        config["serving"] = dict(config["serving"], quant=None)
        config["decode_nf4"] = []
    cfg, params, recs = _serve(config, 7, LENS)
    assert all(len(r.tokens) == 12 for r in recs)
    ref = Reference(reference_dims(config, cfg), params,
                    seq_len=config["serving"]["max_seq"],
                    decode_nf4=config.get("decode_nf4", ()))
    gaps = check.served_gaps(ref, recs)
    assert gaps.size == 12 * len(LENS)
    assert gaps.max() <= GAP, gaps.max()


def test_nf4_decode_is_what_the_reference_checks():
    """Without the NF4 decode weights the reference disagrees with the
    nf4 engine by far more: the check sees the quantized decode."""
    cfg, params, recs = _serve(tiny.ZAMBA2_NF4, 3, LENS, max_new=16)
    dims = reference_dims(tiny.ZAMBA2_NF4, cfg)
    with_nf4 = Reference(dims, params, seq_len=128,
                         decode_nf4=tiny.ZAMBA2_NF4["decode_nf4"])
    without = Reference(dims, params, seq_len=128)
    good = check.served_gaps(with_nf4, recs).max()
    bad = check.served_gaps(without, recs).max()
    assert good <= GAP
    assert bad > 3 * good, (good, bad)
