"""Paged decode attention: the Pallas kernel (interpret mode) against the
``jnp`` gather + ``sdpa`` path it replaces on the TPU, and the wiring in
``gqa_attention`` that calls it.

The kernel reads each row's live blocks in place from the pool; the
reference gathers the whole block table and masks the columns past
``kv_len``.  Both compute in f32, so they agree to f32 rounding: only
the order of the sums and the softmax's one division differ.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention)
from repro.models import attention
from repro.models.attention import sdpa
from repro.models.common import CacheSpec, paged_gather
from repro.models.registry import get_config, get_model

BS, NBLK, NUM_BLOCKS = 8, 5, 24
#: the row under test's kv_len: one position, a block less one, one
#: block, one past it, the middle, the whole table
KV_LENS = {"one": 1, "bs-1": BS - 1, "bs": BS, "bs+1": BS + 1,
           "mid": NBLK * BS // 2 + 3, "full": NBLK * BS}


def paged_decode_attention_ref(q, k_pool, v_pool, block_table, kv_len):
    """``gqa_attention``'s gather path for a one-token decode: every row's
    whole table gathered into logical order, all columns scored in f32
    and those at or past ``kv_len`` masked."""
    out = sdpa(q[:, None], paged_gather(k_pool, block_table),
               paged_gather(v_pool, block_table), causal=True,
               q_offset=kv_len - 1, kv_len=kv_len, f32_operands=True)
    return out[:, 0]


def _inputs(kv_len, hkv, g, dh, dtype, seed=0):
    """Three rows: the row under test on shuffled physical blocks, whose
    table entries past its live blocks name the garbage block 0; a row
    parked on block 0 (a free slot: kv_len 1, every entry garbage); and a
    row of the middle length on other shuffled blocks."""
    rng = np.random.default_rng(seed)
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(NUM_BLOCKS, BS, hkv, dh)), dtype)
    k_pool, v_pool = pool(), pool()
    q = jnp.asarray(rng.normal(size=(3, hkv * g, dh)), dtype)
    phys = rng.permutation(np.arange(1, NUM_BLOCKS))
    table = np.zeros((3, NBLK), np.int32)
    live = -(-kv_len // BS)
    table[0, :live] = phys[:live]
    table[2] = phys[NBLK:2 * NBLK]
    kv = np.array([kv_len, 1, KV_LENS["mid"]], np.int32)
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(kv)


@pytest.mark.parametrize("case", list(KV_LENS))
@pytest.mark.parametrize("hkv,g,dh", [(2, 1, 64), (2, 1, 128), (2, 4, 64),
                                      (1, 2, 128)],
                         ids=["g1-dh64", "g1-dh128", "g4-dh64", "g2-dh128"])
def test_kernel_matches_gather_path(case, hkv, g, dh):
    """f32 pools: the kernel equals the gather path to f32 rounding, two
    blocks a grid step (so rows span live, partly live and dead steps)."""
    args = _inputs(KV_LENS[case], hkv, g, dh, jnp.float32)
    out = paged_decode_attention(*args, blocks_per_step=2, interpret=True)
    ref = paged_decode_attention_ref(*args)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("blocks_per_step", [1, 3, 8])
def test_kernel_bf16_pool_matches_gather_path(blocks_per_step):
    """bf16 pools and query, as the engine stores them: both widen to f32
    and round the result to bf16 once, so they differ by at most one bf16
    step."""
    args = _inputs(KV_LENS["mid"], 2, 2, 64, jnp.bfloat16, seed=3)
    out = paged_decode_attention(*args, blocks_per_step=blocks_per_step,
                                 interpret=True)
    ref = paged_decode_attention_ref(*args)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2 ** -7, atol=2 ** -9)


def test_kernel_reads_nothing_past_kv_len():
    """Only the rows' live blocks enter the answer: NaN in every block no
    row holds, and huge values in the dead tail of the last live block of
    the row under test, leave the kernel's answer as it was."""
    q, k_pool, v_pool, table, kv = _inputs(KV_LENS["bs+1"], 2, 1, 64,
                                           jnp.float32)
    out = paged_decode_attention(q, k_pool, v_pool, table, kv,
                                 blocks_per_step=2, interpret=True)
    tab = np.asarray(table)
    held = np.zeros(NUM_BLOCKS, bool)
    for r, n in enumerate(np.asarray(kv)):
        held[tab[r, :-(-n // BS)]] = True
    tail = np.zeros((NUM_BLOCKS, BS), bool)
    tail[tab[0, 1], 1:] = True              # row 0's last block: 1 live
    fill = np.where(held[:, None], np.where(tail, 1e4, np.nan), np.nan)
    junk = jnp.asarray(~held[:, None] | tail)[:, :, None, None]
    fill = jnp.asarray(fill, jnp.float32)[:, :, None, None]
    out2 = paged_decode_attention(q, jnp.where(junk, fill, k_pool),
                                  jnp.where(junk, fill, v_pool), table, kv,
                                  blocks_per_step=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


def _hybrid_decode_step(monkeypatch, kernel: bool):
    """The jitted decode step of a tiny zamba2 (shared block of 4 heads over 2 KV
    heads, head 32) over paged pools filled with random KV, rows at mixed
    depths; with ``kernel`` the attention takes the kernel branch, in
    interpret mode."""
    if kernel:
        # take every platform_dependent's TPU branch (attention is the
        # only one in this model: no NF4 weights), the kernel interpreted
        monkeypatch.setattr(jax.lax, "platform_dependent",
                            lambda *a, tpu, default: tpu(*a))
        monkeypatch.setattr(
            attention, "paged_decode_attention",
            lambda *a: paged_decode_attention(*a, blocks_per_step=2,
                                              interpret=True))
    cfg = get_config("zamba2-1.2b").reduced(dtype="float32",
                                            attn_impl="full")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, bs, nblk, nb = 3, 8, 6, 20
    attn, ssm = model.init_cache(b, bs * nblk, spec=CacheSpec(bs, nb))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 4 * len(attn) + 2))
    attn = [type(c)(jax.random.normal(next(keys), c.k.shape, c.k.dtype),
                    jax.random.normal(next(keys), c.v.shape, c.v.dtype))
            for c in attn]
    ssm = jax.tree.map(
        lambda a: 0.1 * jax.random.normal(next(keys), a.shape, a.dtype), ssm)
    rng = np.random.default_rng(4)
    table = rng.permutation(np.arange(1, nb))[:b * nblk].reshape(b, nblk)
    table[1, 1:] = 0                           # row 1 holds one block
    positions = jnp.asarray([29, 3, 47], jnp.int32)
    tokens = jnp.asarray([[5], [17], [301]], jnp.int32)
    return (jax.jit(lambda p, t, st, pos, tab: model.decode_step(
                p, t, st, pos, tables=tab)),
            (params, tokens, (attn, ssm), positions,
             jnp.asarray(table, jnp.int32)))


def _hybrid_decode(monkeypatch, kernel: bool):
    step, args = _hybrid_decode_step(monkeypatch, kernel)
    return step(*args)


def test_decode_step_off_the_tpu_gathers(monkeypatch):
    """Lowered for the CPU the decode step keeps the gather path (the
    engine's CPU numbers do not move): a gather, and no kernel call."""
    step, args = _hybrid_decode_step(monkeypatch, False)
    hlo = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" not in hlo and " gather(" in hlo


def test_hybrid_decode_step_same_logits_either_branch(monkeypatch):
    """A hybrid decode step gives the same logits and caches whichever
    branch its shared attention takes: the kernel sees the token the step
    has just written (``kv_len = idx + 1``), at every application."""
    ref_logits, (ref_attn, ref_ssm) = _hybrid_decode(monkeypatch, False)
    logits, (attn, ssm) = _hybrid_decode(monkeypatch, True)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    # the first application writes before any attention has run: its
    # pools are the same bits; later ones carry the f32 rounding on
    np.testing.assert_array_equal(np.asarray(attn[0].k),
                                  np.asarray(ref_attn[0].k))
    jax.tree.map(lambda a, w: np.testing.assert_allclose(
        np.asarray(a), np.asarray(w), rtol=1e-5, atol=1e-5),
        (attn, ssm), (ref_attn, ref_ssm))


@pytest.mark.parametrize("arch,over,counted", [
    ("zamba2-1.2b", {}, True), ("zamba2-1.2b", {"attn_f32": False}, False),
    ("deepseek-v2-lite-16b", {}, False)], ids=["gqa", "gqa-bf16", "mla"])
def test_engine_counts_live_and_table_kv_blocks(arch, over, counted):
    """A paged engine whose decode attention can take the kernel (GQA in
    f32) counts, per decode tick, the blocks holding each active row's
    live positions and the active rows' table entries.  A request of
    prompt P and N tokens decodes N - 1 ticks at positions P .. P + N - 2
    and holds ceil((p + 1) / bs) live blocks at position p.  Attention in
    bf16 and latent attention (MLA) take no such kernel and count
    nothing."""
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Engine, Request
    cfg = get_config(arch).reduced(dtype="float32", attn_impl="full", **over)
    params = get_model(cfg).init(jax.random.PRNGKey(1))
    bs, max_seq, max_new = 8, 48, 6
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq=max_seq,
                                           paged=True, block_size=bs))
    lens = (3, 9, 17)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                               n).tolist(), max_new=max_new)
            for i, n in enumerate(lens)]
    assert eng.serve(reqs)["done"]
    names = ("engine_attn_kv_blocks_read_total",
             "engine_attn_kv_blocks_table_total")
    if not counted:
        assert not set(names) & set(eng.registry.dump())
        return
    read, table = (eng.registry.counter(n).value() for n in names)
    assert read == sum(-(-(p + 1) // bs) for n in lens
                       for p in range(n, n + max_new - 1))
    assert table == len(lens) * (max_new - 1) * (max_seq // bs)
    assert 0 < read < table
