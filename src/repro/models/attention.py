"""Attention modules: GQA (RoPE) and MLA (DeepSeek-V2), with KV caches.

All projections route through ``core.layers.quant_matmul`` so every
architecture can run under any LUNA quantization mode.

Tensor convention: activations (B, S, D); per-head tensors (B, S, H, Dh).
KV caches are preallocated (B, S_max, ...) and written at ``cache_index``
(static-shape decode, dry-run friendly).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.layers import quant_matmul
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention)
from repro.models.common import (apply_rope, dense_init, dense_write_window,
                                 paged_gather, paged_write,
                                 paged_write_window, rms_norm,
                                 yarn_get_mscale)


class KVCache(NamedTuple):
    k: jax.Array   # (B, S_max, Hkv, Dh)  [GQA]  or c_kv (B, S_max, R) [MLA]
    v: jax.Array   # (B, S_max, Hkv, Dh)  [GQA]  or k_rope (B, S_max, dr) [MLA]


# ---------------------------------------------------------------------------
# Scaled dot-product attention with GQA broadcast, three impls
# ---------------------------------------------------------------------------

def _per_row(q_offset, kv_len) -> bool:
    """True when offsets are per-row (B,) arrays (mixed-depth batched decode)."""
    return any(v is not None and getattr(v, "ndim", 0) == 1
               for v in (q_offset, kv_len))


def _bias(sq: int, sk: int, q_offset, causal: bool, kv_len=None) -> jax.Array:
    """Additive mask bias.

    Scalar offsets -> (sq, sk), broadcast over batch and heads.  Per-row
    (B,)-shaped ``q_offset``/``kv_len`` (continuous batching: every slab row
    decodes at its own depth) -> (B, 1, sq, sk).
    """
    if _per_row(q_offset, kv_len):
        off = jnp.asarray(q_offset if q_offset is not None else 0)
        rows = jnp.arange(sq)[None, :, None] + off.reshape(-1, 1, 1)
        cols = jnp.arange(sk)[None, None, :]
        ok = jnp.ones((rows.shape[0], sq, sk), bool)
        if causal:
            ok &= rows >= cols
        if kv_len is not None:
            ok &= cols < jnp.asarray(kv_len).reshape(-1, 1, 1)
        return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[:, None]
    rows = jnp.arange(sq)[:, None] + (q_offset if q_offset is not None else 0)
    cols = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= rows >= cols
    if kv_len is not None:                      # mask unwritten cache slots
        ok &= cols < kv_len
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
         q_offset=0, kv_len=None, impl: str = "chunked",
         chunk: int = 512, unroll: bool = False,
         f32_operands: bool = True, fused_mask: bool = False,
         causal_skip: bool = False) -> jax.Array:
    """q: (B, Sq, H, Dh); k/v: (B, Sk, Hkv, Dh) -> (B, Sq, H, Dh).

    H-major throughout: KV heads are broadcast up to H *before* the score
    einsum so the head axis stays TP-shardable (an (hkv, group) split would
    make hkv=4 unshardable over a 16-way model axis and silently replicate
    every score tensor).  After head-sharding the broadcast costs nothing:
    each device holds only its H/model head slice.
    """
    from repro.parallel.act_sharding import shard_heads
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    if impl == "flash" and sq > 1 and kv_len is None:
        from repro.kernels.flash_attention.ops import mha
        return mha(q, k, v, sm_scale=float(1.0 / dh ** 0.5), causal=causal,
                   use_flash=True)

    if g > 1:
        k = jnp.repeat(k, g, axis=2)            # (B, Sk, H, Dh)
        v = jnp.repeat(v, g, axis=2)
    k = shard_heads(k)
    v = shard_heads(v)
    q = shard_heads(q)
    def _mask(s, sq_c, sk_c, off):
        if not fused_mask or _per_row(off, kv_len):
            # baseline: scale-mul then broadcast-bias add (also the only
            # path that supports per-row offsets)
            return s * scale + _bias(sq_c, sk_c, off, causal, kv_len)
        # fused scale+mask: one where() instead of mul + broadcast-bias-add
        rows = jnp.arange(sq_c)[:, None] + off
        cols = jnp.arange(sk_c)[None, :]
        ok = jnp.ones((sq_c, sk_c), bool)
        if causal:
            ok = rows >= cols
        if kv_len is not None:
            ok = ok & (cols < kv_len)
        return jnp.where(ok[None, None], s * scale, -1e30)

    if f32_operands:
        # baseline: f32 copies of K/V/P (simple, but 2x HBM bytes)
        def _attend(qc, kc, vc, off):
            s = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32))
            s = _mask(s, qc.shape[1], kc.shape[1], off)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32))
            return o.astype(q.dtype)
    else:
        # optimized: bf16 operands, f32 MXU accumulation; P downcast to the
        # operand dtype before P@V (flash-attention numerics)
        def _attend(qc, kc, vc, off):
            s = jax.lax.dot_general(
                qc.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
                (((3,), (3,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)           # (B,H,q,k)
            s = _mask(s, qc.shape[1], kc.shape[1], off)
            p = jax.nn.softmax(s, axis=-1).astype(kc.dtype)
            o = jax.lax.dot_general(
                p, vc.transpose(0, 2, 1, 3),
                (((3,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)           # (B,H,q,d)
            return o.transpose(0, 2, 1, 3).astype(q.dtype)

    if impl == "chunked" and sq > chunk and sq % chunk == 0:
        nc = sq // chunk
        if unroll:
            # python-unrolled (accounting / TPU-kernel stand-in): causal
            # chunks only attend to keys <= chunk end — the flash kernel's
            # block skipping (halves attention work for causal full-seq).
            # Valid with a progressively-written prefill cache too: causal
            # masking already excludes keys beyond the chunk end.
            skip = causal_skip and causal \
                and isinstance(q_offset, int) and q_offset == 0
            outs = []
            for i in range(nc):
                kend = (i + 1) * chunk if skip else k.shape[1]
                outs.append(_attend(q[:, i * chunk:(i + 1) * chunk],
                                    k[:, :kend], v[:, :kend],
                                    i * chunk + q_offset))
            out = jnp.concatenate(outs, axis=1)
        else:
            qs = q.reshape(b, nc, chunk, h, dh).transpose(1, 0, 2, 3, 4)
            offs = jnp.arange(nc) * chunk + q_offset

            def step(_, xs):
                qc, off = xs
                return None, _attend(qc, k, v, off)

            _, outs = jax.lax.scan(step, None, (qs, offs))
            out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)
    else:
        out = _attend(q, k, v, q_offset)
    return out


# ---------------------------------------------------------------------------
# GQA attention (starcoder2 / minitron / yi / deepseek-67b / mistral / whisper)
# ---------------------------------------------------------------------------

def init_gqa(key, cfg, d_model=None, num_heads=None, num_kv_heads=None,
             head_dim=None, dtype=None):
    d = d_model or cfg.d_model
    h = num_heads or cfg.num_heads
    hkv = num_kv_heads or cfg.num_kv_heads
    dh = head_dim or cfg.resolved_head_dim
    dt = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * dh, dt),
        "wk": dense_init(ks[1], d, hkv * dh, dt),
        "wv": dense_init(ks[2], d, hkv * dh, dt),
        "wo": dense_init(ks[3], h * dh, d, dt),
    }


def gqa_attention(params, x: jax.Array, cfg, *, positions: jax.Array,
                  cache: KVCache | None = None, cache_index=None,
                  causal: bool = True, kv_x: jax.Array | None = None,
                  rope: bool = True, num_heads=None, num_kv_heads=None,
                  head_dim=None, impl=None, block_table=None, n_valid=None):
    """Returns (out (B,S,D), new_cache).

    ``kv_x``: cross-attention source (encoder output); disables cache rope.
    ``block_table``: (B, nblk) int32 — the cache leaves are then paged
    pools (num_blocks, block_size, ...) instead of dense (B, S, ...) slabs;
    decode writes at ``table[row, pos // bs]`` and attends over the gathered
    logical-order view, except that a plain one-token decode in f32
    (``attn_f32``) lowered for the TPU runs the Pallas kernel
    ``paged_decode_attention``, which reads each row's live blocks in
    place.  With per-row ``cache_index`` the decode write may
    carry an S > 1 token window (speculative verify); ``n_valid``: optional
    (B,) count of real window tokens per row — the rest write nowhere and
    are masked out of attention via ``kv_len``.
    """
    b, s, d = x.shape
    h = num_heads or cfg.num_heads
    hkv = num_kv_heads or cfg.num_kv_heads
    dh = head_dim or cfg.resolved_head_dim
    q = quant_matmul(x, params["wq"], cfg.quant, "attn").reshape(b, s, h, dh)
    src = kv_x if kv_x is not None else x
    sk = src.shape[1]
    k = quant_matmul(src, params["wk"], cfg.quant, "attn").reshape(b, sk, hkv, dh)
    v = quant_matmul(src, params["wv"], cfg.quant, "attn").reshape(b, sk, hkv, dh)

    if rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    kv_len = None
    q_offset = 0
    if cache is not None and kv_x is None:
        if s == 1 and cfg.decode_attn == "sharded":
            from repro.parallel.act_sharding import current_mesh
            mesh = current_mesh()
            shard_axis = (cache.k.shape[0] if block_table is not None
                          else cache.k.shape[1])
            if mesh is not None and "model" in mesh.axis_names \
                    and shard_axis % mesh.shape["model"] == 0:
                from repro.serve.decode_attention import sharded_gqa_decode
                out, k_all, v_all = sharded_gqa_decode(
                    q, cache.k, cache.v, k, v, cache_index, mesh,
                    sm_scale=1.0 / float(dh) ** 0.5,
                    grouped_bf16=cfg.decode_attn_precision == "bf16_grouped",
                    block_table=block_table)
                out = out.reshape(b, s, h * dh)
                return (quant_matmul(out, params["wo"], cfg.quant, "attn"),
                        KVCache(k_all, v_all))
        if block_table is not None:
            # paged decode: write the new KV at the row's logical depth via
            # the block table, attend over the gathered logical-order view;
            # S > 1 is a speculative verify window (junk columns beyond
            # n_valid are OOB-dropped by the scatter and masked by kv_len)
            idx = jnp.asarray(cache_index, jnp.int32) \
                + jnp.zeros((b,), jnp.int32)
            if s == 1 and n_valid is None:
                k_pool = paged_write(cache.k, k, block_table, idx)
                v_pool = paged_write(cache.v, v, block_table, idx)
            else:
                k_pool = paged_write_window(cache.k, k, block_table, idx,
                                            n_valid)
                v_pool = paged_write_window(cache.v, v, block_table, idx,
                                            n_valid)
            valid = s if n_valid is None else n_valid
            new_cache = KVCache(k_pool, v_pool)

            def gathered(q, k_pool, v_pool):
                return sdpa(q, paged_gather(k_pool, block_table),
                            paged_gather(v_pool, block_table), causal=causal,
                            q_offset=idx, kv_len=idx + valid,
                            impl=impl or cfg.attn_impl, chunk=cfg.attn_chunk,
                            unroll=not cfg.scan_layers,
                            f32_operands=cfg.attn_f32,
                            fused_mask=cfg.attn_fused_mask,
                            causal_skip=cfg.attn_causal_skip)

            def in_place(q, k_pool, v_pool):
                return paged_decode_attention(q[:, 0], k_pool, v_pool,
                                              block_table, idx + 1)[:, None]

            if s == 1 and n_valid is None and cfg.attn_f32:
                # plain decode: on the TPU a kernel reads each row's live
                # blocks in place, the same f32 math as the gather path
                out = jax.lax.platform_dependent(q, k_pool, v_pool,
                                                 tpu=in_place,
                                                 default=gathered)
            else:
                out = gathered(q, k_pool, v_pool)
            out = out.reshape(b, s, h * dh)
            return (quant_matmul(out, params["wo"], cfg.quant, "attn"),
                    new_cache)
        if getattr(cache_index, "ndim", 0) == 1:
            # per-row decode positions: every slab row writes its new KV at
            # its own depth (single batched scatter, static shapes); S > 1
            # is a speculative verify window
            if s == 1 and n_valid is None:
                rows = jnp.arange(b)
                k_all = cache.k.at[rows, cache_index].set(
                    k[:, 0].astype(cache.k.dtype))
                v_all = cache.v.at[rows, cache_index].set(
                    v[:, 0].astype(cache.v.dtype))
            else:
                k_all = dense_write_window(cache.k, k, cache_index, n_valid)
                v_all = dense_write_window(cache.v, v, cache_index, n_valid)
        else:
            k_all = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, cache_index, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, cache_index, 0, 0))
        new_cache = KVCache(k_all, v_all)
        k, v = k_all, v_all
        kv_len = cache_index + (s if n_valid is None else n_valid)
        q_offset = cache_index

    out = sdpa(q, k, v, causal=causal and kv_x is None, q_offset=q_offset,
               kv_len=kv_len, impl=impl or cfg.attn_impl, chunk=cfg.attn_chunk,
               unroll=not cfg.scan_layers, f32_operands=cfg.attn_f32,
               fused_mask=cfg.attn_fused_mask,
               causal_skip=cfg.attn_causal_skip)
    out = out.reshape(b, s, h * dh)
    return quant_matmul(out, params["wo"], cfg.quant, "attn"), new_cache


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v2): compressed KV cache (c_kv + shared k_rope)
# ---------------------------------------------------------------------------

def init_mla(key, cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dt = jnp.dtype(cfg.dtype)
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 7)
    p = {
        "w_dkv": dense_init(ks[0], d, m.kv_lora_rank + m.qk_rope_dim, dt),
        "w_uk": dense_init(ks[1], m.kv_lora_rank, h * m.qk_nope_dim, dt),
        "w_uv": dense_init(ks[2], m.kv_lora_rank, h * m.v_dim, dt),
        "wo": dense_init(ks[3], h * m.v_dim, d, dt),
        # RMSNorm gain on the compressed KV (kv_a_layernorm), applied
        # before it is cached and up-projected
        "kv_norm": {"norm_w": jnp.ones((m.kv_lora_rank,), jnp.float32)},
    }
    if m.q_lora_rank:
        p["w_dq"] = dense_init(ks[4], d, m.q_lora_rank, dt)
        p["w_uq"] = dense_init(ks[5], m.q_lora_rank, h * qd, dt)
    else:
        p["wq"] = dense_init(ks[6], d, h * qd, dt)
    return p


def mla_softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's
    ``yarn_get_mscale(factor, mscale_all_dim) ** 2`` under rope scaling
    (DeepSeek-V2's ``DeepseekV2Attention.softmax_scale``)."""
    m = cfg.mla
    scale = float(m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_get_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def mla_attention(params, x: jax.Array, cfg, *, positions: jax.Array,
                  cache: KVCache | None = None, cache_index=None,
                  block_table=None, n_valid=None):
    """MLA with the compressed-cache decode path.

    Cache stores (c_kv (B,S,R), k_rope (B,S,dr)) — the 'absorbed' form keeps
    decode FLOPs at O(R + dr) per head instead of materializing per-head K/V.
    ``c_kv`` is cached after its RMSNorm (``kv_norm``), as DeepSeek-V2's
    ``kv_a_layernorm`` normalizes it before the up-projection; rope is
    YaRN-scaled when ``cfg.rope_scaling`` is set.
    ``block_table``: (B, nblk) — cache leaves are paged pools
    (num_blocks, block_size, R) / (num_blocks, block_size, dr); ``n_valid``:
    (B,) real-token counts for an S > 1 speculative verify window; see
    :func:`gqa_attention`.
    """
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.num_heads
    qd = m.qk_nope_dim + m.qk_rope_dim

    if m.q_lora_rank:
        q = quant_matmul(quant_matmul(x, params["w_dq"], cfg.quant, "attn"),
                         params["w_uq"], cfg.quant, "attn")
    else:
        q = quant_matmul(x, params["wq"], cfg.quant, "attn")
    q = q.reshape(b, s, h, qd)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    dkv = quant_matmul(x, params["w_dkv"], cfg.quant, "attn")
    c_kv, k_rope = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    c_kv = rms_norm(c_kv, params["kv_norm"]["norm_w"], cfg.norm_eps)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    kv_len = None
    q_offset = 0
    new_cache = None
    if cache is not None and s == 1 and cfg.decode_attn == "sharded":
        from repro.parallel.act_sharding import current_mesh
        mesh = current_mesh()
        shard_axis = (cache.k.shape[0] if block_table is not None
                      else cache.k.shape[1])
        if mesh is not None and "model" in mesh.axis_names \
                and shard_axis % mesh.shape["model"] == 0:
            from repro.serve.decode_attention import sharded_mla_decode
            w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
            q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            ctx_c, c_all, r_all = sharded_mla_decode(
                q_abs, q_rope.astype(jnp.float32), cache.k, cache.v,
                c_kv, k_rope, cache_index, mesh,
                sm_scale=mla_softmax_scale(cfg), block_table=block_table)
            w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_dim)
            ctx = jnp.einsum("bqhr,rhd->bqhd", ctx_c.astype(jnp.float32),
                             w_uv.astype(jnp.float32))
            ctx = ctx.reshape(b, s, h * m.v_dim).astype(x.dtype)
            return (quant_matmul(ctx, params["wo"], cfg.quant, "attn"),
                    KVCache(c_all, r_all))
    if cache is not None:
        if block_table is not None:
            idx = jnp.asarray(cache_index, jnp.int32) \
                + jnp.zeros((b,), jnp.int32)
            if s == 1 and n_valid is None:
                c_all = paged_write(cache.k, c_kv, block_table, idx)
                r_all = paged_write(cache.v, k_rope, block_table, idx)
            else:
                c_all = paged_write_window(cache.k, c_kv, block_table, idx,
                                           n_valid)
                r_all = paged_write_window(cache.v, k_rope, block_table, idx,
                                           n_valid)
            new_cache = KVCache(c_all, r_all)
            c_kv = paged_gather(c_all, block_table)
            k_rope = paged_gather(r_all, block_table)
            kv_len = idx + (s if n_valid is None else n_valid)
            q_offset = idx
        elif getattr(cache_index, "ndim", 0) == 1:
            if s == 1 and n_valid is None:
                rows = jnp.arange(b)
                c_all = cache.k.at[rows, cache_index].set(
                    c_kv[:, 0].astype(cache.k.dtype))
                r_all = cache.v.at[rows, cache_index].set(
                    k_rope[:, 0].astype(cache.v.dtype))
            else:
                c_all = dense_write_window(cache.k, c_kv, cache_index,
                                           n_valid)
                r_all = dense_write_window(cache.v, k_rope, cache_index,
                                           n_valid)
        else:
            c_all = jax.lax.dynamic_update_slice(
                cache.k, c_kv.astype(cache.k.dtype), (0, cache_index, 0))
            r_all = jax.lax.dynamic_update_slice(
                cache.v, k_rope.astype(cache.v.dtype), (0, cache_index, 0))
        if block_table is None:
            new_cache = KVCache(c_all, r_all)
            c_kv, k_rope = c_all, r_all
            kv_len = cache_index + (s if n_valid is None else n_valid)
            q_offset = cache_index

    sk = c_kv.shape[1]
    # Absorbed scores: q_nope^T (W_uk c) == (q_nope W_uk^T)^T c
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))          # (B,Sq,H,R)
    c_f = c_kv.astype(jnp.float32)
    r_f = k_rope.astype(jnp.float32)
    sm_scale = mla_softmax_scale(cfg)

    def _chunk(qa, qr, off):
        s_c = jnp.einsum("bqhr,bkr->bhqk", qa, c_f)
        s_r = jnp.einsum("bqhd,bkd->bhqk", qr, r_f)
        scores = (s_c + s_r) * sm_scale
        bias = _bias(qa.shape[1], sk, off, True, kv_len)
        if bias.ndim == 2:            # scalar offsets: broadcast over (B, H)
            bias = bias[None, None]
        scores = scores + bias
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkr->bqhr", p, c_f)       # (B,cq,H,R)

    cq = cfg.attn_chunk
    if s > cq and s % cq == 0:
        nc = s // cq
        if not cfg.scan_layers:   # accounting mode: unrolled python loop
            outs = [_chunk(q_abs[:, i * cq:(i + 1) * cq],
                           q_rope.astype(jnp.float32)[:, i * cq:(i + 1) * cq],
                           i * cq + q_offset) for i in range(nc)]
            ctx_c = jnp.concatenate(outs, axis=1)
        else:
            qa_s = q_abs.reshape(b, nc, cq, h, -1).transpose(1, 0, 2, 3, 4)
            qr_s = (q_rope.astype(jnp.float32)
                    .reshape(b, nc, cq, h, -1).transpose(1, 0, 2, 3, 4))
            offs = jnp.arange(nc) * cq + q_offset

            def step(_, xs):
                qa, qr, off = xs
                return None, _chunk(qa, qr, off)

            _, outs = jax.lax.scan(step, None, (qa_s, qr_s, offs))
            ctx_c = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h,
                                                          m.kv_lora_rank)
    else:
        ctx_c = _chunk(q_abs, q_rope.astype(jnp.float32), q_offset)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_dim)
    ctx = jnp.einsum("bqhr,rhd->bqhd", ctx_c, w_uv.astype(jnp.float32))
    ctx = ctx.reshape(b, s, h * m.v_dim).astype(x.dtype)
    return quant_matmul(ctx, params["wo"], cfg.quant, "attn"), new_cache
