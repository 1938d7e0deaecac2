"""Sharded decode attention (flash-decode over the model axis).

Problem (measured in the baseline dry-run): GQA KV caches with few KV heads
(kv=4/8 < model=16) are sequence-sharded, and the decode step's
``dynamic_update_slice`` at a dynamic index forces SPMD to rematerialize the
WHOLE cache every layer (the "involuntary full rematerialization" path) —
the baseline decode cells are collective-bound by TBs of cache traffic.

Fix: run decode attention inside ``shard_map`` over the model axis:
  * each rank owns a contiguous sequence slice of the cache — the new KV
    token is written LOCALLY by the one rank that owns slot ``index``;
  * each rank computes online-softmax partials (m, l, o) over its slice;
  * ranks combine with one tiny ``psum`` of (B, H, dh+2) stats.
Per-step collective traffic drops from O(cache) to O(B x H x dh) — the
flash-decode/ring-attention pattern, expressed as a jax-native shard_map.

Paged mode (``block_table`` given): the cache leaves are block pools
(num_blocks, block_size, ...) with no batch axis, sharded over 'model' on
the BLOCK axis.  Batch-shaped inputs stay replicated inside the shard_map
(the pool is shared state — every rank must see every row's write so the
replicas it keeps for foreign blocks never diverge); each rank applies the
writes landing in its block slice, gathers its owned part of each row's
logical view through the table, and combines partials exactly like the
dense path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import batch_axes

NEG = -1e30


def _local_update(cache, new, index, rank, s_shard):
    """Write ``new`` (B,1,...) into the rank-local slice at global ``index``.

    ``index`` may be a scalar (uniform decode depth) or a (B,) array
    (continuous batching: each row writes at its own depth).
    """
    idx = jnp.asarray(index)
    if idx.ndim == 1:
        b = cache.shape[0]
        li = idx - rank * s_shard                      # (B,) local offsets
        in_range = (li >= 0) & (li < s_shard)
        li_c = jnp.clip(li, 0, s_shard - 1)
        rows = jnp.arange(b)
        cur = cache[rows, li_c]
        keep = in_range.reshape((-1,) + (1,) * (cur.ndim - 1))
        return cache.at[rows, li_c].set(
            jnp.where(keep, new[:, 0].astype(cache.dtype), cur))
    li = index - rank * s_shard
    in_range = (li >= 0) & (li < s_shard)
    li_c = jnp.clip(li, 0, s_shard - 1)
    start = (0, li_c) + (0,) * (cache.ndim - 2)
    updated = jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                           start)
    return jnp.where(in_range, updated, cache)


def _paged_local_update(pool, new, phys, off, rank, nb_shard):
    """Write ``new`` (B,1,...) into the rank-local block slice.

    ``phys``/``off``: (B,) GLOBAL physical block id and in-block offset of
    each row's write.  Rows whose block another rank owns are routed to the
    out-of-bounds sentinel ``nb_shard`` and dropped by the scatter (OOB
    updates drop; negative indices would wrap, hence the explicit where).
    """
    local = phys - rank * nb_shard
    safe = jnp.where((local >= 0) & (local < nb_shard), local, nb_shard)
    return pool.at[safe, off].set(new[:, 0].astype(pool.dtype), mode="drop")


def _paged_local_view(pool, block_table, rank, nb_shard):
    """Gather each row's logical-order view from the rank-local block slice.

    Returns (view (B, nblk*bs, ...), owned (B, nblk*bs) bool) — columns in
    blocks this rank does not own gather clamped garbage and are masked.
    """
    bs = pool.shape[1]
    local = block_table - rank * nb_shard              # (B, nblk)
    owned = (local >= 0) & (local < nb_shard)
    g = pool[jnp.clip(local, 0, nb_shard - 1)]         # (B, nblk, bs, ...)
    view = g.reshape((block_table.shape[0], -1) + pool.shape[2:])
    return view, jnp.repeat(owned, bs, axis=1)


def _valid_cols(cols, idx):
    """(B?, 1, Ss) bool mask of cache columns at or before ``idx``."""
    idx = jnp.asarray(idx)
    if idx.ndim == 1:
        return cols[None, None, :] <= idx[:, None, None]
    return cols[None, None, :] <= idx


def _combine(m_loc, l_loc, o_loc, dtype):
    """One tiny cross-rank combine of the online-softmax partials."""
    m = jax.lax.pmax(m_loc, "model")
    corr = jnp.exp(m_loc - m)
    denom = jax.lax.psum(l_loc * corr, "model")
    o = jax.lax.psum(o_loc * corr, "model")
    return (o / jnp.maximum(denom, 1e-30)).astype(dtype)[:, None]


def _gqa_partials(q, k_c, v_c, ok, *, g, sm_scale, grouped_bf16):
    """Rank-local online-softmax partials over a (B, Ss, Hkv, dh) KV view.

    ``ok``: (B?, 1, Ss) or (B, 1, Ss) bool validity of each column.
    Returns (m_loc, l_loc, o_loc) each (B, H, ...).
    """
    b, _, h, dh = q.shape
    s_len = k_c.shape[1]
    hkv = k_c.shape[2]
    if grouped_bf16:
        qg = q[:, 0].reshape(b, hkv, g, dh)               # (B,Hkv,g,dh)
        s_loc = jax.lax.dot_general(                       # (B,Hkv,g,Ss)
            qg, k_c.swapaxes(1, 2),
            (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * sm_scale
        s_loc = s_loc.reshape(b, h, s_len)
    else:
        kf = jnp.repeat(k_c, g, axis=2).astype(jnp.float32)
        qf = q[:, 0].astype(jnp.float32)
        s_loc = jnp.einsum("bhd,bkhd->bhk", qf, kf) * sm_scale
    s_loc = jnp.where(ok, s_loc, NEG)
    m_loc = jnp.max(s_loc, axis=-1, keepdims=True)        # (B,H,1)
    p = jnp.where(ok, jnp.exp(s_loc - m_loc), 0.0)
    l_loc = jnp.sum(p, axis=-1, keepdims=True)            # (B,H,1)
    if grouped_bf16:
        pg = p.reshape(b, hkv, g, s_len).astype(k_c.dtype)
        o_loc = jax.lax.dot_general(                       # (B,Hkv,g,dh)
            pg, v_c.swapaxes(1, 2),
            (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        o_loc = o_loc.reshape(b, h, -1)
    else:
        vf = jnp.repeat(v_c, g, axis=2).astype(jnp.float32)
        o_loc = jnp.einsum("bhk,bkhd->bhd", p, vf)        # (B,H,dh)
    return m_loc, l_loc, o_loc


def sharded_gqa_decode(q, k_cache, v_cache, k_new, v_new, index, mesh,
                       *, sm_scale: float, grouped_bf16: bool = False,
                       block_table=None):
    """q: (B,1,H,dh); k_new/v_new: (B,1,Hkv,dh).

    Dense mode: caches (B,S,Hkv,dh) seq-sharded over 'model'.  Paged mode
    (``block_table`` (B,nblk) given): caches are block pools
    (num_blocks, bs, Hkv, dh) block-sharded over 'model'.  Returns
    (out (B,1,H,dh), k_cache, v_cache).

    ``grouped_bf16``: skip the f32 KV repeat — GQA-grouped einsums on bf16
    operands with f32 accumulation.  Inside shard_map tensors are local, so
    the (Hkv, g) grouping carries no SPMD-propagation hazard.
    """
    ba = batch_axes(mesh)
    msize = mesh.shape["model"]
    b = q.shape[0]
    h = q.shape[2]
    hkv = k_new.shape[2]
    g = h // hkv

    if block_table is not None:
        nb_shard = k_cache.shape[0] // msize
        bs_blk = k_cache.shape[1]
        idx = jnp.asarray(index, jnp.int32) + jnp.zeros((b,), jnp.int32)

        def per_rank(q, k_p, v_p, k_n, v_n, idx, bt):
            rank = jax.lax.axis_index("model")
            rows = jnp.arange(b)
            phys = bt[rows, idx // bs_blk]
            off = idx % bs_blk
            k_p = _paged_local_update(k_p, k_n, phys, off, rank, nb_shard)
            v_p = _paged_local_update(v_p, v_n, phys, off, rank, nb_shard)
            k_c, owned = _paged_local_view(k_p, bt, rank, nb_shard)
            v_c, _ = _paged_local_view(v_p, bt, rank, nb_shard)
            cols = jnp.arange(k_c.shape[1])
            ok = (owned & (cols[None, :] <= idx[:, None]))[:, None]
            m_loc, l_loc, o_loc = _gqa_partials(
                q, k_c, v_c, ok, g=g, sm_scale=sm_scale,
                grouped_bf16=grouped_bf16)
            return _combine(m_loc, l_loc, o_loc, q.dtype), k_p, v_p

        pool_spec = P("model", None, None, None)
        rep = P(None, None, None, None)
        out, k_cache, v_cache = shard_map(
            per_rank, mesh=mesh,
            in_specs=(rep, pool_spec, pool_spec, rep, rep, P(None),
                      P(None, None)),
            out_specs=(rep, pool_spec, pool_spec),
            check_vma=False,
        )(q, k_cache, v_cache, k_new, v_new, idx, block_table)
        return out, k_cache, v_cache

    s = k_cache.shape[1]
    s_shard = s // msize

    def per_rank(q, k_c, v_c, k_n, v_n, idx):
        rank = jax.lax.axis_index("model")
        k_c = _local_update(k_c, k_n, idx, rank, s_shard)
        v_c = _local_update(v_c, v_n, idx, rank, s_shard)
        cols = rank * s_shard + jnp.arange(s_shard)
        ok = _valid_cols(cols, idx)
        m_loc, l_loc, o_loc = _gqa_partials(
            q, k_c, v_c, ok, g=g, sm_scale=sm_scale,
            grouped_bf16=grouped_bf16)
        return _combine(m_loc, l_loc, o_loc, q.dtype), k_c, v_c

    cache_spec = P(ba, "model", None, None)
    io_spec = P(ba, None, None, None)
    # a (B,) per-row index is batch-sharded with the tensors it indexes
    idx_spec = P(ba) if getattr(index, "ndim", 0) == 1 else P()
    out, k_cache, v_cache = shard_map(
        per_rank, mesh=mesh,
        in_specs=(io_spec, cache_spec, cache_spec, io_spec, io_spec,
                  idx_spec),
        out_specs=(io_spec, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, index)
    return out, k_cache, v_cache


def _mla_partials(qa, qr, c_c, r_c, ok, *, sm_scale):
    """Rank-local partials over a (B, Ss, R)/(B, Ss, dr) compressed view."""
    qa_f = qa[:, 0].astype(jnp.float32)                   # (B,H,R)
    qr_f = qr[:, 0].astype(jnp.float32)                   # (B,H,dr)
    cf = c_c.astype(jnp.float32)                          # (B,Ss,R)
    rf = r_c.astype(jnp.float32)                          # (B,Ss,dr)
    s_loc = (jnp.einsum("bhr,bkr->bhk", qa_f, cf)
             + jnp.einsum("bhd,bkd->bhk", qr_f, rf)) * sm_scale
    s_loc = jnp.where(ok, s_loc, NEG)
    m_loc = jnp.max(s_loc, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s_loc - m_loc), 0.0)
    l_loc = jnp.sum(p, axis=-1, keepdims=True)
    o_loc = jnp.einsum("bhk,bkr->bhr", p, cf)             # (B,H,R)
    return m_loc, l_loc, o_loc


def sharded_mla_decode(q_abs, q_rope, c_cache, r_cache, c_new, r_new, index,
                       mesh, *, sm_scale: float, block_table=None):
    """MLA absorbed-form decode with the compressed cache seq-sharded.

    q_abs: (B,1,H,R); q_rope: (B,1,H,dr); dense mode: c_cache (B,S,R) /
    r_cache (B,S,dr); paged mode: (num_blocks, bs, R) / (num_blocks, bs, dr)
    block-sharded over 'model'.  Returns (ctx_c (B,1,H,R), c_cache,
    r_cache).
    """
    ba = batch_axes(mesh)
    msize = mesh.shape["model"]
    b = q_abs.shape[0]

    if block_table is not None:
        nb_shard = c_cache.shape[0] // msize
        bs_blk = c_cache.shape[1]
        idx = jnp.asarray(index, jnp.int32) + jnp.zeros((b,), jnp.int32)

        def per_rank(qa, qr, c_p, r_p, c_n, r_n, idx, bt):
            rank = jax.lax.axis_index("model")
            rows = jnp.arange(b)
            phys = bt[rows, idx // bs_blk]
            off = idx % bs_blk
            c_p = _paged_local_update(c_p, c_n, phys, off, rank, nb_shard)
            r_p = _paged_local_update(r_p, r_n, phys, off, rank, nb_shard)
            c_c, owned = _paged_local_view(c_p, bt, rank, nb_shard)
            r_c, _ = _paged_local_view(r_p, bt, rank, nb_shard)
            cols = jnp.arange(c_c.shape[1])
            ok = (owned & (cols[None, :] <= idx[:, None]))[:, None]
            m_loc, l_loc, o_loc = _mla_partials(qa, qr, c_c, r_c, ok,
                                                sm_scale=sm_scale)
            return _combine(m_loc, l_loc, o_loc, qa.dtype), c_p, r_p

        pool_spec = P("model", None, None)
        qrep = P(None, None, None, None)
        ctx, c_cache, r_cache = shard_map(
            per_rank, mesh=mesh,
            in_specs=(qrep, qrep, pool_spec, pool_spec, P(None, None, None),
                      P(None, None, None), P(None), P(None, None)),
            out_specs=(qrep, pool_spec, pool_spec),
            check_vma=False,
        )(q_abs, q_rope, c_cache, r_cache, c_new, r_new, idx, block_table)
        return ctx, c_cache, r_cache

    s = c_cache.shape[1]
    s_shard = s // msize

    def per_rank(qa, qr, c_c, r_c, c_n, r_n, idx):
        rank = jax.lax.axis_index("model")
        c_c = _local_update(c_c, c_n, idx, rank, s_shard)
        r_c = _local_update(r_c, r_n, idx, rank, s_shard)
        cols = rank * s_shard + jnp.arange(s_shard)
        ok = _valid_cols(cols, idx)
        m_loc, l_loc, o_loc = _mla_partials(qa, qr, c_c, r_c, ok,
                                            sm_scale=sm_scale)
        return _combine(m_loc, l_loc, o_loc, qa.dtype), c_c, r_c

    cache_spec = P(ba, "model", None)
    qspec = P(ba, None, None, None)
    idx_spec = P(ba) if getattr(index, "ndim", 0) == 1 else P()
    ctx, c_cache, r_cache = shard_map(
        per_rank, mesh=mesh,
        in_specs=(qspec, qspec, cache_spec, cache_spec,
                  P(ba, None, None), P(ba, None, None), idx_spec),
        out_specs=(qspec, cache_spec, cache_spec),
        check_vma=False,
    )(q_abs, q_rope, c_cache, r_cache, c_new, r_new, index)
    return ctx, c_cache, r_cache
