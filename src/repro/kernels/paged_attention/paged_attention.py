"""Pallas TPU kernel: one-token decode attention over a paged KV pool.

A decode step attends each row's one new query to the keys and values
it has cached.  The paged cache keeps them in a pool of fixed-size
blocks, ``(num_blocks, block_size, Hkv, Dh)``, and each row's block
table lists its blocks in logical order.  :func:`paged_decode_attention`
reads the pool where it lies, in that layout, and reads only the blocks
that hold a row's live positions: ``ceil(kv_len / block_size)`` of them.
Nothing is gathered into a logical-order copy, and no column past a
row's ``kv_len`` is scored.

A grid step holds ``c`` = ``blocks_per_step`` blocks of one row.  The
kernel takes each pool ``c`` times, as ``c`` inputs whose block is one
pool block ``(block_size, Hkv, Dh)``, and an index map reads the block
table (scalar prefetch) to name the block each input holds.  The grid
has ``B * cdiv(nblk, c)`` steps: first every row's live chunks, in
(row, chunk) order (:func:`schedule`), then the steps that the short
rows leave over, which compute nothing.  The pipeline copies the next
step's blocks while a step is scored, from one row into the next too.
Past a row's last live block an input names the block it held the step
before, so that no copy starts there.  (A block named from the table
can only be copied by the pipeline: Mosaic refuses a copy sliced by
hand out of a pool whose last axis is narrower than the 128 lanes its
HBM tiles are padded to, as zamba2-1.2b's ``Dh`` of 64 is.)

Each block is widened to f32 in VMEM, and the scores, the online
softmax and P·V are f32: the math of the ``jnp`` path with
``attn_f32=True`` (``models.attention.sdpa``), up to the order of the
f32 sums and the one division by the softmax's sum, which comes after
P·V here.  A query head ``h`` reads KV head ``h // g`` (``g = H /
Hkv``), as ``sdpa``'s ``jnp.repeat`` does; the wrapper hands the query
over as ``(B, g, Hkv, Dh)`` so that a block of K and V is read once for
all ``g`` heads that share it.

Named ``paged_decode_attention`` in a profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: blocks of K (and of V) in one grid step: enough bytes per step that
#: the step's fixed cost stays small beside its copies, few enough that a
#: short row reads little past its last live block
BLOCKS_PER_STEP = 8
#: the score of a column past ``kv_len``: ``sdpa``'s mask value
MASK_VALUE = -1e30


def _live(kv_len_ref, row, bs: int):
    return (kv_len_ref[row] + bs - 1) // bs


def schedule(kv_len: jax.Array, bs: int, c: int, steps: int):
    """Row and chunk of each grid step: every row's live chunks (``c``
    blocks each) in (row, chunk) order, then chunks past the last row's
    live ones, which the kernel skips.  A row's first chunk follows the
    last of the row before it, so the pipeline copies it while that one
    is scored."""
    live_chunks = (kv_len + bs * c - 1) // (bs * c)
    ends = jnp.cumsum(live_chunks)
    step = jnp.arange(steps, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(step[:, None] >= ends[None, :], axis=1),
                      kv_len.shape[0] - 1).astype(jnp.int32)
    chunk = step - (ends[row] - live_chunks[row])
    return row, chunk.astype(jnp.int32)


def _block_index(i: int, c: int, bs: int):
    """Index map of the ``i``-th K (or V) input at grid step ``s``: the
    pool block that holds logical block ``chunk * c + i`` of the step's
    row.  Past the row's last live block it names the block this input
    held one step before, so the pipeline, which skips a copy whose block
    index has not changed, starts none."""
    def index(s, kv_len_ref, table_ref, row_ref, chunk_ref):
        row = row_ref[s]
        live = _live(kv_len_ref, row, bs)
        last = (live - 1) // c
        j = jnp.minimum(chunk_ref[s], last) * c + i
        j = jnp.where(j < live, j, jnp.where(j >= c, j - c, live - 1))
        return table_ref[row, j], 0, 0, 0
    return index


def _kernel(kv_len_ref, table_ref, row_ref, chunk_ref, q_ref, *refs, c: int,
            scale: float):
    k_refs, v_refs = refs[:c], refs[c:2 * c]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * c:]
    step = pl.program_id(0)
    row, chunk = row_ref[step], chunk_ref[step]
    bs = k_refs[0].shape[0]
    g, hkv, dh = q_ref.shape[1:]
    kv_len = kv_len_ref[row]
    live = _live(kv_len_ref, row, bs)

    @pl.when(chunk == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, MASK_VALUE, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    for i in range(c):
        j = chunk * c + i

        @pl.when(j < live)
        def _block(i=i, j=j):
            k = k_refs[i][...].astype(jnp.float32)       # (bs, Hkv, Dh)
            v = v_refs[i][...].astype(jnp.float32)
            pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, hkv, 1),
                                                    0)
            for t in range(g):
                q = q_ref[0, t].astype(jnp.float32)      # (Hkv, Dh)
                s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
                s = jnp.where(pos < kv_len, s, MASK_VALUE)   # (bs, Hkv, 1)
                m = m_ref[t]
                m_new = jnp.maximum(m, jnp.max(s, axis=0))   # (Hkv, 1)
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[None])
                m_ref[t] = m_new
                l_ref[t] = alpha * l_ref[t] + jnp.sum(p, axis=0)
                acc_ref[t] = alpha * acc_ref[t] + jnp.sum(p * v, axis=0)

    @pl.when(chunk == (live - 1) // c)
    def _out():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks_per_step", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           kv_len: jax.Array, *,
                           blocks_per_step: int = BLOCKS_PER_STEP,
                           interpret: bool = False) -> jax.Array:
    """``softmax(q·K / sqrt(Dh)) V`` over each row's first ``kv_len``
    cached positions, read in place from the paged pools.

    q: (B, H, Dh); k_pool/v_pool: (num_blocks, block_size, Hkv, Dh), as
    the paged cache stores them; block_table: (B, nblk) int32 physical
    block ids in logical order (entries past a row's live blocks are
    never read, and ids are clipped into the pool as ``paged_gather``
    clips them); kv_len: (B,) int32, at least 1 (``idx + 1`` in a decode
    step, after the new token is written).  H must be a multiple of Hkv.
    ``blocks_per_step``: pool blocks of K (and of V) a grid step holds.
    Returns (B, H, Dh) in ``q``'s dtype.
    """
    b, h, dh = q.shape
    nb, bs, hkv, dh2 = k_pool.shape
    assert dh == dh2 and h % hkv == 0 and v_pool.shape == k_pool.shape, (
        q.shape, k_pool.shape, v_pool.shape)
    g = h // hkv
    c = min(blocks_per_step, block_table.shape[1])
    # head h = kv * g + t reads KV head kv: group the query by t
    qg = q.reshape(b, hkv, g, dh).transpose(0, 2, 1, 3)
    scale = float(np.float32(1.0) / np.sqrt(np.float32(dh)))
    kv_len = jnp.asarray(kv_len, jnp.int32)
    steps = b * pl.cdiv(block_table.shape[1], c)
    rows, chunks = schedule(kv_len, bs, c, steps)
    row = pl.BlockSpec((1, g, hkv, dh),
                       lambda s, kv, tab, rows, chunks: (rows[s], 0, 0, 0))
    blocks = [pl.BlockSpec((None, bs, hkv, dh), _block_index(i, c, bs))
              for i in range(c)]
    out = pl.pallas_call(
        functools.partial(_kernel, c=c, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[row] + blocks + blocks,
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((g, hkv, 1), jnp.float32),
                            pltpu.VMEM((g, hkv, 1), jnp.float32),
                            pltpu.VMEM((g, hkv, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, g, hkv, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret,
    )(kv_len, jnp.clip(jnp.asarray(block_table, jnp.int32), 0, nb - 1),
      rows, chunks, qg, *[k_pool] * c, *[v_pool] * c)
    return out.transpose(0, 2, 1, 3).reshape(b, h, dh)
