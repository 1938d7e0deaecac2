"""Shared model building blocks (pure-functional, param-dict style)."""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class CacheSpec:
    """Cache-layout request: THE uniform ``init_cache`` contract.

    Every family exposes ``init_cache(batch, s_max, *, spec=None)``.  With
    ``spec=None`` (or a spec without paging) the cache is a dense slab of
    per-slot (batch, s_max, ...) rows.  A paged spec turns every pageable
    KV leaf into a pool of ``num_blocks`` fixed ``block_size``-token blocks
    indexed via per-row block tables (families without pageable leaves —
    recurrent state, modality caches — must reject a paged spec rather
    than silently ignore it)."""
    block_size: int | None = None
    num_blocks: int | None = None

    def __post_init__(self):
        if (self.block_size is None) != (self.num_blocks is None):
            raise ValueError(
                "CacheSpec paging needs BOTH block_size and num_blocks "
                f"(got block_size={self.block_size}, "
                f"num_blocks={self.num_blocks})")

    @property
    def paged(self) -> bool:
        return self.block_size is not None


def reject_paged_spec(spec: CacheSpec | None, family: str, why: str) -> None:
    """Shared guard for families with nothing to page."""
    if spec is not None and spec.paged:
        raise ValueError(f"family {family!r} rejects a paged CacheSpec: "
                         f"{why}")


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def remat_policy_of(cfg):
    if getattr(cfg, "remat_policy", "nothing") == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * weight.astype(jnp.float32)).astype(dt)


def token_positions(s: int, cache_index) -> jax.Array:
    """Absolute positions of ``s`` new tokens appended at ``cache_index``.

    ``cache_index`` is a scalar (shared depth: prefill / uniform decode) or a
    (B,) int32 array (continuous batching: each slab row at its own depth).
    Returns (1, S) or (B, S), broadcastable against (B, S) activations.
    """
    idx = cache_index
    if getattr(idx, "ndim", 0) == 1:
        return idx[:, None] + jnp.arange(s)[None, :]
    return jnp.arange(s)[None, :] + idx


def paged_gather(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Logical-order view of each row's paged cache.

    ``pool``: (num_blocks, block_size, ...); ``block_table``: (B, nblk)
    int32 physical block ids in logical order.  Returns
    (B, nblk * block_size, ...) — column ``j`` is logical token ``j`` of the
    row.  Unreserved table entries point at the garbage block; their columns
    sit beyond the row's ``kv_len`` and are masked by the caller.
    """
    g = pool[jnp.clip(block_table, 0, pool.shape[0] - 1)]
    return g.reshape((block_table.shape[0], -1) + pool.shape[2:])


def paged_write(pool: jax.Array, new: jax.Array, block_table: jax.Array,
                index: jax.Array) -> jax.Array:
    """Write one new token per row into a paged pool at its logical depth.

    ``new``: (B, 1, ...); ``index``: (B,) logical positions.  The physical
    target is ``block_table[row, index // block_size]`` at offset
    ``index % block_size``.  Rows the engine parks on the garbage block all
    write there (duplicate indices — nondeterministic winner, never read).
    """
    bs = pool.shape[1]
    idx = jnp.asarray(index, jnp.int32)
    rows = jnp.arange(new.shape[0])
    phys = block_table[rows, idx // bs]
    return pool.at[phys, idx % bs].set(new[:, 0].astype(pool.dtype))


def dense_write_window(cache: jax.Array, new: jax.Array, index: jax.Array,
                       n_valid: jax.Array | None = None) -> jax.Array:
    """Scatter an S-token window per row into a dense (B, S_max, ...) slab.

    ``new``: (B, S, ...); ``index``: (B,) per-row start positions — row
    ``b``'s token ``i`` lands at ``index[b] + i``.  ``n_valid``: optional
    (B,) count of REAL tokens per row; entries at or beyond it are routed
    to an out-of-bounds index and DROPPED (speculative verify windows mix
    rows with different draft counts — junk columns must write nowhere,
    not clamp onto committed positions).
    """
    b, s = new.shape[0], new.shape[1]
    idx = jnp.asarray(index, jnp.int32)[:, None] + jnp.arange(s)[None, :]
    if n_valid is not None:
        ok = jnp.arange(s)[None, :] < jnp.asarray(n_valid,
                                                  jnp.int32)[:, None]
        idx = jnp.where(ok, idx, cache.shape[1])
    rows = jnp.arange(b)[:, None]
    return cache.at[rows, idx].set(new.astype(cache.dtype), mode="drop")


def paged_write_window(pool: jax.Array, new: jax.Array,
                       block_table: jax.Array, index: jax.Array,
                       n_valid: jax.Array | None = None) -> jax.Array:
    """:func:`paged_write` generalized to an S-token window per row.

    ``new``: (B, S, ...); ``index``: (B,) per-row logical start positions.
    ``n_valid``: optional (B,) count of real tokens — invalid window
    entries get the out-of-bounds physical id ``num_blocks`` and are
    DROPPED by the scatter, so a row's junk columns can never collide
    with another row's committed KV (clamping would).
    """
    b, s = new.shape[0], new.shape[1]
    bs = pool.shape[1]
    idx = jnp.asarray(index, jnp.int32)[:, None] + jnp.arange(s)[None, :]
    col = jnp.clip(idx // bs, 0, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, col, axis=1)        # (B, S)
    if n_valid is not None:
        ok = jnp.arange(s)[None, :] < jnp.asarray(n_valid,
                                                  jnp.int32)[:, None]
        phys = jnp.where(ok, phys, pool.shape[0])
    return pool.at[phys, idx % bs].set(new.astype(pool.dtype), mode="drop")


def gather_last(hidden: jax.Array, last_pos) -> jax.Array:
    """hidden: (B, S, D) -> (B, 1, D) at per-row ``last_pos`` (B,) (the last
    REAL token of each row in a right-padded prefill batch)."""
    idx = jnp.asarray(last_pos, jnp.int32).reshape(-1, 1, 1)
    return jnp.take_along_axis(hidden, idx, axis=1)


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse rope frequencies; ``scaling``: a
    :class:`~repro.configs.base.RopeScaling` (YaRN) or None."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is None:
        return freqs
    # DeepSeek-V2's DeepseekV2YarnRotaryEmbedding: frequencies below the
    # correction range keep their value, those above it are divided by
    # the factor, and a linear ramp joins the two
    low, high = yarn_correction_range(scaling, head_dim, theta)
    mask = 1.0 - yarn_linear_ramp_mask(low, high, head_dim // 2)
    return freqs / scaling.factor * (1.0 - mask) + freqs * mask


def yarn_correction_dim(rotations: float, dim: int, theta: float,
                        max_positions: int) -> float:
    """The rope dimension that turns ``rotations`` times over
    ``max_positions`` (``yarn_find_correction_dim``)."""
    return (dim * math.log(max_positions / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_correction_range(scaling, dim: int, theta: float
                          ) -> tuple[int, int]:
    """``yarn_find_correction_range``: the rope dimensions between
    ``beta_fast`` and ``beta_slow`` rotations."""
    n = scaling.original_max_position_embeddings
    low = math.floor(yarn_correction_dim(scaling.beta_fast, dim, theta, n))
    high = math.ceil(yarn_correction_dim(scaling.beta_slow, dim, theta, n))
    return max(low, 0), min(high, dim - 1)


def yarn_linear_ramp_mask(low: float, high: float, n: int) -> jax.Array:
    """``yarn_linear_ramp_mask``: 0 up to ``low``, 1 from ``high``."""
    if low == high:
        high += 0.001
    ramp = (jnp.arange(n, dtype=jnp.float32) - low) / (high - low)
    return jnp.clip(ramp, 0.0, 1.0)


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """``yarn_get_mscale``: YaRN's attention temperature."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: broadcastable to (..., S).
    ``scaling``: YaRN (:class:`~repro.configs.base.RopeScaling`) or None,
    which is plain rope."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)              # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    if x.ndim == angles.ndim + 1:                      # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_get_mscale(scaling.factor, scaling.mscale)
             / yarn_get_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def causal_mask_bias(s_q: int, s_k: int, q_offset: jax.Array | int = 0
                     ) -> jax.Array:
    """(s_q, s_k) additive bias; q global position = q_offset + row."""
    rows = jnp.arange(s_q)[:, None] + q_offset
    cols = jnp.arange(s_k)[None, :]
    return jnp.where(rows >= cols, 0.0, -1e30).astype(jnp.float32)


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross-entropy; logits (..., V), labels (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
