"""Cache substrates behind one protocol: the engine is substrate-blind.

Before this module the engine branched on ``PAGED_FAMILIES``, probed pool
leaves inline, and carried per-family seed/snapshot paths.  Now every
substrate decision lives behind :class:`CacheBackend`:

* :class:`DenseSlab` — per-slot (max_batch, max_seq, ...) rows; a slot
  reserves a full row for its lifetime (the reference oracle).
* :class:`PagedPool` — every pageable KV leaf becomes a pool of
  ``num_blocks`` fixed ``block_size``-token blocks with per-slot block
  tables; admission reserves only the request's lifetime block budget and
  backpressures when the pool is short (attention families).
* :class:`RecurrentState` — dense O(1)-per-slot recurrent state plus the
  snapshot/seed hooks the prefix cache needs (ssm).
* :class:`HybridComposite` — the split substrate: paged attention pools
  AND dense recurrent state, discovered structurally per leaf (hybrid).

A backend owns allocation (``reserve``/``free_slot``), the block tables
(admission/decode/copy-on-write scatter redirects), the jit-safe
scatter/gather routing along each leaf's structural batch axis, the
recurrent snapshot policy, and the prefix-cache storage policy
(``prefix_payload``).  The paged backends also expose the narrow block-op
surface (``ref``/``release``/``refcount``/``writable``/``free_blocks``)
that ``repro.serve.prefix_cache`` programs against — the cache talks to
the backend, never to ``BlockAllocator`` internals.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import CacheSpec
from repro.serve.paged import (GARBAGE_BLOCK, BlockAllocator, blocks_needed,
                               ceil_div)

# every served family tolerates right-padded prefill rows: attention masks
# pad columns causally, and the recurrent families (ssm/hybrid) mask them
# out of the carried state (masked SSD scan + per-row conv-state gather)
SERVED_FAMILIES = ("dense", "moe", "ssm", "hybrid")

# families with attention KV leaves the paged block pool can back; "ssm"
# is excluded on purpose — its whole cache is O(1) recurrent state per
# slot, there is nothing to page
PAGED_FAMILIES = ("dense", "moe", "hybrid")

# families whose cache carries recurrent state the prefix cache snapshots
RECURRENT_FAMILIES = ("ssm", "hybrid")


class CacheBackend:
    """Base substrate: dense per-slot rows.  Subclasses override the
    reservation, table, snapshot, and prefix-policy hooks; the probe and
    scatter/gather machinery is shared (it is structural, not per-family).
    """

    paged = False
    needs_state = False

    def __init__(self, model, max_batch: int, max_seq: int,
                 spec: CacheSpec | None = None):
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.spec = spec
        self.caches = model.init_cache(max_batch, max_seq, spec=spec)
        self.stage_len = max_seq
        self._batch_axes = self._find_batch_axes()
        self._pool_leaves = self._find_pool_leaves()
        self._lock = None

    # --- thread discipline ----------------------------------------------
    def bind_lock(self, lock) -> None:
        """The engine hands over its state lock: backend state (block
        pool accounting, slot tables, the cache slab reference) is only
        ever mutated while that lock is held — the tick and the public
        engine mutators all run under it, so the backend itself stays
        lock-free with a single-writer guarantee.  Mutating entry points
        assert the discipline instead of silently racing."""
        self._lock = lock

    def _assert_owned(self) -> None:
        lock = self._lock
        if lock is not None:
            owned = getattr(lock, "_is_owned", None)
            assert owned is None or owned(), \
                "backend state mutated without holding the engine lock"

    # --- cache-slab layout (structural probes) --------------------------
    def _find_batch_axes(self):
        """Per-leaf batch axis of the cache tree, found structurally by
        diffing the shapes of two differently-sized DENSE cache trees
        (cache layouts are family-specific: KV slabs are (B, S, ...),
        scanned layers stack an (L,) axis in front).  Paged pools sit at
        the same tree positions, with (num_blocks, block_size) replacing
        (B, S) — the same axis indexes their block axis."""
        a = self.model.init_cache(2, 4)
        b = self.model.init_cache(3, 4)

        def one(la, lb):
            diff = [ax for ax, (da, db) in enumerate(zip(la.shape, lb.shape))
                    if da != db]
            if len(diff) != 1:
                raise ValueError(
                    f"ambiguous batch axis for cache leaf {la.shape}")
            return diff[0]

        return jax.tree.map(one, a, b)

    def _find_pool_leaves(self):
        """Boolean tree marking which cache leaves are paged block pools —
        found structurally by diffing a dense probe tree against a paged
        probe tree at sizes whose leading dims cannot coincide.  Hybrid's
        SPLIT SUBSTRATE falls out of this: its attention KV leaves differ
        (pool-shaped) while its dense SSM state leaves match."""
        if self.spec is None or not self.spec.paged:
            return jax.tree.map(lambda a: False, self.caches)
        dense = self.model.init_cache(2, 4)
        pooled = self.model.init_cache(2, 4, spec=CacheSpec(2, 7))
        return jax.tree.map(lambda a, b: a.shape != b.shape, dense, pooled)

    # --- jit-safe bodies ------------------------------------------------
    def fresh(self, batch: int):
        """Fresh dense (batch, stage_len) staging tree (jit-safe)."""
        return self.model.init_cache(batch, self.stage_len)

    def scatter(self, slab_tree, rows_tree, slots, tables):
        """Write ``k`` freshly-prefilled cache rows into the slab — one
        batched scatter per leaf, inside jit.  Dense leaves land whole rows
        at ``slots``; pool leaves are reshaped into
        (k, nblk, block_size, ...) blocks and scattered to the physical ids
        in ``tables`` (k, nblk).  Unreserved table entries all point at the
        garbage block — their writes collide there harmlessly (never read
        back)."""
        def one(slab, rows, ax, is_pool):
            if is_pool:
                bs = self.spec.block_size
                shape = (rows.shape[:ax + 1] + (tables.shape[1], bs)
                         + rows.shape[ax + 2:])
                blocks = rows.reshape(shape).astype(slab.dtype)
                idx = (slice(None),) * ax + (tables,)
                return slab.at[idx].set(blocks)
            idx = (slice(None),) * ax + (slots,)
            return slab.at[idx].set(rows.astype(slab.dtype))

        return jax.tree.map(one, slab_tree, rows_tree, self._batch_axes,
                            self._pool_leaves)

    def gather_staging(self, caches, tbl):
        """Jit body: fresh 1-row staging tree with every pool leaf's shared
        blocks gathered into its dense staging leaf (logical order, exactly
        the values the cold prefill wrote).  Gathers run along each leaf's
        structural block axis (scan-stacked leaves carry a leading layer
        axis), mirroring :meth:`scatter`.  Dense leaves stay fresh."""
        staging = self.fresh(1)

        def one(stg, pool, ax, is_pool):
            if not is_pool:
                return stg
            g = jnp.take(pool, tbl, axis=ax)      # (..., 1, nblk, bs, ...)
            return g.reshape(stg.shape)

        return jax.tree.map(one, staging, caches, self._batch_axes,
                            self._pool_leaves)

    # --- decode weights (backend-owned quantized state) -----------------
    def prepare_decode_params(self, params, quant: str | None):
        """Freeze the decode-step weight tree once at construction.

        ``quant=None`` keeps the caller's tree untouched (decode params ARE
        the prefill params — the token-identity guarantee).  ``"lut4"``
        replaces every decode-projection leaf with a 4-bit
        :class:`~repro.core.quant.QuantizedWeight` on the exact affine
        grid; ``"nf4"`` / ``"nf4p"`` freeze the same leaves against the
        non-affine NF4 codebook, whose table folds the least-squares D&C
        split with its per-code residual (full, or pruned below the
        magnitude threshold).  The quantized tree is backend-owned state,
        like the cache slab: prefill always runs the full-precision tree,
        only the decode hot path reads this one.
        """
        if quant is None:
            self.decode_params = params
        else:
            from repro.core.quant import quantize_decode_params
            self.decode_params = quantize_decode_params(params, quant)
        return self.decode_params

    # --- host-side reservation ------------------------------------------
    def validate_request(self, rid: int, prompt_len: int,
                         max_new: int) -> None:
        """Raise for requests this substrate can NEVER serve."""

    def reservation_need(self, prompt_len: int, max_new: int) -> int:
        """Capacity units :meth:`reserve` would claim (the scheduler's
        stall gate compares failed demands).  Dense substrates need only
        the slot the caller already holds."""
        return 0

    def reserve(self, slot: int, prompt_len: int, max_new: int,
                shared: list[int] | None = None, on_short=None) -> bool:
        """Claim the request's lifetime capacity; False = backpressure.
        The dense slab's capacity IS the slot, already held by the
        caller."""
        return True

    def free_slot(self, slot: int) -> None:
        """Return a slot's substrate resources (no-op for dense rows)."""

    def rollback(self, slot: int, n: int) -> None:
        """Discard the last ``n`` REJECTED speculative tokens of ``slot``.

        Every substrate supports this; what it costs differs.  Dense
        attention rows are position-indexed: the engine rewinds the
        slot's decode pointer and the rejected KV beyond it becomes dead
        weight the next writes overwrite — rollback is pure bookkeeping
        (kv_len masking already hides the junk from attention).  The
        recurrent substrates override the DOC, not the mechanics: their
        state cannot rewind positionally, so the engine re-commits it
        from the pre-verify cache tree with the SSD scan masked at the
        accept boundary (``Engine._spec_tick``); the backend-level call
        still runs to assert the locking discipline and validate the
        slot's accounting."""
        self._assert_owned()
        assert n >= 0, n

    def slot_blocks(self, slot: int) -> list[int]:
        return []

    @property
    def free_capacity(self) -> int:
        """Reservation headroom the scheduler's stall bookkeeping watches
        (paged: free blocks).  Dense reservation never fails, so any
        constant works."""
        return self.max_batch

    @property
    def total_capacity(self) -> int:
        """Capacity ceiling in the same unit as :attr:`free_capacity`
        (dense: slots; paged: reservable blocks) — lets the pool-occupancy
        gauge report a meaningful fraction."""
        return self.max_batch

    # --- block tables (all None for dense substrates) -------------------
    def admission_tables(self, slots: list[int]):
        return None

    def decode_tables(self, staged_slots: list[int]):
        return None

    def cow_table(self, slot: int, n_shared: int):
        return None

    def finish_tables(self, slot: int, cow):
        return None

    def staging_table(self, blocks: list[int]):
        raise NotImplementedError("dense substrates share no blocks")

    # --- recurrent state ------------------------------------------------
    def capture_grid(self, prefill_bucket: int) -> int:
        """Boundary grid for prefix-cache snapshots/payloads."""
        return prefill_bucket

    def snapshot(self, caches, row: int = 0):
        """Recurrent-state snapshot at ``row`` (None: nothing to snap)."""
        return None

    def seed_snapshot(self, staging, snap):
        """Swap a snapshot into a staging row (identity when stateless)."""
        return staging

    # --- prefix-cache binding -------------------------------------------
    def prefix_cache_kwargs(self) -> dict:
        """Constructor kwargs binding ``PrefixCache`` to this substrate."""
        return {}

    def prefix_payload(self, prompt: list[int], slot: int, state):
        """THE per-family storage policy: what a finished prefill at
        ``len(prompt)`` contributes to the radix tree, or None.  Returns
        (tokens, blocks, state)."""
        return None


class DenseSlab(CacheBackend):
    """Reference substrate: full per-slot rows, no sharing, no paging."""


class RecurrentState(DenseSlab):
    """Dense O(1)-per-slot recurrent state (ssm): nothing to page, but the
    prefix cache snapshots (conv, ssd) rows at capture-grid boundaries."""

    needs_state = True

    def rollback(self, slot: int, n: int) -> None:
        """Recurrent state has no positions to rewind: a rejected draft's
        contribution is kept OUT of the state rather than removed from it
        — the engine's commit pass re-runs the window from the pre-verify
        tree with dt masked beyond the accept boundary (state frozen,
        rejected tokens contribute exactly zero), which is what the
        fixed-size ``state_snapshot`` machinery already guarantees is
        sufficient to reconstruct any boundary.  Bookkeeping-only here."""
        super().rollback(slot, n)

    def snapshot(self, caches, row: int = 0):
        return self.model.state_snapshot(caches, row)

    def seed_snapshot(self, staging, snap):
        return self.model.seed_from_snapshot(staging, snap)

    def prefix_payload(self, prompt, slot, state):
        if state is None:
            return None
        return (prompt, None, state)


class PagedPool(CacheBackend):
    """Paged-block KV substrate: refcounted fixed-size blocks with per-slot
    block tables; admission reserves ``blocks_needed`` up front so decode
    can never run out mid-request."""

    paged = True

    def __init__(self, model, max_batch: int, max_seq: int,
                 block_size: int, num_blocks: int | None = None):
        self.block_size = block_size
        self.blocks_per_row = ceil_div(max_seq, block_size)
        self.num_blocks = (num_blocks if num_blocks is not None
                           else max_batch * self.blocks_per_row + 1)
        super().__init__(model, max_batch, max_seq,
                         spec=CacheSpec(block_size, self.num_blocks))
        # staged/fresh prefill rows cover whole blocks for the scatter
        self.stage_len = self.blocks_per_row * block_size
        self.allocator = BlockAllocator(self.num_blocks, block_size)
        self.block_tables = np.full(
            (max_batch, self.blocks_per_row), GARBAGE_BLOCK, np.int32)
        self._slot_blocks: list[list[int]] = [[] for _ in range(max_batch)]

    # --- reservation ----------------------------------------------------
    def validate_request(self, rid, prompt_len, max_new):
        need = blocks_needed(prompt_len, max_new, self.max_seq,
                             self.block_size)
        if need > self.num_blocks - 1:
            raise ValueError(
                f"request {rid} needs {need} blocks but the pool "
                f"holds {self.num_blocks - 1}")

    def reservation_need(self, prompt_len, max_new):
        return blocks_needed(prompt_len, max_new, self.max_seq,
                             self.block_size)

    def reserve(self, slot, prompt_len, max_new, shared=None, on_short=None):
        """Claim the request's lifetime block budget up front.  A
        prefix-hit admission refs the ``shared`` blocks (copy-on-write
        share) and allocates only the tail privately; when the pool runs
        short, ``on_short(need)`` may free capacity (prefix-cache LRU
        eviction) before backpressuring.  False = pool short."""
        self._assert_owned()
        shared = list(shared) if shared else []
        need = blocks_needed(prompt_len, max_new, self.max_seq,
                             self.block_size) - len(shared)
        assert need >= 0, (need, len(shared))
        # take the request's ref BEFORE any eviction: the extra owner makes
        # the matched node's blocks non-evictable, so on_short can neither
        # free them nor recycle them as this admission's private tail
        if shared:
            self.allocator.ref(shared)
        if need > self.allocator.free_blocks and on_short is not None:
            on_short(need)
        fresh = self.allocator.alloc(need)
        if fresh is None:
            if shared:
                self.allocator.release(shared)
            return False
        blocks = shared + fresh
        self._slot_blocks[slot] = blocks
        self.block_tables[slot, :] = GARBAGE_BLOCK
        self.block_tables[slot, :len(blocks)] = blocks
        return True

    def free_slot(self, slot):
        self._assert_owned()
        if self._slot_blocks[slot]:
            self.allocator.release(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self.block_tables[slot, :] = GARBAGE_BLOCK

    def rollback(self, slot, n):
        """Rejected drafts occupied block-table positions beyond the
        rewound pointer.  Blocks are reserved for the request's LIFETIME
        budget at admission, so nothing is freed and the table is not
        truncated — the rewound positions stay inside the reservation by
        construction (the engine's window clamp), and the junk KV there
        is overwritten as the row re-advances.  Validates the accounting
        instead of mutating it."""
        super().rollback(slot, n)
        assert n == 0 or self._slot_blocks[slot], \
            f"rollback({slot}, {n}) on a slot with no reservation"

    def slot_blocks(self, slot):
        return self._slot_blocks[slot]

    @property
    def free_capacity(self):
        return self.allocator.free_blocks

    @property
    def total_capacity(self):
        return self.num_blocks - 1     # the garbage block is never free

    # --- block tables ---------------------------------------------------
    def admission_tables(self, slots):
        return jnp.asarray(self.block_tables[slots])

    def decode_tables(self, staged_slots):
        """Decode-tick tables.  Mid-admission slots decode masked garbage
        at position 0 — park their rows on the garbage block so the write
        can never land in a reserved block (a warm admission's table starts
        with SHARED prefix blocks, which must never be written in place)."""
        tables = self.block_tables
        if staged_slots:
            tables = tables.copy()
            for slot in staged_slots:
                tables[slot, :] = GARBAGE_BLOCK
        return jnp.asarray(tables)

    def cow_table(self, slot, n_shared):
        """Copy-on-write scatter redirect: the staged scatter's shared
        range lands on the garbage block, private tail blocks stay."""
        table = self.block_tables[slot].copy()
        table[:n_shared] = GARBAGE_BLOCK
        return table

    def finish_tables(self, slot, cow):
        table = cow if cow is not None else self.block_tables[slot]
        return jnp.asarray(table[None])

    def staging_table(self, blocks):
        """(1, blocks_per_row) gather table over ``blocks`` (shared prefix
        in logical order), garbage elsewhere."""
        table = np.full((1, self.blocks_per_row), GARBAGE_BLOCK, np.int32)
        table[0, :len(blocks)] = blocks
        return table

    # --- prefix-cache binding -------------------------------------------
    def capture_grid(self, prefill_bucket):
        return self.block_size

    def prefix_cache_kwargs(self):
        return {"block_size": self.block_size, "backend": self}

    def prefix_payload(self, prompt, slot, state):
        nb = len(prompt) // self.block_size
        if nb == 0:
            return None
        blocks = self._slot_blocks[slot][:nb]
        return (prompt[:nb * self.block_size], blocks, None)

    # --- block ops (the PrefixCache-facing surface) ---------------------
    def ref(self, blocks):
        self.allocator.ref(blocks)

    def release(self, blocks):
        self.allocator.release(blocks)

    def refcount(self, block):
        return self.allocator.refcount(block)

    def writable(self, block):
        return self.allocator.writable(block)

    @property
    def free_blocks(self):
        return self.allocator.free_blocks


class HybridComposite(PagedPool):
    """Split substrate (hybrid): shared-attention KV leaves in the paged
    block pool, O(1) SSM state dense per slot — each leaf gets the
    substrate that pays off.  Prefix boundaries need BOTH halves, so
    payloads exist only at block-aligned prompt lengths."""

    needs_state = True

    def rollback(self, slot, n):
        """Split-substrate rollback composes both halves: the paged
        attention KV beyond the rewound pointer is dead weight inside the
        slot's lifetime reservation (PagedPool semantics), and the
        recurrent half is re-committed by the engine from the pre-verify
        tree with the scan masked at the accept boundary (RecurrentState
        semantics).  The PagedPool accounting check applies."""
        super().rollback(slot, n)

    def snapshot(self, caches, row: int = 0):
        return self.model.state_snapshot(caches, row)

    def seed_snapshot(self, staging, snap):
        return self.model.seed_from_snapshot(staging, snap)

    def prefix_payload(self, prompt, slot, state):
        if state is None or len(prompt) % self.block_size:
            return None
        nb = len(prompt) // self.block_size
        if nb == 0:
            return None
        return (prompt, self._slot_blocks[slot][:nb], state)


def make_backend(model, family: str, config) -> CacheBackend:
    """Pick the substrate for (family, config) — the ONLY place that maps
    families to cache substrates.  ``config`` must already be validated
    against the family (``EngineConfig.validate``)."""
    if config.paged:
        cls = HybridComposite if family in RECURRENT_FAMILIES else PagedPool
        return cls(model, config.max_batch, config.max_seq,
                   block_size=config.block_size,
                   num_blocks=config.num_blocks)
    if family in RECURRENT_FAMILIES:
        return RecurrentState(model, config.max_batch, config.max_seq)
    return DenseSlab(model, config.max_batch, config.max_seq)
