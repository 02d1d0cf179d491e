"""Paged decode-cache scratchpad — the serving ladder's O6 rung.

The contiguous ``cache.CacheManager`` reserves ``batch x max_seq`` cache
memory per slot no matter how short the requests are.  This module is the
vLLM-style alternative (scratchpad reorganization, level 2): every cache
leaf with a sequence axis is stored as a pool of fixed-size KV *blocks*,
and each slot owns a per-request *block table* mapping logical block
``j`` (positions ``j*T .. j*T+T-1``) to a physical pool block.  Capacity
is then the pool size over the *actual* per-request reservations
(``min(n_prompt + max_new_tokens, max_seq)`` tokens), so long-tail
prompt mixes admit more concurrent requests at equal memory.

Recurrent state (RWKV wkv, Mamba conv/ssm) has no sequence axis at all —
it is O(1) per slot — so per-position blocks are the wrong shape for it.
Those leaves get the *state pool* instead: a pool of per-slot state ROWS
with a slot -> row indirection map, no block tables.  One level of
indirection buys the same things block tables buy the KV leaves —
admit-without-reshape, pool-row sharding, defrag by row copy — at one
int per slot.  Hybrid models compose both pools (block tables for the
shared-attention KV, state rows for the mamba trunk); enc-dec stores its
fixed-length cross-attention KV as a state row too (cross attention is
unmasked, so the stale-positions-are-masked argument below never applies
to it — a whole-blob row swap does).

Layering (so the allocators are testable without jax):

  * :class:`BlockAllocator` — pure free-list arithmetic: allocate /
    append / release over integer block ids.  Block 0 is reserved as the
    NULL block: unallocated block-table entries point at it, it is never
    handed out, and its contents are write-garbage by design (see below).
  * :class:`PagedAllocator` — per-slot block tables + reservation-based
    admission on top of the free list.  Drives the scheduler's admission
    gate: a request whose reservation exceeds the free blocks *queues*
    (never raises) until retirements free blocks.
  * :class:`StatePool` — the state-row sibling: slot -> row map plus a
    row free list (row 0 reserved as the NULL row — the write-garbage
    sink for parked and inactive slots), with the same conservation
    invariants.
  * :class:`StatePagingPlan` — the jax layer for state leaves: pooled
    ``(rows, ...)`` storage, row gather/scatter, per-row byte
    accounting.  Sibling of :class:`BlockPagingPlan`, composed by the
    manager, never forked on inside the engine.
  * :class:`PagedCacheManager` — the jax layer: owns the pooled cache
    tree and presents the contiguous manager's ``reset_slots`` / cache
    interface to the engine; the jitted decode step threads the block
    table through a gather (pool -> dense per-slot view) and a scatter
    (the one block each slot wrote this tick -> pool), and the state
    rows through a row gather/scatter on the state leaves.

Bit-identity with the contiguous path (the ladder's O0..O6 contract)
rests on one invariant: a slot at position ``p`` has itself written every
cache entry at positions ``< p`` (blocks are reserved for the whole
request up front, and positions advance one per tick), position ``p`` is
written in-graph before attention reads it, and every position ``> p`` —
stale block contents, NULL-block garbage, neighbours' leftovers — is
masked to -1e30 before the softmax, where float32 ``exp`` underflows to
exactly 0.  Nothing unmasked can differ, so greedy argmax cannot either.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import kvquant
from repro.serving.tracing import Counters, span

NULL_BLOCK = 0
NULL_ROW = 0


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` positions."""
    return -(-max(n_tokens, 0) // block_size)


class BlockAllocator:
    """Fixed pool of KV blocks with a LIFO free list.

    ``n_blocks`` is the number of *allocatable* blocks; physical pool
    storage has ``n_blocks + 1`` rows (row 0 is the reserved NULL block).
    ``defrag`` makes allocation take the lowest-numbered free blocks
    (keeps live blocks packed toward the pool's start after churn — the
    copy-on-admit compaction in :meth:`PagedCacheManager.compact` then
    has less to move).
    """

    def __init__(self, n_blocks: int, *, defrag: bool = False):
        if n_blocks < 1:
            raise ValueError(f"need at least one block (got {n_blocks})")
        self.n_blocks = n_blocks
        self.defrag = defrag
        self._free = list(range(n_blocks, 0, -1))   # pop() -> lowest id

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def allocate(self, n: int) -> list:
        """Take ``n`` blocks off the free list; raises if short (callers
        gate on ``free_blocks`` first — the scheduler's admission gate)."""
        if n > len(self._free):
            raise RuntimeError(
                f"block pool exhausted: want {n}, free {len(self._free)} "
                f"of {self.n_blocks} (admission gate should have queued)")
        if self.defrag:
            self._free.sort(reverse=True)
        return [self._free.pop() for _ in range(n)]

    def append(self) -> int:
        """Grow a request by one block (the incremental-growth API; the
        engine reserves whole requests up front, tests exercise this)."""
        return self.allocate(1)[0]

    def release(self, blocks) -> None:
        live = set(self._free)
        for b in blocks:
            if b == NULL_BLOCK:
                continue
            if b in live or not (1 <= b <= self.n_blocks):
                raise RuntimeError(f"double/invalid free of block {b}")
            live.add(b)
            self._free.append(b)

    def rebuild(self, n_held: int) -> None:
        """Reset to the state where blocks ``1..n_held`` are held and the
        rest are free (the compacted layout) — keeps the free-list
        representation invariant in this class only."""
        self._free = list(range(self.n_blocks, n_held, -1))


class PagedAllocator:
    """Per-slot block tables over a :class:`BlockAllocator`.

    Pure host arithmetic (numpy tables, python free list) so the
    scheduler property tests can drive random admit/retire sequences
    against the real bookkeeping without touching jax.
    """

    def __init__(self, batch_size: int, max_seq: int, *,
                 block_size: int = 16, pool_blocks: int = 0,
                 defrag: bool = False):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1 (got {block_size})")
        self.B = batch_size
        self.max_seq = max_seq
        self.block_size = block_size
        self.blocks_per_seq = blocks_for(max_seq, block_size)
        # 0 = auto: equal worst-case capacity to the contiguous cache.
        # A pool SMALLER than one worst-case (max_seq) reservation is a
        # legitimate memory-saving config — real mixes rarely reserve the
        # full horizon — but it means some statically-valid requests can
        # NEVER be admitted; those are rejected per request at submit
        # time (``infeasible_reason``, wired to ``Scheduler.submit_gate``)
        # instead of being banned for the whole engine here.
        self.pool_blocks = pool_blocks or batch_size * self.blocks_per_seq
        if self.pool_blocks < 1:
            raise ValueError(
                f"pool_blocks must be >= 1 (got {self.pool_blocks})")
        self.allocator = BlockAllocator(self.pool_blocks, defrag=defrag)
        # tables[i, j] = physical block of slot i's logical block j
        self.tables = np.full((batch_size, self.blocks_per_seq),
                              NULL_BLOCK, np.int32)
        self._held = [0] * batch_size      # blocks held per slot

    # -- admission gate + lifecycle (wired to Scheduler callbacks) ----------
    def reserved_tokens(self, req) -> int:
        """Positions the request can ever write: the prompt is consumed
        one token per tick through the same cache, so the reservation is
        prompt + budget, clipped to the engine's max_seq horizon."""
        return min(req.n_prompt + req.max_new_tokens, self.max_seq)

    def blocks_needed(self, req) -> int:
        return blocks_for(self.reserved_tokens(req), self.block_size)

    def can_admit(self, req) -> bool:
        """The scheduler's admission gate: a request that fits max_seq but
        not the remaining free blocks queues (never raises)."""
        return self.blocks_needed(req) <= self.allocator.free_blocks

    def infeasible_reason(self, req):
        """The scheduler's SUBMIT gate: an error string when the
        request's reservation exceeds the TOTAL pool — no sequence of
        retirements can ever free enough blocks, so queuing it would
        gate out every admission wave forever and ``run()`` would spin
        its whole tick budget doing nothing.  None = feasible (it may
        still have to queue for the CURRENT free count, which is
        ``can_admit``'s job)."""
        need = self.blocks_needed(req)
        if need > self.pool_blocks:
            return (f"reservation of {need} KV blocks "
                    f"({self.reserved_tokens(req)} tokens at block size "
                    f"{self.block_size}) can never fit the total pool of "
                    f"{self.pool_blocks} blocks — shrink the request or "
                    f"enlarge kv_pool_blocks")
        return None

    def admit_slot(self, i: int, req) -> None:
        """Allocate the request's full reservation into slot ``i``'s
        table (up-front reservation = no mid-flight exhaustion)."""
        if self._held[i]:
            raise RuntimeError(f"slot {i} admitted while holding blocks")
        self.tables[i, :] = NULL_BLOCK
        self.grow_slot(i, self.reserved_tokens(req))

    def grow_slot(self, i: int, total_tokens: int) -> int:
        """Grow slot ``i``'s table to cover ``total_tokens`` positions,
        allocating exactly ``blocks_for(total) - held`` new blocks — the
        chunked-admission arithmetic: a chunk that ends mid-block shares
        its active block with the next chunk, so growing by totals (not
        by per-chunk ceil sums) never double-counts it.  Returns the
        number of blocks added (0 when the reservation already covers
        the total)."""
        want = blocks_for(min(total_tokens, self.max_seq), self.block_size)
        delta = want - self._held[i]
        if delta <= 0:
            return 0
        self.tables[i, self._held[i]:want] = self.allocator.allocate(delta)
        self._held[i] = want
        return delta

    def release_slot(self, i: int, req=None) -> None:
        n = self._held[i]
        if n:
            self.allocator.release(self.tables[i, :n].tolist())
        self.tables[i, :] = NULL_BLOCK
        self._held[i] = 0

    # -- accounting ---------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def held_blocks(self) -> list:
        """Blocks currently held per slot (the up-front reservation) —
        the per-slot upper bound on blocks a decode tick can touch; the
        kernel's actual per-tick walk is ``ceil(position + 1 / T)``."""
        return list(self._held)

    def slot_lengths(self, positions) -> list:
        """Per-slot valid KV lengths for a tick at ``positions`` (the
        engine's per-slot write positions): length = position + 1,
        clipped to the slot's reservation; slots holding nothing
        (inactive — every table entry NULL) report 0."""
        return [min(int(p) + 1, h * self.block_size) if h else 0
                for p, h in zip(positions, self._held)]

    @property
    def capacity_tokens(self) -> int:
        return self.pool_blocks * self.block_size

    def pool_usage(self, lengths) -> dict:
        """Blocks reserved against blocks holding context, for slots
        holding ``lengths`` tokens of context each (the scheduler's
        per-slot positions): computed when asked, not every tick."""
        return {"pool_blocks": self.pool_blocks,
                "reserved_blocks": sum(self._held),
                "context_blocks": sum(
                    min(blocks_for(int(n), self.block_size), h)
                    for n, h in zip(lengths, self._held))}

    def check_conservation(self) -> None:
        """allocated + free == total, and no block is in two places."""
        held = [b for row, n in zip(self.tables, self._held)
                for b in row[:n].tolist()]
        free = self.allocator._free
        assert len(held) + len(free) == self.pool_blocks, (held, free)
        assert not (set(held) & set(free)), "block both held and free"
        assert len(set(held)) == len(held), "block held twice"


class StatePool:
    """Slot -> state-row indirection for O(1)-per-slot cache leaves.

    The state-row sibling of :class:`PagedAllocator`: pure host
    bookkeeping (a numpy row map + python free list) so the scheduler
    property tests can drive random admit/retire traffic against the
    real invariants without touching jax.  Row 0 is the reserved NULL
    row — never handed out, aliased by parked and unoccupied slots, its
    contents write-garbage by design (the state-pool analogue of the
    NULL block).

    ``n_rows`` is the number of *allocatable* rows (default: one per
    engine slot, the capacity-parity configuration); physical pool
    storage has ``n_rows + 1`` rows.  Unlike blocks, a slot holds
    exactly ONE row for its whole lifetime — recurrent state does not
    grow with the sequence — so admission is a single pop and there is
    no reservation arithmetic.
    """

    def __init__(self, batch_size: int, *, n_rows: int = 0):
        self.B = batch_size
        self.n_rows = n_rows or batch_size
        if self.n_rows < 1:
            raise ValueError(f"need at least one row (got {self.n_rows})")
        # rows[i] = physical state row of slot i (NULL_ROW = unoccupied)
        self.rows = np.full((batch_size,), NULL_ROW, np.int32)
        self._free = list(range(self.n_rows, 0, -1))   # pop() -> lowest id

    @property
    def free_rows(self) -> int:
        return len(self._free)

    @property
    def used_rows(self) -> int:
        return self.n_rows - len(self._free)

    def can_admit(self, req=None) -> bool:
        return bool(self._free)

    def infeasible_reason(self, req=None):
        return None      # one row always fits a pool of >= 1 rows

    def admit_slot(self, i: int, req=None) -> None:
        if self.rows[i] != NULL_ROW:
            raise RuntimeError(f"slot {i} admitted while holding row "
                               f"{int(self.rows[i])}")
        if not self._free:
            raise RuntimeError(
                "state pool exhausted (admission gate should have queued)")
        self.rows[i] = self._free.pop()

    def release_slot(self, i: int, req=None) -> None:
        r = int(self.rows[i])
        if r == NULL_ROW:
            return                       # releasing an empty slot: no-op
        if r in self._free or not (1 <= r <= self.n_rows):
            raise RuntimeError(f"double/invalid free of state row {r}")
        self.rows[i] = NULL_ROW
        self._free.append(r)

    def compaction_moves(self) -> dict:
        """{old_row: new_row} packing the held rows into the lowest ids
        in slot order (the defrag plan — the manager applies the device
        copies, then calls :meth:`apply_moves`)."""
        held = [(i, int(r)) for i, r in enumerate(self.rows)
                if r != NULL_ROW]
        return {old: new for (_, old), new in
                zip(held, range(1, len(held) + 1)) if old != new}

    def apply_moves(self, moves: dict) -> None:
        for i in range(self.B):
            r = int(self.rows[i])
            if r in moves:
                self.rows[i] = moves[r]
        held = {int(r) for r in self.rows if r != NULL_ROW}
        self._free = [r for r in range(self.n_rows, 0, -1) if r not in held]

    def check_conservation(self) -> None:
        """held + free == total, and no row is in two places."""
        held = [int(r) for r in self.rows if r != NULL_ROW]
        assert len(set(held)) == len(held), "state row held twice"
        assert len(held) + len(self._free) == self.n_rows, (
            held, self._free)
        assert not (set(held) & set(self._free)), "row both held and free"
        assert all(1 <= r <= self.n_rows for r in held), held


# ---------------------------------------------------------------------------
# The jax layer: pooled cache tree + gather/scatter layout.
# ---------------------------------------------------------------------------


def _axes_leaves_with_paths(tree, prefix=()):
    """(path, axes-tuple) pairs in ``jax.tree.leaves`` order (dicts sort
    their keys) for the plain dict-of-tuples trees ``cache_axes`` returns.
    The path lets the layout classify leaves by *identity* (self- vs
    cross-attention cache), not by shape coincidence."""
    if isinstance(tree, tuple):
        return [(prefix, tree)]
    assert isinstance(tree, dict), f"unexpected cache_axes node {tree!r}"
    out = []
    for k in sorted(tree):
        out.extend(_axes_leaves_with_paths(tree[k], prefix + (k,)))
    return out


class BlockPagingPlan:
    """Per-leaf paging plan derived from the model's ``cache_axes()``.

    A leaf is paged iff its logical axes name both "batch" and "kv_seq",
    the sequence axis spans the engine's max_seq, and it is a *decode*
    cache — cross-attention caches (path contains "cross") pass through
    untouched, whatever their length: cross attention is unmasked, so
    the stale-positions-are-masked argument that makes paging safe does
    not apply to them.  Non-paged leaves — recurrent state (RWKV wkv,
    Mamba conv/ssm: no sequence axis, nothing to block-page) and the
    cross caches — are *state* leaves: with ``state_pooled=False``
    (direct construction, the legacy single-plan mode) they keep dense
    per-slot storage and scatter replaces them wholesale; with
    ``state_pooled=True`` (the manager composing this plan with a
    :class:`StatePagingPlan`) they pass through gather AND scatter
    untouched in their pooled row shape, and the state plan owns their
    row indirection.  In every paged leaf of every
    model family here the sequence axis sits immediately after the batch
    axis, which makes the (batch, seq) <-> (block, in-block) reshapes
    below pure metadata.
    """

    def __init__(self, model, batch_size: int, max_seq: int,
                 block_size: int, pool_blocks: int, *,
                 row_multiple: int = 1, kv_dtype: str = "bf16",
                 state_pooled: bool = False):
        self.B = batch_size
        self.max_seq = max_seq
        self.T = block_size
        self.nb = blocks_for(max_seq, block_size)
        self.state_pooled = state_pooled
        self.kv_dtype = kvquant.validate_kv_dtype(kv_dtype)
        self.quantized = kvquant.is_quantized(kv_dtype)
        self.store_dtype = kvquant.pool_dtype(kv_dtype)
        # + NULL block row; rounded up so a block-axis PlacementPlan can
        # shard the rows evenly (padding rows are never in any table, so
        # gather/scatter never touch them — pure dead memory).
        self.pool_rows = -(-(pool_blocks + 1) // row_multiple) * row_multiple
        axes_tree = model.cache_axes()
        paths_axes = _axes_leaves_with_paths(axes_tree)
        axes_flat = jax.tree.leaves(axes_tree,
                                    is_leaf=lambda x: isinstance(x, tuple))
        assert [ax for _, ax in paths_axes] == axes_flat, "leaf-order drift"
        specs = jax.tree.leaves(model.cache_spec(batch_size, max_seq))
        assert len(paths_axes) == len(specs), "cache axes drift"
        self.plans = []           # (bax, paged) per leaf
        self.scale_axes = []      # per leaf: scale reduce-axes or None
        self.compute_dtypes = []  # per leaf: the dense/compute dtype
        # Bytes-per-token accounting derives from the STORED pool dtype
        # (1 byte for int8/fp8), not the compute dtype — the `KV
        # bytes/tick` ladder column is about traffic actually moved.
        self.token_bytes = 0          # paged-leaf STORED bytes per token
        self.compute_token_bytes = 0  # dense-view bytes per token (bf16)
        self.scale_bytes_per_block = 0  # f32 scale bytes per pool row
        for (path, ax), spec in zip(paths_axes, specs):
            bax = ax.index("batch")
            cross = any("cross" in str(k) for k in path)
            paged = ("kv_seq" in ax and not cross
                     and spec.shape[ax.index("kv_seq")] == max_seq)
            sx = None
            if paged:
                assert ax.index("kv_seq") == bax + 1, (
                    f"paged leaf needs seq right after batch, got {ax}")
                n = 1
                for d in spec.shape:
                    n *= d
                per_tok = n // (batch_size * max_seq)
                item = jnp.dtype(spec.dtype).itemsize
                self.compute_token_bytes += per_tok * item
                self.token_bytes += per_tok * (
                    jnp.dtype(self.store_dtype).itemsize
                    if self.quantized else item)
                if self.quantized:
                    # One f32 scale per (block row x every named axis
                    # that isn't the sequence): reduce the block's token
                    # axis and the unnamed head-dim axes, keep layers /
                    # kv heads.
                    sx = tuple(i for i, name in enumerate(ax)
                               if name == "kv_seq" or name is None)
                    scale_elems = 1
                    for i, d in enumerate(spec.shape):
                        if i != bax and i not in sx:
                            scale_elems *= d
                    self.scale_bytes_per_block += scale_elems * 4
            self.plans.append((bax, paged))
            self.scale_axes.append(sx)
            self.compute_dtypes.append(spec.dtype)

    def init_pool(self, model) -> tuple:
        """(pool tree, treedef): paged leaves become
        (..., pool_rows, block_size, ...) zeros in the STORED dtype;
        recurrent leaves keep their contiguous per-slot shape."""
        dense = model.init_cache(self.B, self.max_seq)
        leaves, treedef = jax.tree.flatten(dense)
        out = []
        for leaf, (bax, paged) in zip(leaves, self.plans):
            if not paged:
                out.append(leaf)
                continue
            shape = list(leaf.shape)
            shape[bax] = self.pool_rows
            shape[bax + 1] = self.T
            dt = self.store_dtype if self.quantized else leaf.dtype
            out.append(jnp.zeros(tuple(shape), dt))
        return jax.tree.unflatten(treedef, out), treedef

    def scales_for_pool(self, pool):
        """Zero-initialized scale tree matching the pool treedef: paged
        leaves get their keepdims (..., pool_rows, 1, kv, 1) f32 scale
        array (zeros: an unwritten block dequantizes to exactly 0, like
        the zero bf16 pool); non-paged leaves get a scalar placeholder
        so the scale tree zips leaf-for-leaf with the pool tree."""
        leaves, treedef = jax.tree.flatten(pool)
        out = []
        for leaf, (bax, paged), sx in zip(leaves, self.plans,
                                          self.scale_axes):
            if sx is None:
                out.append(jnp.zeros((), jnp.float32))
                continue
            shape = tuple(1 if i in sx else d
                          for i, d in enumerate(leaf.shape))
            out.append(jnp.zeros(shape, jnp.float32))
        return jax.tree.unflatten(treedef, out)

    @property
    def geometry(self) -> dict:
        """Pool geometry for kernels / benchmarks / bytes accounting.
        ``pool_bytes`` counts the whole persistent footprint: stored
        block rows PLUS the per-block scale metadata."""
        pool_bytes = self.pool_rows * (self.T * self.token_bytes
                                       + self.scale_bytes_per_block)
        return {"block_size": self.T, "blocks_per_seq": self.nb,
                "pool_rows": self.pool_rows, "batch": self.B,
                "max_seq": self.max_seq, "token_bytes": self.token_bytes,
                "kv_dtype": self.kv_dtype,
                "scale_bytes_per_block": self.scale_bytes_per_block,
                "pool_bytes": pool_bytes,
                "pool_mb": pool_bytes / 2**20}

    # -- per-tick KV traffic estimates (the gather-vs-kernel delta) ----------
    def gather_bytes_per_tick(self) -> int:
        """KV bytes the GATHER step moves per decode tick: the pool is
        read in its STORED dtype (plus per-block scales when narrow),
        the dense compute-dtype view is written then read again by dense
        attention, and one block per slot is quantized and scattered
        back — O(B * max_seq) no matter how short the live requests.
        For ``kv_dtype=bf16`` this reduces exactly to the historical
        ``3 * dense + B * T * token_bytes``."""
        pool_read = self.B * self.nb * (self.T * self.token_bytes
                                        + self.scale_bytes_per_block)
        dense = self.B * self.nb * self.T * self.compute_token_bytes
        writeback = self.B * (self.T * self.token_bytes
                              + self.scale_bytes_per_block)
        return pool_read + 2 * dense + writeback

    def kernel_bytes_per_tick(self, lengths) -> int:
        """KV bytes the gather-free KERNEL step touches for the given
        per-slot valid lengths: only the blocks each slot's table
        references (streamed once, in the STORED dtype plus their
        scales), plus the per-slot append — one stored position for
        bf16; for narrow pools the append re-quantizes the tail block
        in place (read + write of one block row and its scale).
        For ``kv_dtype=bf16`` this reduces exactly to the historical
        ``(blocks * T + len(lengths)) * token_bytes``."""
        lengths = [int(x) for x in lengths]
        blocks = sum(blocks_for(x, self.T) for x in lengths)
        stream = blocks * (self.T * self.token_bytes
                           + self.scale_bytes_per_block)
        if self.quantized:
            append = len(lengths) * 2 * (self.T * self.token_bytes
                                         + self.scale_bytes_per_block)
        else:
            append = len(lengths) * self.token_bytes
        return stream + append

    def map_batch_axes(self, dense, fn):
        """Apply ``fn(leaf, batch_axis)`` to every leaf of a DENSE
        per-slot view (as produced by :meth:`gather`) — how the sharded
        paged step re-constrains the view onto the batch axis."""
        leaves, treedef = jax.tree.flatten(dense)
        return jax.tree.unflatten(treedef, [
            fn(leaf, bax) for leaf, (bax, _) in zip(leaves, self.plans)])

    # Both halves below are traced inside the jitted decode step.
    def gather(self, pool, tables, scales=None):
        """pool tree + tables (Bv, nb) -> dense per-slot cache view with
        a (possibly block-padded) sequence axis of nb*T >= max_seq.  Bv
        is usually the full batch; the chunked-prefill step passes one
        slot's table row (Bv == 1) to build a single-slot view.

        With ``scales`` (narrow pools), each gathered block is
        dequantized — ``kvquant.dequantize`` is THE shared rounding
        site, so this dense view is bit-identical to what the
        block-table kernel computes per streamed block."""
        Bv = tables.shape[0]
        leaves, treedef = jax.tree.flatten(pool)
        scale_leaves = (jax.tree.leaves(scales) if scales is not None
                        else [None] * len(leaves))
        flat = tables.reshape(-1)                     # (Bv*nb,)
        out = []
        for leaf, sleaf, (bax, paged), cdt in zip(
                leaves, scale_leaves, self.plans, self.compute_dtypes):
            if not paged:
                out.append(leaf)
                continue
            g = jnp.take(leaf, flat, axis=bax)        # bax: Bv*nb, bax+1: T
            if scales is not None:
                s = jnp.take(sleaf, flat, axis=bax)
                g = kvquant.dequantize(g, s, cdt)
            shape = (g.shape[:bax] + (Bv, self.nb * self.T)
                     + g.shape[bax + 2:])
            out.append(g.reshape(shape))
        return jax.tree.unflatten(treedef, out)

    def scatter_view(self, pool, tables, new_dense, scales=None,
                     lengths=None):
        """Write back EVERY block of the given slots' dense views — the
        chunked-prefill counterpart of :meth:`scatter` (a prompt chunk
        spans several blocks, so the whole per-slot view gathered this
        same tick is scattered back).  Untouched blocks rewrite their own
        just-gathered values and NULL table entries absorb the padded
        tail into the write-garbage NULL row.

        Narrow pools (``scales`` given) quantize each folded block with
        a fresh absmax scale; ``lengths`` (Bv,) masks positions at or
        beyond each slot's valid length to zero first, so stale-tenant
        garbage in the just-gathered view can never inflate a scale.
        Returns ``(pool, scales)`` in that mode, ``pool`` otherwise."""
        Bv, nb = tables.shape
        pool_leaves, treedef = jax.tree.flatten(pool)
        scale_leaves = (jax.tree.leaves(scales) if scales is not None
                        else [None] * len(pool_leaves))
        dense_leaves = jax.tree.leaves(new_dense)
        valid = None
        if scales is not None and lengths is not None:
            valid = (jnp.arange(nb * self.T)[None, :]
                     < lengths[:, None]).reshape(Bv * nb, self.T)
        out, out_s = [], []
        for leaf, sleaf, dense, (bax, paged), sx in zip(
                pool_leaves, scale_leaves, dense_leaves, self.plans,
                self.scale_axes):
            if not paged:
                # state_pooled: the StatePagingPlan row-scattered this
                # leaf already (or will) — keep the pool leaf untouched.
                # Legacy single-plan mode: whole-state replace.
                out.append(leaf if self.state_pooled else dense)
                out_s.append(sleaf)
                continue
            shape = (dense.shape[:bax] + (Bv * nb, self.T)
                     + dense.shape[bax + 2:])
            folded = dense.reshape(shape)
            sel = (slice(None),) * bax + (tables.reshape(-1),)
            if scales is None:
                out.append(leaf.at[sel].set(folded))
                out_s.append(sleaf)
                continue
            if valid is not None:
                vm = valid.reshape((1,) * bax + valid.shape
                                   + (1,) * (folded.ndim - bax - 2))
                folded = jnp.where(vm, folded, 0)
            s = kvquant.block_scale(folded, sx, self.kv_dtype)
            q = kvquant.quantize(folded, s, self.kv_dtype)
            out.append(leaf.at[sel].set(q))
            out_s.append(sleaf.at[sel].set(s))
        new_pool = jax.tree.unflatten(treedef, out)
        if scales is None:
            return new_pool
        return new_pool, jax.tree.unflatten(treedef, out_s)

    def scatter(self, pool, tables, new_dense, positions, scales=None):
        """Write back the ONE block each slot touched this tick.

        A decode tick writes exactly position ``positions[b]`` per slot,
        so only logical block ``positions[b] // T`` changed; the other
        nb-1 blocks still hold what the pool holds.  Inactive slots point
        at the NULL block, which absorbs their garbage chunk.

        Narrow pools (``scales`` given) mask positions beyond
        ``positions[b]`` to zero (not-yet-written garbage must not
        inflate the absmax), re-derive the block's scale, quantize, and
        write both the block row and its scale row; returns
        ``(pool, scales)`` in that mode, ``pool`` otherwise.  bf16 pools
        deliberately skip the masking so the write-back is the exact
        gathered bits (the round-trip test pins pool rows
        bit-identical)."""
        jb = positions // self.T                      # (B,) logical block
        pb = jnp.take_along_axis(tables, jb[:, None], axis=1)[:, 0]
        seq_idx = (jb * self.T)[:, None] + jnp.arange(self.T)[None]  # (B, T)
        valid = seq_idx <= positions[:, None]                        # (B, T)
        pool_leaves, treedef = jax.tree.flatten(pool)
        scale_leaves = (jax.tree.leaves(scales) if scales is not None
                        else [None] * len(pool_leaves))
        dense_leaves = jax.tree.leaves(new_dense)
        out, out_s = [], []
        for leaf, sleaf, dense, (bax, paged), sx in zip(
                pool_leaves, scale_leaves, dense_leaves, self.plans,
                self.scale_axes):
            if not paged:
                # state_pooled: the StatePagingPlan row-scattered this
                # leaf already (or will) — keep the pool leaf untouched.
                # Legacy single-plan mode: whole-state replace.
                out.append(leaf if self.state_pooled else dense)
                out_s.append(sleaf)
                continue
            idx = seq_idx.reshape(
                (1,) * bax + seq_idx.shape + (1,) * (dense.ndim - bax - 2))
            chunk = jnp.take_along_axis(dense, idx, axis=bax + 1)
            sel = (slice(None),) * bax + (pb,)
            if scales is None:
                out.append(leaf.at[sel].set(chunk))
                out_s.append(sleaf)
                continue
            vm = valid.reshape(
                (1,) * bax + valid.shape + (1,) * (chunk.ndim - bax - 2))
            chunk = jnp.where(vm, chunk, 0)
            s = kvquant.block_scale(chunk, sx, self.kv_dtype)
            q = kvquant.quantize(chunk, s, self.kv_dtype)
            out.append(leaf.at[sel].set(q))
            out_s.append(sleaf.at[sel].set(s))
        new_pool = jax.tree.unflatten(treedef, out)
        if scales is None:
            return new_pool
        return new_pool, jax.tree.unflatten(treedef, out_s)


class StatePagingPlan:
    """Row-pooled storage plan for the non-block leaves of a
    :class:`BlockPagingPlan` — recurrent state and cross-attention KV.

    State leaves trade their dense ``batch`` axis for a pool-row axis of
    ``total_rows = roundup(n_rows + 1, row_multiple)`` physical rows
    (row 0 = NULL, padding rows for even device sharding) at the SAME
    axis position ``bax``, so the sharding plan and the packed-zero
    helper work unchanged.  ``gather(tree, rows)`` takes each slot's row
    back out into a dense batch view; ``scatter(tree, rows, new_dense)``
    writes the dense view into the rows (duplicate NULL-row writes from
    parked/inactive slots collapse into the garbage sink).  Composes
    with the block plan in either order on disjoint leaves.
    """

    def __init__(self, block_plan: BlockPagingPlan, model,
                 batch_size: int, max_seq: int, *,
                 n_rows: int = 0, row_multiple: int = 1):
        self.n_rows = n_rows or batch_size
        self.total_rows = -(-(self.n_rows + 1) // row_multiple) \
            * row_multiple
        self.baxes = [bax for bax, _ in block_plan.plans]
        self.state = [not paged for _, paged in block_plan.plans]
        specs = jax.tree.leaves(model.cache_spec(batch_size, max_seq))
        # Per-row stored bytes across all state leaves (state is never
        # quantized — it is carried, not masked, and the tolerance
        # contract only covers attention reads).
        self.state_row_bytes = 0
        for spec, st, bax in zip(specs, self.state, self.baxes):
            if not st:
                continue
            n = 1
            for i, d in enumerate(spec.shape):
                if i != bax:
                    n *= d
            self.state_row_bytes += n * jnp.dtype(spec.dtype).itemsize

    @property
    def geometry(self) -> dict:
        return {"state_rows": self.total_rows,
                "state_row_bytes": self.state_row_bytes,
                "state_bytes": self.total_rows * self.state_row_bytes}

    def init_pool(self, pool):
        """Re-shape the state leaves of a freshly built pool tree from
        dense (batch at bax) to pooled (total_rows at bax) zeros."""
        leaves, treedef = jax.tree.flatten(pool)
        out = []
        for leaf, st, bax in zip(leaves, self.state, self.baxes):
            if not st:
                out.append(leaf)
                continue
            shape = list(leaf.shape)
            shape[bax] = self.total_rows
            out.append(jnp.zeros(tuple(shape), leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    # Both halves below are traced inside the jitted decode step.
    def gather(self, tree, rows):
        """Pooled state leaves + rows (Bv,) -> dense per-slot view (the
        block leaves — already dense from the block gather, or absent —
        pass through untouched)."""
        leaves, treedef = jax.tree.flatten(tree)
        out = []
        for leaf, st, bax in zip(leaves, self.state, self.baxes):
            out.append(jnp.take(leaf, rows, axis=bax) if st else leaf)
        return jax.tree.unflatten(treedef, out)

    def scatter(self, tree, rows, new_dense):
        """Write each slot's dense state back into its pool row.  Slots
        whose row is NULL (parked mid-prefill, inactive) all land in row
        0 — the write-garbage sink — so their carried state is exactly
        NOT advanced, which is what makes chunked prefill safe for
        recurrent families (satellite: park via no-advance, not via
        degrading to token-by-token)."""
        leaves, treedef = jax.tree.flatten(tree)
        dense_leaves = jax.tree.leaves(new_dense)
        out = []
        for leaf, dense, st, bax in zip(leaves, dense_leaves,
                                        self.state, self.baxes):
            if not st:
                out.append(leaf)
                continue
            sel = (slice(None),) * bax + (rows,)
            out.append(leaf.at[sel].set(dense.astype(leaf.dtype)))
        return jax.tree.unflatten(treedef, out)


class PagedCacheManager(PagedAllocator):
    """Block-pooled drop-in for ``cache.CacheManager`` at O6.

    Same engine-facing surface — ``.cache`` (the pool tree),
    ``reset_slots(indices, live)``, ``step_extras()`` — plus the
    allocator lifecycle the scheduler drives through its
    ``admission_gate`` / ``on_admit`` / ``on_retire`` hooks.  Slot
    admission allocates the request's whole reservation (so
    ``reset_slots`` has nothing left to do: stale block contents are
    masked, not zeroed — see the module docstring), and retirement
    returns the blocks before the next admission wave runs.

    Families with state leaves (recurrent state, cross KV) additionally
    own a :class:`StatePool` + :class:`StatePagingPlan` pair: admission
    takes one state row per slot next to the block reservation (pure-
    state families skip block allocation entirely — no phantom
    reservations), retirement returns it, ``reset_slots`` zeroes the
    freshly assigned rows (state is carried, not masked), and
    ``insert_slot``/``compact`` move state through row indirection.

    Under a sharded :class:`~repro.parallel.sharding.PlacementPlan` the
    pool leaves are sharded on their BLOCK axis and the state leaves on
    their ROW axis (both padded to a device multiple by their plan);
    block tables and row maps stay replicated.
    """

    def __init__(self, model, batch_size: int, max_seq: int, *,
                 block_size: int = 16, pool_blocks: int = 0,
                 defrag: bool = False, placement=None,
                 kv_dtype: str = "bf16", counters: Counters | None = None):
        super().__init__(batch_size, max_seq, block_size=block_size,
                         pool_blocks=pool_blocks, defrag=defrag)
        self.model = model
        self.placement = placement
        row_mult = placement.n_devices if placement is not None else 1
        self.plan = BlockPagingPlan(
            model, batch_size, max_seq, self.block_size, self.pool_blocks,
            row_multiple=row_mult, kv_dtype=kv_dtype, state_pooled=True)
        self.has_blocks = any(paged for _, paged in self.plan.plans)
        # State leaves (recurrent state, cross KV) get the row pool;
        # pure-state families have no block leaves at all and their
        # admission runs entirely on state rows (no phantom block
        # reservations — the admit-without-reshape win).
        if all(paged for _, paged in self.plan.plans):
            self.state = None
            self.state_plan = None
        else:
            self.state = StatePool(batch_size)
            self.state_plan = StatePagingPlan(
                self.plan, model, batch_size, max_seq,
                n_rows=self.state.n_rows, row_multiple=row_mult)
        pool, self._treedef = self.plan.init_pool(model)
        if self.state_plan is not None:
            pool = self.state_plan.init_pool(pool)
        # Narrow pools carry their per-block scales as a sibling subtree
        # of the SAME treedef: ``.cache`` becomes {"pool", "scale"} and
        # the engine threads the bundle opaquely (it is just a pytree).
        if self.plan.quantized:
            self.cache = {"pool": pool,
                          "scale": self.plan.scales_for_pool(pool)}
        else:
            self.cache = pool
        if placement is not None and placement.sharded:
            self.cache = jax.device_put(self.cache,
                                        self.pool_shardings(placement))
        self._state_zero = None
        self.counters = counters if counters is not None else Counters()
        self._tables_dev = None     # cached device copy of the tables
        self._rows_dev = None       # cached device copy of the row map

    @property
    def kv_dtype(self) -> str:
        return self.plan.kv_dtype

    def _split_cache(self):
        """(pool tree, scale tree-or-None) view of ``.cache``."""
        if self.plan.quantized:
            return self.cache["pool"], self.cache["scale"]
        return self.cache, None

    def _join_cache(self, pool, scales) -> None:
        self.cache = ({"pool": pool, "scale": scales}
                      if self.plan.quantized else pool)

    # -- step inputs ---------------------------------------------------------
    @property
    def geometry(self) -> dict:
        """Pool geometry (block size / blocks-per-seq / pool rows /
        per-token bytes, plus the state-row pool when the family has
        state leaves) — what the KV-bytes accounting in
        ``benchmarks/serving_ladder.py`` and ad-hoc tooling consume
        instead of reaching into the plan.  ``pool_bytes`` covers the
        whole persistent footprint: block rows + scales + state rows."""
        g = dict(self.plan.geometry)
        if self.state_plan is not None:
            g.update(self.state_plan.geometry)
            g["pool_bytes"] += g["state_bytes"]
            g["pool_mb"] = g["pool_bytes"] / 2**20
        else:
            g.update({"state_rows": 0, "state_row_bytes": 0,
                      "state_bytes": 0})
        return g

    def pool_shardings(self, placement):
        """Sharding tree for the pool: every leaf sharded at its plan
        axis — the block-row axis for paged leaves, the state-row axis
        for state leaves (both sit at ``bax``).  Scale leaves
        shard on the same pool-row axis (their other dims are keepdims
        1s); the scalar placeholders stay replicated."""
        pool_sh = jax.tree.unflatten(self._treedef, [
            placement.axis(bax) for bax, _p in self.plan.plans])
        if not self.plan.quantized:
            return pool_sh
        scale_sh = jax.tree.unflatten(self._treedef, [
            placement.axis(bax) if sx is not None else placement.replicated
            for (bax, _p), sx in zip(self.plan.plans,
                                     self.plan.scale_axes)])
        return {"pool": pool_sh, "scale": scale_sh}

    def _put_host(self, arr):
        # Upload a snapshot: admission and retirement rewrite the host
        # tables in place while a dispatched step may still be reading
        # them, and on the CPU backend an upload can alias host memory.
        arr = np.array(arr, copy=True)
        if self.placement is not None and self.placement.sharded:
            return jax.device_put(arr, self.placement.replicated)
        return jnp.asarray(arr)

    def step_extras(self, parked=None) -> tuple:
        """Per-tick step inputs beyond (params, cache, tokens, positions,
        seeds): the block tables (iff the family has block leaves) then
        the state rows (iff it has state leaves), as CACHED device
        arrays.  Tables/rows only change at admission / retirement /
        compaction — those paths invalidate — so steady-state decode
        ticks re-use one upload instead of paying a host->device
        transfer per tick.

        ``parked``: slot indices whose state row is aliased to the NULL
        row for THIS tick — the chunked-prefill park.  A parked slot's
        batched-decode read pulls NULL garbage (its output is discarded
        anyway; batch rows are independent in every family) and its
        state write lands in the garbage sink, so its real carried state
        advances only through the prefill chunks.  Block tables are NOT
        aliased: a parked slot's KV write at position p is rewritten by
        its next chunk — the standing stale-positions invariant."""
        out = []
        if self.has_blocks:
            if self._tables_dev is None:
                with span("serving.tables"):
                    self._tables_dev = self._put_host(self.tables)
                self.counters.table_uploads += 1
            out.append(self._tables_dev)
        if self.state is not None:
            if parked:
                rows = self.state.rows.copy()
                rows[list(parked)] = NULL_ROW
                out.append(self._put_host(rows))
            else:
                if self._rows_dev is None:
                    self._rows_dev = self._put_host(self.state.rows)
                out.append(self._rows_dev)
        return tuple(out)

    # -- admission: both pools must say yes -----------------------------------
    def blocks_needed(self, req) -> int:
        return super().blocks_needed(req) if self.has_blocks else 0

    def can_admit(self, req) -> bool:
        if self.has_blocks and not super().can_admit(req):
            return False
        return self.state is None or self.state.can_admit(req)

    def admit_slot(self, i: int, req) -> None:
        if self.has_blocks:
            super().admit_slot(i, req)
            self._tables_dev = None
        if self.state is not None:
            self.state.admit_slot(i, req)
            self._rows_dev = None

    def grow_slot(self, i: int, total_tokens: int) -> int:
        added = super().grow_slot(i, total_tokens)
        if added:
            self._tables_dev = None
        return added

    def release_slot(self, i: int, req=None) -> None:
        if self.has_blocks:
            super().release_slot(i, req)
            self._tables_dev = None
        if self.state is not None:
            self.state.release_slot(i, req)
            self._rows_dev = None

    def check_conservation(self) -> None:
        if self.has_blocks:
            super().check_conservation()
        if self.state is not None:
            self.state.check_conservation()

    def reset_slots(self, indices: list, live: list) -> None:
        """Admission reset under paging.

        Paged (sequence-axis) leaves need NO zeroing: the slots in
        ``indices`` had their tables rebuilt by ``admit_slot`` and every
        stale position is masked before the softmax.  Recurrent-STATE
        leaves (RWKV wkv / Mamba conv+ssm — per-slot, no sequence axis)
        are different: state is carried, not masked, so the previous
        tenant's state would leak straight into the new request's first
        step.  Their freshly allocated pool ROWS get the O5-style packed
        one-call zeroing (``admit_slot`` assigned the rows before this
        runs).
        """
        if not indices or self.state is None:
            return
        if self._state_zero is None:
            from repro.serving.cache import make_packed_zero

            self._state_zero = make_packed_zero(
                [bax for bax, _ in self.plan.plans],
                skip=[paged for _, paged in self.plan.plans])
        rows = [int(self.state.rows[i]) for i in indices]
        pool, scales = self._split_cache()
        pool = self._state_zero(pool, jnp.asarray(rows, jnp.int32))
        self._join_cache(pool, scales)

    def insert_slot(self, i: int, state) -> None:
        """Install an externally prefilled batch-1 DENSE cache tree into
        slot ``i``'s pool blocks (the INSERT phase of
        prefill->insert->generate).  Paged leaves pad their sequence axis
        to the table horizon (nb*T), fold it to (nb, T) and scatter
        through slot ``i``'s block table — ``place``/``admit`` rebuilt
        the table before this runs, and NULL entries past the reservation
        absorb the padded tail into the write-garbage NULL row.
        State leaves (recurrent state, cross KV) copy the batch-1 slice
        into slot ``i``'s pool row — cross-attention KV built offline
        (``encdec.build_cross_cache``) rides in through the same door.

        Narrow pools quantize each folded block with a fresh absmax
        scale (the dense prefill state is zero past the prompt, so no
        masking is needed) and install the scales alongside."""
        nb, T = self.plan.nb, self.plan.T
        row = jnp.asarray(self.tables[i], jnp.int32)        # (nb,)
        pool, scales = self._split_cache()
        leaves, treedef = jax.tree.flatten(pool)
        scale_leaves = (jax.tree.leaves(scales) if scales is not None
                        else [None] * len(leaves))
        st_leaves = jax.tree.leaves(state)
        assert len(leaves) == len(st_leaves), "prefill state tree drift"
        out, out_s = [], []
        for leaf, sleaf, st, (bax, paged), sx in zip(
                leaves, scale_leaves, st_leaves, self.plan.plans,
                self.plan.scale_axes):
            if not paged:
                st0 = jnp.take(st, 0, axis=bax).astype(leaf.dtype)
                sel = (slice(None),) * bax + (int(self.state.rows[i]),)
                out.append(leaf.at[sel].set(st0))
                out_s.append(sleaf)
                continue
            st0 = jnp.take(st, 0, axis=bax)
            pad = nb * T - st0.shape[bax]         # seq axis now at bax
            if pad:
                widths = [(0, 0)] * st0.ndim
                widths[bax] = (0, pad)
                st0 = jnp.pad(st0, widths)
            folded = st0.reshape(
                st0.shape[:bax] + (nb, T) + st0.shape[bax + 1:])
            sel = (slice(None),) * bax + (row,)
            if scales is None:
                out.append(leaf.at[sel].set(folded.astype(leaf.dtype)))
                out_s.append(sleaf)
                continue
            s = kvquant.block_scale(folded, sx, self.plan.kv_dtype)
            q = kvquant.quantize(folded, s, self.plan.kv_dtype)
            out.append(leaf.at[sel].set(q))
            out_s.append(sleaf.at[sel].set(s))
        new_scales = (jax.tree.unflatten(treedef, out_s)
                      if scales is not None else None)
        self._join_cache(jax.tree.unflatten(treedef, out), new_scales)
        self._tables_dev = None

    def compact(self) -> None:
        """Copy-on-admit defrag: relocate every held block to the lowest
        free ids, rewriting tables and physically copying pool rows.
        Optional — correctness never needs it (block ids are fully
        virtualized); it keeps the live set dense so a future pool-shrink
        or sequence-sharded gather touches a compact prefix."""
        held = sorted({b for row, n in zip(self.tables, self._held)
                       for b in row[:n].tolist()})
        want = list(range(1, len(held) + 1))
        moves = {old: new for old, new in zip(held, want) if old != new}
        smoves = (self.state.compaction_moves()
                  if self.state is not None else {})
        if not moves and not smoves:
            return
        src = jnp.asarray(list(moves.keys()) or [0], jnp.int32)
        dst = jnp.asarray(list(moves.values()) or [0], jnp.int32)
        ssrc = jnp.asarray(list(smoves.keys()) or [0], jnp.int32)
        sdst = jnp.asarray(list(smoves.values()) or [0], jnp.int32)
        pool, scales = self._split_cache()

        def move_rows(tree):
            # relocate pool rows — block rows by the block moves, state
            # rows by the state moves; scale rows ride along (same bax)
            # and scalar placeholders are left alone.  "or [0]" above
            # keeps an empty move set a NULL-row self-copy no-op.
            leaves, moved = jax.tree.leaves(tree), []
            for leaf, (bax, paged) in zip(leaves, self.plan.plans):
                if leaf.ndim == 0:
                    moved.append(leaf)
                    continue
                s, d = (src, dst) if paged else (ssrc, sdst)
                sel_src = (slice(None),) * bax + (s,)
                sel_dst = (slice(None),) * bax + (d,)
                moved.append(leaf.at[sel_dst].set(leaf[sel_src]))
            return jax.tree.unflatten(self._treedef, moved)

        pool = move_rows(pool)
        if scales is not None:
            scales = move_rows(scales)
        self._join_cache(pool, scales)
        if moves:
            remap = np.vectorize(lambda b: moves.get(int(b), int(b)))
            self.tables = remap(self.tables).astype(np.int32)
            self.allocator.rebuild(len(held))
            self._tables_dev = None
        if smoves:
            self.state.apply_moves(smoves)
            self._rows_dev = None
