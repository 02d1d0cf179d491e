"""KVLayout: the cache-LAYOUT half of the serving engine's
layout x placement product.

The paper's whole point is that refinement steps COMPOSE — PE
duplication (step 3) and scratchpad reorganization (step 5) are applied
together, not as alternatives — and AutoDSE-style search needs the knob
space to stay a product of independent axes.  So the engine selects two
orthogonal strategy objects instead of forking on ``if paged``:

  * :class:`KVLayout` (this module) — HOW the decode cache is stored:
    :class:`ContiguousLayout` (one ``batch x max_seq`` slice per slot,
    O0..O5) or :class:`PagedLayout` (a pooled KV-block scratchpad with
    per-request block tables, O6).  The layout owns cache-manager
    construction, scheduler wiring (admission gates for the block pool)
    and the step-wrapping that used to be inlined in the engine as
    ``_make_fused`` / ``_make_paged_fused``.
  * :class:`repro.parallel.sharding.PlacementPlan` — WHERE the arrays
    live: replicated, or PE-sharded over a 1-D data mesh.

Every (layout, placement) cell compiles a decode step:

  contiguous x replicated  — the process-wide shared jitted step
  contiguous x sharded     — per-engine step; cache/tokens sharded on
                             the batch axis (classic O3)
  paged      x replicated  — per-engine step (pool geometry is part of
                             the program); gather -> decode -> scatter,
                             or — ``paged_attn="kernel"`` — the
                             gather-free block-table Pallas kernel on
                             the raw pool (no dense view at all)
  paged      x sharded     — per-engine step; the pool is sharded on the
                             BLOCK axis (rows padded to a device
                             multiple), block tables replicated, and the
                             gathered dense view is re-sharded onto the
                             batch axis so the model itself runs
                             PE-duplicated (O3 x O6 composed); the
                             kernel variant replicates the pool
                             in-graph for the (single-device) kernel
                             call and re-shards the written pool

Greedy tokens are bit-identical across all four cells: sharding touches
only non-contraction axes (batch, pool rows), so no reduction is ever
split — the same oracle the O0..O6 ladder tests pin.

The shared step cache here is weakref-keyed: entries hold the model only
through a weak proxy and are evicted the moment the model dies, so a
process that keeps constructing engines never pins dead models (the old
``id(model)``-keyed cache did, until LRU churn).
"""

from __future__ import annotations

import collections
import logging
import weakref

import jax

from repro.core.optlevel import BestEffortConfig
from repro.serving.cache import CacheManager
from repro.serving.paged import PagedCacheManager
from repro.serving.sampler import make_sampler

log = logging.getLogger("repro.serving")


def _last_logits(logits):
    """(B, V) or (B, 1, V) -> (B, V): the newest position's logits."""
    if logits.ndim == 3:
        return logits[:, -1, :]
    return logits


def make_fused(model, sample):
    """The batched fused decode+sample step (contiguous O2+); one
    definition shared by the replicated and the PE-sharded instantiation
    so they can never drift apart."""
    def _fused(params, cache, tokens, positions, seeds):
        logits, new_cache = model.decode_step(
            params, cache, tokens, positions)
        return sample(_last_logits(logits), seeds), new_cache

    return _fused


def _split_cache(cache, quantized):
    """(pool, scales) from the step's cache argument: narrow pools
    travel as a ``{"pool", "scale"}`` bundle, wide pools bare."""
    if quantized:
        return cache["pool"], cache["scale"]
    return cache, None


def _join_cache(pool, scales, quantized):
    if quantized:
        return {"pool": pool, "scale": scales}
    return pool


def _split_extras(manager, extras):
    """(tables, rows) from a step's variadic extras, per the manager's
    leaf population: tables iff it has block leaves, rows iff it has
    state leaves — the same order ``step_extras()`` emits."""
    tables = rows = None
    it = iter(extras)
    if manager.has_blocks:
        tables = next(it)
    if manager.state is not None:
        rows = next(it)
    return tables, rows


def make_paged_fused(model, sample, manager, constrain=None):
    """The paged GATHER step: block-table gather (block leaves) +
    state-row gather (state leaves) -> the SAME ``decode_step`` the
    dense rungs run -> state-row scatter + single-block scatter.  The
    dense view the model sees is bit-identical at every unmasked
    position (see ``paged`` docstring) and state rows gather the exact
    carried state, so greedy tokens cannot drift from the contiguous
    path.  Narrow pools (``kv_dtype`` int8/fp8) dequantize inside the
    gather and re-quantize each slot's active block inside the scatter —
    tokens then track the dense oracle only up to the dtype's tolerance
    contract, never bit-exactly (state rows are never quantized).

    ``constrain`` (from the sharded placement) re-shards the gathered
    dense view onto the batch axis in-graph, so under a mesh the model
    body runs PE-duplicated while the pool stays block/row-sharded.
    """
    plan, splan = manager.plan, manager.state_plan
    quantized = plan.quantized

    def _fused(params, cache, *rest):
        extras, (tokens, positions, seeds) = rest[:-3], rest[-3:]
        tables, rows = _split_extras(manager, extras)
        pool, scales = _split_cache(cache, quantized)
        dense = pool
        if tables is not None:
            dense = plan.gather(dense, tables, scales)
        if rows is not None:
            dense = splan.gather(dense, rows)
        if constrain is not None:
            dense = plan.map_batch_axes(dense, constrain)
        logits, new_dense = model.decode_step(
            params, dense, tokens, positions)
        toks = sample(_last_logits(logits), seeds)
        new_pool = pool
        if rows is not None:
            new_pool = splan.scatter(new_pool, rows, new_dense)
        if tables is None:
            return toks, _join_cache(new_pool, scales, quantized)
        if quantized:
            new_pool, scales = plan.scatter(new_pool, tables, new_dense,
                                            positions, scales=scales)
            return toks, _join_cache(new_pool, scales, True)
        return toks, plan.scatter(new_pool, tables, new_dense, positions)

    return _fused


def make_paged_kernel_fused(model, sample, manager, placement):
    """The paged KERNEL step (``paged_attn="kernel"``): the model's
    ``paged_decode_step`` consumes the block pool + tables (+ state
    rows) + positions DIRECTLY — the per-tick O(B * max_seq) dense
    gather/scatter of :func:`make_paged_fused` is gone; each attention
    layer appends the current token's K/V into the active block in place
    and the block-table-aware Pallas kernel streams only the blocks each
    slot references (O(blocks touched) KV traffic per tick), while state
    leaves move through O(B) row indirection.  Narrow pools thread the
    per-block scale subtree alongside and the kernel dequantizes each
    streamed block in place.

    Under a sharded ``placement`` the Pallas kernel is a single-device
    program: the step re-constrains the BLOCK-axis-sharded pool leaves
    to replicated in-graph, traces under the placement's mesh so each
    kernel call runs on every device over replicated operands
    (``PlacementPlan.tracing``), and ``out_shardings`` re-shards the
    written pool back onto the block axis.  Correct everywhere; whether
    it *wins* there is the autotuner's call, like every best-effort rung.
    """
    quantized = manager.plan.quantized
    kv_dtype = manager.plan.kv_dtype

    def _fused(params, cache, *rest):
        with placement.tracing():
            return _step(params, cache, *rest)

    def _step(params, cache, *rest):
        extras, (tokens, positions, seeds) = rest[:-3], rest[-3:]
        pool, scales = _split_cache(cache, quantized)
        pool = jax.tree.map(placement.constrain_replicated, pool)
        if scales is not None:
            scales = jax.tree.map(placement.constrain_replicated, scales)
        if quantized:
            logits, new_pool, new_scales = model.paged_decode_step(
                params, pool, *extras, tokens, positions,
                scales=scales, kv_dtype=kv_dtype)
        else:
            logits, new_pool = model.paged_decode_step(
                params, pool, *extras, tokens, positions)
            new_scales = None
        toks = sample(_last_logits(logits), seeds)
        return toks, _join_cache(new_pool, new_scales, quantized)

    return _fused


# ---------------------------------------------------------------------------
# Shared jitted steps (contiguous, replicated) — weakref-keyed.
# ---------------------------------------------------------------------------

# Jitted step functions are shared across engines of the same
# (model, sampler, fusion mode): every replicated contiguous level from
# O2 up runs the *same* compiled decode program, so measured differences
# between ladder rungs come from the host-side mechanics each rung
# actually changes, not from per-engine jit-instance luck.  (Sharded and
# paged engines build their own step: shardings and pool geometry are
# part of the program.)  Entries reference the model only through a weak
# proxy and a ``weakref.finalize`` evicts them when the model dies, so
# the cache never outlives its models; the LRU bound stays as a backstop
# against many live models.
_STEP_CACHE = collections.OrderedDict()
_STEP_CACHE_MAX = 8


class _WeakModel:
    """Attribute proxy holding the model weakly.  The jitted closures
    resolve it at trace time only (some engine is mid-construction or
    mid-retrace, so the model is alive); once compiled, the executable
    needs no model at all."""

    __slots__ = ("_ref",)

    def __init__(self, model):
        self._ref = weakref.ref(model)

    def __getattr__(self, name):
        model = self._ref()
        if model is None:
            raise ReferenceError(
                "shared decode step retraced after its model was "
                "garbage-collected (the owning engine must outlive "
                "retraces)")
        return getattr(model, name)


def shared_steps(model, sampler_cfg):
    key = (id(model), sampler_cfg)
    if key in _STEP_CACHE:
        _STEP_CACHE.move_to_end(key)
        return _STEP_CACHE[key]

    sample = make_sampler(sampler_cfg)
    axes_tree = model.cache_axes()
    leaves_axes = jax.tree.leaves(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    batch_axes = [ax.index("batch") for ax in leaves_axes]
    weak = _WeakModel(model)

    def _single(params, cache, token, position, islot):
        """One request's decode step: slice slot ``islot``'s cache rows,
        run a batch-1 model step, write the rows back.  The un-pipelined
        serving loop — each request pays its own model call (and its own
        pass over the weights)."""
        leaves, treedef = jax.tree.flatten(cache)
        row = jax.tree.unflatten(treedef, [
            jax.lax.dynamic_slice_in_dim(leaf, islot, 1, axis=bax)
            for leaf, bax in zip(leaves, batch_axes)])
        logits, new_row = weak.decode_step(
            params, row, token[None, None], position[None])
        row_leaves = jax.tree.leaves(new_row)
        new_cache = jax.tree.unflatten(treedef, [
            jax.lax.dynamic_update_slice_in_dim(leaf, new, islot, axis=bax)
            for leaf, new, bax in zip(leaves, row_leaves, batch_axes)])
        return _last_logits(logits)[0], new_cache

    def _prefill(params, cache, islot, tokens, start, last, seeds):
        """One slot's prefill CHUNK: slice slot ``islot``'s cache rows,
        run a batch-1 multi-token prefill step over ``tokens`` (1, C)
        starting at absolute position ``start``, write the rows back and
        sample the logits at row ``last`` (the chunk's final real
        token — only the final chunk's sample is ever used).  Chunks are
        PADDED to a fixed C, so one trace serves the whole prompt: pad
        rows write at future (or clipped) positions that are either
        overwritten in-graph before first read or masked, and their
        logits are never selected."""
        leaves, treedef = jax.tree.flatten(cache)
        row = jax.tree.unflatten(treedef, [
            jax.lax.dynamic_slice_in_dim(leaf, islot, 1, axis=bax)
            for leaf, bax in zip(leaves, batch_axes)])
        logits, new_row = weak.prefill_step(params, row, tokens, start,
                                            last)
        row_leaves = jax.tree.leaves(new_row)
        new_cache = jax.tree.unflatten(treedef, [
            jax.lax.dynamic_update_slice_in_dim(leaf, new, islot, axis=bax)
            for leaf, new, bax in zip(leaves, row_leaves, batch_axes)])
        return sample(logits, seeds)[0], new_cache

    def _verify(params, cache, tokens, start):
        """Speculative verify: ONE batched forward over tokens (B, C) —
        each slot's pending token + C-1 drafts written at positions
        ``start`` .. ``start + C - 1`` — returning the greedy token at
        EVERY row (B, C).  Only traced for greedy samplers (the engine
        gates speculation on determinism), where ``sample`` reduces over
        the last axis row-independently."""
        logits, new_cache = weak.verify_step(params, cache, tokens, start)
        return sample(logits, None), new_cache

    _STEP_CACHE[key] = {
        "fused": jax.jit(make_fused(weak, sample), donate_argnums=(1,)),
        "single": jax.jit(_single, donate_argnums=(1,)),
        "prefill": jax.jit(_prefill, donate_argnums=(1,)),
        "verify": jax.jit(_verify, donate_argnums=(1,)),
        "sample": jax.jit(sample),
    }
    # Evict on model death (runs at deallocation, before the id can be
    # recycled, so a stale entry can never alias a new model).
    weakref.finalize(model, _STEP_CACHE.pop, key, None)
    if len(_STEP_CACHE) > _STEP_CACHE_MAX:
        _STEP_CACHE.popitem(last=False)
    return _STEP_CACHE[key]


# ---------------------------------------------------------------------------
# The layout protocol + its two implementations.
# ---------------------------------------------------------------------------


class KVLayout:
    """Strategy protocol for the decode-cache layout.

    ``name``              — "contiguous" / "paged" (mirrors
                            ``BestEffortConfig.kv_layout``).
    ``supports_step_fn``  — whether a caller-supplied fused step can
                            drive this layout (the paged step needs the
                            block-table argument, so it cannot).
    ``build_manager``     — construct the cache manager, already placed
                            per the :class:`PlacementPlan`.
    ``wire_scheduler``    — attach admission gate / lifecycle hooks.
    ``make_step``         — the jitted fused decode+sample step for
                            (this layout) x (this placement).
    ``make_prefill_step`` — the jitted single-slot prefill-CHUNK step
                            (or None when this layout x placement x
                            model cell cannot chunk — the engine then
                            degrades to the legacy one-token-per-tick
                            prestaged prefill).

    The engine holds one of each and never branches on layout again; the
    extra per-tick step inputs (block tables, state rows) come from the
    manager's ``step_extras()`` so the dispatch path is layout-blind
    too — the prefill step takes the same extras between cache and slot
    index.

    Three RECORDED strings replace silent degrades (the best-effort
    contract: degrade, don't fail, and say so):

    ``attn_impl``   — the attention implementation the built step
                      actually uses ("gather"/"kernel"; None on the
                      contiguous layout).
    ``state_impl``  — how recurrent/cross state moves ("rows" when the
                      paged manager row-pools state leaves, "none" when
                      the family has none or the layout is contiguous).
    ``degrade_reason`` — why a requested capability fell back (kernel ->
                      gather, chunked -> token), None when nothing did.
    """

    name: str = "?"
    supports_step_fn: bool = False
    attn_impl = None
    state_impl: str = "none"
    degrade_reason = None

    def build_manager(self, model, batch_size, max_seq, config, placement):
        raise NotImplementedError

    def wire_scheduler(self, scheduler, manager) -> None:
        pass

    def make_step(self, model, sampler_cfg, manager, placement):
        raise NotImplementedError

    def make_prefill_step(self, model, sampler_cfg, manager, placement):
        """(params, cache, *extras, islot, tokens (1, C), start (1,),
        last (1,), seeds (1,)) -> (token, cache), or None when this cell
        cannot run a chunked prefill (no model prefill step, or a
        sharded placement — a batch-1 chunk under a batch/block-sharded
        program would retrace the whole step; the legacy path already
        serves that cell)."""
        return None

    def make_verify_step(self, model, sampler_cfg, manager, placement):
        """The jitted speculative-verify step for (this layout) x (this
        placement): (params, cache, *extras, tokens (B, C), start (B,))
        -> (greedy tokens (B, C), cache) — one batched multi-token
        forward over every slot's pending token + drafts, greedy argmax
        at every row in-graph.  None when this layout x placement x
        model cell cannot verify (no model verify hook) — the engine
        then degrades speculation to plain decode."""
        return None


class ContiguousLayout(KVLayout):
    """One ``batch x max_seq`` cache slice per slot (rungs O0..O5).
    Placement shards every leaf on its batch axis."""

    name = "contiguous"
    supports_step_fn = True

    def build_manager(self, model, batch_size, max_seq,
                      config: BestEffortConfig, placement):
        return CacheManager(
            model, batch_size, max_seq, config.level,
            shardings=placement.cache_shardings(model, batch_size, max_seq))

    def make_step(self, model, sampler_cfg, manager, placement):
        if not placement.sharded:
            return shared_steps(model, sampler_cfg)["fused"]
        # Sharded PE duplication: shardings are part of the program, so
        # this engine compiles its own instance of the fused step.
        tok_sh, pos_sh = placement.token_shardings()
        return jax.jit(
            make_fused(model, make_sampler(sampler_cfg)),
            donate_argnums=(1,),
            in_shardings=(placement.replicated, manager.shardings,
                          tok_sh, pos_sh, pos_sh),
            out_shardings=(pos_sh, manager.shardings))

    def make_prefill_step(self, model, sampler_cfg, manager, placement):
        if placement.sharded or model.prefill_step is None:
            return None
        if model.carries_state:
            # Chunked prefill parks mid-prompt slots inside the BATCHED
            # decode tick by feeding them their next prompt token; for
            # KV families that write is rewritten by the next chunk, but
            # carried state would advance twice.  The contiguous layout
            # has no indirection to park through — the paged layout
            # aliases parked slots to the NULL state row instead.
            self.degrade_reason = (
                f"prefill_chunk requested but family "
                f"'{model.cfg.family}' carries recurrent state, which the "
                f"contiguous layout cannot park mid-prompt; degraded to "
                f"token-by-token prefill (the paged layout (level>=6) "
                f"chunks this family via NULL-row parking)")
            log.warning("%s", self.degrade_reason)
            return None
        return shared_steps(model, sampler_cfg)["prefill"]

    def make_verify_step(self, model, sampler_cfg, manager, placement):
        if model.verify_step is None:
            return None
        if not placement.sharded:
            return shared_steps(model, sampler_cfg)["verify"]
        # Sharded PE duplication: the verify window shards on the batch
        # axis exactly like the decode step's tokens — no reduction is
        # split, so greedy rows stay bit-identical to the replicated cell.
        sample = make_sampler(sampler_cfg)

        def _verify(params, cache, tokens, start):
            logits, new_cache = model.verify_step(params, cache, tokens,
                                                  start)
            return sample(logits, None), new_cache

        tok_sh, pos_sh = placement.token_shardings()
        return jax.jit(
            _verify, donate_argnums=(1,),
            in_shardings=(placement.replicated, manager.shardings,
                          tok_sh, pos_sh),
            out_shardings=(tok_sh, manager.shardings))


class PagedLayout(KVLayout):
    """Pooled KV-block scratchpad with per-request block tables (O6).

    Placement shards the POOL on its block axis (the pool's leading
    rows, padded up to a device multiple at construction) while block
    tables stay replicated; inside the step the gathered per-slot dense
    view is re-sharded onto the batch axis so the model body runs
    PE-duplicated exactly like the contiguous O3 path — layout and
    placement compose instead of excluding each other.

    ``paged_attn`` selects the step's attention implementation
    (``BestEffortConfig.paged_attn``): "gather" re-materializes the
    dense per-slot view every tick; "kernel" runs the block-table-aware
    Pallas decode kernel straight on the pool.  ``attn_impl`` records
    what :meth:`make_step` actually built — a model without a paged
    decode step degrades to gather, never fails, and ``degrade_reason``
    + a warning log say why (every zoo family ships one now, so this
    fires only for stripped/exotic ModelAPIs).  ``state_impl`` records
    "rows" when the family's recurrent/cross state leaves live in the
    row pool.

    ``kv_dtype`` selects the pool's STORED dtype
    (``BestEffortConfig.kv_dtype``): "bf16" stores compute-width blocks
    (bit-identical ladder contract); "int8"/"fp8" store narrow blocks
    with per-block absmax scales — the manager's cache becomes a
    ``{"pool", "scale"}`` bundle the steps split and re-join, and the
    rung's contract relaxes to the dtype's tolerance contract
    (``serving.kvquant.tolerance_contract``).
    """

    name = "paged"
    supports_step_fn = False

    def __init__(self, paged_attn: str = "gather",
                 kv_dtype: str = "bf16"):
        from repro.serving import kvquant
        if paged_attn not in ("gather", "kernel"):
            raise ValueError(
                f"paged_attn must be 'gather' or 'kernel' "
                f"(got {paged_attn!r})")
        kvquant.validate_kv_dtype(kv_dtype)
        self.paged_attn = paged_attn
        self.attn_impl = paged_attn      # updated by make_step
        self.state_impl = "none"         # "rows" when state leaves pool
        self.degrade_reason = None       # recorded fallback, or None
        self.kv_dtype = kv_dtype
        self.quantized = kvquant.is_quantized(kv_dtype)

    def build_manager(self, model, batch_size, max_seq,
                      config: BestEffortConfig, placement):
        return PagedCacheManager(
            model, batch_size, max_seq,
            block_size=config.kv_block_size,
            pool_blocks=config.kv_pool_blocks,
            placement=placement,
            kv_dtype=self.kv_dtype)

    def wire_scheduler(self, scheduler, manager) -> None:
        # The scheduler drives the block lifecycle: admission is gated
        # on free blocks (a request that fits max_seq but not the pool
        # queues), admit allocates the reservation, retire returns it
        # before the next admission wave.  The submit gate rejects the
        # one class of request no wave can ever admit — a reservation
        # larger than the TOTAL pool — at the submission boundary.
        scheduler.admission_gate = manager.can_admit
        scheduler.submit_gate = manager.infeasible_reason
        scheduler.on_admit = manager.admit_slot
        scheduler.on_retire = manager.release_slot

    def make_step(self, model, sampler_cfg, manager, placement):
        # Pool geometry (and any shardings) are part of the program, so
        # each paged engine compiles its own step.
        use_kernel = (self.paged_attn == "kernel"
                      and model.paged_decode_step is not None)
        self.attn_impl = "kernel" if use_kernel else "gather"
        self.state_impl = "rows" if manager.state is not None else "none"
        if self.paged_attn == "kernel" and not use_kernel:
            self.degrade_reason = (
                f"paged_attn='kernel' requested but family "
                f"'{model.cfg.family}' has no paged_decode_step; "
                f"degraded to the dense gather step")
            log.warning("%s", self.degrade_reason)
        sample = make_sampler(sampler_cfg)
        if use_kernel:
            fused = make_paged_kernel_fused(model, sample, manager,
                                            placement)
        else:
            fused = make_paged_fused(
                model, sample, manager,
                constrain=placement.constrain_axis if placement.sharded
                else None)
        if not placement.sharded:
            return jax.jit(fused, donate_argnums=(1,))
        pool_sh = manager.pool_shardings(placement)
        tok_sh, pos_sh = placement.token_shardings()
        repl = placement.replicated
        n_extras = int(manager.has_blocks) + int(manager.state is not None)
        return jax.jit(
            fused, donate_argnums=(1,),
            in_shardings=(repl, pool_sh) + (repl,) * n_extras
            + (tok_sh, pos_sh, pos_sh),
            out_shardings=(pos_sh, pool_sh))

    def make_prefill_step(self, model, sampler_cfg, manager, placement):
        """The paged prefill chunk, matching ``attn_impl``:

        * gather — slice slot ``islot``'s block-table row and/or state
          row, gather its single-slot dense view, run the SAME dense
          ``prefill_step`` the contiguous rungs run, scatter the state
          row and every block of the view back (``scatter_view`` — a
          chunk spans several blocks).  This is how carried-state
          families chunk: the chunk advances the slot's REAL state row
          here, while the batched decode tick parks the slot on the
          NULL row (``step_extras(parked=...)``).
        * kernel — the model's ``paged_prefill_step`` writes chunk K/V
          straight into pool blocks and runs the multi-query
          block-table Pallas kernel; no dense view is built at all.

        A kernel-mode engine whose model lacks a paged prefill step
        degrades to gather (same best-effort rule as ``make_step``).
        """
        if placement.sharded or model.prefill_step is None:
            return None
        sample = make_sampler(sampler_cfg)
        plan, splan = manager.plan, manager.state_plan
        quantized = plan.quantized
        kv_dtype = plan.kv_dtype
        use_kernel = (self.attn_impl == "kernel"
                      and model.paged_prefill_step is not None)
        if use_kernel:
            def _prefill(params, cache, *rest):
                extras = rest[:-5]
                islot, tokens, start, last, seeds = rest[-5:]
                tables, _rows = _split_extras(manager, extras)
                pool, scales = _split_cache(cache, quantized)
                row = jax.lax.dynamic_slice_in_dim(tables, islot, 1,
                                                   axis=0)
                if quantized:
                    logits, new_pool, new_scales = model.paged_prefill_step(
                        params, pool, row, tokens, start, last,
                        scales=scales, kv_dtype=kv_dtype)
                else:
                    logits, new_pool = model.paged_prefill_step(
                        params, pool, row, tokens, start, last)
                    new_scales = None
                return (sample(logits, seeds)[0],
                        _join_cache(new_pool, new_scales, quantized))
        else:
            def _prefill(params, cache, *rest):
                extras = rest[:-5]
                islot, tokens, start, last, seeds = rest[-5:]
                tables, rows = _split_extras(manager, extras)
                pool, scales = _split_cache(cache, quantized)
                row_t = row_r = None
                dense = pool
                if tables is not None:
                    row_t = jax.lax.dynamic_slice_in_dim(tables, islot, 1,
                                                         axis=0)
                    dense = plan.gather(dense, row_t, scales)
                if rows is not None:
                    row_r = jax.lax.dynamic_slice_in_dim(rows, islot, 1,
                                                         axis=0)
                    dense = splan.gather(dense, row_r)
                logits, new_dense = model.prefill_step(
                    params, dense, tokens, start, last)
                new_pool = pool
                if rows is not None:
                    new_pool = splan.scatter(new_pool, row_r, new_dense)
                if tables is None:
                    return (sample(logits, seeds)[0],
                            _join_cache(new_pool, scales, quantized))
                if quantized:
                    new_pool, new_scales = plan.scatter_view(
                        new_pool, row_t, new_dense, scales=scales,
                        lengths=start + tokens.shape[1])
                    return (sample(logits, seeds)[0],
                            _join_cache(new_pool, new_scales, True))
                new_pool = plan.scatter_view(new_pool, row_t, new_dense)
                return sample(logits, seeds)[0], new_pool
        return jax.jit(_prefill, donate_argnums=(1,))

    def make_verify_step(self, model, sampler_cfg, manager, placement):
        """The paged speculative verify, matching ``attn_impl``:

        * gather — materialize every slot's dense view, run the SAME
          dense ``verify_step`` the contiguous rung runs, scatter the
          WHOLE view back (``scatter_view`` — a speculative window spans
          several blocks; writes past a slot's reservation land in NULL
          table entries and vanish into the write-garbage NULL row, so
          rejection rolls back by slot-length truncation alone and
          blocks never leak).
        * kernel — the model's ``paged_verify_step`` scatters the
          window's K/V straight into pool blocks and the multi-query
          block-table Pallas kernel attends the prefix; no dense view.

        A kernel-mode engine whose model lacks a paged verify step
        degrades to gather (same best-effort rule as ``make_step``)."""
        if model.verify_step is None:
            return None
        sample = make_sampler(sampler_cfg)
        plan = manager.plan
        quantized = plan.quantized
        kv_dtype = plan.kv_dtype
        use_kernel = (self.attn_impl == "kernel"
                      and model.paged_verify_step is not None)
        splan = manager.state_plan
        if use_kernel:
            def _verify(params, cache, *rest):
                extras, (tokens, start) = rest[:-2], rest[-2:]
                tables, _rows = _split_extras(manager, extras)
                pool, scales = _split_cache(cache, quantized)
                pool = jax.tree.map(placement.constrain_replicated, pool)
                if scales is not None:
                    scales = jax.tree.map(placement.constrain_replicated,
                                          scales)
                with placement.tracing():
                    if quantized:
                        logits, new_pool, new_scales = (
                            model.paged_verify_step(
                                params, pool, tables, tokens, start,
                                scales=scales, kv_dtype=kv_dtype))
                    else:
                        logits, new_pool = model.paged_verify_step(
                            params, pool, tables, tokens, start)
                        new_scales = None
                return (sample(logits, None),
                        _join_cache(new_pool, new_scales, quantized))
        else:
            def _verify(params, cache, *rest):
                extras, (tokens, start) = rest[:-2], rest[-2:]
                tables, rows = _split_extras(manager, extras)
                pool, scales = _split_cache(cache, quantized)
                dense = pool
                if tables is not None:
                    dense = plan.gather(dense, tables, scales)
                if rows is not None:
                    dense = splan.gather(dense, rows)
                if placement.sharded:
                    dense = plan.map_batch_axes(dense,
                                                placement.constrain_axis)
                logits, new_dense = model.verify_step(params, dense,
                                                      tokens, start)
                new_pool = pool
                if rows is not None:
                    new_pool = splan.scatter(new_pool, rows, new_dense)
                if tables is None:
                    return (sample(logits, None),
                            _join_cache(new_pool, scales, quantized))
                if quantized:
                    new_pool, new_scales = plan.scatter_view(
                        new_pool, tables, new_dense, scales=scales,
                        lengths=start + tokens.shape[1])
                    return (sample(logits, None),
                            _join_cache(new_pool, new_scales, True))
                new_pool = plan.scatter_view(new_pool, tables, new_dense)
                return sample(logits, None), new_pool
        if not placement.sharded:
            return jax.jit(_verify, donate_argnums=(1,))
        pool_sh = manager.pool_shardings(placement)
        tok_sh, pos_sh = placement.token_shardings()
        repl = placement.replicated
        n_extras = int(manager.has_blocks) + int(manager.state is not None)
        return jax.jit(
            _verify, donate_argnums=(1,),
            in_shardings=(repl, pool_sh) + (repl,) * n_extras
            + (tok_sh, pos_sh),
            out_shardings=(tok_sh, pool_sh))


def select_layout(config: BestEffortConfig) -> KVLayout:
    """The layout axis of the config, as a strategy object."""
    if config.kv_layout == "paged":
        return PagedLayout(config.paged_attn, kv_dtype=config.kv_dtype)
    return ContiguousLayout()
