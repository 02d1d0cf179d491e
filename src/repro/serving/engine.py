"""Slot-based continuous-batching decode engine, built at an OptLevel.

The serving counterpart of the paper's five-step ladder, with every step a
real, independently toggleable stage keyed by ``BestEffortConfig.level``:

  O1 data caching      — persistent device-resident cache with in-place
                         per-slot resets (``cache.CacheManager``); O0 falls
                         back to a per-request cache rebuild.
  O2 pipelining        — continuous batching: every active slot decodes in
                         ONE fused jitted step with sampling in-graph
                         (``sampler``), amortizing the pass over the
                         weights; O0/O1 run the un-pipelined loop — one
                         batch-1 model call per request per tick, host-side
                         sampling over that request's full-vocab logits.
  O3 PE duplication    — sharding across devices when
                         ``config.effective_pe > 1``
                         (``parallel.sharding.PlacementPlan`` on a 1-D
                         data mesh): the contiguous cache on its batch
                         axis, the paged pool on its BLOCK axis.
  O4 double buffering  — host prestages next tick's token/position buffers
                         while the device runs this tick (``overlap``).
  O5 scratchpad reorg  — packed slot admission: all slots admitted in a
                         tick are zeroed by one fused donated call.
  O6 paged scratchpad  — the decode cache becomes a pool of fixed-size
                         KV blocks with per-request block tables
                         (``paged.PagedCacheManager``); the jitted step
                         gathers each slot's dense view from the pool and
                         scatters back the one block it wrote.  Admission
                         is gated on free blocks (queue, never reject).

Cache LAYOUT (contiguous vs paged, ``serving.layout.KVLayout``) and
device PLACEMENT (replicated vs PE-sharded,
``parallel.sharding.PlacementPlan``) are two orthogonal strategy objects
selected here once — the engine itself never branches on them again, so
O3 x O6 compose (a paged engine with ``effective_pe > 1`` on >= 2
devices runs a block-axis-sharded step) instead of excluding each other.

Prefill is a first-class phase with two implementations:

  * LEGACY prestaged (``config.prefill_chunk == 0``): every step feeds
    one token per active slot — a slot still consuming its prompt feeds
    the next prompt token (its logits are discarded), a generating slot
    feeds its last sampled token.  One jitted step serves all families
    (KV-cache transformers, RWKV/SSM state models, enc-dec) and all
    request phases; TTFT is O(prompt_len) ticks.
  * CHUNKED (``config.prefill_chunk > 0``): prompts are consumed in
    fixed-size multi-token chunks — one batch-1 chunk dispatch per tick
    for the head of the scheduler's prefill queue, interleaved with the
    batched decode step over the generating slots (prefilling slots are
    parked in that step: fed their real next prompt token so the row
    stays harmless, but advanced only by chunks).  TTFT drops to
    O(ceil(prompt_len / chunk)) ticks.  Families without a model prefill
    step (MoE, recurrent-state), sharded placements, caller step_fns and
    the un-pipelined O0/O1 loop degrade to the legacy path
    (``prefill_mode == "token"``); greedy tokens are bit-identical
    either way — the same oracle the O0..O6 ladder pins.

  O7 speculative decode — a small drafter model proposes ``draft_k``
                         tokens per generating slot per tick; the target
                         verifies the whole window in ONE batched
                         multi-token forward (the layout's verify step —
                         PR 6's qlen>1 machinery) and greedy rejection
                         accepts exactly the target's argmax prefix, so
                         output stays bit-identical to O5/O6 while up to
                         ``1 + acceptance * K`` tokens land per tick.
                         Rollback is free on both layouts: rejected
                         writes sit beyond the slot's frontier
                         (contiguous — rewritten before unmasked read;
                         paged — confined to the slot's own reservation
                         or the NULL block, so truncating the logical
                         length rolls back without touching the block
                         tables and blocks never leak).  No drafter
                         configured, ``draft_k == 0``, a stochastic
                         sampler, or a family without verify hooks all
                         degrade to the plain decode path — recorded in
                         ``engine.spec_mode`` ("draft" / "off"), never a
                         failure.  The speculative tick replaces the O4
                         double-buffered schedule (acceptance must be
                         known before the next window can be drafted);
                         the drafter's own dispatches pipeline against
                         the verify step instead.

The phases are also exposed directly (the JetStream-style serving API):
``prefill(prompt)`` consumes a prompt on a standalone batch-1 cache and
samples the first token, ``insert(result)`` installs that KV state into
a free slot (scattering it through a freshly reserved block table under
the paged layout), and ``generate()`` drains the decode loop.

Admission, slot bookkeeping and retirement live in ``scheduler``; the
engine is only the tick loop that wires scheduler, cache manager, sampler
and overlap together under one config.  Every tick is a ``serving.tick``
span with a child span per phase, and ``engine.counters`` counts what the
ticks did (``serving.tracing``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.optlevel import BestEffortConfig, OptLevel, Step
from repro.parallel.sharding import plan_pe_placement
from repro.serving.layout import select_layout, shared_steps
from repro.serving.overlap import HostOverlap
from repro.serving.sampler import SamplerConfig
from repro.serving.scheduler import Request, Scheduler
from repro.serving.tracing import Counters, span


class TickBudgetExceeded(RuntimeError):
    """``DecodeEngine.run`` exhausted ``max_ticks`` with work still
    queued or in flight.  Every surviving request has been marked
    ``truncated`` (its partial ``generated`` list is intact); the
    engine's slots and queue are untouched, so a caller that expected a
    long drain can catch this and keep ticking.  The silent alternative
    — returning only ``finished`` — let a permanently-gated queue spin
    the whole budget and then LOOK like a clean drain."""

    def __init__(self, msg: str, survivors: list):
        super().__init__(msg)
        self.survivors = survivors


@dataclasses.dataclass
class PrefillResult:
    """Output of the standalone PREFILL phase — everything INSERT needs:
    the request (rid already assigned, so stochastic sampling seeds are
    stable), the first sampled token, and the batch-1 dense cache tree
    holding the prompt's K/V (or recurrent state)."""
    request: Request
    first_token: int
    kv_state: object
    length: int          # prompt tokens consumed


class DecodeEngine:
    def __init__(self, model, params, *, batch_size: int, max_seq: int,
                 pad_id: int = 0, config: Optional[BestEffortConfig] = None,
                 sampler: Optional[SamplerConfig] = None,
                 policy: str = "fcfs", step_fn=None,
                 draft_model=None, draft_params=None):
        self.model = model
        self.B = batch_size
        self.max_seq = max_seq
        self.pad_id = pad_id
        self.config = config or BestEffortConfig(level=OptLevel.O5)
        self.level = self.config.level
        self.sampler_cfg = sampler or SamplerConfig()
        # One record of counts, shared by the scheduler and the cache
        # manager, which increment it where the work happens.
        self.counters = Counters()
        self.scheduler = Scheduler(batch_size, max_seq, policy=policy,
                                   counters=self.counters)
        self.n_steps = 0

        # The two orthogonal serving axes, as strategy objects: cache
        # layout (contiguous O0..O5 / paged O6) and device placement
        # (replicated / PE-sharded).  Every combination compiles a step.
        self.layout = select_layout(self.config)
        if step_fn is not None and not self.layout.supports_step_fn:
            # A caller-supplied fused step has no block-table argument;
            # silently falling back to the contiguous cache would let an
            # operator believe they are measuring the paged rung.
            raise ValueError(
                "step_fn is incompatible with the paged O6 cache (the "
                "jitted step must thread block tables); build the engine "
                "at O5 or drop step_fn")
        self.placement = plan_pe_placement(self.config, batch_size)
        self.params = self.placement.put_replicated(params)
        self.cache_mgr = self.layout.build_manager(
            model, batch_size, max_seq, self.config, self.placement,
            self.counters)
        self.layout.wire_scheduler(self.scheduler, self.cache_mgr)

        self._fused = self.level.has(Step.PIPELINING) or step_fn is not None
        if step_fn is not None:
            # Back-compat hook: a caller-supplied fused step
            # (params, cache, tokens, positions) -> (tokens, cache).
            self._step_fn = lambda p, c, t, pos, seeds: step_fn(p, c, t, pos)
        elif self._fused:
            self._step_fn = self.layout.make_step(
                model, self.sampler_cfg, self.cache_mgr, self.placement)
        else:
            # O0/O1: the un-pipelined serving loop — each active request
            # runs its OWN batch-1 model call per tick (every request pays
            # a full pass over the weights; no continuous batching), and
            # sampling happens OUTSIDE the graph: greedy argmax runs on
            # the host over the request's transferred logits; stochastic
            # kinds run as a separate device dispatch (host RNG would
            # diverge from the fused path's bits).
            shared = shared_steps(model, self.sampler_cfg)
            self._single_fn = shared["single"]
            self._sample_fn = shared["sample"]
            self._host_greedy = not self.sampler_cfg.stochastic

        # O4: host/device overlap via rotating prestaged buffers plus the
        # split-tick protocol (dispatch -> bookkeeping under the running
        # step -> finalize next tick).
        self._overlap = (HostOverlap(batch_size, pad_id,
                                     self.config.effective_buffers)
                         if self.level.has(Step.DOUBLE_BUFFERING) else None)
        self._pending = None        # (toks_future, emissions) of last tick

        # Chunked prefill: a single-slot multi-token chunk step, or None
        # when this (model, layout, placement) cell cannot chunk — the
        # tick loop then runs the legacy prestaged prompt path.
        self._prefill_chunk = int(self.config.prefill_chunk)
        self._prefill_fn = None
        if (self._prefill_chunk > 0 and self._fused and step_fn is None
                and not self.placement.sharded):
            self._prefill_fn = self.layout.make_prefill_step(
                model, self.sampler_cfg, self.cache_mgr, self.placement)
        self.prefill_mode = ("chunked" if self._prefill_fn is not None
                             else "token")
        # Best-effort degrades are RECORDED, never silent: the layout
        # stamps a reason when a requested capability fell back (kernel
        # attention without a paged step, chunked prefill on a family
        # the cell cannot chunk) — surfaced in serve/autotune meta.
        self.degrade_reason = getattr(self.layout, "degrade_reason", None)

        # O7: speculative decoding.  Active only when every piece is in
        # place — the rung enabled, a drafter configured (by name in the
        # config or passed in directly), draft_k > 0, a deterministic
        # (greedy) sampler, the fused engine path, and a layout verify
        # step for this (layout x placement x model) cell.  Anything
        # missing degrades to the plain decode path above, recorded in
        # ``spec_mode`` — never a failure.  A vocab-incompatible
        # (drafter, target) pair, however, raises loudly
        # (``model_zoo.compatible_drafter``): that is an operator error,
        # not a best-effort gap.
        self._spec = False
        self.spec_mode = "off"
        self._draft_k = max(int(self.config.draft_k), 0)
        self._verify_fn = None
        # Start of spec_stats_window's interval (long-running servers
        # need per-interval acceptance, not lifetime averages that drift
        # stale).
        self._spec_window = self.counters.snapshot()
        self._dstate = [(-1, 0)] * batch_size   # per-slot (rid, drafter pos)
        spec_wanted = (self.level.has(Step.SPECULATIVE)
                       and (draft_model is not None
                            or bool(self.config.draft_model))
                       and self._draft_k > 0)
        if (spec_wanted and self._fused and step_fn is None
                and not self.sampler_cfg.stochastic):
            self._verify_fn = self.layout.make_verify_step(
                model, self.sampler_cfg, self.cache_mgr, self.placement)
            if self._verify_fn is not None:
                self._wire_drafter(draft_model, draft_params)
                self._spec = True
                self.spec_mode = "draft"

    def _wire_drafter(self, api, params):
        """Build (or adopt) the drafter: a small zoo model with its own
        batch-B contiguous cache, running the shared greedy fused step.
        The pairing is validated by ``model_zoo.compatible_drafter`` —
        the drafter proposes token IDS the target scores, so the two
        must share one vocab."""
        from repro.models import model_zoo
        if api is None:
            dcfg = model_zoo.compatible_drafter(self.model.cfg,
                                                self.config.draft_model)
            api = model_zoo.get_model(dcfg)
        else:
            model_zoo.compatible_drafter(self.model.cfg, api.cfg)
        if params is None:
            params = api.init(jax.random.PRNGKey(0))
        self._draft_api = api
        self._draft_params = self.placement.put_replicated(params)
        self._draft_cache = api.init_cache(self.B, self.max_seq)
        dsteps = shared_steps(api, SamplerConfig())     # greedy drafts
        self._draft_fused = dsteps["fused"]
        self._draft_prefill_fn = (dsteps["prefill"]
                                  if api.prefill_step is not None else None)
        self._draft_seeds = jnp.zeros((self.B,), jnp.int32)

    # -- public API -----------------------------------------------------------
    @property
    def cache(self):
        return self.cache_mgr.cache

    def _spec_view(self, c: Counters) -> dict:
        drafted, windows = c.spec_drafted, c.spec_windows
        return {
            "spec_mode": self.spec_mode,
            "draft_k": self._draft_k if self._spec else 0,
            "drafted": drafted,
            "accepted": c.spec_accepted,
            "accept_rate": (c.spec_accepted / drafted) if drafted else 0.0,
            "emitted": c.spec_emitted,
            "eff_tok_per_step": (c.spec_emitted / windows) if windows
            else 0.0,
        }

    @property
    def spec_stats(self) -> dict:
        """Speculation counters: drafts proposed/accepted over the
        engine's lifetime, tokens emitted through verify windows, and
        the two ladder columns — ``accept_rate`` (accepted / proposed)
        and ``eff_tok_per_step`` (tokens emitted per slot per verify
        window, in [1, K+1] and equal to ``1 + accept_rate * draft_k``
        absent mid-window retirements)."""
        return self._spec_view(self.counters)

    def spec_stats_window(self, *, reset: bool = True) -> dict:
        """Speculation counters over the window SINCE the last reset —
        the per-measurement-interval view a long-running server needs
        (the lifetime ``spec_stats`` averages drift stale as traffic
        shifts).  Same shape as ``spec_stats``; ``reset=True`` (the
        default) starts the next window at the current counters, so
        back-to-back calls bracket disjoint intervals.  The lifetime
        counters themselves are never rewound."""
        out = self._spec_view(self.counters.since(self._spec_window))
        if reset:
            self._spec_window = self.counters.snapshot()
        return out

    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def finished(self):
        return self.scheduler.finished

    @property
    def slots(self):
        return self.scheduler.slots

    def submit(self, req: Request) -> int:
        return self.scheduler.submit(req)

    # -- prefill -> insert -> generate ---------------------------------------
    def prefill(self, prompt, *, max_new_tokens: int = 16,
                eos_id: Optional[int] = None,
                chunk: Optional[int] = None) -> PrefillResult:
        """PREFILL phase: consume ``prompt`` on a standalone batch-1
        contiguous cache — in multi-token chunks when the model has a
        prefill step, else one token per step — and sample the first
        generated token.  No engine slot is touched: :meth:`insert`
        installs the returned KV state into a free slot (scattering it
        through a block table under the paged layout) and
        :meth:`generate` decodes from there.  Greedy tokens are
        bit-identical to submitting the same request through the
        engine's internal admission path."""
        req = Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                      eos_id=eos_id)
        req.rid = next(self.scheduler._rid)
        if req.n_prompt < 1:
            raise ValueError(f"req {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"req {req.rid}: prefill needs max_new_tokens >= 1")
        if req.n_prompt + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"req {req.rid}: prompt ({req.n_prompt}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds engine max_seq "
                f"({self.max_seq})")
        cfg = self.sampler_cfg
        shared = shared_steps(self.model, cfg)
        cache = self.model.init_cache(1, self.max_seq)
        P = req.n_prompt
        seed = cfg.request_seed(req.rid, 0) if cfg.stochastic else 0
        if self.model.prefill_step is not None:
            C = int(chunk or self._prefill_chunk or min(P, 64))
            fn = shared["prefill"]
            pos = 0
            tok_dev = None
            while pos < P:
                n = min(C, P - pos)
                toks = np.full((1, C), self.pad_id, np.int32)
                toks[0, :n] = req.prompt[pos:pos + n]
                tok_dev, cache = fn(
                    self.params, cache, jnp.int32(0), jnp.asarray(toks),
                    jnp.asarray([pos], jnp.int32),
                    jnp.asarray([n - 1], jnp.int32),
                    jnp.asarray([seed], jnp.int32))
                pos += n
            first = int(np.asarray(tok_dev))
        else:
            # Per-token fallback: works for every family (recurrent
            # state included) — the same batch-1 step the O0 loop runs.
            single, sample = shared["single"], shared["sample"]
            logits = None
            for p in range(P):
                logits, cache = single(
                    self.params, cache, jnp.int32(req.prompt[p]),
                    jnp.int32(p), jnp.int32(0))
            if cfg.stochastic:
                first = int(sample(jnp.asarray(logits)[None],
                                   jnp.asarray([seed], jnp.int32))[0])
            else:
                first = int(np.asarray(logits).argmax())
        return PrefillResult(request=req, first_token=first,
                             kv_state=cache, length=P)

    def insert(self, result: PrefillResult,
               slot: Optional[int] = None) -> int:
        """INSERT phase: occupy a free slot with a prefilled request.
        Copies the batch-1 KV state over the slot's cache slice
        (contiguous) or scatters it through the slot's freshly reserved
        block table (paged), places the scheduler slot at the
        post-prompt position and records the first token — after which
        the request decodes like any other.  Raises when no slot is
        free or (paged) the pool cannot hold the request's reservation
        right now; callers queue and retry after retirements."""
        sched = self.scheduler
        req = result.request
        if slot is None:
            free = [i for i, s in enumerate(sched.slots) if not s.active]
            if not free:
                raise ValueError("no free slot to insert into")
            slot = free[0]
        if sched.submit_gate is not None:
            reason = sched.submit_gate(req)
            if reason:
                # Never-fits: distinct from the transient gate below —
                # no retirement will ever make room for this one.
                raise ValueError(f"req {req.rid}: {reason}")
        if (sched.admission_gate is not None
                and not sched.admission_gate(req)):
            raise ValueError(
                "insufficient free KV blocks to insert (retire requests "
                "or enlarge the pool)")
        sched.place(req, slot)          # fires on_admit (block reserve)
        self.cache_mgr.insert_slot(slot, result.kv_state)
        sched.advance(slot, result.first_token)
        return slot

    def generate(self, *, max_ticks: int = 10_000) -> list:
        """GENERATE phase: drain inserted and queued requests — an alias
        of :meth:`run`, named for the prefill->insert->generate
        protocol."""
        return self.run(max_ticks=max_ticks)

    def step(self) -> bool:
        """One engine tick: admit, run the batched decode step, retire.

        The tick is one ``serving.tick`` span; its metadata counts the
        slots holding a request (``n``), those its decode dispatch
        advanced (``gen``) and those it parked mid-prefill
        (``parked``)."""
        with span("serving.tick") as tick:
            if self._spec:
                progressed = self._step_spec(tick)
            elif self._overlap is not None:
                progressed = self._step_overlapped(tick)
            else:
                progressed = self._step_serial(tick)
        if progressed:
            self.counters.ticks += 1
        return progressed

    def _admit(self) -> list:
        """One admission wave and the admitted slots' cache reset (the
        ``serving.admit`` span).  Without chunked prefill the admitting
        tick starts the prompt, so it stamps ``prefill_start_s``."""
        sched = self.scheduler
        c = self.counters
        with span("serving.admit") as sp:
            gated = c.admit_gated_pool
            admitted = sched.admit()
            if admitted:
                self.cache_mgr.reset_slots(admitted, sched.active_indices)
                if self._spec or self._prefill_fn is None:
                    for i in admitted:
                        r = sched.slots[i].req
                        r.prefill_start_s = r.admit_s
            sp.set_metadata(admitted=len(admitted), queued=len(sched.queue),
                            gated=c.admit_gated_pool - gated)
        return admitted

    def _dispatch(self, tokens_np, positions_np, seeds_np, n_gen: int,
                  parked=None):
        """Run the batched fused device step; returns the (possibly still
        in-flight) sampled tokens and installs the new cache.  The
        manager's ``step_extras()`` supplies any layout-specific step
        inputs — the paged manager's cached device block tables and
        state rows (invalidated at admission/retirement; the shapes
        never change, so there is no retrace) — keeping this path
        layout-blind.  ``parked`` names slots mid-chunked-prefill this
        tick: managers with carried state alias them to the NULL state
        row so the batched pad-feed cannot advance their real state
        (their prompt advances only through ``_prefill_tick``).
        ``n_gen`` is the number of slots the step advances."""
        with span("serving.decode"):
            toks_dev, new_cache = self._step_fn(
                self.params, self.cache_mgr.cache,
                *self.cache_mgr.step_extras(parked=parked),
                jnp.asarray(tokens_np), jnp.asarray(positions_np),
                jnp.asarray(seeds_np))
        self.cache_mgr.cache = new_cache
        self.n_steps += 1
        self.counters.decode_dispatches += 1
        self.counters.decode_slots += n_gen
        return toks_dev

    def _prefill_tick(self, i: int):
        """Dispatch one prefill CHUNK for slot ``i`` and do its
        bookkeeping: up to ``prefill_chunk`` prompt tokens in one
        batch-1 multi-token step (padded to the fixed chunk width so a
        single trace serves every chunk).  The chunk that consumes the
        LAST prompt token also emits the request's first generated token
        — sampled in-graph from the chunk's closing logits and handed to
        ``advance`` so all retirement logic is reused; earlier chunks
        only move the position (``advance_chunk``).  The final chunk
        waits for that token (``serving.first_token``)."""
        sched = self.scheduler
        c = self.counters
        s = sched.slots[i]
        r = s.req
        C = self._prefill_chunk
        start = s.pos
        n = min(C, r.n_prompt - start)
        final = start + n == r.n_prompt
        with span("serving.prefill", rid=r.rid, slot=i, start=start, n=n,
                  final=int(final)):
            if r.prefill_start_s is None:
                r.prefill_start_s = time.monotonic()
            toks = np.full((1, C), self.pad_id, np.int32)
            toks[0, :n] = r.prompt[start:start + n]
            cfg = self.sampler_cfg
            seed = (cfg.request_seed(r.rid, 0) if cfg.stochastic and final
                    else 0)
            tok_dev, new_cache = self._prefill_fn(
                self.params, self.cache_mgr.cache,
                *self.cache_mgr.step_extras(),
                jnp.int32(i), jnp.asarray(toks),
                jnp.asarray([start], jnp.int32),
                jnp.asarray([n - 1], jnp.int32),
                jnp.asarray([seed], jnp.int32))
            self.cache_mgr.cache = new_cache
            c.prefill_chunks += 1
            c.prefill_tokens += n
            if final:
                sched.advance_chunk(i, n - 1)
                with span("serving.first_token", rid=r.rid):
                    tok = int(np.asarray(tok_dev))
                c.first_token_syncs += 1
                sched.advance(i, tok)
            else:
                sched.advance_chunk(i, n)

    # -- speculative decoding (O7) -------------------------------------------
    def _token_at(self, i: int, q: int) -> int:
        """Token ``q`` of slot ``i``'s sequence (prompt, then generated) —
        what the drafter replays while catching up to the target."""
        r = self.scheduler.slots[i].req
        return r.prompt[q] if q < r.n_prompt else r.generated[q - r.n_prompt]

    def _draft_dispatch(self, tokens_np, positions_np):
        """One batched drafter decode tick on the drafter's own cache."""
        toks, self._draft_cache = self._draft_fused(
            self._draft_params, self._draft_cache,
            jnp.asarray(tokens_np), jnp.asarray(positions_np),
            self._draft_seeds)
        return np.asarray(toks).reshape(self.B, -1)[:, -1]

    def _draft_catchup_chunks(self, i: int, tgt: int):
        """Replay a LONG stretch of slot ``i``'s known tokens into the
        drafter cache via the drafter's chunked prefill step (a fresh
        tenant's whole prompt) — fixed-width chunks so one trace serves
        every catch-up."""
        C = 16
        rid, dpos = self._dstate[i]
        while dpos < tgt:
            n = min(C, tgt - dpos)
            toks = np.full((1, C), self.pad_id, np.int32)
            toks[0, :n] = [self._token_at(i, q) for q in range(dpos,
                                                               dpos + n)]
            _, self._draft_cache = self._draft_prefill_fn(
                self._draft_params, self._draft_cache, jnp.int32(i),
                jnp.asarray(toks), jnp.asarray([dpos], jnp.int32),
                jnp.asarray([n - 1], jnp.int32),
                jnp.asarray([0], jnp.int32))
            dpos += n
        self._dstate[i] = (rid, dpos)

    def _draft_tokens(self, emit: list) -> dict:
        """Catch the drafter up to each emitting slot's frontier, then
        run K batched greedy drafter ticks from the pending token —
        returns ``{slot: [d_1 .. d_K]}``.

        Catch-up replays KNOWN tokens only (prompt + accepted output),
        so the drafter cache never depends on rejected drafts: after a
        partial acceptance the drafter position is truncated to the
        accepted frontier and the stale draft K/V beyond it is rewritten
        here before the drafter ever attends it unmasked — the same
        standing-garbage discipline the target caches use.  Slots not
        being drafted this dispatch are parked: pad token written at
        ``max_seq - 1``, a position every real consumer rewrites in the
        same dispatch that first reads it."""
        slots = self.scheduler.slots
        K = self._draft_k
        for i in emit:
            rid = slots[i].req.rid
            if self._dstate[i][0] != rid:
                self._dstate[i] = (rid, 0)      # fresh tenant: replay all
            if (self._draft_prefill_fn is not None
                    and slots[i].pos - self._dstate[i][1] > 2 * (K + 1)):
                self._draft_catchup_chunks(i, slots[i].pos)
        while True:
            behind = [i for i in emit if self._dstate[i][1] < slots[i].pos]
            if not behind:
                break
            tokens = np.full((self.B, 1), self.pad_id, np.int32)
            positions = np.full((self.B,), self.max_seq - 1, np.int32)
            for i in behind:
                dpos = self._dstate[i][1]
                tokens[i, 0] = self._token_at(i, dpos)
                positions[i] = dpos
            self._draft_dispatch(tokens, positions)
            for i in behind:
                rid, dpos = self._dstate[i]
                self._dstate[i] = (rid, dpos + 1)
        drafts = {i: [] for i in emit}
        cur = {i: slots[i].next_token() for i in emit}
        for j in range(K):
            tokens = np.full((self.B, 1), self.pad_id, np.int32)
            positions = np.full((self.B,), self.max_seq - 1, np.int32)
            for i in emit:
                tokens[i, 0] = cur[i]
                positions[i] = slots[i].pos + j
            out = self._draft_dispatch(tokens, positions)
            for i in emit:
                cur[i] = int(out[i])
                drafts[i].append(cur[i])
        for i in emit:
            # Drafter K/V now covers positions .. pos+K-1; acceptance
            # bookkeeping truncates this back if drafts are rejected.
            self._dstate[i] = (self._dstate[i][0], slots[i].pos + K)
        return drafts

    def _step_spec(self, tick) -> bool:
        """One speculative tick: draft K per generating slot, verify the
        whole batch's windows in ONE multi-token target forward, accept
        each slot's longest draft==argmax prefix plus the target's
        bonus/correction token, and roll rejected tails back by frontier
        truncation.  Prompt-consuming slots ride the SAME verify forward
        as fixed-width prefill chunks; slots within K of the ``max_seq``
        boundary (where window positions would clip onto each other)
        take a plain decode dispatch instead — at most their last few
        ticks."""
        sched = self.scheduler
        slots = sched.slots
        c = self.counters
        self._admit()
        active = sched.active_indices
        if not active:
            return False
        K = self._draft_k
        W = K + 1
        emit, boundary, prefill = [], [], []
        for i in active:
            s = slots[i]
            if s.pos < s.req.n_prompt - 1:
                prefill.append(i)
            elif s.pos + K < self.max_seq:
                emit.append(i)
            else:
                boundary.append(i)

        drafts = {}
        if emit:
            with span("serving.draft", slots=len(emit)):
                drafts = self._draft_tokens(emit)

        greedy = None
        if emit or prefill:
            tokens = np.full((self.B, W), self.pad_id, np.int32)
            start = np.full((self.B,), self.max_seq - 1, np.int32)
            pf_real = {}
            for i in emit:
                s = slots[i]
                start[i] = s.pos
                tokens[i, 0] = s.next_token()
                tokens[i, 1:] = drafts[i]
            for i in prefill:
                s = slots[i]
                r = s.req
                start[i] = s.pos
                n = min(W, r.n_prompt - s.pos)
                tokens[i, :n] = r.prompt[s.pos:s.pos + n]
                pf_real[i] = n
            with span("serving.verify", slots=len(emit),
                      prefill=len(prefill)):
                toks_dev, new_cache = self._verify_fn(
                    self.params, self.cache_mgr.cache,
                    *self.cache_mgr.step_extras(),
                    jnp.asarray(tokens), jnp.asarray(start))
                self.cache_mgr.cache = new_cache
                greedy = np.asarray(toks_dev).reshape(self.B, W)
            self.n_steps += 1
            c.decode_dispatches += 1
            c.decode_slots += len(emit)

        btoks = None
        if boundary:
            tokens_np = np.full((self.B, 1), self.pad_id, np.int32)
            positions_np = np.full((self.B,), self.max_seq - 1, np.int32)
            for i in boundary:
                s = slots[i]
                tokens_np[i, 0] = s.next_token()
                positions_np[i] = s.pos
            toks_b = self._dispatch(tokens_np, positions_np,
                                    np.zeros((self.B,), np.int32),
                                    len(boundary))
            btoks = np.asarray(toks_b).reshape(self.B, -1)[:, -1]
        tick.set_metadata(n=len(active), gen=len(emit) + len(boundary),
                          parked=0)

        # -- bookkeeping (host) ----------------------------------------------
        for i in emit:
            g = greedy[i]
            d = drafts[i]
            a = 0
            while a < K and d[a] == g[a]:
                a += 1          # draft j+1 must equal the target's row j
            p = slots[i].pos
            rid = slots[i].req.rid
            window = [int(x) for x in g[:a + 1]]
            n_rec, _ = sched.advance_multi(i, window)
            c.spec_drafted += K
            c.spec_accepted += a
            c.spec_emitted += n_rec
            c.spec_windows += 1
            # Truncate the drafter to what actually survived: positions
            # beyond pos + n_rec hold rejected-draft K/V, replayed from
            # the accepted tokens before the next draft attends them.
            self._dstate[i] = (rid, min(p + K, p + n_rec))
        if prefill:
            # the prompt windows rode the verify forward
            with span("serving.prefill", slots=len(prefill)):
                for i in prefill:
                    s = slots[i]
                    n = pf_real[i]
                    if s.pos + n == s.req.n_prompt:  # window closes prompt
                        sched.advance_chunk(i, n - 1)
                        sched.advance(i, int(greedy[i][n - 1]))
                    else:
                        sched.advance_chunk(i, n)
        for i in boundary:
            sched.advance(i, int(btoks[i]))
        return True

    def _step_serial(self, tick) -> bool:
        """O0..O3: admit -> fill -> dispatch -> wait -> retire, in order.

        Below O2 (no pipelining) each active request additionally runs its
        own batch-1 model call, one after another — the naive per-request
        loop a batched tick replaces.
        """
        sched = self.scheduler
        self._admit()
        active = sched.active_indices
        if not active:
            return False

        cfg = self.sampler_cfg
        slots = sched.slots
        if not self._fused:
            # O0/O1: one model call per request, host-side sampling.
            toks = np.zeros((self.B,), np.int32)
            with span("serving.decode"):
                for i in active:
                    s = slots[i]
                    logits, self.cache_mgr.cache = self._single_fn(
                        self.params, self.cache_mgr.cache,
                        jnp.int32(s.next_token()), jnp.int32(s.pos),
                        jnp.int32(i))
                    if self._host_greedy:
                        toks[i] = int(np.asarray(logits).argmax())
                    else:
                        seed = cfg.request_seed(s.req.rid,
                                                len(s.req.generated))
                        toks[i] = int(self._sample_fn(
                            jnp.asarray(logits)[None],
                            jnp.asarray([seed], jnp.int32))[0])
            self.n_steps += 1
            self.counters.decode_dispatches += len(active)
            self.counters.decode_slots += len(active)
            tick.set_metadata(n=len(active), gen=len(active), parked=0)
            with span("serving.advance"):
                for i in active:
                    sched.advance(i, toks[i])
            return True

        # Chunked prefill: one prompt chunk (head of the prefill queue)
        # dispatches before the batched step; slots still consuming
        # their prompt are PARKED in that step — fed their real next
        # prompt token (so the row's write is the value a later chunk
        # rewrites; carried-state families additionally alias parked
        # slots to the NULL state row) but advanced only by chunks.
        parked = None
        if self._prefill_fn is not None:
            pf = sched.prefill_queue()
            if pf:
                self._prefill_tick(pf[0])
                active = sched.active_indices   # chunk may have retired
            if not active:
                return True
            gen = [i for i in active
                   if slots[i].pos >= slots[i].req.n_prompt]
            if not gen:
                tick.set_metadata(n=len(active), gen=0, parked=len(active))
                return True                     # prefill-only tick
            parked = [i for i in active if i not in set(gen)]
        else:
            gen = active
        tick.set_metadata(n=len(active), gen=len(gen),
                          parked=len(parked or ()))

        # O2/O3: one batched fused step for every active slot.
        with span("serving.fill"):
            tokens_np = np.asarray(
                [[s.next_token() if s.active else self.pad_id]
                 for s in slots], np.int32)
            positions_np = np.asarray(
                [s.pos if s.active else 0 for s in slots], np.int32)
            seeds_np = (np.asarray(
                [cfg.request_seed(s.req.rid, len(s.req.generated))
                 if s.active else 0 for s in slots], np.int32)
                if cfg.stochastic else np.zeros((self.B,), np.int32))

        toks_dev = self._dispatch(tokens_np, positions_np, seeds_np,
                                  len(gen), parked=parked)
        with span("serving.finalize"):
            with span("serving.fetch"):
                toks = np.asarray(toks_dev).reshape(self.B, -1)[:, -1]
            for i in gen:
                sched.advance(i, toks[i])
        return True

    def _step_overlapped(self, tick) -> bool:
        """O4+: double-buffered schedule.  Each call finalizes the
        previous tick (its tokens have been computing since last call),
        dispatches this tick from mostly-prestaged buffers, then does all
        token-independent bookkeeping — position advance, count-based
        retirement planning, admission, cache-slot resets, next tick's
        prompt prestaging — while the device runs."""
        sched = self.scheduler
        cfg = self.sampler_cfg
        if self._pending is not None:
            with span("serving.finalize"):
                toks_dev, emissions = self._pending
                self._pending = None
                with span("serving.fetch"):
                    toks = np.asarray(toks_dev).reshape(self.B, -1)[:, -1]
                sched.finalize(emissions, toks)
        active = sched.active_indices
        if not active:
            # cold start / wake-up: nothing was admitted under a running
            # step, so admit + reset inline.
            if not self._admit():
                return False
            active = sched.active_indices

        # fill: only slots not prestaged during the previous tick
        with span("serving.fill"):
            buf = self._overlap.rotate()
            skip = self._overlap.prestaged
            for i in active:
                if i in skip:
                    continue
                s = sched.slots[i]
                buf.tokens[i, 0] = s.next_token()
                buf.positions[i] = s.pos
                if cfg.stochastic:
                    buf.seeds[i] = cfg.request_seed(
                        s.req.rid, len(s.req.generated))

        # Chunked prefill rides the overlap seam: prefilling slots are
        # parked — excluded from tick_advance (their positions move
        # through the chunk's own bookkeeping) and flagged to the cache
        # manager so carried-state families alias them to the NULL
        # state row for this decode step.
        if self._prefill_fn is not None:
            gen = [i for i in active
                   if sched.slots[i].pos >= sched.slots[i].req.n_prompt]
            parked = [i for i in active if i not in set(gen)]
        else:
            gen = active
            parked = None
        tick.set_metadata(n=len(active), gen=len(gen),
                          parked=len(parked or ()))

        toks_dev = self._dispatch(buf.tokens, buf.positions, buf.seeds,
                                  len(gen), parked=parked)

        # -- bookkeeping for the next tick, under the running step -----------
        # The chunk dispatch is queued behind the decode step (so the
        # device never idles).
        if self._prefill_fn is not None:
            pf = sched.prefill_queue()
            if pf:
                self._prefill_tick(pf[0])
        with span("serving.advance"):
            emissions = sched.tick_advance(gen)
        self._pending = (toks_dev, emissions)
        self._admit()                            # refills planned-free slots
        with span("serving.prestage"):
            self._overlap.prestage(sched, cfg)
        return True

    def run(self, *, max_ticks: int = 10_000) -> list:
        """Drain queue + slots; returns finished requests.

        Raises :class:`TickBudgetExceeded` when ``max_ticks`` expires
        with requests still queued or mid-flight — each survivor is
        marked ``truncated`` first, so the caller can distinguish
        partial completions from real finishes.  (The old behavior
        returned ``finished`` silently, leaving in-flight slots active
        and queued requests unreported.)
        """
        for _ in range(max_ticks):
            if not self.step() and not self.queue:
                break
        else:
            sched = self.scheduler
            if sched.has_work():
                survivors = [s.req for s in sched.slots if s.active]
                survivors += list(sched.queue)
                for r in survivors:
                    r.truncated = True
                raise TickBudgetExceeded(
                    f"run(max_ticks={max_ticks}) exhausted its tick "
                    f"budget with {len(survivors)} request(s) unfinished "
                    f"({sum(1 for s in sched.slots if s.active)} in "
                    f"flight, {len(sched.queue)} queued); survivors "
                    f"marked truncated", survivors)
        return self.finished
