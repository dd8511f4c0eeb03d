"""Serving engines: continuous batching over paged KV, plus baselines.

Three ways to serve the same model, in decreasing order of fidelity to the
production design and increasing order of simplicity:

  * :class:`ContinuousEngine` — the tentpole.  ``submit()`` enqueues,
    ``step()`` interleaves prefill of newly admitted requests with one
    batched decode step over all live rows (reading KV through per-request
    block tables into shared page pools), ``drain()`` runs to completion.
    Requests are admitted mid-flight as slots/budget free up; finished
    requests are evicted and their blocks recycled immediately.
  * :class:`StaticEngine` — the classic fixed-batch baseline: FCFS requests
    are grouped into equal-prompt-length batches, each batch prefills once
    and decodes in lockstep until the *longest* generation in the batch
    finishes (shorter rows keep burning decode steps — that waste is the
    point of the comparison).
  * :func:`run_sequential` — one request at a time through the reference
    ``model.prefill`` / ``model.decode_step`` path.  This is the semantic
    oracle: for greedy sampling both engines must reproduce its tokens
    bit-for-bit (tests/test_serve_engine.py), which is what lets later perf
    PRs rework the hot loop without fear.

Parity is engineered, not hoped for: the continuous engine prefills each
request at its exact prompt length through the *reference* prefill (then
scatters the cache into pages), decode rows never interact (per-row
attention, per-token norms), and the gathered paged view presents the same
positions mask as a contiguous cache of ``max_blocks * page`` slots.

Robustness layer (see repro.serve.lifecycle / faults / snapshot): every
request carries an explicit lifecycle state; admission can oversubscribe
the pool (``reserve="prompt"``), in which case mid-decode growth preempts
the lowest-priority live request instead of failing — pages are freed, the
prompt + generated prefix kept, and re-admission *re-prefills* prompt+prefix
so the resumed greedy stream is bit-identical to the uninterrupted one
(sampling keys are per-(request, step)).  Deadlines (``deadline_steps``),
``cancel(rid)``, bounded retries with exponential backoff, a no-progress
watchdog, deterministic fault injection, and crash-consistent snapshots
complete the failure story.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import NULL_RECORDER, EngineStats

from .cache import PagedKVCache, blocks_for_tokens, pack_prefill_pages
from .chunked import ChunkedPrefillState, chunk_cache_len, \
    mask_cache_rows, run_one_chunk, slice_cache
from .faults import FaultInjector, FaultSchedule
from .lifecycle import (CANCELLED, DECODING, EXPIRED, FAILED, FINISHED,
                        PREFILLING, QUEUED, TERMINAL_STATES,
                        EngineStallError, RequestError, transition)
from .prefix import PrefixIndex
from .sampling import SamplingParams, sample_token
from .scheduler import FCFSScheduler

__all__ = ["Request", "ServingEngine", "ContinuousEngine", "StaticEngine",
           "run_sequential", "make_engine"]


@dataclasses.dataclass(eq=False)   # identity equality: ndarray fields
class Request:
    rid: int
    prompt: np.ndarray               # (S,) or (S, n_codebooks) int32
    max_new_tokens: int
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    arrival_step: int = 0
    priority: int = 0                # higher = evicted later under pressure
    deadline_step: Optional[int] = None   # absolute engine-clock deadline
    # runtime state
    generated: list = dataclasses.field(default_factory=list)
    blocks: list = dataclasses.field(default_factory=list)
    n_shared: int = 0                # leading blocks[:n_shared] are shared
    cow_src: Optional[int] = None    # pinned copy-on-write source block
    slot: Optional[int] = None
    reserved_blocks: int = 0
    state: str = QUEUED              # lifecycle.py state machine
    not_before: int = 0              # re-admission backoff (engine clock)
    preemptions: int = 0             # pool-pressure evictions survived
    restarts: int = 0                # fault kills survived (prefix discarded)
    error: Optional[RequestError] = None   # set on FAILED / EXPIRED
    # host clock when the request last joined the queue (submit, or the
    # re-queue after a preemption): read at admission for queue_wait_s
    queued_at: float = dataclasses.field(default_factory=time.perf_counter)

    @property
    def prompt_len(self) -> int:
        return self.prompt.shape[0]

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens

    @property
    def input_pos(self) -> int:
        """Position of the next decode input (the last sampled token)."""
        return self.prompt_len + len(self.generated) - 1

    @property
    def prefill_len(self) -> int:
        """Tokens a (re-)prefill must feed: prompt plus any generated
        prefix a preemption preserved.  Equals ``prompt_len`` for fresh
        requests."""
        return self.prompt_len + len(self.generated)

    @property
    def prefill_tokens(self) -> np.ndarray:
        """(prefill_len[, n_cb]) prompt ++ generated prefix — the resume
        re-prefill input.  Feeding these through prefill puts the KV cache
        in exactly the state the uninterrupted run had after sampling
        ``len(generated)`` tokens, so the next sample (keyed per (request,
        step)) continues the stream bit-identically."""
        if not self.generated:
            return self.prompt
        gen = np.asarray(self.generated, np.int32).reshape(
            (len(self.generated),) + self.prompt.shape[1:]
        )
        return np.concatenate([self.prompt, gen], axis=0)

    @property
    def tokens(self) -> np.ndarray:
        return np.stack(self.generated) if self.generated else \
            np.zeros((0,), np.int32)


class ServingEngine:
    """submit()/step()/drain() surface shared by both engines."""

    kind = "base"

    def __init__(self, model, params, *, cache_dtype=jnp.float32,
                 recorder=None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.cache_dtype = cache_dtype
        self.requests: dict[int, Request] = {}
        self.finished: dict[int, Request] = {}
        self._next_rid = 0
        self._clock = 0                 # engine step clock (deadline basis)
        # observability: NULL_RECORDER (bare spans, every other hook a
        # no-op) unless the caller attaches a repro.obs.Recorder.
        # ``stats`` stays a real dict — EngineStats mirrors writes into the
        # recorder's metrics registry when one is attached and is a plain
        # dict otherwise.  Each span adds ``<phase>_s``/``<phase>_calls``.
        self._obs = recorder if recorder is not None else NULL_RECORDER
        self.stats = EngineStats(self._obs.registry, {
            "steps": 0, "prefill_calls": 0, "decode_steps": 0,
            "prompt_tokens": 0, "generated_tokens": 0, "wasted_row_steps": 0,
            # blocking device-to-host reads: logits fetches and samples
            "device_syncs": 0,
            # submit (or re-queue) to admission, summed over admissions
            "queue_wait_s": 0.0, "admissions": 0,
            # robustness counters (lifecycle / preemption / faults)
            "rejected": 0, "cancelled": 0, "expired": 0, "failed": 0,
            "finished": 0,
            "preemptions": 0, "fault_kills": 0, "resumed_prefills": 0,
            "fault_events": 0, "fault_paused_steps": 0,
            # MoE work dispatched, from static shapes (``_count_moe``)
            "moe_rows": 0, "moe_routed": 0,
        })

    # -- API -----------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               sampling: Optional[SamplingParams] = None,
               arrival_step: int = 0, *,
               deadline_steps: Optional[int] = None,
               priority: int = 0) -> int:
        """Enqueue a request; returns its rid.

        ``deadline_steps``: optional step budget — the request EXPIREs (and
        releases every page) once the engine clock passes
        ``max(clock, arrival_step) + deadline_steps``.  ``priority``:
        higher values are preempted later under pool pressure (ties break
        by youngest-first, see ``_pick_victim``).  Rejections raise
        :class:`RequestError` whose ``reason`` code distinguishes malformed
        arguments from budget/capacity impossibility.
        """
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim not in (1, 2) or prompt.shape[0] < 1:
            self.stats["rejected"] += 1
            raise RequestError("bad_prompt", f"prompt shape {prompt.shape}")
        if max_new_tokens < 1:
            self.stats["rejected"] += 1
            raise RequestError("bad_max_new_tokens",
                               f"max_new_tokens={max_new_tokens}")
        if deadline_steps is not None and deadline_steps < 1:
            self.stats["rejected"] += 1
            raise RequestError("bad_deadline",
                               f"deadline_steps={deadline_steps}")
        rid = self._next_rid
        deadline = None if deadline_steps is None else \
            max(self._clock, arrival_step) + deadline_steps
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      sampling=sampling or SamplingParams(),
                      arrival_step=arrival_step, priority=priority,
                      deadline_step=deadline)
        try:
            self._enqueue(req)
        except RequestError:
            self.stats["rejected"] += 1
            raise
        self._next_rid += 1
        self.requests[rid] = req
        self._obs.on_submit(req, self._clock)
        return rid

    def cancel(self, rid: int) -> bool:
        """Withdraw a live request: frees its pages/slot immediately and
        moves it to CANCELLED (its partial ``tokens`` stay readable).
        Returns False if the rid is unknown or already terminal."""
        req = self.requests.get(rid)
        if req is None or req.state in TERMINAL_STATES:
            return False
        self._terminate(req, CANCELLED)
        self.stats["cancelled"] += 1
        return True

    def step(self) -> list[Request]:
        raise NotImplementedError

    @property
    def idle(self) -> bool:
        raise NotImplementedError

    def drain(self, max_steps: int = 100_000) -> dict[int, np.ndarray]:
        """Run steps until every submitted request completed."""
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return {rid: r.tokens for rid, r in sorted(self.finished.items())}

    # -- shared helpers --------------------------------------------------------------
    def _enqueue(self, req: Request) -> None:
        raise NotImplementedError

    def _terminate(self, req: Request, state: str,
                   error: Optional[RequestError] = None) -> None:
        raise NotImplementedError

    def _next_input(self, req: Request) -> np.ndarray:
        """(1[, n_cb]) last sampled token, as the next decode input."""
        return np.asarray(req.generated[-1], np.int32).reshape(
            (1,) + req.prompt.shape[1:]
        )

    def _count_moe(self, tokens: int) -> None:
        """Count one program call over ``tokens`` token rows: the expert-
        buffer rows the model's MoE layers compute (``moe_rows``) and the
        routed (token, expert) pairs (``moe_routed``), as the model reports
        them (``serve_moe_rows``).  Shapes only, no sync."""
        rows, routed = self.model.serve_moe_rows(tokens)
        self.stats["moe_rows"] += rows
        self.stats["moe_routed"] += routed

    def _span(self, name: str):
        """A ``repro.obs`` span of this engine, counted in ``stats``."""
        return self._obs.span(name, self.stats)

    def _fetch(self, logits) -> np.ndarray:
        """Blocking read of a program's logits to the host."""
        with self._span("serve.fetch"):
            out = np.asarray(logits)
        self.stats["device_syncs"] += 1
        return out

    def _sample(self, req: Request, logits_row: np.ndarray) -> None:
        # greedy and stochastic sampling each round-trip through the device
        tok = sample_token(logits_row, req.sampling, request_salt=req.rid,
                           step=len(req.generated))
        self.stats["device_syncs"] += 1
        req.generated.append(tok)
        self.stats["generated_tokens"] += 1
        self._obs.on_token(req, self._clock)

    def _admit(self, req: Request) -> None:
        """QUEUED -> PREFILLING, counting the request's wait in the queue."""
        self._transition(req, PREFILLING)
        self.stats["queue_wait_s"] += time.perf_counter() - req.queued_at
        self.stats["admissions"] += 1

    def _mark_finished(self, req: Request) -> None:
        self.finished[req.rid] = req
        if req.state == FINISHED:
            self.stats["finished"] += 1

    def _transition(self, req: Request, to: str) -> None:
        """Lifecycle edge + span hook at the current engine clock."""
        transition(req, to, obs=self._obs, clock=self._clock)


class ContinuousEngine(ServingEngine):
    """Continuous batching with a paged KV cache.

    page_size:        tokens per cache block.
    max_slots:        decode-batch rows (concurrent requests).
    n_blocks:         physical pool blocks incl. the reserved trash block;
                      0 = enough for max_slots full-length requests.
    max_live_tokens:  admission budget over sum(prompt + max_new) of the
                      running set; 0 = bounded only by pool capacity.
    max_request_len:  longest admissible prompt + max_new (sets the block-
                      table width, a static shape of the decode step).
    prefill_chunk:    0 = single-shot prefill (reference path).  > 0 =
                      chunked prefill: admitted prompts are fed in fixed
                      ``prefill_chunk``-token pieces, at most ONE piece per
                      engine step, interleaved with the batched decode (see
                      repro.serve.chunked) — decode latency is bounded by
                      one chunk's work regardless of prompt length, and all
                      prompt lengths share one compiled chunk program.
    plan:             optional :class:`repro.sparsity.SparsityPlan` of the
                      served weights.  With a non-zero ``max_live_tokens``
                      the admission budget is grown by the weight HBM the
                      plan frees (``scheduler.plan_aware_live_tokens``):
                      sparser layers leave more room for KV pages, so
                      admission no longer assumes uniform dense weight
                      residency.  Pool capacity still caps admission.
    reserve:          admission block-reservation policy.  "worst_case"
                      (default) reserves ``blocks_for(prompt + max_new)``
                      so growth never fails; "prompt" reserves only the
                      prefill's blocks — the pool oversubscribes and
                      mid-decode growth preempts the lowest-priority live
                      request (bit-exact resume via re-prefill).
    max_retries:      preemptions + fault restarts a request survives
                      before it is FAILED (``retries_exhausted``).
    preempt_backoff:  base of the exponential re-admission backoff (steps).
    max_idle_steps:   watchdog fuse — consecutive no-progress steps with
                      work pending before ``EngineStallError`` (with live
                      rids / pool occupancy / queue diagnostics) is raised.
    faults:           optional :class:`FaultSchedule` (or prepared
                      :class:`FaultInjector`) applied at each step.
    prefix_cache:     enable prefix sharing (see repro.serve.prefix): a
                      radix index over finished prompts' full pages lets a
                      new request reuse every resident page its prompt
                      head matches — prefill recomputes only the suffix,
                      block tables mix shared (read-only) and private
                      blocks, the partial tail page is copied-on-write,
                      and cold cached prefixes are LRU-evicted under pool
                      pressure.  Greedy outputs are bit-identical with
                      sharing on or off (pinned in tests/
                      test_prefix_cache.py).  Default off: the index
                      intentionally keeps pages allocated after requests
                      finish, which changes pool-occupancy accounting
                      some callers assert on.
    """

    kind = "continuous"

    def __init__(self, model, params, *, page_size: int = 8,
                 max_slots: int = 8, n_blocks: int = 0,
                 max_live_tokens: int = 0, max_request_len: int = 0,
                 prefill_chunk: int = 0,
                 cache_dtype=jnp.float32, plan=None,
                 reserve: str = "worst_case", max_retries: int = 32,
                 preempt_backoff: int = 1, max_idle_steps: int = 1000,
                 faults=None, prefix_cache: bool = False, recorder=None):
        super().__init__(model, params, cache_dtype=cache_dtype,
                         recorder=recorder)
        self.page = page_size
        self.max_slots = max_slots
        self.max_request_len = max_request_len or self.cfg.max_seq_len
        self.max_blocks = blocks_for_tokens(self.max_request_len, page_size)
        if n_blocks <= 0:
            n_blocks = 1 + max_slots * self.max_blocks
        self.prefill_chunk = prefill_chunk
        if prefill_chunk > 0:
            self.chunk_cache = chunk_cache_len(
                self.max_request_len, page_size, prefill_chunk
            )
        self.max_retries = max_retries
        self.preempt_backoff = max(preempt_backoff, 0)
        self.max_idle_steps = max_idle_steps
        self._idle_streak = 0
        if isinstance(faults, FaultSchedule):
            faults = FaultInjector(faults)
        self._injector: Optional[FaultInjector] = faults
        self._prefilling: dict[int, ChunkedPrefillState] = {}
        self.step_trace: list[dict] = []
        # (clock, rid, "preempt"|"restart") — the deterministic eviction
        # trace the sharded tests compare across mesh shapes
        self.preempt_log: list[tuple[int, int, str]] = []
        self.kv = self._make_kv(n_blocks)
        self.base_live_tokens = max_live_tokens
        self.plan = plan
        self.plan_fingerprint = plan.fingerprint() if plan is not None \
            else None
        self.prefix = PrefixIndex(page_size) if prefix_cache else None
        # everything snapshot.restore_engine needs to rebuild this engine
        # (the radix index itself restores EMPTY — snapshots carry no KV
        # pages, so there is nothing resident to re-index; re-prefills
        # repopulate it)
        self._init_kw = dict(
            page_size=page_size, max_slots=max_slots, n_blocks=n_blocks,
            max_live_tokens=max_live_tokens,
            max_request_len=self.max_request_len,
            prefill_chunk=prefill_chunk, reserve=reserve,
            max_retries=max_retries, preempt_backoff=preempt_backoff,
            max_idle_steps=max_idle_steps, prefix_cache=prefix_cache,
        )
        if plan is not None and max_live_tokens > 0:
            from repro.sparsity import model_matmul_shapes

            from .scheduler import plan_aware_live_tokens

            # the freed bytes are *weight* residency: size them by the
            # served params' dtype, not the KV cache dtype
            wdt = next(
                (leaf.dtype for leaf in jax.tree_util.tree_leaves(params)
                 if jnp.issubdtype(leaf.dtype, jnp.floating)),
                jnp.dtype(jnp.float32),
            )
            max_live_tokens = plan_aware_live_tokens(
                max_live_tokens, plan=plan,
                shapes=model_matmul_shapes(self.cfg),
                kv_bytes_per_token=self.kv_bytes_per_token(),
                value_bytes=jnp.dtype(wdt).itemsize,
            )
        self.plan_live_tokens = max_live_tokens
        self.scheduler = FCFSScheduler(
            page_size=page_size, max_slots=max_slots,
            max_live_tokens=max_live_tokens,
            n_blocks_capacity=self.kv.allocator.n_total,
            reserve=reserve,
            prefix_probe=self._prefix_probe if prefix_cache else None,
            pinned_external=(self._prefix_pinned_external
                             if prefix_cache else None),
        )
        self.prefill_params = self.params
        self._jit_fns()
        self.stats.update(block_steps=0, allocated_block_steps=0,
                          live_token_steps=0, peak_allocated_blocks=0,
                          prefill_chunks=0, decode_row_steps=0,
                          prefix_hits=0, prefix_hit_tokens=0,
                          prefix_misses=0, prefix_evictions=0,
                          prefix_cow_copies=0, shared_prefills=0,
                          # serve.step time of the steps that fed a chunk
                          chunk_step_s=0.0, chunk_steps=0)

    # -- hooks the sharded engines override ------------------------------------------
    def _make_kv(self, n_blocks: int) -> PagedKVCache:
        return PagedKVCache(self.model, n_blocks, self.page, self.cache_dtype)

    def _jit_fns(self) -> None:
        # jitted programs are cached on the model object so many engines
        # over the same model (the fault soak builds dozens) share compiles
        cache = getattr(self.model, "_serve_jit", None)
        if cache is None:
            cache = {}
            self.model._serve_jit = cache
        fns = cache.get("continuous")
        if fns is None:
            fns = (
                jax.jit(self.model.prefill),
                jax.jit(self.model.decode_step_paged, donate_argnums=(2,)),
                jax.jit(self.model.prefill_chunk, donate_argnums=(2,)),
            )
            cache["continuous"] = fns
        self._prefill, self._decode, self._chunk = fns

    def _handoff(self, paged):
        """Identity in the single-role engines; the disaggregated engine
        overrides this with the cross-mesh ``device_put`` KV-page handoff."""
        return paged

    def _localize(self, cache):
        """Identity in the single-role engines; the disaggregated engine
        overrides this to move a prefix gather (read from the decode-role
        pools) onto the prefill role before the suffix chunk runs."""
        return cache

    # -- prefix sharing ----------------------------------------------------------------
    def _release_blocks(self, blocks: list) -> None:
        """Drop this engine's reference on ``blocks``; blocks whose last
        reader left go back to the free list with their position marks
        reset.  Blocks other readers (the index, sharing requests) still
        hold keep their data — the refcounted replacement for the old
        unconditional reset + free."""
        freed = self.kv.allocator.release(blocks)
        self.kv.reset_blocks(freed)

    def _release_request_blocks(self, req: Request) -> None:
        """Release everything ``req`` holds: its block list (shared prefix
        + private pages) and, mid-prefill, its pinned COW source."""
        if req.cow_src is not None:
            self._release_blocks([req.cow_src])
            req.cow_src = None
        if req.blocks:
            self._release_blocks(req.blocks)
            req.blocks = []
        req.n_shared = 0

    def _prefix_probe(self, req: Request) -> tuple:
        """Scheduler admission probe: (reservation discount, new pins).

        The discount counts only the read-only shared blocks (the COW
        source still costs a private block, so it never discounts).
        ``new_pins`` is the *set* of matched block ids currently held by
        the index alone — claiming stops them being evictable, so
        admission must charge them against pool capacity; the scheduler
        accumulates the sets across one admit pass so two same-batch
        requests pinning disjoint prefixes are charged jointly (their
        claims land only after admit returns, so refcounts alone cannot
        see the earlier admittee's pins).  Read-only: ``plan(…, None)``
        does no LRU stamping, and no refcounting happens here (the claim
        after admission does both).
        """
        plan = self.prefix.plan(req.prefill_tokens, None)
        matched = set(plan.blocks)
        if plan.cow_src is not None:
            matched.add(plan.cow_src)
        alloc = self.kv.allocator
        new_pins = frozenset(b for b in matched if alloc.refcount(b) == 1)
        return len(plan.blocks), new_pins

    def _prefix_pinned_external(self) -> int:
        """Index blocks with live readers that no running request's
        private reservation covers.  The scheduler charges these against
        capacity so worst-case reservations keep the 'lazy allocation
        never fails' guarantee with sharing on: every other allocated
        block is either inside some reservation or evictable on demand.
        O(index + running blocks); the scheduler calls it once per admit
        pass — refcounts and private spans only change after admit
        returns (claims, prefills), so the count is invariant within one
        pass and need not be recomputed per candidate."""
        priv: set = set()
        for r in self.scheduler.running.values():
            priv.update(r.blocks[r.n_shared:])
        alloc = self.kv.allocator
        return sum(1 for b in self.prefix.blocks()
                   if alloc.refcount(b) > 1 and b not in priv)

    def _claim_prefix(self, req: Request) -> None:
        """Pin the request's resident prefix right after admission.

        Every matched block takes an extra allocator reference before any
        prefill (and with it any eviction pressure) runs this step, so
        LRU eviction (refcount == 1 only) and quarantine (free blocks
        only) can never touch a page this request is about to read.  The
        claim matches at least what the admission probe saw: between the
        two, nothing evicts — inserts can only add nodes.
        """
        plan = self.prefix.plan(req.prefill_tokens, self._clock)
        if plan.hit_pages == 0:
            self.stats["prefix_misses"] += 1
            return
        alloc = self.kv.allocator
        alloc.share(plan.blocks)
        req.blocks = list(plan.blocks)
        req.n_shared = len(plan.blocks)
        if plan.cow_src is not None:
            alloc.share([plan.cow_src])
            req.cow_src = plan.cow_src
        self.stats["prefix_hits"] += plan.hit_pages
        self.stats["prefix_hit_tokens"] += plan.hit_tokens
        # per-request prefill discount: the span aggregation sums these,
        # and the counter audit cross-checks them against the stats totals
        self._obs.annotate(req.rid, prefix_hit_tokens=plan.hit_tokens,
                           prefix_hit_pages=plan.hit_pages)

    def _insert_prefix(self, req: Request) -> None:
        """Index the request's full *prompt* pages after its prefill
        scatter.  Never the partial tail page and never generated pages —
        decode writes land at positions >= prefill_len, which is beyond
        every indexed page, so indexed pages are write-free for life.
        Pages already indexed keep the original block (the request's
        duplicate stays private and recycles normally)."""
        new = self.prefix.insert(req.prefill_tokens, req.blocks,
                                 req.prompt_len, self._clock)
        if new:
            self.kv.allocator.share(new)

    def _gather_prefix(self, req: Request, cache):
        """Fill the temp prefill cache from the claimed blocks (shared
        pages + the pinned COW source), then drop the COW pin — from here
        the request only ever writes private blocks, so a shared page can
        never be mutated by construction.  Returns (cache, suffix_start,
        span) — ``span`` is the gathered slot count, the end of the window
        the caller must re-mask before re-feeding slots below it
        (:func:`mask_cache_rows`).
        """
        if req.cow_src is not None:
            suffix_start = req.prefill_len - 1
            gather = req.blocks[:req.n_shared] + [req.cow_src]
        else:
            suffix_start = req.n_shared * self.page
            gather = req.blocks[:req.n_shared]
        span = len(gather) * self.page
        cache = self._localize(self.kv.read_pages(cache, gather))
        if req.cow_src is not None:
            self._release_blocks([req.cow_src])
            req.cow_src = None
            self.stats["prefix_cow_copies"] += 1
            self._obs.instant("prefix_cow", rid=req.rid, step=self._clock)
        self.stats["shared_prefills"] += 1
        return cache, suffix_start, span

    @property
    def gather_tokens(self) -> int:
        """KV slots a decode row attends over (block-table width x page)."""
        return self.max_blocks * self.page

    def snapshot(self, path: str) -> dict:
        """Crash-consistent snapshot (see serve.snapshot): host state only,
        atomic write; call between steps.  Restore with
        ``serve.snapshot.restore_engine`` finishes in-flight requests with
        byte-identical outputs."""
        from .snapshot import save_engine

        return save_engine(self, path)

    def kv_bytes_per_token(self) -> float:
        """Cache footprint of one token across every layer's page pools."""
        total = sum(leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree_util.tree_leaves(self.kv.pools))
        return total / max(self.kv.allocator.n_total * self.page, 1)

    @property
    def idle(self) -> bool:
        return self.scheduler.idle

    def _enqueue(self, req: Request) -> None:
        if req.prompt_len + req.max_new_tokens > self.max_request_len:
            raise RequestError(
                "too_long",
                f"request {req.rid}: prompt {req.prompt_len} + max_new "
                f"{req.max_new_tokens} exceeds max_request_len="
                f"{self.max_request_len}",
                rid=req.rid,
            )
        self.scheduler.submit(req)

    # -- steps -----------------------------------------------------------------------
    def step(self) -> list[Request]:
        """One engine tick: faults, expiry, admit+prefill, batched decode.

        Spans (``repro.obs.span``): ``serve.step`` around the tick and,
        inside it, the phases ``serve.admit``, ``serve.prefill_full`` (single-
        shot prefill), ``serve.prefill_chunk``, ``serve.pages`` (the page
        moves), ``serve.decode``, ``serve.fetch``, ``serve.sample`` and
        ``serve.finish``.  Phases never nest, so their ``<phase>_s``
        counters sum to ``step_s``; page resets of a request preempted or
        expired during admission or decode growth count in that phase.
        """
        finished: list[Request] = []
        step_span = self._span("serve.step")
        with step_span:
            with self._span("serve.admit"):
                paused = False
                if self._injector is not None:
                    paused = self._injector.begin_step(self, self._clock)
                self._expire(finished)
                batch = [] if paused else self.scheduler.admit(self._clock)
                for req in batch:
                    # claim the whole batch BEFORE any prefill runs: pinned
                    # prefix blocks can't be evicted by an earlier admittee's
                    # allocation pressure, so every claim matches at least
                    # what the admission probe reserved against
                    self._admit(req)
                    if self.prefix is not None:
                        self._claim_prefix(req)
                if self.prefill_chunk > 0:
                    for req in batch:
                        # slot None: preempted by an earlier admittee
                        if req.slot is not None:
                            self._begin_chunked(req)
            chunks = decoded = 0
            if not paused:
                if self.prefill_chunk <= 0:
                    for req in batch:
                        if req.slot is not None:
                            self._prefill_request(req, finished)
                chunks = self._run_prefill_chunk(finished)
                decoded = self._decode_batch(finished)
            with self._span("serve.finish"):
                self._end_step(len(batch), chunks, decoded, finished, paused)
        if chunks:
            self.stats["chunk_step_s"] += step_span.elapsed
            self.stats["chunk_steps"] += 1
        return finished

    def _end_step(self, admitted: int, chunks: int, decoded: int,
                  finished: list[Request], paused: bool) -> None:
        """Step bookkeeping: trace row, pool counters, gauges, watchdog."""
        self.step_trace.append({"admitted": admitted,
                                "prefill_chunks": chunks,
                                "decode_rows": decoded})
        self.stats["steps"] += 1
        na = self.kv.allocator.n_allocated
        self.stats["allocated_block_steps"] += na
        self.stats["block_steps"] += self.kv.allocator.n_total
        self.stats["live_token_steps"] += sum(
            r.input_pos + 1 for r in self.scheduler.running.values()
        )
        self.stats["peak_allocated_blocks"] = max(
            self.stats["peak_allocated_blocks"], na
        )
        if self._obs.enabled:
            reg = self._obs.registry
            for k, v in self.scheduler.occupancy().items():
                reg.gauge(f"sched_{k}").set(v)
            reg.gauge("pool_allocated_blocks").set(na)
        self._watchdog(admitted + chunks + decoded + len(finished), paused)
        self._clock += 1

    # -- lifecycle: expiry / cancellation / preemption ---------------------------------
    def _terminate(self, req: Request, state: str,
                   error: Optional[RequestError] = None) -> None:
        """Move a live request to a terminal state, releasing everything."""
        self._prefilling.pop(req.rid, None)
        self._release_request_blocks(req)
        if req.slot is not None:
            self.scheduler.finish(req)
        else:
            self.scheduler.remove(req)
        self._transition(req, state)
        req.error = error
        self._mark_finished(req)

    def _expire(self, finished: list[Request]) -> None:
        for req in list(self.requests.values()):
            if (req.state not in TERMINAL_STATES
                    and req.deadline_step is not None
                    and self._clock >= req.deadline_step):
                self._terminate(req, EXPIRED, RequestError(
                    "deadline",
                    f"request {req.rid} missed deadline_step="
                    f"{req.deadline_step} at engine clock {self._clock}",
                    rid=req.rid,
                ))
                self.stats["expired"] += 1
                finished.append(req)

    def _pick_victim(self) -> Optional[Request]:
        """Deterministic preemption order: lowest priority first, then
        youngest arrival, then highest rid.  Host-side state only, so the
        choice is identical across mesh shapes (the sharded engines
        inherit this verbatim — the PR-6 determinism carry-over)."""
        live = list(self.scheduler.running.values())
        if not live:
            return None
        return min(live, key=lambda r: (r.priority, -r.arrival_step, -r.rid))

    def _preempt(self, req: Request, restart: bool = False) -> None:
        """Evict a live request: free its pages, keep prompt (+ generated
        prefix unless ``restart``), re-queue with exponential backoff.
        Exhausting ``max_retries`` moves it to FAILED instead."""
        self._prefilling.pop(req.rid, None)
        self._release_request_blocks(req)
        self.scheduler.finish(req)
        self.preempt_log.append(
            (self._clock, req.rid, "restart" if restart else "preempt")
        )
        self._obs.instant("restart" if restart else "preempt",
                          rid=req.rid, step=self._clock,
                          generated=len(req.generated))
        if restart:
            # fault kill: the generated prefix is lost with the "crash";
            # per-(request, step) sampling keys regenerate it identically
            req.generated = []
            req.restarts += 1
            self.stats["fault_kills"] += 1
        else:
            req.preemptions += 1
            self.stats["preemptions"] += 1
        retries = req.preemptions + req.restarts
        if retries > self.max_retries:
            self._transition(req, FAILED)
            req.error = RequestError(
                "retries_exhausted",
                f"request {req.rid} exceeded max_retries={self.max_retries} "
                f"({req.preemptions} preemptions, {req.restarts} fault "
                f"restarts)",
                rid=req.rid,
            )
            self.stats["failed"] += 1
            self._mark_finished(req)
            return
        self._transition(req, QUEUED)
        req.queued_at = time.perf_counter()
        req.not_before = self._clock + 1 + \
            self.preempt_backoff * (2 ** min(retries - 1, 6))
        self.scheduler.requeue(req)

    def _fault_kill(self, idx: int) -> None:
        """Injected crash of one live request (victim = sorted live rids
        indexed mod n — deterministic for a given schedule + workload)."""
        rids = sorted(r.rid for r in self.scheduler.running.values())
        if not rids:
            return
        self._preempt(self.requests[rids[idx % len(rids)]], restart=True)

    def _ensure_blocks(self, req: Request, n_new: int) -> Optional[list]:
        """Allocate ``n_new`` blocks for ``req``, preempting under pressure.

        Evicts ``_pick_victim()`` (which may be ``req`` itself) until the
        allocation fits.  Returns the blocks, or None if ``req`` was the
        victim (caller must drop the request's work for this step).  While
        an injected ``alloc_fail`` fault is armed, every allocation is a
        transient failure — ``req`` is preempted and retried after backoff.
        """
        if n_new <= 0:
            return []
        if (self._injector is not None
                and not self._injector.alloc_allowed(self._clock)):
            self._preempt(req)
            return None
        alloc = self.kv.allocator
        while not alloc.can_alloc(n_new):
            # cold cached prefixes go first: LRU-evict index blocks no
            # request is reading before preempting any live request; the
            # whole deficit goes in one tree scan (evict_lru) so sustained
            # pressure costs O(index) per event, not per evicted block
            if self.prefix is not None:
                blks = self.prefix.evict_lru(
                    lambda b: alloc.refcount(b) == 1,
                    n_new - alloc.n_free)
                if blks:
                    self._release_blocks(blks)
                    self.stats["prefix_evictions"] += len(blks)
                    continue
            victim = self._pick_victim()
            if victim is None:
                self._preempt(req)
                return None
            self._preempt(victim)
            if victim is req:
                return None
        return alloc.alloc(n_new)

    def _watchdog(self, progress: int, paused: bool) -> None:
        """Raise EngineStallError after ``max_idle_steps`` consecutive
        no-progress steps with work pending.  Injected pauses and pure
        backoff waits (nothing running, every waiting request's
        ``not_before`` in the future) are benign and reset the streak."""
        if progress > 0 or paused or self.idle:
            self._idle_streak = 0
            return
        waiting = list(self.scheduler.waiting)
        if (not self.scheduler.running and waiting and all(
                getattr(r, "not_before", 0) > self._clock for r in waiting)):
            self._idle_streak = 0
            return
        self._idle_streak += 1
        if self._idle_streak < self.max_idle_steps:
            return
        alloc = self.kv.allocator
        diag = {
            "clock": self._clock,
            "live": {r.rid: r.state
                     for r in self.scheduler.running.values()},
            "waiting": [(r.rid, getattr(r, "not_before", 0))
                        for r in waiting],
            "pool": {"n_free": alloc.n_free, "n_allocated": alloc.n_allocated,
                     "n_quarantined": alloc.n_quarantined,
                     "n_total": alloc.n_total},
            "budget": self.scheduler.occupancy(),
        }
        raise EngineStallError(
            f"engine made no progress for {self._idle_streak} consecutive "
            f"steps with work pending ({len(diag['live'])} running, "
            f"{len(waiting)} waiting; pool {alloc.n_free} free / "
            f"{alloc.n_quarantined} quarantined of {alloc.n_total}); "
            f"diagnostics attached",
            diag,
        )

    def _prefill_request(self, req: Request,
                         finished: list[Request]) -> None:
        """Reference prefill at the exact prefill length, then page it.

        For a fresh request that is the prompt; for a preempted one it is
        prompt ++ generated prefix (the bit-exact resume path — the next
        ``_sample`` call is keyed at ``step=len(generated)``, exactly the
        step the uninterrupted run would be at).

        With a claimed prefix the matched pages are gathered into the
        temp cache instead of recomputed, and only the suffix runs
        (through the chunk program, whose parity vs single-shot prefill
        is already pinned); the scatter then covers only the privately
        written page span."""
        L = req.prefill_len
        nb = self.kv.blocks_for(L)
        shared = req.n_shared > 0 or req.cow_src is not None
        with self._span("serve.admit"):
            got = self._ensure_blocks(req, nb - req.n_shared)
            if got is not None:
                req.blocks = req.blocks + got
                cache = self.model.init_cache(
                    1, nb * self.page if shared else L, self.cache_dtype,
                    full_length=True)
                if shared:
                    cache, start, span = self._gather_prefix(req, cache)
                    cache = mask_cache_rows(cache, start, span)
        if got is None:
            return   # req itself was preempted under pool pressure
        with self._span("serve.prefill_full"):
            if shared:
                suffix = np.asarray(req.prefill_tokens)[start:]
                fed = L - start
                logits, cache = self._chunk(
                    self.prefill_params, {"tokens": jnp.asarray(suffix[None])},
                    cache, jnp.int32(start), jnp.int32(fed),
                )
            else:
                fed = L
                logits, cache = self._prefill(
                    self.prefill_params,
                    {"tokens": jnp.asarray(req.prefill_tokens[None])},
                    cache
                )
            self._count_moe(fed)
        if req.generated:
            self.stats["resumed_prefills"] += 1
        self.stats["prompt_tokens"] += fed
        self._land_prefill(req, cache, logits, finished)

    # -- chunked prefill ---------------------------------------------------------------
    def _begin_chunked(self, req: Request) -> None:
        """Allocate the request's prefill blocks and queue its chunked
        prefill.  Its temp prefill cache is made when its first chunk runs
        (``_start_chunked``): chunks run one request at a time, oldest
        first, so at most one temp cache is live however many admitted
        requests wait for their first chunk."""
        nb = self.kv.blocks_for(req.prefill_len)
        got = self._ensure_blocks(req, nb - req.n_shared)
        if got is None:
            return   # req itself was preempted under pool pressure
        req.blocks = req.blocks + got
        self._prefilling[req.rid] = ChunkedPrefillState(
            req=req, cache=None, chunk=self.prefill_chunk,
            tokens=req.prefill_tokens,
        )
        if req.generated:
            self.stats["resumed_prefills"] += 1

    def _start_chunked(self, state: ChunkedPrefillState) -> None:
        """Make the temp prefill cache of a request about to feed its first
        chunk, filled from its claimed prefix pages if it has any.

        The temp cache has the ONE shared ``chunk_cache`` length for every
        request, so all prompts reuse a single compiled chunk program.
        Resumed requests chunk prompt ++ generated prefix (never longer
        than ``max_request_len``, so the shared cache always fits).
        """
        req = state.req
        cache = self.model.init_cache(1, self.chunk_cache, self.cache_dtype,
                                      full_length=True)
        if req.n_shared > 0 or req.cow_src is not None:
            cache, start, span = self._gather_prefix(req, cache)
            # chunk starts must stay multiples of ``prefill_chunk`` (the
            # chunk_cache_len clamp-guard argument assumes it), so round
            # the resume point down: the re-fed rows recompute over the
            # gathered prefix and land bit-identical, and only the
            # private page span is scattered at the end anyway
            state.pos = start - start % self.prefill_chunk
            cache = mask_cache_rows(cache, state.pos, span)
        state.cache = cache

    def _run_prefill_chunk(self, finished: list[Request]) -> int:
        """Feed at most ONE chunk (of the oldest in-flight prefill) per
        step — the bound the step-trace test asserts.  After the final
        chunk the request's prefill lands (``_land_prefill``)."""
        if not self._prefilling:
            return 0
        rid = next(iter(self._prefilling))   # dict preserves FCFS order
        state = self._prefilling[rid]
        with self._span("serve.prefill_chunk"):
            if state.cache is None:
                self._start_chunked(state)
            fed = run_one_chunk(state, self.prefill_params, self._chunk)
            self._count_moe(state.chunk)
            self.stats["prefill_chunks"] += 1
            self.stats["prompt_tokens"] += fed
        if state.done:
            del self._prefilling[rid]
            self._land_prefill(state.req, state.cache, state.logits, finished)
        return 1

    def _land_prefill(self, req: Request, cache, logits,
                      finished: list[Request]) -> None:
        """Finish a prefill: read its last-row logits, trim the temp cache
        to the request's private block span and scatter it into the page
        pools, index the prompt pages, sample the first token."""
        logits = self._fetch(logits)
        nb = len(req.blocks)
        n_sh = req.n_shared
        with self._span("serve.pages"):
            self.kv.write_pages(
                self._handoff(pack_prefill_pages(
                    slice_cache(cache, n_sh * self.page, nb * self.page),
                    nb - n_sh, self.page
                )),
                req.blocks[n_sh:],
            )
            if self.prefix is not None:
                self._insert_prefix(req)
        with self._span("serve.sample"):
            self._sample(req, logits[0])
        with self._span("serve.finish"):
            self._transition(req, DECODING)
            self.stats["prefill_calls"] += 1
        if req.done:
            self._finish(req, finished)

    def _decode_batch(self, finished: list[Request]) -> int:
        with self._span("serve.decode"):
            logits, active = self._dispatch_decode()
        if not active:
            return 0
        logits = self._fetch(logits)
        with self._span("serve.sample"):
            for r in active:
                self._sample(r, logits[r.slot])
        for r in active:
            if r.done:
                self._finish(r, finished)
        return len(active)

    def _dispatch_decode(self):
        """Grow blocks, build the batch and dispatch one paged decode step.
        Returns (device logits, rows) — (None, []) with no row to decode."""
        # sorted by rid: deterministic row layout whatever the admission
        # interleaving was (cross-role reproducibility for disaggregation);
        # rows still mid-prefill have no sampled token yet and are skipped
        active = sorted(
            (r for r in self.scheduler.running.values()
             if not r.done and r.rid not in self._prefilling),
            key=lambda r: r.rid,
        )
        for r in active:
            if r.slot is None:
                continue   # preempted while growing an earlier row
            need = self.kv.blocks_for(r.input_pos + 1)
            if need > len(r.blocks):
                got = self._ensure_blocks(r, need - len(r.blocks))
                if got is None:
                    continue   # r itself was the preemption victim
                r.blocks += got
                self.scheduler.grow(r, len(got))
        # growth may have evicted rows (theirs or later ones): re-filter
        active = [r for r in active if r.slot is not None]
        if not active:
            return None, []
        B = self.max_slots
        tok_shape = (B, 1) + active[0].prompt.shape[1:]
        tokens = np.zeros(tok_shape, np.int32)
        positions = np.zeros((B,), np.int32)
        bt_rows: list[Optional[list[int]]] = [None] * B
        for r in active:
            tokens[r.slot, 0] = r.generated[-1]
            positions[r.slot] = r.input_pos
            bt_rows[r.slot] = r.blocks
        bt = self.kv.block_table(bt_rows, self.max_blocks)
        logits, self.kv.pools = self._decode(
            self.params, jnp.asarray(tokens), self.kv.pools,
            jnp.asarray(bt), jnp.asarray(positions),
        )
        self._count_moe(B)
        self.stats["decode_steps"] += 1
        self.stats["decode_row_steps"] += len(active)
        return logits, active

    def _finish(self, req: Request, finished: list[Request]) -> None:
        """Evict: release every block the request held (pages the index
        or another reader still references stay resident)."""
        with self._span("serve.pages"):
            self._release_request_blocks(req)
        with self._span("serve.finish"):
            self.scheduler.finish(req)
            self._transition(req, FINISHED)
            self._mark_finished(req)
            finished.append(req)


class StaticEngine(ServingEngine):
    """Fixed-batch baseline: equal-prompt-length groups, lockstep decode."""

    kind = "static"

    def __init__(self, model, params, *, batch: int = 4,
                 cache_dtype=jnp.float32, recorder=None):
        super().__init__(model, params, cache_dtype=cache_dtype,
                         recorder=recorder)
        self.batch = batch
        self._queue: list[Request] = []
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step, donate_argnums=(2,))
        self.stats.update(cache_slot_steps=0, live_token_steps=0)

    @property
    def idle(self) -> bool:
        return not self._queue

    def _enqueue(self, req: Request) -> None:
        self._queue.append(req)

    def _terminate(self, req: Request, state: str,
                   error: Optional[RequestError] = None) -> None:
        """Static batches run to completion inside one step(), so only
        still-queued requests can be cancelled/expired here."""
        if req in self._queue:
            self._queue.remove(req)
        self._transition(req, state)
        req.error = error
        self._mark_finished(req)

    def step(self) -> list[Request]:
        """Serve one batch to completion (the static-batching granularity).

        The head of the FCFS queue picks the batch; the rest of the batch
        is the next ``batch - 1`` requests with the *same prompt length*
        (classic bucketed static batching — ragged prompts cannot share a
        lockstep prefill without cache-corrupting padding).
        """
        if not self._queue:
            return []
        S = self._queue[0].prompt_len
        group = [r for r in self._queue if r.prompt_len == S][: self.batch]
        self._queue = [r for r in self._queue if r not in group]
        B = len(group)
        max_gen = max(r.max_new_tokens for r in group)
        cache = self.model.init_cache(B, S + max_gen, self.cache_dtype)
        prompts = np.stack([r.prompt for r in group])
        for r in group:
            self._admit(r)
        with self._span("serve.prefill_full"):
            logits, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(prompts)}, cache
            )
        logits = self._fetch(logits)
        with self._span("serve.sample"):
            for i, r in enumerate(group):
                self._sample(r, logits[i])
        for r in group:
            self._transition(r, DECODING)
        self.stats["prefill_calls"] += 1
        self.stats["prompt_tokens"] += B * S
        for step_i in range(1, max_gen):
            nxt = np.stack([self._next_input(r) for r in group])
            with self._span("serve.decode"):
                logits, cache = self._decode(
                    self.params, jnp.asarray(nxt), cache,
                    jnp.int32(S + step_i - 1),
                )
            logits = self._fetch(logits)
            self.stats["decode_steps"] += 1
            self.stats["cache_slot_steps"] += B * (S + max_gen)
            self.stats["live_token_steps"] += sum(
                min(r.input_pos + 1, r.prompt_len + r.max_new_tokens)
                for r in group
            )
            with self._span("serve.sample"):
                for i, r in enumerate(group):
                    if r.done:
                        # lockstep: the row keeps burning the step anyway
                        self.stats["wasted_row_steps"] += 1
                    else:
                        self._sample(r, logits[i])
        for r in group:
            self._transition(r, FINISHED)
            self._mark_finished(r)
        self.stats["steps"] += 1
        self._clock += 1
        return group


def run_sequential(model, params, requests, *, cache_len=None,
                   cache_dtype=jnp.float32) -> dict[int, np.ndarray]:
    """Reference path: one request at a time, contiguous cache, B = 1.

    ``requests``: iterable of dicts {"prompt", "max_new_tokens",
    optional "sampling", "rid"} (the format ``RequestStream.requests()``
    emits).  ``cache_len``: cache slots per request (default
    prompt + max_new); the parity tests pass the engine's
    ``gather_tokens`` so both paths reduce attention over identical
    masked lengths.
    """
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    out: dict[int, np.ndarray] = {}
    for i, req in enumerate(requests):
        prompt = np.asarray(req["prompt"], np.int32)
        S = prompt.shape[0]
        gen = req["max_new_tokens"]
        sp = req.get("sampling") or SamplingParams()
        rid = req.get("rid", i)
        C = cache_len or (S + gen)
        cache = model.init_cache(1, C, cache_dtype)
        logits, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                cache)
        toks = [sample_token(np.asarray(logits)[0], sp, request_salt=rid,
                             step=0)]
        for step_i in range(1, gen):
            nxt = np.asarray(toks[-1], np.int32).reshape(
                (1, 1) + prompt.shape[1:]
            )
            logits, cache = decode(params, jnp.asarray(nxt), cache,
                                   jnp.int32(S + step_i - 1))
            toks.append(sample_token(np.asarray(logits)[0], sp,
                                     request_salt=rid, step=step_i))
        out[rid] = np.stack(toks)
    return out


def make_engine(kind: str, model, params, **kw) -> ServingEngine:
    if kind == "continuous":
        return ContinuousEngine(model, params, **kw)
    if kind == "static":
        return StaticEngine(model, params, **kw)
    if kind in ("sharded", "disagg"):
        from .distributed import DisaggregatedEngine, ShardedContinuousEngine

        cls = ShardedContinuousEngine if kind == "sharded" \
            else DisaggregatedEngine
        return cls(model, params, **kw)
    raise ValueError(
        f"unknown engine kind {kind!r}; have continuous|static|sharded|disagg"
    )
