"""Serving engine: continuous batching with per-slot positions.

Requests enter a queue; every ``step()`` the engine (1) admits queued
requests into any free cache slot (honouring ``admit_cap`` — the actuation
knob a ``Throttle`` action programs), and (2) advances ALL active slots with
ONE fused jitted step: chunked-prefill extends for slots still consuming
their prompt, single-token decode for slots mid-generation, sampling fused
on-device (one host sync per tick).  There is no global decode position and
no admission barrier — each slot runs at its own ``pos`` (the ragged
``pos``/``n_valid`` contract of ``Model.decode``), so a request admitted
while others are mid-decode produces outputs identical to running alone.

Cache state lives in :class:`~repro.serve.cache.KVCacheManager` (or, with
``paged=True``, :class:`~repro.serve.cache.PagedKVCacheManager` — free-list
pages behind per-slot block tables): per-slot positions, page accounting,
slot recycling (freed rows/pages are invalidated via ``pos_ids = -1`` and
reused without growing the arrays).  On the paged path, when every cache
of the stack holds full-length attention entries (GQA K/V or MLA latents,
dense prefix blocks included: ``Model.decodes_in_pool``), the entries never
leave the page pool: the fused step hands the donated pool and the block
tables to ``Model.decode_paged``, whose layers each write the tick's new
entries into their pages and read their own pages through the tables.
Ring caches of sliding-window models gather a slot-contiguous logical cache
through the block tables, run ``Model.decode`` and scatter pages back.
Either way it is one jit with outputs bitwise identical to the
contiguous manager, and admission/extension run at page granularity off
the actual free list, so churn that would fragment contiguous rows costs
nothing.

``speculate=k`` adds draft-k self-speculative decode (greedy only):
n-gram prompt-lookup drafts ride the same ragged ``pos``/``n_valid``
contract as an ``S = k+1`` extend, one fused verify step scores every draft
row, and the accepted prefix (+ the bonus token) is bitwise what sequential
greedy would have produced; the rejected tail's pages roll back through
the allocator (``trim``).  Stale rejected entries are self-healing: their
``pos_ids`` exceed every later query position until the sequential path
overwrites them (chunk K/V is written before attention).

Two scheduling paths, picked by model family:

- **ragged** (attention-only stacks, no sliding window): prompts stream
  through the fused step in ``prefill_chunk``-token extends — admission is
  pure bookkeeping (no model call, no compile), and the fused step compiles
  exactly twice (S in {1, chunk}).
- **stateful** (SSM/hybrid and window-clamped ring caches): recurrent state
  would be polluted by padded prompt tokens, so admission runs an
  exact-length prefill per request and scatters the row; decode then joins
  the same fused step.

``overlap=True`` (the in-pool path, without speculation) runs one tick
ahead of the host: each ``step()`` launches tick n, then syncs and commits
tick n-1, whose sampled tokens feed tick n's decode rows on the device.
Composing, uploading and dispatching a tick then happen while the device
still runs the one before, so they drop out of the gap between tokens.  A
request's tokens land one step after its tick is launched; the host cannot
see an EOS before the next tick is launched, so a slot whose request ended
on EOS runs one tick more, whose token is dropped.  Preemption and
``drain`` land the tick in flight first.

Control-plane hooks (repro.control, DESIGN.md §3): EVERY ``step()`` emits a
``TickSample`` — including admit-only and fully-throttled iterations, so
queue-depth bursts are visible exactly when ``Throttle`` decisions matter.

Tracing: each tick's host phases are profiler spans
(``jax.profiler.TraceAnnotation``, nearly free while no trace is taken),
so they land on the same clock as the device's operations:
``serve.engine.admit``, ``.compose`` (plan, page reservation, preemption),
``.upload`` (key split, tick arrays, block table; stat ``bt_sent``),
``.dispatch`` (the fused call; stats ``width``, ``prefill``, ``decode``,
``prompt_tokens``, ``kv_pool``: 1 on the in-pool path), ``.sync`` (the
host copy of the output), ``.commit`` (advance and append; on the
in-pool path of an expert model, stats ``routed_local`` and
``experts_touched``, the tick's expert counters, also totalled on
``Engine``) and
``.release`` (pages returned, stat ``pages``; nested in ``commit``, or in
``compose`` when preemption frees a slot).  Inside the fused step,
``jax.named_scope`` marks ``decode`` (with ``kv_write`` and ``kv_read`` in
each layer on the in-pool path, and ``moe_route``, ``moe_experts`` and
``moe_shared`` in each expert layer), ``sample``, and on the gather path
``kv_gather`` and ``kv_scatter``, in every device op's metadata.
Each :class:`Request` carries ``perf_counter`` stamps of its submission,
first admission, first token and completion.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.control.telemetry import TickSample
from repro.models.model import Model
from repro.serve import scheduler as sched
from repro.serve.cache import (ExpandableKVCacheManager,
                               ExpandablePagedKVCacheManager, HostPagePool,
                               KVCacheManager, PagedKVCacheManager)
from repro.serve.step import sample


@dataclass
class _Flight:
    """A tick launched and not yet landed (``overlap=True``): its plan, the
    request each slot served, and the device's sampled tokens and
    counters."""
    plan: sched.TickPlan
    reqs: dict
    out: jax.Array
    stats: dict

    def yields(self, slot: int, req) -> bool:
        """True when this tick gives ``req`` (still in ``slot``) a token."""
        if self.reqs.get(slot) is not req:
            return False
        w = next(w for w in self.plan.work if w.slot == slot)
        return w.kind == "decode" or w.completes

    def feeds(self, slot: int, req) -> int:
        """Prompt tokens this tick feeds ``req`` in ``slot``."""
        if self.reqs.get(slot) is not req:
            return 0
        w = next(w for w in self.plan.work if w.slot == slot)
        return len(w.tokens) if w.kind == "prefill" else 0


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (P,) int32
    max_new: int = 16
    priority: int = 0     # lower preempts first under thermal emergency
    out: List[int] = field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    fed: int = 0          # prompt tokens already written to the cache
    submit_tick: int = 0  # engine tick at submission (queue-age / SLO)
    finish_tick: int = 0
    preempts: int = 0     # times evicted to the host page pool
    # time.perf_counter() stamps: submit(), first slot assignment (a resume
    # after preemption keeps it), first token appended, completion
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


class Engine:
    def __init__(self, model: Model, params, batch_slots: int = 4,
                 max_len: int = 256, eos_id: int = 1,
                 temperature: float = 0.0,
                 admit_cap: Optional[int] = None,
                 top_k: int = 0, prefill_chunk: int = 16,
                 page_size: int = 16, expandable: bool = False,
                 paged: bool = False, total_pages: Optional[int] = None,
                 speculate: int = 0, overlap: bool = False,
                 seed: int = 0, warmup: bool = True,
                 pool: Optional[HostPagePool] = None):
        self.model = model
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.eos = eos_id
        self.temperature = temperature
        self.top_k = top_k
        self.prefill_chunk = max(1, min(prefill_chunk, max_len))
        cfg = model.cfg
        # ragged chunked prefill needs position-table masking all the way
        # down; recurrent state (ssm/hybrid) would absorb the padded chunk
        # tails.  Ring buffers (sliding window) ride the ragged path too —
        # the masked per-row ring scatter keeps padded tails out — as long
        # as one chunk cannot lap the window
        self._ragged = (cfg.family in ("dense", "moe")
                        and (not cfg.sliding_window
                             or self.prefill_chunk <= cfg.sliding_window))
        self._paged = bool(paged)
        if self._paged and not self._ragged:
            raise ValueError(
                "paged=True requires the ragged path (dense/moe attention); "
                "recurrent state cannot be gathered through block tables")
        self._spec_k = max(int(speculate), 0)
        if self._spec_k:
            if temperature != 0.0:
                raise ValueError("speculate requires greedy decoding "
                                 "(temperature=0): verification compares "
                                 "drafts against the argmax rows")
            if not self._ragged:
                raise ValueError("speculate requires the ragged path")
            if cfg.sliding_window and cfg.sliding_window < max_len:
                raise ValueError(
                    "speculate requires sliding_window >= max_len: a "
                    "wrapping ring scatter would destroy live window "
                    "entries a rejected draft cannot restore")
        if self._paged:
            mgr_cls = (ExpandablePagedKVCacheManager if expandable
                       else PagedKVCacheManager)
            self.mgr = mgr_cls(model, batch_slots, max_len,
                               page_size=page_size, total_pages=total_pages)
        else:
            mgr_cls = (ExpandableKVCacheManager if expandable
                       else KVCacheManager)
            self.mgr = mgr_cls(model, batch_slots, max_len,
                               page_size=page_size)
        self.slot_req: List[Optional[Request]] = [None] * self.B
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        # preempted KV rows, host side; pass a shared pool to let several
        # pod engines exchange requests (fleet migration, DESIGN.md §10)
        self.pool = pool if pool is not None else HostPagePool()
        self.preempts = 0
        self.spec_proposed = 0  # draft tokens offered to verification
        self.spec_accepted = 0  # draft tokens accepted (bitwise == greedy)
        self._bt_host: Optional[np.ndarray] = None  # device bt cache key
        self._bt_dev = None
        self.key = jax.random.PRNGKey(seed)
        # control plane: admission throttle + tick telemetry subscribers
        self.admit_cap = admit_cap
        self.on_tick: List[Callable[[TickSample], None]] = []
        self.ticks = 0

        def decode(params, tokens, cache, pos, n_valid):
            with jax.named_scope("decode"):
                return model.decode(params, tokens, cache, pos,
                                     n_valid=n_valid)

        def pick(logits, n_valid, key):
            """Each slot's next token, from the logit after its last valid
            input."""
            with jax.named_scope("sample"):
                idx = jnp.clip(n_valid - 1, 0, logits.shape[1] - 1)
                last = jnp.take_along_axis(
                    logits, idx[:, None, None], axis=1)[:, 0]  # (B,V)
                return sample(last, key, self.temperature, self.top_k)

        def verify_rows(logits):
            # every row's greedy continuation — the verify step
            with jax.named_scope("sample"):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        self._kv_pool = self._paged and model.decodes_in_pool
        if overlap and (not self._kv_pool or self._spec_k):
            raise ValueError(
                "overlap requires the in-pool paged step (paged=True on a "
                "full-length attention stack) and no speculation")
        self._overlap = bool(overlap)
        self._inflight: Optional[_Flight] = None
        self.kv_pool_ticks = 0  # fused ticks run on the in-pool path
        # the in-pool step's expert counters, summed over layers and ticks:
        # picks that landed on experts this chip holds, and held experts
        # given at least one token
        self.routed_local = 0
        self.experts_touched = 0
        if self._kv_pool:
            mgr = self.mgr

            def decode_paged(params, tokens, pool, bt, pos, n_valid):
                with jax.named_scope("decode"):
                    logits, out, stats = model.decode_paged(
                        params, tokens, pool, bt, pos, n_valid=n_valid)
                # the layers wrote their entries in place; the manager's
                # write-back hands the stepped pool on as it is
                return logits, mgr.scatter_all(pool, out, None), stats

            def fused(params, pool, bt, tokens, pos, n_valid, key):
                logits, pool, stats = decode_paged(params, tokens, pool, bt,
                                                   pos, n_valid)
                return pick(logits, n_valid, key), pool, stats

            def fused_spec(params, pool, bt, tokens, pos, n_valid, key):
                logits, pool, stats = decode_paged(params, tokens, pool, bt,
                                                   pos, n_valid)
                return verify_rows(logits), pool, stats
        elif self._paged:
            mgr = self.mgr

            def gather(pool, bt):
                with jax.named_scope("kv_gather"):
                    return mgr.gather_logical(pool, bt)

            def scatter(pool, cache, inv):
                with jax.named_scope("kv_scatter"):
                    return mgr.scatter_all(pool, cache, inv)

            def fused(params, pool, bt, inv, tokens, pos, n_valid, key):
                logits, cache = decode(params, tokens, gather(pool, bt), pos,
                                       n_valid)
                return (pick(logits, n_valid, key),
                        scatter(pool, cache, inv), {})

            def fused_spec(params, pool, bt, inv, tokens, pos, n_valid, key):
                logits, cache = decode(params, tokens, gather(pool, bt), pos,
                                       n_valid)
                return verify_rows(logits), scatter(pool, cache, inv), {}
        else:
            def fused(params, cache, tokens, pos, n_valid, key):
                logits, cache = decode(params, tokens, cache, pos, n_valid)
                return pick(logits, n_valid, key), cache, {}

            def fused_spec(params, cache, tokens, pos, n_valid, key):
                logits, cache = decode(params, tokens, cache, pos, n_valid)
                return verify_rows(logits), cache, {}

        # the paged step donates the pool: its writes then update the page
        # buffers in place instead of copying the whole pool
        donate = (1,) if self._paged else ()
        self._fused = jax.jit(fused, donate_argnums=donate)
        self._fused_spec = jax.jit(fused_spec, donate_argnums=donate)

        @jax.jit
        def feed(tokens, prev):
            # a -1 marks a decode row whose input is the token the tick in
            # flight samples for its slot
            return jnp.where(tokens < 0, prev[:, None].astype(tokens.dtype),
                             tokens)

        self._feed = feed
        if warmup:
            self._warmup()

    def _run_fused(self, fn, plan: sched.TickPlan) -> Tuple[np.ndarray, dict]:
        """One fused device step over the plan (on the paged path: in the
        pool, or gather -> decode -> scatter) under the upload, dispatch
        and sync spans; returns the host copy of the sampled output and the
        tick's expert counters (empty where the step has none)."""
        return self._sync(*self._launch(fn, plan))

    def _launch(self, fn, plan: sched.TickPlan, prev=None):
        """Upload the tick's arrays and dispatch the fused step; returns its
        device output and counters.  ``prev``, the sampled tokens of the
        tick in flight, fills the plan's -1 decode inputs on the device."""
        with span("serve.engine.upload",
                  bt_sent=int(self._paged and self._bt_stale())):
            self.key, key = jax.random.split(self.key)
            toks = jnp.asarray(plan.tokens)
            if prev is not None:
                toks = self._feed(toks, prev)
            pos = jnp.asarray(plan.pos)
            nv = jnp.asarray(plan.n_valid)
            if self._paged:
                tables = self._bt_device()
        prefill = [w for w in plan.work if w.kind == "prefill"]
        with span("serve.engine.dispatch", width=plan.width,
                  prefill=len(prefill), decode=len(plan.work) - len(prefill),
                  prompt_tokens=sum(len(w.tokens) for w in prefill),
                  kv_pool=int(self._kv_pool)):
            if self._paged:
                out, self.mgr.pool, stats = fn(self.params, self.mgr.pool,
                                               *tables, toks, pos, nv, key)
                self.kv_pool_ticks += self._kv_pool
            else:
                out, self.mgr.cache, stats = fn(self.params, self.mgr.cache,
                                                toks, pos, nv, key)
        return out, stats

    def _sync(self, out, stats) -> Tuple[np.ndarray, dict]:
        with span("serve.engine.sync"):
            # the tick's single host sync; the counters come with the output
            out, stats = jax.device_get((out, stats))
        stats = {k: int(v) for k, v in stats.items()}
        self.routed_local += stats.get("routed_local", 0)
        self.experts_touched += stats.get("experts_touched", 0)
        return out, stats

    def _bt_stale(self) -> bool:
        """True when the host block table differs from its device copy."""
        return self._bt_host is None or not np.array_equal(
            self._bt_host, self.mgr.block_table)

    def _bt_device(self) -> tuple:
        """The fused step's page-table arguments on the device: the block
        table, and on the gather/scatter path its inverse page map too;
        re-uploaded only when the host table actually changed (steady
        decode re-uses pages for page_size ticks at a time, so most ticks
        skip the transfer)."""
        if self._bt_stale():
            self._bt_host = self.mgr.block_table.copy()
            self._bt_dev = (jnp.asarray(self._bt_host, jnp.int32),)
            if not self._kv_pool:
                self._bt_dev += (jnp.asarray(self.mgr.inverse_map(),
                                             jnp.int32),)
        return self._bt_dev

    def _warmup(self):
        """Pre-compile the fused step's width buckets and the invalidation
        paths so no compile lands mid-traffic (n_valid = 0 rows make the
        warmup calls no-ops on cache contents)."""
        widths = {1, self.prefill_chunk} if self._ragged else {1}
        zero = jnp.zeros((self.B,), jnp.int32)
        calls = [(self._fused, S) for S in sorted(widths)]
        if self._spec_k:
            calls.append((self._fused_spec, self._spec_k + 1))
        for fn, S in calls:
            toks = jnp.zeros((self.B, S), jnp.int32)
            if self._paged:
                # the pool is donated into the jit — rebind the returned
                # buffer or the manager would hold a deleted array
                out, self.mgr.pool, _ = fn(self.params, self.mgr.pool,
                                           *self._bt_device(), toks, zero,
                                           zero, self.key)
                if self._overlap:
                    self._feed(toks, out)
            else:
                fn(self.params, self.mgr.cache, toks, zero, zero, self.key)
        if self._paged:
            self.mgr.pool = self.mgr._invalidate_pages(
                self.mgr.pool, jnp.asarray([self.mgr.null_page]))
        else:
            self.mgr._invalidate(self.mgr.cache, jnp.asarray([0]))

    # -- public API -----------------------------------------------------------
    @property
    def cache(self):
        return self.mgr.cache

    def submit(self, req: Request):
        req.submit_tick = self.ticks
        if req.t_submit is None:  # a fleet resubmission keeps the first
            req.t_submit = time.perf_counter()
        self.queue.append(req)

    # -- admission ------------------------------------------------------------
    def _admit(self) -> int:
        """Admit queued requests into free slots (<= admit_cap per step).
        On the paged path admission is additionally priced off the *actual*
        free page list: a fresh request needs one page now, a resume needs
        exactly the pages it parked — fragmentation-free by construction,
        so "has pages" always means "can admit"."""
        cap = self.B if self.admit_cap is None else max(self.admit_cap, 0)
        admitted = 0
        while self.queue and self.mgr.free_slots and admitted < cap:
            if self._paged:
                head = self.queue[0]
                need = (self.pool.put_pages(head.rid)
                        if head.rid in self.pool else 1)
                if self.mgr.free_pages < max(need, 1):
                    break  # no pages — keep FIFO order, retry next tick
            req = self.queue.pop(0)
            if req.rid in self.pool:
                # resume a preempted request: its KV rows come back from
                # the host page pool bit for bit — no recompute, no drift
                slot = self.mgr.allocate(len(req.prompt))
                rows, pos = self.pool.take(req.rid, owner=self.mgr)
                if isinstance(self.mgr, (ExpandableKVCacheManager,
                                         ExpandablePagedKVCacheManager)):
                    self.mgr.ensure(pos + 1)
                self.mgr.restore(slot, rows, pos)
                self.slot_req[slot] = req
                admitted += 1
                continue
            if len(req.prompt) >= self.max_len:
                req.done = True
                req.error = "prompt_too_long"
                req.finish_tick = self.ticks
                req.t_done = time.perf_counter()
                self.finished.append(req)
                continue  # a reject is not an admission
            slot = self.mgr.allocate(len(req.prompt))
            self.slot_req[slot] = req
            req.t_admit = time.perf_counter()
            req.fed = 0
            if not self._ragged:
                self._prefill_into(slot, req)
            admitted += 1
        return admitted

    # -- thermal-emergency preemption -----------------------------------------
    def preempt_to(self, keep_active: int) -> int:
        """Evict active slots until at most ``keep_active`` stay busy (the
        :class:`~repro.control.controller.Preempt` actuation).  Victims are
        the lowest-priority, newest requests; each one's KV rows move to the
        host page pool, its device slot is freed (pages actually return to
        the admission budget), and the request re-queues at the head for
        bitwise-identical resumption.  Returns the eviction count."""
        self._land(self._take_inflight())  # rows and tokens as committed
        active = [(s, r) for s, r in enumerate(self.slot_req)
                  if r is not None]
        n_evict = len(active) - max(int(keep_active), 0)
        if n_evict <= 0:
            return 0
        victims = sorted(active, key=lambda sr: (sr[1].priority,
                                                 -sr[1].submit_tick,
                                                 -sr[0]))[:n_evict]
        requeue = []
        for slot, req in sorted(victims, key=lambda sr: sr[1].submit_tick):
            # page-exact eviction: ship and account exactly the pages the
            # request holds (the paged read gathers only its block-table
            # entries; a short request never pays its slot's full span)
            pages = self.mgr.slot_pages(slot)
            rows = self.mgr.read_rows([slot])
            page_ids = (self.mgr.block_table[slot, :pages].copy()
                        if self._paged else None)
            self.pool.put(req.rid, rows, int(self.mgr.pos[slot]),
                          pages=pages, owner=self.mgr, page_ids=page_ids,
                          freed=True)
            self.slot_req[slot] = None
            self._release(slot)
            req.preempts += 1
            self.preempts += 1
            requeue.append(req)
        self.queue[:0] = requeue  # resume first, oldest first
        return n_evict

    def drain(self) -> List[Request]:
        """Quarantine drain (DESIGN.md §10): evict every active slot to the
        host page pool and hand back the whole pending queue — resumable
        requests first, oldest first — so a fleet router can resubmit them
        to healthy pods.  The engine is left empty (no active slots, no
        queue) with all device pages back on the free list."""
        self.preempt_to(0)
        out, self.queue = self.queue, []
        return out

    def _prefill_into(self, slot: int, req: Request):
        """Stateful-family path: exact-length prefill, scatter one row."""
        toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
        if isinstance(self.mgr, ExpandableKVCacheManager):
            self.mgr.ensure(len(req.prompt) + 1)
            cap = self.mgr.capacity
        else:
            cap = self.max_len
        logits, rows = self.model.prefill(self.params, {"tokens": toks},
                                          max_len=cap)
        self.mgr.write_rows([slot], rows)
        self.mgr.advance([slot], [len(req.prompt)])
        req.fed = len(req.prompt)
        self.key, sk = jax.random.split(self.key)
        tok = int(sample(logits[:, -1], sk, self.temperature, self.top_k)[0])
        self._append(req, slot, tok)

    # -- speculative drafting -------------------------------------------------
    def _draft(self, req: Request, k: int) -> np.ndarray:
        """n-gram prompt-lookup self-speculation (model-free, greedy): find
        the most recent earlier occurrence of the last generated token in
        the request's own prompt+output context and propose the tokens that
        followed it.  Returns up to ``k`` draft tokens (possibly none)."""
        ctx = np.concatenate([np.asarray(req.prompt, np.int32),
                              np.asarray(req.out, np.int32)])
        hits = np.nonzero(ctx[:-1] == ctx[-1])[0]
        if hits.size == 0:
            return np.zeros(0, np.int32)
        j = int(hits[-1])
        return ctx[j + 1:j + 1 + k].astype(np.int32)

    # -- the fused tick -------------------------------------------------------
    def _compose(self) -> Tuple[Optional[sched.TickPlan], bool]:
        """Compose the tick's work; second return marks a speculative
        (all-decode, width ``k+1``) verify tick.  Speculation stands down
        whenever any slot prefills or sits too close to ``max_len`` for the
        fixed verify width (``_row_update`` would clamp the write)."""
        k = self._spec_k
        fl = self._inflight  # overlap: the tick the device still runs
        active = [(s, r) for s, r in enumerate(self.slot_req)
                  if r is not None and not (fl and self._ends_in(fl, s, r))]
        spec = bool(k) and bool(active) and all(
            r.fed >= len(r.prompt)
            and int(self.mgr.pos[s]) + k + 1 <= self.max_len
            for s, r in active)
        work: List[sched.SlotWork] = []
        for s, req in active:
            P = len(req.prompt)
            fed = req.fed + (fl.feeds(s, req) if fl else 0)
            if fed < P:  # ragged path only: stream the prompt
                n = min(self.prefill_chunk, P - fed)
                work.append(sched.SlotWork(
                    s, "prefill",
                    np.asarray(req.prompt[fed:fed + n], np.int32),
                    completes=(fed + n == P)))
            elif spec:
                drafts = self._draft(req, k)
                toks = np.zeros(k + 1, np.int32)  # fixed width: one bucket
                toks[0] = req.out[-1]
                toks[1:1 + len(drafts)] = drafts
                work.append(sched.SlotWork(
                    s, "decode", toks, n_valid=1 + len(drafts)))
            else:  # -1: the token the tick in flight samples (_feed)
                tok = -1 if fl and fl.yields(s, req) else req.out[-1]
                work.append(sched.SlotWork(
                    s, "decode", np.asarray([tok], np.int32)))
        plan = sched.compose(work, self.mgr.pos, self.B, self.prefill_chunk)
        return plan, spec

    def _reserve_pages(self, plan: sched.TickPlan) -> bool:
        """Claim the pages this tick's real tokens will write (padded tails
        land on the inert null page).  All-or-nothing: False when the free
        list cannot cover the whole plan, so the caller can shed load and
        recompose instead of extending half the slots."""
        need = sum(
            self.mgr.pages_needed(
                w.slot, int(self.mgr.pos[w.slot]) + int(plan.n_valid[w.slot]))
            for w in plan.work)
        if need > self.mgr.free_pages:
            return False
        for w in plan.work:
            self.mgr.extend(
                w.slot, int(self.mgr.pos[w.slot]) + int(plan.n_valid[w.slot]))
        return True

    def _plan(self) -> Tuple[Optional[sched.TickPlan], bool]:
        """Compose the tick and reserve the pages its real tokens write,
        preempting and recomposing while the free list falls short."""
        plan, spec = self._compose()
        if plan is None:
            return None, False
        if self._paged:
            if isinstance(self.mgr, ExpandablePagedKVCacheManager):
                self.mgr.ensure(int(plan.pos.max() + plan.width))
            while not self._reserve_pages(plan):
                # out of pages mid-decode: thermal-preempt the newest
                # low-priority request (pages return to the free list,
                # bitwise resume later) and recompose the tick
                n_active = sum(r is not None for r in self.slot_req)
                if n_active <= 1:
                    raise RuntimeError(
                        "page pool exhausted: one request needs more pages "
                        f"than total_pages={self.mgr.total_pages}")
                self.preempt_to(n_active - 1)
                plan, spec = self._compose()
                if plan is None:
                    return None, False
                if isinstance(self.mgr, ExpandablePagedKVCacheManager):
                    self.mgr.ensure(int(plan.pos.max() + plan.width))
        elif isinstance(self.mgr, ExpandableKVCacheManager):
            self.mgr.ensure(int(plan.pos.max() + plan.width))
        return plan, spec

    def _tick(self) -> int:
        with span("serve.engine.compose"):
            plan, spec = self._plan()
        if self._overlap:
            return self._tick_ahead(plan)
        if plan is None:
            return 0
        if spec:
            rows, stats = self._run_fused(self._fused_spec, plan)  # (B, k+1)
            with span("serve.engine.commit", **stats):
                return self._commit_spec(plan, rows)
        nxt, stats = self._run_fused(self._fused, plan)
        with span("serve.engine.commit", **stats):
            return self._commit(plan, nxt)

    def _tick_ahead(self, plan: Optional[sched.TickPlan]) -> int:
        """``overlap``: launch the planned tick, then land the one in
        flight.  Positions advance at launch, so the next plan writes
        after this tick's entries; prompt progress and tokens are
        committed when the tick lands."""
        prev = self._take_inflight()
        if plan is not None:
            fill = prev is not None and any(
                w.kind == "decode" and plan.tokens[w.slot, 0] < 0
                for w in plan.work)
            out, stats = self._launch(self._fused, plan,
                                      prev.out if fill else None)
            self.mgr.advance([w.slot for w in plan.work],
                             [len(w.tokens) for w in plan.work])
            self._inflight = _Flight(
                plan, {w.slot: self.slot_req[w.slot] for w in plan.work},
                out, stats)
        return self._land(prev)

    def _take_inflight(self) -> Optional[_Flight]:
        fl, self._inflight = self._inflight, None
        return fl

    def _ends_in(self, fl: _Flight, slot: int, req: Request) -> bool:
        """True when the tick in flight ends ``req`` by its length limits
        (an EOS shows only when the tick lands)."""
        return fl.yields(slot, req) and (
            len(req.out) + 1 >= req.max_new
            or self.mgr.pos[slot] >= self.max_len - 1)

    def _land(self, fl: Optional[_Flight]) -> int:
        """Sync a launched tick and commit it: prompt progress, and each
        slot's sampled token unless its request ended on the tick before."""
        if fl is None:
            return 0
        nxt, stats = self._sync(fl.out, fl.stats)
        gen = 0
        with span("serve.engine.commit", **stats):
            for w in fl.plan.work:
                req = fl.reqs[w.slot]
                if w.kind == "prefill":
                    req.fed += len(w.tokens)
                    if not w.completes:
                        continue
                if req.done:
                    continue
                self._append(req, w.slot, int(nxt[w.slot]),
                             end=int(fl.plan.pos[w.slot]) + len(w.tokens))
                gen += 1
        return gen

    def _commit(self, plan: sched.TickPlan, nxt: np.ndarray) -> int:
        """Advance every slot the tick fed and append its sampled token
        (a prefill slot only once its last prompt chunk is in)."""
        gen = 0
        self.mgr.advance([w.slot for w in plan.work],
                         [len(w.tokens) for w in plan.work])
        for w in plan.work:
            req = self.slot_req[w.slot]
            if w.kind == "prefill":
                req.fed += len(w.tokens)
                if w.completes:  # logit after the last prompt token
                    self._append(req, w.slot, int(nxt[w.slot]))
                    gen += 1
            else:
                self._append(req, w.slot, int(nxt[w.slot]))
                gen += 1
        return gen

    def _commit_spec(self, plan: sched.TickPlan, rows: np.ndarray) -> int:
        """Verify draft rows against the greedy argmax and commit the
        accepted prefix plus the bonus token, one token at a time (the
        sequential EOS / max_new / max_len checks apply mid-prefix exactly
        as they would tick by tick); roll the rejected tail's pages back
        through the allocator."""
        gen = 0
        for w in plan.work:
            req = self.slot_req[w.slot]
            nv = int(plan.n_valid[w.slot])
            drafts = w.tokens[1:nv]
            a = 0
            while a < len(drafts) and int(drafts[a]) == int(rows[w.slot, a]):
                a += 1
            self.spec_proposed += len(drafts)
            self.spec_accepted += a
            for i in range(a + 1):  # accepted drafts + the bonus token
                self.mgr.advance([w.slot], [1])
                self._append(req, w.slot, int(rows[w.slot, i]))
                gen += 1
                if req.done:
                    break
            if self._paged and not req.done:
                # rejected tail: return its pages, keeping the span the
                # next verify tick must reserve anyway (the hysteresis
                # avoids a free/invalidate/realloc round trip per tick);
                # stale entries in kept pages self-heal (pos_ids > every
                # later query position until sequentially overwritten)
                upto = min(int(self.mgr.pos[w.slot]) + self._spec_k + 1,
                           self.max_len)
                freed = self.mgr.slot_pages(w.slot) - -(
                    -upto // self.mgr.page_size)
                if freed > 0:
                    with span("serve.engine.release", pages=freed):
                        self.mgr.trim(w.slot, upto)
        return gen

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of proposed draft tokens verification accepted."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    def _append(self, req: Request, slot: int, tok: int,
                end: Optional[int] = None):
        """Append a sampled token; ``end`` is the slot's cache length after
        the tick that sampled it (the manager's, unless positions already
        advanced for a later tick)."""
        if not req.out:
            req.t_first = time.perf_counter()
        req.out.append(tok)
        end = self.mgr.pos[slot] if end is None else end
        if (tok == self.eos or len(req.out) >= req.max_new
                or end >= self.max_len - 1):
            req.done = True
            req.finish_tick = self.ticks
            req.t_done = time.perf_counter()
            self.finished.append(req)
            self.slot_req[slot] = None
            self._release(slot)

    def _release(self, slot: int):
        """Return a slot and its pages (the invalidation is a dispatch)."""
        with span("serve.engine.release", pages=self.mgr.slot_pages(slot)):
            self.mgr.free(slot)

    # -- scheduler loop -------------------------------------------------------
    def step(self) -> bool:
        """One scheduler iteration (admit, then one fused tick); True while
        there is still work.  ``run`` loops this; control-plane drivers
        interleave it with ``ControlLoop.step`` ticks."""
        if not (self.queue or self._inflight is not None
                or any(r is not None for r in self.slot_req)):
            return False
        t0 = time.perf_counter()
        with span("serve.engine.admit"):
            admitted = self._admit()
        gen = self._tick()
        oldest = (float(self.ticks - min(r.submit_tick for r in self.queue))
                  if self.queue else 0.0)
        if self.on_tick:
            # slots rides along so the control plane can fold active/slots
            # into the load fraction feeding the RailField utilization axis
            smp = TickSample(
                tick=self.ticks, queued=len(self.queue),
                active=sum(r is not None for r in self.slot_req),
                finished=len(self.finished), tokens=gen,
                tick_s=time.perf_counter() - t0, slots=self.B,
                admitted=admitted, oldest_wait=oldest,
                pages_free=self.mgr.free_pages)
            for cb in self.on_tick:
                cb(smp)
        self.ticks += 1
        return bool(self.queue or self._inflight is not None
                    or any(r is not None for r in self.slot_req))

    def run(self, max_ticks: int = 512) -> List[Request]:
        ticks = 0
        while ticks < max_ticks and self.step():
            ticks += 1
        return self.finished
