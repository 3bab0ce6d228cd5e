"""Paged KV-cache management for the continuous-batching engine.

``KVCacheManager`` owns the decode-cache pytree for a fixed set of slots and
all per-slot bookkeeping the scheduler needs:

- **per-slot positions** — ``pos[slot]`` is each slot's next decode position;
  there is no global aligned position, so requests at different depths share
  one fused decode step (the ragged ``pos``/``n_valid`` contract of
  ``Model.decode``).
- **slot recycling** — freeing a slot returns its pages to the pool and
  invalidates its ``pos_ids`` rows; the arrays are allocated once, so cache
  memory never grows with request count.
- **page accounting** — capacity is tracked in fixed-size pages
  (``page_size`` tokens); ``pages_in_use``/``peak_pages`` expose occupancy to
  the admission controller.  The counter is maintained *incrementally* on
  allocate/free/advance/restore (it sits on the per-tick admission hot
  path); ``recount_pages()`` recomputes it from scratch for verification.
- **batch-axis probing** — the cache pytree mixes leaf ranks (attention K/V,
  SSM conv/ssm states, cross-attn K/V, stacked layer dims), so the manager
  finds each leaf's batch axis *structurally*: build the abstract cache at
  two batch sizes and diff the shapes. Scatter/gather then move that axis to
  the front — no shape-matching heuristics (which break when a layer count
  equals the slot count).

``PagedKVCacheManager`` makes pages *real* (vLLM-style): the device cache is
a pool of ``total_pages`` physical pages (pages carried on the probed batch
axis, ``page_size`` tokens on the probed sequence axis) plus one permanently
invalid **null page**; each slot owns a block table mapping logical page
index -> physical page, filled from a free-list :class:`PageAllocator` at
``page_size`` granularity.  Layout is non-contiguous by construction — any
free page serves any slot, so admission never fails on fragmentation.
Freed/trimmed pages get their ``pos_ids`` invalidated before returning to
the pool so a recycled page can never leak stale entries through another
slot's attention mask.

Each manager owns how its storage meets the model: ``decode_step`` is the
traceable model call the engine fuses into its one jitted step, over the
state (``cache``, donated where ``donates``) and the device ``tables()``.
The contiguous manager runs ``Model.decode`` on its cache.  The paged one
runs ``Model.decode_paged`` in the pool where the stack holds full-length
attention entries (``kv_pool``), and otherwise (ring caches) gathers a
slot-contiguous logical cache through the block tables, runs the
*unchanged* ``Model.decode`` and scatters the pages back — identical ops
on identical visible values, so outputs stay bitwise identical to the
contiguous manager.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


NO_AXIS = -1  # sentinel: None leaves would vanish from the pytree


def _probe_axes(model, make_a, make_b):
    """Per-leaf axis where two abstract cache builds disagree (else NO_AXIS)."""
    a = make_a()
    b = make_b()

    def diff(x, y):
        for i, (p, q) in enumerate(zip(x.shape, y.shape)):
            if p != q:
                return i
        return NO_AXIS

    return jax.tree_util.tree_map(diff, a, b)


def _is_pos_ids(path) -> bool:
    for p in path:
        if getattr(p, "key", None) == "pos_ids":
            return True
    return False


class KVCacheManager:
    """Fixed-capacity paged cache over ``slots`` rows of length ``max_len``."""

    kv_pool = False  # the model steps the slot-contiguous cache
    donates = False

    def __init__(self, model, slots: int, max_len: int,
                 page_size: int = 16):
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        self.batch_axes = _probe_axes(
            model,
            lambda: model.cache(slots, max_len, abstract=True),
            lambda: model.cache(slots + 1, max_len, abstract=True))
        self.cache = model.cache(slots, max_len)
        # host-side bookkeeping (no device sync needed to schedule)
        self.pos = np.zeros(slots, np.int32)        # next decode position
        self.lengths = np.zeros(slots, np.int32)    # prompt length
        self._free: List[int] = list(range(slots))
        self._pages_per_slot = math.ceil(max_len / page_size)
        self.peak_pages = 0
        # incremental page accounting: per-slot page counts + running total,
        # updated on allocate/free/advance/restore (admission reads
        # pages_in_use every tick — no O(slots) recount on the hot path)
        self._slot_pages = np.zeros(slots, np.int32)
        self._pages_in_use = 0

        def _scatter(cache, rows, slot_ids):
            def put(ax, ec, pc):
                if ax == NO_AXIS:
                    return ec
                ecm = jnp.moveaxis(ec, ax, 0)
                pcm = jnp.moveaxis(pc, ax, 0)
                ecm = ecm.at[slot_ids].set(pcm.astype(ecm.dtype))
                return jnp.moveaxis(ecm, 0, ax)

            return jax.tree_util.tree_map(put, self.batch_axes, cache, rows)

        def _invalidate(cache, slot_ids):
            def inv(path, ax, ec):
                if ax == NO_AXIS or not _is_pos_ids(path):
                    return ec
                ecm = jnp.moveaxis(ec, ax, 0)
                ecm = ecm.at[slot_ids].set(-1)
                return jnp.moveaxis(ecm, 0, ax)

            return jax.tree_util.tree_map_with_path(
                inv, self.batch_axes, cache)

        def _gather(cache, slot_ids):
            def take(ax, ec):
                if ax == NO_AXIS:
                    return ec
                ecm = jnp.moveaxis(ec, ax, 0)
                return jnp.moveaxis(ecm[slot_ids], 0, ax)

            return jax.tree_util.tree_map(take, self.batch_axes, cache)

        self._scatter = jax.jit(_scatter)
        self._invalidate = jax.jit(_invalidate)
        self._gather = jax.jit(_gather)

    # -- the fused step's model call ------------------------------------------
    def decode_step(self, params, cache, tables, tokens, pos, n_valid):
        """``Model.decode`` on the cache (traceable; no tables)."""
        with jax.named_scope("decode"):
            logits, cache = self.model.decode(params, tokens, cache, pos,
                                              n_valid=n_valid)
        return logits, cache, {}

    def tables_stale(self) -> bool:
        return False

    def tables(self) -> tuple:
        return ()

    def warm_release(self):
        """Compile the release path's invalidation ahead of traffic."""
        self._invalidate(self.cache, jnp.asarray([0]))

    # -- slot lifecycle -------------------------------------------------------
    @property
    def free_slots(self) -> List[int]:
        return list(self._free)

    @property
    def active_slots(self) -> List[int]:
        return [s for s in range(self.slots) if s not in self._free]

    def _set_slot_pages(self, slot: int, n: int) -> None:
        self._pages_in_use += n - int(self._slot_pages[slot])
        self._slot_pages[slot] = n
        self.peak_pages = max(self.peak_pages, self._pages_in_use)

    def allocate(self, prompt_len: int) -> int:
        """Claim a free slot for a request; returns the slot id."""
        slot = self._free.pop(0)
        self.pos[slot] = 0
        self.lengths[slot] = prompt_len
        self._set_slot_pages(slot, 1)  # an allocated slot holds >= 1 page
        return slot

    def free(self, slot: int):
        """Recycle a slot: pages return to the pool, row marked invalid.

        Raises on double-free or free-of-unallocated: a silent accept
        would duplicate the slot in the free list, hand it to two requests
        at once, and corrupt the page accounting."""
        if not 0 <= slot < self.slots:
            raise ValueError(
                f"free of invalid slot {slot} (valid: 0..{self.slots - 1})")
        if slot in self._free:
            raise ValueError(f"double free of slot {slot}")
        self.pos[slot] = 0
        self.lengths[slot] = 0
        self._set_slot_pages(slot, 0)
        self._free.append(slot)
        self.cache = self._invalidate(self.cache, jnp.asarray([slot]))

    # -- page accounting ------------------------------------------------------
    @property
    def total_pages(self) -> int:
        return self.slots * self._pages_per_slot

    @property
    def pages_in_use(self) -> int:
        return self._pages_in_use

    @property
    def free_pages(self) -> int:
        return self.total_pages - self._pages_in_use

    def slot_pages(self, slot: int) -> int:
        return int(self._slot_pages[slot])

    def recount_pages(self) -> int:
        """Recompute page occupancy from scratch (O(slots)) — the reference
        the incremental counter is pinned against in tests."""
        used = 0
        for s in range(self.slots):
            if s in self._free:
                continue
            used += max(1, math.ceil(int(self.pos[s]) / self.page_size))
        return used

    # -- cache writes ---------------------------------------------------------
    def write_rows(self, slot_ids, rows):
        """Scatter prefilled cache rows (batch == len(slot_ids)) into slots."""
        self.cache = self._scatter(self.cache, rows,
                                   jnp.asarray(slot_ids, jnp.int32))

    def read_rows(self, slot_ids):
        """Gather cache rows (batch == len(slot_ids)) out of slots — the
        device->host read of thermal-emergency preemption."""
        return self._gather(self.cache, jnp.asarray(slot_ids, jnp.int32))

    def restore(self, slot: int, rows, pos: int):
        """Scatter one preempted row set back into a (re)allocated slot and
        rewind its decode position — the resume half of preemption."""
        self.write_rows([slot], rows)
        self.pos[slot] = int(pos)
        self._set_slot_pages(
            slot, max(1, math.ceil(int(pos) / self.page_size)))

    def advance(self, slot_ids, counts):
        for s, n in zip(slot_ids, counts):
            self.pos[s] += int(n)
            self._set_slot_pages(
                s, max(1, math.ceil(int(self.pos[s]) / self.page_size)))


class HostPagePool:
    """Host-side page pool for preempted requests: evicted KV rows live in
    host memory (``jax.device_get``) keyed by request id until resumption.
    The device cache slot is freed meanwhile — preemption actually returns
    pages to the admission pool, it does not just hide them.

    Accounting is **page-exact**: ``put`` records how many device pages the
    eviction actually released (a short request holds fewer pages than its
    slot's full span), so ``pages_held``/``peak_pages`` match the allocator
    ledger instead of over-counting whole slots.

    Migration contract (DESIGN.md §10): when pods share one pool, each
    entry carries a provenance ledger — the *origin* allocator, the device
    page ids the eviction covered, and whether the origin actually freed
    them.  ``take(owner=...)`` hard-errors on a cross-allocator resume
    whose origin still owns the pages (resuming would double-represent the
    KV: the stale block table could still scatter into them) and on a
    resume whose position cannot fit the target allocator's block-table
    span — both print the ledger instead of silently corrupting state."""

    def __init__(self):
        self._rows: Dict[Any, Any] = {}
        self._ledger: Dict[Any, Dict[str, Any]] = {}
        self.puts = 0
        self.peak = 0
        self.pages_held = 0   # device pages currently parked host-side
        self.pages_evicted = 0  # cumulative pages moved to host
        self.peak_pages = 0
        self.migrations = 0   # cross-allocator resumes (pod -> pod)

    def put(self, rid, rows, pos: int, pages: int = 1, *,
            owner=None, page_ids=None, freed: bool = True) -> None:
        self._rows[rid] = (jax.device_get(rows), int(pos), int(pages))
        self._ledger[rid] = {
            "owner": owner,
            "page_ids": (None if page_ids is None
                         else [int(p) for p in np.asarray(page_ids).ravel()]),
            "freed": bool(freed),
        }
        self.puts += 1
        self.peak = max(self.peak, len(self._rows))
        self.pages_held += int(pages)
        self.pages_evicted += int(pages)
        self.peak_pages = max(self.peak_pages, self.pages_held)

    def put_pages(self, rid) -> int:
        """Pages a parked request holds (0 if not parked)."""
        entry = self._rows.get(rid)
        return 0 if entry is None else entry[2]

    def ledger(self, rid) -> Optional[Dict[str, Any]]:
        """Provenance of a parked request (origin allocator, device page
        ids, freed flag); None if unknown."""
        return self._ledger.get(rid)

    def take(self, rid, *, owner=None):
        """Pop (rows, pos) for a request being resumed.

        ``owner`` is the allocator about to receive the rows; pass it on
        every resume so cross-pod migrations are checked against the
        provenance ledger recorded at eviction time."""
        led = self._ledger.get(rid, {})
        rows, pos, pages = self._rows[rid]
        if owner is not None:
            origin = led.get("owner")
            foreign = origin is not None and origin is not owner
            if foreign and not led.get("freed", True):
                raise RuntimeError(
                    f"HostPagePool: refusing to resume request {rid!r} into "
                    f"a foreign allocator while its origin still owns the "
                    f"evicted pages (resume would scatter into a stale "
                    f"block table); ledger={led}")
            cap = getattr(owner, "max_len", None)
            if cap is not None and int(pos) > int(cap):
                raise RuntimeError(
                    f"HostPagePool: request {rid!r} parked at pos={pos} "
                    f"exceeds the target allocator's max_len {cap}; "
                    f"ledger={led}")
            if foreign:
                self.migrations += 1
        del self._rows[rid]
        self._ledger.pop(rid, None)
        self.pages_held -= pages
        return rows, pos

    def __contains__(self, rid) -> bool:
        return rid in self._rows

    def __len__(self) -> int:
        return len(self._rows)


# =============================================================================
# true paged attention: free-list allocator + block-table managers
# =============================================================================


class PageAllocator:
    """Free-list allocator over ``total_pages`` physical pages.

    O(1) alloc/free with an ownership bitmap guarding double-frees — the
    same silent-corruption class the slot free list guards against."""

    def __init__(self, total_pages: int):
        self.total = int(total_pages)
        self._free: List[int] = list(range(self.total))
        self._owned = np.zeros(self.total, bool)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.total - len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Claim ``n`` pages; raises when the pool cannot cover them (the
        engine preempts *before* extending, so this firing means a bug)."""
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)}")
        take, self._free = self._free[:n], self._free[n:]
        for p in take:
            self._owned[p] = True
        return take

    def free(self, pages) -> None:
        for p in pages:
            p = int(p)
            if not 0 <= p < self.total:
                raise ValueError(
                    f"free of invalid page {p} (valid: 0..{self.total - 1})")
            if not self._owned[p]:
                raise ValueError(f"double free of page {p}")
            self._owned[p] = False
            self._free.append(p)


class PagedKVCacheManager:
    """Block-table KV cache: non-contiguous pages behind the same slot API.

    The device cache is ``model.cache(total_pages + 1, page_size)`` — the
    probed batch axis carries physical pages, the probed sequence axis
    carries ``page_size`` tokens, and index ``total_pages`` is the **null
    page**: permanently invalid (``pos_ids = -1``), the target of every
    unallocated block-table entry (so gathers never index negatively and
    padded-tail writes land somewhere inert).

    ``gather_logical``/``scatter_logical`` convert between the pool and the
    slot-contiguous logical layout ``Model.decode`` expects; they are plain
    traceable functions so :meth:`decode_step` fuses gather -> decode ->
    scatter into one jitted step for layouts that ``Model.decode_paged``
    (which works on the pool itself) does not take.  Because the gathered
    logical cache is bitwise equal to the contiguous manager's cache at every
    mask-visible entry (and ``pos_ids`` equal everywhere — freed pages are
    invalidated), the paged engine's logits are bitwise identical to the
    contiguous path.
    """

    donates = True  # the step's writes update the page buffers in place

    def __init__(self, model, slots: int, max_len: int,
                 page_size: int = 16, total_pages: Optional[int] = None):
        cfg = getattr(model, "cfg", None)
        window = getattr(cfg, "sliding_window", 0) or 0
        if window and window <= page_size:
            raise ValueError(
                f"page_size {page_size} must be < sliding_window {window}")
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        # every cache of the stack holds full-length attention entries: the
        # model steps the pool itself
        self.kv_pool = model.decodes_in_pool
        # logical per-slot sequence extent: probe the contiguous abstract
        # build — ring caches clamp at min(max_len, window)
        ref = model.cache(slots, max_len, abstract=True)
        ref_axes = _probe_axes(
            model,
            lambda: model.cache(slots, max_len, abstract=True),
            lambda: model.cache(slots + 1, max_len, abstract=True))
        seq_ref = _probe_axes(
            model,
            lambda: model.cache(slots, page_size, abstract=True),
            lambda: model.cache(slots, 2 * page_size, abstract=True))
        extents = set()
        for (ba, sa, leaf) in zip(jax.tree_util.tree_leaves(ref_axes),
                                  jax.tree_util.tree_leaves(seq_ref),
                                  jax.tree_util.tree_leaves(ref)):
            if ba == NO_AXIS:
                continue
            if sa == NO_AXIS:
                raise ValueError(
                    "paged cache requires every per-slot leaf to carry a "
                    "sequence axis (recurrent SSM/hybrid state cannot be "
                    "paged — use the contiguous manager)")
            extents.add(leaf.shape[sa])
        if not extents:
            raise ValueError("model cache has no per-slot leaves to page")
        if len(extents) > 1:
            raise ValueError(
                f"per-slot leaves disagree on sequence extent: {extents}")
        self.seq_len = extents.pop()
        if self.seq_len % page_size:
            raise ValueError(
                f"sequence extent {self.seq_len} not divisible by "
                f"page_size {page_size}")
        self.pages_per_slot = self.seq_len // page_size
        self.total_pages = (slots * self.pages_per_slot
                            if total_pages is None else int(total_pages))
        self.null_page = self.total_pages
        n_pool = self.total_pages + 1
        self.batch_axes = _probe_axes(
            model,
            lambda: model.cache(n_pool, page_size, abstract=True),
            lambda: model.cache(n_pool + 1, page_size, abstract=True))
        self.seq_axes = _probe_axes(
            model,
            lambda: model.cache(n_pool, page_size, abstract=True),
            lambda: model.cache(n_pool, 2 * page_size, abstract=True))
        self.pool = model.cache(n_pool, page_size)
        self.allocator = PageAllocator(self.total_pages)
        self.block_table = np.full((slots, self.pages_per_slot),
                                   self.null_page, np.int32)
        # host-side bookkeeping, mirroring KVCacheManager
        self.pos = np.zeros(slots, np.int32)
        self.lengths = np.zeros(slots, np.int32)
        self._free: List[int] = list(range(slots))
        self._slot_pages = np.zeros(slots, np.int32)
        self._pages_in_use = 0
        self.peak_pages = 0
        self._tables_host: Optional[np.ndarray] = None  # what is on device
        self._tables_dev: tuple = ()

        def _invalidate_pages(pool, page_ids):
            def inv(path, ba, pc):
                if ba == NO_AXIS or not _is_pos_ids(path):
                    return pc
                pcm = jnp.moveaxis(pc, ba, 0)
                pcm = pcm.at[page_ids].set(-1)
                return jnp.moveaxis(pcm, 0, ba)

            return jax.tree_util.tree_map_with_path(
                inv, self.batch_axes, pool)

        # donated: only pos_ids rows change, so the K/V leaves stay in
        # place instead of a whole-pool copy on every release
        self._invalidate_pages = jax.jit(_invalidate_pages, donate_argnums=0)
        self._gather = jax.jit(self.gather_logical)
        self._scatter = jax.jit(self.scatter_logical)

    # -- the fused step's model call ------------------------------------------
    def decode_step(self, params, pool, tables, tokens, pos, n_valid):
        """The step's model call over the pool (traceable): in the pool
        (``Model.decode_paged``) when ``kv_pool``, else gather ->
        ``Model.decode`` -> :meth:`scatter_all` through the inverse map."""
        if self.kv_pool:
            (bt,) = tables
            with jax.named_scope("decode"):
                logits, out, stats = self.model.decode_paged(
                    params, tokens, pool, bt, pos, n_valid=n_valid)
            # the layers wrote their entries in place; the write-back hands
            # the stepped pool on as it is
            return logits, self.scatter_all(pool, out, None), stats
        bt, inv = tables
        with jax.named_scope("kv_gather"):
            cache = self.gather_logical(pool, bt)
        with jax.named_scope("decode"):
            logits, cache = self.model.decode(params, tokens, cache, pos,
                                              n_valid=n_valid)
        with jax.named_scope("kv_scatter"):
            return logits, self.scatter_all(pool, cache, inv), {}

    def tables_stale(self) -> bool:
        """True when the host block table differs from its device copy."""
        return self._tables_host is None or not np.array_equal(
            self._tables_host, self.block_table)

    def tables(self) -> tuple:
        """:meth:`decode_step`'s table arguments on the device: the block
        table, and on the gather path its inverse page map too; re-uploaded
        only when the host table changed (steady decode re-uses pages for
        page_size ticks at a time, so most ticks skip the transfer)."""
        if self.tables_stale():
            self._tables_host = self.block_table.copy()
            self._tables_dev = (jnp.asarray(self._tables_host, jnp.int32),)
            if not self.kv_pool:
                self._tables_dev += (jnp.asarray(self.inverse_map(),
                                                 jnp.int32),)
        return self._tables_dev

    def warm_release(self):
        """Compile the release path's invalidation ahead of traffic (on
        the null page, which stays invalid)."""
        self.pool = self._invalidate_pages(
            self.pool, jnp.asarray([self.null_page]))

    # -- pool <-> logical layout (traceable) ----------------------------------
    def gather_logical(self, pool, bt):
        """Gather block tables ``bt`` (n, pages) into a slot-contiguous
        logical cache (n, pages*page_size) — what ``Model.decode`` sees."""
        ps = self.page_size

        def take(ba, sa, leaf):
            if ba == NO_AXIS:
                return leaf
            x = jnp.moveaxis(leaf, (ba, sa), (0, 1))
            g = x[bt]  # (n, pages, page_size, ...)
            g = g.reshape((bt.shape[0], bt.shape[1] * ps) + x.shape[2:])
            return jnp.moveaxis(g, (0, 1), (ba, sa))

        return jax.tree_util.tree_map(
            take, self.batch_axes, self.seq_axes, pool)

    def inverse_map(self) -> np.ndarray:
        """Host-side inverse of the block tables: physical page -> flat
        logical page index (``slot * width + j``), or ``slots * width``
        (the fill source) for unallocated pages and the null page.  Valid
        because the allocator hands each page to exactly one slot, so the
        full-batch scatter is a permutation — :meth:`scatter_all` replays
        it as a cheap gather instead of an XLA scatter."""
        B, W = self.block_table.shape
        inv = np.full(self.total_pages + 1, B * W, np.int32)
        flat = self.block_table.reshape(-1)
        idx = np.arange(B * W, dtype=np.int32)
        alloc = flat != self.null_page
        inv[flat[alloc]] = idx[alloc]
        return inv

    def scatter_all(self, pool, logical, inv):
        """Write the fused step's cache back into the pool: the full-batch
        logical cache through the :meth:`inverse_map` — one gather per leaf
        (no scatter op on the hot path).  Unallocated pages and the null
        page come out as the fill (``pos_ids = -1``, zeros elsewhere), so
        stale entries and the aliased null writes stay inert by
        construction.  With ``inv`` None the step ran in the pool
        (``Model.decode_paged``), ``logical`` is the stepped pool itself,
        and it is the result."""
        if inv is None:
            return logical
        ps = self.page_size

        def put(path, ba, sa, pc, lg):
            if ba == NO_AXIS:
                return pc
            x = jnp.moveaxis(pc, (ba, sa), (0, 1))
            v = jnp.moveaxis(lg, (ba, sa), (0, 1))
            v = v.reshape((-1, ps) + x.shape[2:]).astype(x.dtype)
            fill = -1 if _is_pos_ids(path) else 0
            pad = jnp.full((1,) + v.shape[1:], fill, x.dtype)
            out = jnp.concatenate([v, pad], axis=0)[inv]
            return jnp.moveaxis(out, (0, 1), (ba, sa))

        return jax.tree_util.tree_map_with_path(
            put, self.batch_axes, self.seq_axes, pool, logical)

    def scatter_logical(self, pool, logical, bt):
        """Scatter a logical cache back into the pool through ``bt`` (the
        subset path — ``write_rows``/``restore``; the fused engine step
        uses :meth:`scatter_all`).  The null page is re-zeroed
        (``pos_ids = -1``) afterwards: every slot's unallocated entries
        alias it, so it must stay inert."""
        ps = self.page_size
        null = self.null_page

        def put(path, ba, sa, pc, lg):
            if ba == NO_AXIS:
                return pc
            x = jnp.moveaxis(pc, (ba, sa), (0, 1))
            v = jnp.moveaxis(lg, (ba, sa), (0, 1))
            v = v.reshape((bt.shape[0], bt.shape[1], ps) + x.shape[2:])
            x = x.at[bt].set(v.astype(x.dtype))
            fill = -1 if _is_pos_ids(path) else 0
            x = x.at[null].set(jnp.full(x.shape[1:], fill, x.dtype))
            return jnp.moveaxis(x, (0, 1), (ba, sa))

        return jax.tree_util.tree_map_with_path(
            put, self.batch_axes, self.seq_axes, pool, logical)

    # -- slot lifecycle -------------------------------------------------------
    @property
    def cache(self):
        return self.pool

    @cache.setter
    def cache(self, pool):
        self.pool = pool

    @property
    def free_slots(self) -> List[int]:
        return list(self._free)

    @property
    def active_slots(self) -> List[int]:
        return [s for s in range(self.slots) if s not in self._free]

    @property
    def pages_in_use(self) -> int:
        return self._pages_in_use

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages

    def recount_pages(self) -> int:
        """Count allocated block-table entries from scratch — pinned equal
        to both the incremental counter and the allocator ledger."""
        return int(np.sum(self.block_table != self.null_page))

    def slot_pages(self, slot: int) -> int:
        return int(self._slot_pages[slot])

    def pages_needed(self, slot: int, upto: int) -> int:
        """New pages ``extend(slot, upto)`` would have to claim."""
        upto = min(int(upto), self.block_table.shape[1] * self.page_size)
        need = max(1, math.ceil(upto / self.page_size))
        return max(0, min(need, self.block_table.shape[1])
                   - int(self._slot_pages[slot]))

    def allocate(self, prompt_len: int) -> int:
        """Claim a free slot and its first page; returns the slot id."""
        slot = self._free.pop(0)
        self.pos[slot] = 0
        self.lengths[slot] = prompt_len
        (page,) = self.allocator.alloc(1)
        self.block_table[slot, 0] = page
        self._slot_pages[slot] = 1
        self._pages_in_use += 1
        self.peak_pages = max(self.peak_pages, self._pages_in_use)
        return slot

    def extend(self, slot: int, upto: int) -> int:
        """Grow a slot's block table to cover positions ``[0, upto)``;
        returns the number of pages claimed (non-contiguous, from the free
        list — no relocation, no fragmentation)."""
        width = self.block_table.shape[1]
        upto = min(int(upto), width * self.page_size)
        need = min(max(1, math.ceil(upto / self.page_size)), width)
        have = int(self._slot_pages[slot])
        if need <= have:
            return 0
        new = self.allocator.alloc(need - have)
        self.block_table[slot, have:need] = new
        self._slot_pages[slot] = need
        self._pages_in_use += need - have
        self.peak_pages = max(self.peak_pages, self._pages_in_use)
        return need - have

    def trim(self, slot: int, upto: int) -> int:
        """Return pages past ``ceil(upto / page_size)`` to the pool — the
        speculative-decode rollback.  Freed pages are invalidated
        (``pos_ids = -1``) so their stale entries can never surface under a
        future owner's mask; returns the number of pages freed."""
        keep = max(1, math.ceil(int(upto) / self.page_size))
        have = int(self._slot_pages[slot])
        if keep >= have:
            return 0
        pages = self.block_table[slot, keep:have].copy()
        self.block_table[slot, keep:have] = self.null_page
        self._slot_pages[slot] = keep
        self._pages_in_use -= have - keep
        self.allocator.free(pages)
        self.pool = self._invalidate_pages(
            self.pool, jnp.asarray(pages, jnp.int32))
        return have - keep

    def free(self, slot: int):
        """Recycle a slot: all its pages are invalidated and returned."""
        if not 0 <= slot < self.slots:
            raise ValueError(
                f"free of invalid slot {slot} (valid: 0..{self.slots - 1})")
        if slot in self._free:
            raise ValueError(f"double free of slot {slot}")
        have = int(self._slot_pages[slot])
        pages = self.block_table[slot, :have].copy()
        self.block_table[slot, :have] = self.null_page
        self._slot_pages[slot] = 0
        self._pages_in_use -= have
        self.allocator.free(pages)
        self.pool = self._invalidate_pages(
            self.pool, jnp.asarray(pages, jnp.int32))
        self.pos[slot] = 0
        self.lengths[slot] = 0
        self._free.append(slot)

    # -- cache reads/writes (logical rows, for preemption + prefill scatter) --
    def write_rows(self, slot_ids, rows):
        """Scatter logical rows (batch == len(slot_ids)) into the slots'
        pages (the rows must already be covered by ``extend``)."""
        bt = jnp.asarray(self.block_table[np.asarray(slot_ids)], jnp.int32)
        rows = self._fit_rows(rows)
        self.pool = self._scatter(self.pool, rows, bt)

    def read_rows(self, slot_ids):
        """Gather logical rows **trimmed to the slots' allocated pages** —
        the page-exact device->host payload of preemption (a short request
        ships its pages, not its slot's full span)."""
        ids = np.asarray(slot_ids)
        width = int(max(1, self._slot_pages[ids].max()))
        bt = jnp.asarray(self.block_table[ids, :width], jnp.int32)
        return self._gather(self.pool, bt)

    def _fit_rows(self, rows):
        """Pad logical rows out to the block-table width (fill -1 for
        ``pos_ids``) — short preemption payloads land here."""
        width = self.block_table.shape[1] * self.page_size

        def fit(path, ba, sa, row):
            if ba == NO_AXIS:
                return row
            row = jnp.asarray(row)
            pad = width - row.shape[sa]
            if pad <= 0:
                return row
            widths = [(0, 0)] * row.ndim
            widths[sa] = (0, pad)
            fill = -1 if _is_pos_ids(path) else 0
            return jnp.pad(row, widths, constant_values=fill)

        return jax.tree_util.tree_map_with_path(
            fit, self.batch_axes, self.seq_axes, rows)

    def restore(self, slot: int, rows, pos: int):
        """Scatter a preempted row set back into a (re)allocated slot —
        possibly onto *different* physical pages than it left (the layout
        is free-list order); bitwise resume holds because pages are carried
        bit for bit and the mask only keys on ``pos_ids``."""
        self.extend(slot, int(pos))
        self.write_rows([slot], rows)
        self.pos[slot] = int(pos)

    def advance(self, slot_ids, counts):
        for s, n in zip(slot_ids, counts):
            self.pos[s] += int(n)
            self.extend(s, int(self.pos[s]))
