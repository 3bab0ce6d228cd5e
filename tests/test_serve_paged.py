"""True paged attention (repro.serve.cache.PagedKVCacheManager + engine).

The §8 acceptance pins: block-table indirection is a memory-layout change,
never a numerics change — paged replay is bitwise the contiguous replay
(dense + SWA), the speculative accepted prefix is bitwise the greedy
sequence, and the free-list allocator admits strictly more concurrent
work than contiguous slots at the same page budget (the churn workload).
Full-length GQA and MLA stacks keep their entries in the page pool
(``TestInPoolStep``); ring caches keep the gather/scatter step.  With
``overlap=True`` the in-pool engine launches each tick before it lands the
one before and serves the same tokens (``TestOverlap``); left unset, it
does so wherever that step runs (``TestOverlapDefault``).
"""
import functools
import hashlib

import jax
import numpy as np
import pytest

from repro import scenarios as sc
from repro.configs import registry
from repro.models.model import Model
from repro.serve.cache import (KVCacheManager, PageAllocator,
                               PagedKVCacheManager)
from repro.serve.engine import Engine, Request


@pytest.fixture(scope="module")
def dense():
    cfg = registry.get("llama3.2-1b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def swa():
    cfg = registry.get("mixtral-8x7b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def _prompt(cfg, rid, n=5):
    return ((np.arange(n) * 3 + rid * 7) % cfg.vocab_size).astype(np.int32)


def _outs(cfg, model, params, n_req=4, max_new=12, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("eos_id", -1)
    kw.setdefault("warmup", False)
    eng = Engine(model, params, **kw)
    for rid in range(n_req):
        eng.submit(Request(rid, _prompt(cfg, rid), max_new=max_new))
    eng.run()
    return eng, {r.rid: tuple(r.out) for r in eng.finished}


class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        al = PageAllocator(4)
        assert al.free_pages == 4 and al.used_pages == 0
        a = al.alloc(3)
        assert len(a) == 3 and len(set(a)) == 3
        assert al.free_pages == 1 and al.used_pages == 3
        al.free(a[:2])
        assert al.free_pages == 3
        b = al.alloc(3)  # reuses the freed pages
        assert al.free_pages == 0 and sorted(a[2:] + b) == list(range(4))

    def test_exhaustion_raises(self):
        al = PageAllocator(2)
        al.alloc(2)
        with pytest.raises(RuntimeError, match="exhausted"):
            al.alloc(1)

    def test_double_free_and_invalid_page_raise(self):
        al = PageAllocator(3)
        pages = al.alloc(2)
        al.free(pages)
        with pytest.raises(ValueError, match="double free"):
            al.free([pages[0]])
        with pytest.raises(ValueError, match="invalid page"):
            al.free([3])
        with pytest.raises(ValueError, match="invalid page"):
            al.free([-1])
        # the free list stayed sane: all three pages allocate exactly once
        assert sorted(al.alloc(3)) == [0, 1, 2]


class TestPagedManagerLifecycle:
    def test_non_contiguous_allocation(self, dense):
        """Pages come from the free list, not from a per-slot span: after
        interleaved alloc/free, a slot's block table holds non-adjacent
        physical pages (the whole point of the indirection)."""
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=3, max_len=64, page_size=16)
        a = mgr.allocate(5)
        b = mgr.allocate(5)
        mgr.advance([a], [20])  # a claims a second page *after* b's first
        pages_a = list(mgr.block_table[a, :2])
        assert pages_a[1] - pages_a[0] != 1  # b's page sits in between
        freed = int(mgr.block_table[b, 0])
        mgr.free(b)
        assert not mgr.allocator._owned[freed]  # b's page back in the pool
        c = mgr.allocate(5)  # new slot allocates without relocating a
        assert list(mgr.block_table[a, :2]) == pages_a
        assert mgr.block_table[c, 0] != mgr.null_page
        assert mgr.pages_in_use == mgr.recount_pages() == 3

    def test_incremental_pages_pinned_against_recount(self, dense):
        """The O(1) counter, the O(slots*width) recount, and the allocator
        ledger agree after every mutation."""
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=2, max_len=64, page_size=16)

        def pinned():
            assert (mgr.pages_in_use == mgr.recount_pages()
                    == mgr.allocator.used_pages)

        s = mgr.allocate(5)
        pinned()
        mgr.advance([s], [30])  # 30 tokens -> 2 pages
        pinned()
        assert mgr.pages_in_use == 2
        mgr.extend(s, 50)
        pinned()
        assert mgr.pages_in_use == 4 and mgr.peak_pages == 4
        mgr.trim(s, 30)
        pinned()
        assert mgr.pages_in_use == 2
        t = mgr.allocate(3)
        pinned()
        mgr.free(s)
        mgr.free(t)
        pinned()
        assert mgr.pages_in_use == 0 and mgr.peak_pages == 4

    def test_trim_is_the_spec_rollback(self, dense):
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=1, max_len=64, page_size=16)
        s = mgr.allocate(4)
        mgr.extend(s, 64)
        assert mgr.slot_pages(s) == 4
        assert mgr.trim(s, 17) == 2  # keep ceil(17/16) = 2 pages
        assert mgr.slot_pages(s) == 2 and mgr.allocator.free_pages == 2
        assert mgr.trim(s, 32) == 0  # trim never grows
        assert mgr.trim(s, 0) == 1   # but always keeps one page
        assert mgr.slot_pages(s) == 1

    def test_slot_free_guards(self, dense):
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=2, max_len=64, page_size=16)
        s = mgr.allocate(4)
        mgr.free(s)
        with pytest.raises(ValueError, match="double free"):
            mgr.free(s)
        with pytest.raises(ValueError, match="invalid slot"):
            mgr.free(2)

    @pytest.mark.parametrize("release", ["free", "trim"])
    def test_release_invalidates_in_place(self, dense, release):
        """A release writes only the freed pages' ``pos_ids``: the pool is
        donated, so the K/V leaves keep their buffers (no whole-pool copy)
        and the other slot's entries are untouched."""
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=2, max_len=64, page_size=16)
        a, b = mgr.allocate(40), mgr.allocate(20)
        mgr.extend(a, 40)
        mgr.extend(b, 20)
        mgr.pool = jax.tree_util.tree_map(lambda x: x + 1, mgr.pool)
        kv = {n: mgr.pool["stack"][n] for n in ("k", "v")}
        at = {n: x.unsafe_buffer_pointer() for n, x in kv.items()}
        pages = mgr.block_table[a, :3].copy()
        if release == "free":
            mgr.free(a)
        else:
            assert mgr.trim(a, 16) == 2
            pages = pages[1:]
        assert all(x.is_deleted() for x in kv.values())
        assert {n: mgr.pool["stack"][n].unsafe_buffer_pointer()
                for n in kv} == at
        ids = np.asarray(mgr.pool["stack"]["pos_ids"])
        assert (ids[:, pages] == -1).all()
        kept = [int(p) for p in mgr.block_table[b] if p != mgr.null_page]
        assert (ids[:, kept] == 0).all()  # 0 = -1 + 1: not invalidated

    def test_inverse_map_inverts_the_block_table(self, dense):
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=2, max_len=64, page_size=16)
        a = mgr.allocate(5)
        mgr.advance([a], [20])
        b = mgr.allocate(5)
        inv = mgr.inverse_map()
        B, W = mgr.block_table.shape
        for s in range(B):
            for j in range(W):
                pg = mgr.block_table[s, j]
                if pg != mgr.null_page:
                    assert inv[pg] == s * W + j
        # unallocated pages and the null page map to the fill source
        assert inv[mgr.null_page] == B * W
        unalloc = set(range(mgr.total_pages)) - {
            int(p) for p in mgr.block_table.reshape(-1)
            if p != mgr.null_page}
        assert all(inv[p] == B * W for p in unalloc)

    def test_null_page_stays_invalid_through_scatter_all(self, dense):
        """Every unallocated block-table entry aliases the null page; the
        fused-step writeback must leave it (and any unallocated page)
        invalid, or stale entries would surface under a future owner."""
        import jax.numpy as jnp
        _, model, _ = dense
        mgr = PagedKVCacheManager(model, slots=2, max_len=64, page_size=16)
        s = mgr.allocate(4)
        bt = jnp.asarray(mgr.block_table, jnp.int32)
        logical = mgr.gather_logical(mgr.pool, bt)
        # poison the logical view everywhere; only owned pages may keep it
        logical = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, 7), logical)
        pool = mgr.scatter_all(mgr.pool, logical,
                               jnp.asarray(mgr.inverse_map(), jnp.int32))
        ids = np.asarray(pool["stack"]["pos_ids"])
        owned = int(mgr.block_table[s, 0])
        assert (ids[:, owned] == 7).all()          # owned page written
        assert (ids[:, mgr.null_page] == -1).all()  # null page inert
        unowned = next(p for p in range(mgr.total_pages) if p != owned)
        assert (ids[:, unowned] == -1).all()       # unallocated page inert


class TestPagedEngineBitwise:
    def test_dense_paged_and_spec_match_contiguous(self, dense):
        cfg, model, params = dense
        _, ref = _outs(cfg, model, params)
        _, paged = _outs(cfg, model, params, paged=True)
        eng, spec = _outs(cfg, model, params, paged=True, speculate=3)
        assert ref == paged, "block-table indirection changed the tokens"
        assert ref == spec, "speculative accepted prefix != greedy"
        assert eng.spec_accepted > 0 and eng.spec_accept_rate > 0.0
        assert eng.mgr.pages_in_use == eng.mgr.recount_pages() == 0

    def test_swa_paged_matches_contiguous(self, swa):
        cfg, model, params = swa
        _, ref = _outs(cfg, model, params, n_req=3, max_new=8)
        _, paged = _outs(cfg, model, params, n_req=3, max_new=8, paged=True)
        assert ref == paged

    def test_spec_requires_greedy_and_full_window(self, dense):
        cfg, model, params = dense
        with pytest.raises(ValueError, match="greedy"):
            Engine(model, params, batch_slots=2, max_len=64,
                   temperature=0.7, speculate=2, warmup=False)
        swa_cfg = cfg.replace(sliding_window=32)
        with pytest.raises(ValueError, match="sliding_window"):
            Engine(Model(swa_cfg), params, batch_slots=2, max_len=64,
                   speculate=2, warmup=False)


class TestServeReplayPaged:
    """Fingerprint-level pins on the full closed loop (engine + admission
    + rails + energy ledger)."""

    @pytest.fixture(scope="class")
    def replays(self, dense):
        _, model, params = dense
        day = sc.serve_day(ticks=6, cool_at=3)
        wl = sc.poisson_burst(burst_at=1, burst_n=5, seed=0)
        kw = dict(engine_steps=4, drain_ticks=16)
        return {
            "contig": sc.serve_replay(day, wl, model, params, **kw),
            "paged": sc.serve_replay(day, wl, model, params, paged=True,
                                     **kw),
            "spec": sc.serve_replay(day, wl, model, params, paged=True,
                                    speculate=3, **kw),
        }

    def test_paged_fingerprint_bitwise_contiguous(self, replays):
        # outputs AND caps AND energy: the whole day replays bit for bit
        assert replays["paged"].fingerprint == replays["contig"].fingerprint

    def test_spec_outputs_match_but_day_compresses(self, replays):
        """Speculation must not change a single token — but it legitimately
        changes the *day* (fewer engine ticks -> different load trace ->
        different rail/energy fingerprint), so the pin is output equality,
        not fingerprint equality."""
        assert replays["spec"].outputs == replays["contig"].outputs
        assert replays["spec"].finished == replays["contig"].finished


class TestChurnAdmission:
    def test_paged_admits_strictly_more_at_equal_page_budget(self, dense):
        """16 pages = 4 contiguous slots (max_len=64, page_size=16). The
        paged engine runs 8 slots over the same 16 pages because short
        churn requests only ever hold 1-2 pages each — the vLLM
        fragmentation argument, live."""
        cfg, model, params = dense
        wl = sc.churn_requests()

        def run(**kw):
            eng = Engine(model, params, max_len=64, eos_id=-1,
                         warmup=False, **kw)
            for a in wl.arrivals:
                eng.submit(Request(a.rid, _prompt(cfg, a.rid, a.prompt_len),
                                   max_new=a.max_new))
            peak = 0
            while eng.step():
                peak = max(peak, sum(r is not None for r in eng.slot_req))
                assert (eng.mgr.pages_in_use == eng.mgr.recount_pages())
            assert len(eng.finished) == len(wl.arrivals)
            return eng, peak

        eng_c, peak_c = run(batch_slots=4)              # 4 slots * 4 pages
        eng_p, peak_p = run(batch_slots=8, paged=True, total_pages=16)
        assert peak_c <= 4
        assert peak_p > peak_c, (peak_p, peak_c)
        assert eng_p.mgr.peak_pages <= 16
        assert eng_p.mgr.pages_in_use == eng_p.mgr.recount_pages() == 0
        # same tokens either way — admission order changes, outputs don't
        assert ({r.rid: tuple(r.out) for r in eng_c.finished}
                == {r.rid: tuple(r.out) for r in eng_p.finished})


class TestPagedPreemption:
    def test_page_exact_eviction_and_bitwise_resume(self, dense):
        cfg, model, params = dense
        _, ref = _outs(cfg, model, params, n_req=2, max_new=16)

        eng = Engine(model, params, batch_slots=2, max_len=64, eos_id=-1,
                     warmup=False, paged=True)
        for rid in range(2):
            eng.submit(Request(rid, _prompt(cfg, rid), max_new=16))
        for _ in range(4):
            eng.step()
        pages_before = eng.mgr.pages_in_use
        assert eng.preempt_to(1) == 1
        # page-exact accounting: the parked payload counts exactly the
        # pages the victim held, and those pages actually returned to the
        # admission budget (in_use dropped by the same amount)
        victim_rid = eng.queue[0].rid
        held = eng.pool.put_pages(victim_rid)
        assert held >= 1 and eng.pool.pages_held == held
        assert eng.mgr.pages_in_use == pages_before - held
        assert eng.mgr.pages_in_use == eng.mgr.recount_pages()
        eng.run()
        assert {r.rid: tuple(r.out) for r in eng.finished} == ref
        assert eng.pool.pages_held == 0 and eng.preempts == 1


@pytest.fixture(scope="module")
def mla():
    cfg = registry.get("deepseek-v2-236b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    return cfg, model, params


# (tick, rid, prompt length, max_new): three slots of three 8-token pages
# and chunks of 8; rids 2-4 arrive mid-flight, 3 and 4 into released slots
WAVES = [(0, 0, 12, 9), (0, 1, 5, 14), (2, 2, 11, 6), (5, 3, 3, 10),
         (6, 4, 9, 5)]


def _serve_waves(model, params, cfg, on_fused=None, **kw):
    """Serve WAVES through an engine; ``on_fused(eng, run, fn, plan)``
    wraps every fused tick's launch.  Returns the engine and the tokens by
    rid."""
    opts = dict(batch_slots=3, max_len=64, page_size=8, prefill_chunk=8,
                eos_id=-1, warmup=False)
    eng = Engine(model, params, **{**opts, **kw})
    if on_fused is not None:
        launch = eng._launch
        eng._launch = lambda fn, plan, prev=None: on_fused(
            eng, lambda f, p: launch(f, p, prev), fn, plan)
    due = list(WAVES)
    tick = 0
    while due or eng.step():
        while due and due[0][0] <= tick:
            _, rid, n, m = due.pop(0)
            eng.submit(Request(rid, _prompt(cfg, rid, n), max_new=m))
        if due:
            eng.step()
        tick += 1
    return eng, {r.rid: tuple(r.out) for r in eng.finished}


class TestInPoolStep:
    """Full-length GQA stacks keep K/V in the page pool: each layer writes
    only the tick's new entries into their pages and reads its own pages
    through the block table (``Model.decode_paged``)."""

    @pytest.mark.parametrize("speculate", [0, 3])
    def test_mixed_ticks_bitwise_contiguous(self, dense, speculate):
        cfg, model, params = dense
        _, ref = _serve_waves(model, params, cfg)
        widths = []

        def spy(eng, run, fn, plan):
            widths.append((plan.width, tuple(plan.n_valid)))
            return run(fn, plan)

        eng, got = _serve_waves(model, params, cfg, on_fused=spy, paged=True,
                                speculate=speculate, overlap=False)
        assert got == ref, "the in-pool step changed the tokens"
        assert eng.kv_pool_ticks == len(widths) > 0
        chunk = [nv for w, nv in widths if w == 8]
        assert any(0 in nv and 1 in nv and any(1 < n < 8 for n in nv)
                   for nv in chunk), chunk  # empty, decode, partial prompt
        if speculate:
            assert eng.spec_accepted > 0
        assert eng.mgr.pages_in_use == eng.mgr.recount_pages() == 0

    def test_pool_after_each_tick_matches_gather_decode_scatter(self, dense):
        """Every tick's pool equals what the gather -> ``Model.decode`` ->
        ``scatter_all`` step leaves from the same pool: ``pos_ids``
        everywhere, K/V at every entry with ``pos_ids >= 0``; the null page
        stays invalid."""
        import jax.numpy as jnp
        cfg, model, params = dense
        checked = []

        @functools.partial(jax.jit, static_argnums=0)
        def old_step(mgr, pool, bt, inv, tokens, pos, n_valid):
            _, logical = model.decode(params, tokens,
                                      mgr.gather_logical(pool, bt), pos,
                                      n_valid=n_valid)
            return mgr.scatter_all(pool, logical, inv)

        def check(eng, run, fn, plan):
            mgr = eng.mgr
            before = jax.tree_util.tree_map(jnp.copy, mgr.pool)
            bt = jnp.asarray(mgr.block_table, jnp.int32)
            inv = jnp.asarray(mgr.inverse_map(), jnp.int32)
            out = run(fn, plan)
            ref = old_step(mgr, before, bt, inv, jnp.asarray(plan.tokens),
                           jnp.asarray(plan.pos),
                           jnp.asarray(plan.n_valid))["stack"]
            got = mgr.pool["stack"]
            ids = np.asarray(got["pos_ids"])
            np.testing.assert_array_equal(ids, np.asarray(ref["pos_ids"]))
            live = ids >= 0
            for name in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(got[name])[live], np.asarray(ref[name])[live])
            assert (ids[:, mgr.null_page] == -1).all()
            checked.append(plan.width)
            return out

        _serve_waves(model, params, cfg, on_fused=check, paged=True,
                     overlap=False)
        assert 1 in checked and 8 in checked

    def test_write_near_max_len_keeps_live_entries(self, dense):
        """PERF.md §7's witness (2 layers x 64, ``max_len`` 64, chunk 16):
        a slot decoding at position 54 keeps its 54 cached entries through
        another slot's prompt tick and adds the 55th.  The parent's paged
        step held 49: its logical write (``attention._row_update``) clamps
        an S-wide row to start at ``T - S``, over live entries.  The
        contiguous manager still writes through ``_row_update`` and still
        clamps there."""
        cfg, model, params = dense
        eng = Engine(model, params, batch_slots=2, max_len=64,
                     prefill_chunk=16, page_size=16, paged=True, eos_id=-1,
                     overlap=False, warmup=False)
        a = Request(0, _prompt(cfg, 0, 40), max_new=20)
        eng.submit(a)
        slot = None
        while slot is None or eng.mgr.pos[slot] < 54:
            eng.step()
            slot = eng.slot_req.index(a)
        assert eng.mgr.pos[slot] == 54
        b = Request(1, _prompt(cfg, 1, 20), max_new=4)
        eng.submit(b)
        eng.step()
        assert b.fed == 16  # the tick was chunk-wide
        ids = np.asarray(eng.mgr.read_rows([slot])["stack"]["pos_ids"])
        for layer in ids[:, 0]:
            assert sorted(layer[layer >= 0]) == list(range(55))

    def test_ring_caches_keep_the_gather_path(self, swa):
        """Sliding-window (ring) stacks do not hold full-length pages: they
        keep gather -> decode -> scatter, bitwise the contiguous engine,
        and the in-pool entry point refuses them.  (MLA latents are in the
        pool: ``test_latent_pool_bitwise_contiguous``.)"""
        import jax.numpy as jnp
        cfg, model, params = swa
        _, ref = _outs(cfg, model, params, n_req=3, max_new=6)
        eng, paged = _outs(cfg, model, params, n_req=3, max_new=6,
                           paged=True)
        assert paged == ref
        assert eng.kv_pool_ticks == 0 and eng.ticks > 0
        bt = jnp.asarray(eng.mgr.block_table, jnp.int32)
        with pytest.raises(ValueError, match="full-length GQA"):
            model.decode_paged(params, jnp.zeros((2, 1), jnp.int32),
                               eng.mgr.pool, bt, jnp.zeros(2, jnp.int32))

    @pytest.mark.parametrize("speculate", [0, 3])
    def test_latent_pool_bitwise_contiguous(self, mla, speculate):
        """DeepSeek-V2's latents (its dense first layer included) stay in
        the page pool on every tick, and the served tokens are bitwise the
        contiguous engine's: the same absorbed decode and expanded prefill
        over the same entries."""
        cfg, model, params = mla
        _, ref = _serve_waves(model, params, cfg)
        eng, got = _serve_waves(model, params, cfg, paged=True,
                                speculate=speculate)
        assert got == ref
        assert eng.kv_pool_ticks == eng.ticks > 0
        assert eng.routed_local > 0 and eng.experts_touched > 0
        assert eng.mgr.pages_in_use == eng.mgr.recount_pages() == 0


KINDS = ["contiguous", "gather", "in_pool"]


def _manager(kind, dense, swa):
    """A two-slot manager of ``kind``: contiguous and in-pool serve the
    dense stack, the gather path the sliding-window one."""
    cfg, model, params = swa if kind == "gather" else dense
    cls = KVCacheManager if kind == "contiguous" else PagedKVCacheManager
    return cfg, model, params, cls(model, slots=2, max_len=32, page_size=8)


def _same(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestManagerStep:
    """Each manager owns how its storage meets the model: ``decode_step``
    is ``Model.decode`` on the contiguous cache, ``Model.decode_paged`` in
    the pool, and ``Model.decode`` between the block-table gather and the
    write-back on the gather path; the engine's one fused step calls it."""

    @pytest.mark.parametrize("kind", KINDS)
    def test_manager_step_is_the_model_call(self, dense, swa, kind):
        import jax.numpy as jnp
        cfg, model, params, mgr = _manager(kind, dense, swa)
        assert (mgr.kv_pool, mgr.donates) == (kind == "in_pool",
                                              kind != "contiguous")
        for n in (5, 11):
            mgr.allocate(n)
        state = ref = mgr.cache
        # a prompt chunk (one slot partial), then a decode tick
        ticks = [(np.arange(16).reshape(2, 8) * 3 % cfg.vocab_size, [5, 8]),
                 (np.asarray([[7], [9]]), [1, 1])]
        for tokens, n_valid in ticks:
            toks = jnp.asarray(tokens, jnp.int32)
            # a copy: advance moves mgr.pos, maybe before the upload reads it
            pos = jnp.asarray(mgr.pos.copy())
            nv = jnp.asarray(n_valid, jnp.int32)
            mgr.advance([0, 1], n_valid)  # paged: claims the pages written
            tables = mgr.tables()
            logits, state, stats = mgr.decode_step(params, state, tables,
                                                   toks, pos, nv)
            if kind == "contiguous":
                assert tables == () and not mgr.tables_stale()
                want, ref = model.decode(params, toks, ref, pos, n_valid=nv)
                _same((logits, state, stats), (want, ref, {}))
            elif kind == "in_pool":
                want, ref, got = model.decode_paged(params, toks, ref,
                                                    tables[0], pos,
                                                    n_valid=nv)
                _same((logits, state, stats), (want, ref, got))
            else:
                bt, _ = tables
                want, logical = model.decode(
                    params, toks, mgr.gather_logical(ref, bt), pos,
                    n_valid=nv)
                # the pool holds what the model call wrote, page for page
                _same((logits, mgr.gather_logical(state, bt), stats),
                      (want, logical, {}))
                ids = np.asarray(state["stack"]["pos_ids"])
                assert (ids[:, mgr.null_page] == -1).all()
                ref = state

    @pytest.mark.parametrize("kind", ["gather", "in_pool"])
    def test_tables_upload_only_when_the_block_table_changes(self, dense,
                                                             swa, kind):
        _, _, _, mgr = _manager(kind, dense, swa)
        a = mgr.allocate(5)
        assert mgr.tables_stale()
        first = mgr.tables()
        assert not mgr.tables_stale() and mgr.tables() is first
        mgr.advance([a], [6])  # inside its first page: the table stands
        assert not mgr.tables_stale() and mgr.tables() is first
        mgr.advance([a], [6])  # into a second page
        assert mgr.tables_stale()
        now = mgr.tables()
        assert now is not first and mgr.tables() is now
        np.testing.assert_array_equal(np.asarray(now[0]), mgr.block_table)
        if kind == "gather":  # the write-back's inverse page map rides along
            assert len(now) == 2
            np.testing.assert_array_equal(np.asarray(now[1]),
                                          mgr.inverse_map())
        else:
            assert len(now) == 1
        mgr.free(a)
        assert mgr.tables_stale()

    @pytest.mark.parametrize("kind", KINDS)
    def test_synchronous_tick_lands_in_its_own_step(self, dense, swa, kind):
        """``overlap=False``: every step lands the tick it launched, so no
        tick is in flight after any step and none is launched ahead; the
        tokens are the overlapped in-pool engine's (the contiguous
        engine's for the sliding-window stack, which cannot overlap)."""
        cfg, model, params, _ = _manager(kind, dense, swa)

        def serve(**kw):
            eng = Engine(model, params, batch_slots=3, max_len=64,
                         page_size=8, prefill_chunk=8, eos_id=-1,
                         warmup=False, **kw)
            inflight = []
            eng.on_tick.append(lambda _: inflight.append(eng._inflight))
            for _, rid, n, m in WAVES:
                eng.submit(Request(rid, _prompt(cfg, rid, n), max_new=m))
            eng.run()
            return eng, inflight, {r.rid: tuple(r.out) for r in eng.finished}

        eng, inflight, got = serve(paged=kind != "contiguous", overlap=False)
        assert len(inflight) == eng.ticks > 0
        assert all(fl is None for fl in inflight) and eng.ticks_ahead == 0
        if kind == "gather":
            _, _, ref = serve(paged=False)
        else:
            ahead, _, ref = serve(paged=True)
            assert ahead.ticks_ahead > 0
        assert got == ref


@pytest.fixture(scope="module")
def qwen3():
    cfg = registry.get("qwen3-1.7b").reduced()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


class TestGqaStepPinned:
    """The GQA in-pool step is the program it was before MLA moved into the
    pool: its lowered StableHLO (without debug info) and what it serves,
    for the qwen3 preset, hash as they did on the tree before that change
    (JAX 0.9.0, CPU)."""

    PROGRAM = {1: "459b23d6b95136f9cd646cf9907a7a5990fd6f4f601a15f37201951362aaf45a",
               8: "666208b439e0c7d3e98de5395c956784fc201aaa8671c655253cd3dea6e2290d"}
    POOL = "233a17436bec541e723a129a4dcbb1bb56c3154c7415eaa248aaba396805b393"
    TOKENS = {0: (187, 187, 187, 200, 10, 181, 187, 187, 200), 1: (180,) * 14,
              2: (169, 219, 219, 247, 6, 50),
              3: (169, 169, 169) + (239,) * 7, 4: (209, 227, 208, 58, 58)}

    @pytest.mark.parametrize("width", [1, 8])
    def test_program_unchanged(self, qwen3, width):
        import jax.numpy as jnp
        _, model, _ = qwen3
        eng = Engine(model, None, batch_slots=3, max_len=64, page_size=8,
                     prefill_chunk=8, paged=True, eos_id=-1, warmup=False)

        def shape(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

        slots = jax.ShapeDtypeStruct((3,), jnp.int32)
        text = eng._fused.lower(
            model.abstract_params(), shape(eng.mgr.pool),
            *shape(eng.mgr.tables()),
            jax.ShapeDtypeStruct((3, width), jnp.int32), slots, slots,
            shape(eng.key)).as_text(debug_info=False)
        assert hashlib.sha256(text.encode()).hexdigest() == \
            self.PROGRAM[width]

    def test_served_tokens_and_pool_bitwise(self, qwen3):
        cfg, model, params = qwen3
        eng, got = _serve_waves(model, params, cfg, paged=True,
                                overlap=False)
        assert got == self.TOKENS
        assert eng.kv_pool_ticks == eng.ticks and eng.routed_local == 0
        h = hashlib.sha256()
        for name in ("k", "v", "pos_ids"):
            h.update(np.asarray(eng.mgr.pool["stack"][name]).tobytes())
        assert h.hexdigest() == self.POOL


class TestOverlap:
    """``overlap=True``: each step launches tick n, then syncs and commits
    tick n-1, whose sampled tokens feed tick n's decode rows on the
    device.  The served tokens are the synchronous engine's."""

    @staticmethod
    def _eos(ref):
        """The token the reference serves most often: with it as EOS some
        requests end early, one tick before the host can see it."""
        toks = [t for out in ref.values() for t in out]
        return max(sorted(set(toks)), key=toks.count)

    @pytest.mark.parametrize("eos", [False, True])
    @pytest.mark.parametrize("arch", ["dense", "mla"])
    def test_tokens_match_the_synchronous_engine(self, request, arch, eos):
        cfg, model, params = request.getfixturevalue(arch)
        _, ref = _serve_waves(model, params, cfg, paged=True, overlap=False)
        kw = {"eos_id": self._eos(ref)} if eos else {}
        if eos:
            _, ref = _serve_waves(model, params, cfg, paged=True,
                                  overlap=False, **kw)
            assert any(len(out) < m for (_, rid, _, m) in WAVES
                       for r, out in ref.items() if r == rid)
        eng, got = _serve_waves(model, params, cfg, paged=True, overlap=True,
                                **kw)
        assert got == ref
        assert eng.kv_pool_ticks > 0 and eng._inflight is None
        assert eng.mgr.pages_in_use == eng.mgr.recount_pages() == 0

    def test_launches_before_it_lands(self, dense):
        """Every tick but the first is launched before the one before it
        is synced; the last, which every request ends in, lands in the
        step that launches it, since no tick can follow it."""
        cfg, model, params = dense
        seq = []
        eng = Engine(model, params, batch_slots=2, max_len=64, page_size=8,
                     prefill_chunk=8, paged=True, overlap=True, eos_id=-1,
                     warmup=False)
        launch, sync = eng._launch, eng._sync
        eng._launch = lambda *a: (seq.append("L"), launch(*a))[1]
        eng._sync = lambda *a: (seq.append("S"), sync(*a))[1]
        for rid in range(2):
            eng.submit(Request(rid, _prompt(cfg, rid), max_new=4))
        steps = 0
        while eng.step():
            steps += 1
        assert "".join(seq) == "L" + "LS" * (len(seq) // 2 - 1) + "S"
        # the step that launches and lands the last tick reports no work
        # left: one step per launch, and nothing in flight after it
        assert steps + 1 == seq.count("L") == eng.ticks
        assert eng._inflight is None
        assert [len(r.out) for r in eng.finished] == [4, 4]

    def test_preemption_lands_the_tick_in_flight(self, dense):
        """An eviction commits the tick in flight first, so the request
        parks the rows and tokens of every launched tick and resumes to
        the tokens it would have served."""
        cfg, model, params = dense
        _, ref = _outs(cfg, model, params, n_req=2, max_new=12, paged=True,
                       overlap=False)
        eng = Engine(model, params, batch_slots=2, max_len=64, paged=True,
                     overlap=True, eos_id=-1, warmup=False)
        for rid in range(2):
            eng.submit(Request(rid, _prompt(cfg, rid), max_new=12))
        for _ in range(5):
            eng.step()
        assert eng._inflight is not None
        assert eng.preempt_to(1) == 1
        assert eng._inflight is None and eng.pool.pages_held > 0
        eng.run()
        assert {r.rid: tuple(r.out) for r in eng.finished} == ref
        assert eng.preempts == 1 and eng.pool.pages_held == 0

    def test_requires_the_in_pool_step(self, dense, swa):
        cfg, model, params = dense
        for kw in ({"paged": False}, {"paged": True, "speculate": 2}):
            with pytest.raises(ValueError, match="overlap"):
                Engine(model, params, batch_slots=2, max_len=64,
                       overlap=True, warmup=False, **kw)
        _, swa_model, swa_params = swa
        with pytest.raises(ValueError, match="overlap"):
            Engine(swa_model, swa_params, batch_slots=2, max_len=64,
                   paged=True, overlap=True, warmup=False)


class TestOverlapDefault:
    """Left unset, ``overlap`` is on wherever the overlapped step runs (the
    in-pool paged step without speculation) and off everywhere else,
    without raising."""

    @pytest.mark.parametrize("arch, kw, ahead", [
        ("dense", {"paged": True}, True),
        ("mla", {"paged": True}, True),
        ("dense", {"paged": True, "speculate": 3}, False),
        ("swa", {"paged": True}, False),
        ("dense", {"paged": False}, False),
    ])
    def test_overlaps_where_the_in_pool_step_runs(self, request, arch, kw,
                                                  ahead):
        cfg, model, params = request.getfixturevalue(arch)
        _, ref = _serve_waves(model, params, cfg, overlap=False, **kw)
        eng, got = _serve_waves(model, params, cfg, **kw)
        assert got == ref
        assert (eng.ticks_ahead > 0) == ahead and eng._inflight is None
        if not ahead:  # asked for where it cannot run, it still raises
            with pytest.raises(ValueError, match="overlap"):
                Engine(model, params, batch_slots=3, max_len=64,
                       overlap=True, warmup=False, **kw)

    def test_open_loop_arrivals(self, dense):
        """Requests offered between steps as an open-loop server offers
        them: one while a tick is in flight, which the next step admits
        and launches, and two after the engine has emptied and idled.
        Nothing stays in flight while the engine is empty, a first token
        is stamped only once the prompt's last chunk has landed, after its
        submission, and the tokens are the synchronous engine's."""
        cfg, model, params = dense
        # (step, rid, prompt length, max_new): the engine is empty by step 30
        arrivals = [(0, 0, 12, 6), (1, 1, 5, 9), (3, 2, 11, 4),
                    (30, 3, 3, 5), (31, 4, 9, 3)]

        def serve(overlap):
            eng = Engine(model, params, batch_slots=3, max_len=64,
                         page_size=8, prefill_chunk=8, paged=True, eos_id=-1,
                         overlap=overlap, warmup=False)
            due, reqs, idle, ahead, offered = list(arrivals), [], [], 0, 0
            for step in range(40):
                mid_flight = []
                while due and due[0][0] <= step:
                    _, rid, n, m = due.pop(0)
                    reqs.append(Request(rid, _prompt(cfg, rid, n), max_new=m))
                    eng.submit(reqs[-1])
                    if eng._inflight is not None:
                        mid_flight.append(reqs[-1])
                if all(r.done for r in reqs):  # waiting for arrivals
                    idle.append(eng._inflight)
                    continue
                launched, was = eng.kv_pool_ticks, eng._inflight is not None
                eng.step()
                ahead += was and eng.kv_pool_ticks > launched
                offered += len(mid_flight)
                for r in mid_flight:  # admitted and launched at once
                    assert r.t_admit is not None
                    assert r in eng._inflight.reqs.values()
                for r in reqs:
                    assert not r.out or r.fed == len(r.prompt)
            assert not due and all(r.done for r in reqs)
            assert eng._inflight is None and idle
            assert all(fl is None for fl in idle)
            assert all(r.t_submit <= r.t_admit <= r.t_first <= r.t_done
                       for r in reqs)
            assert eng.ticks_ahead == ahead
            assert (offered > 0) == (overlap is None)
            return eng, {r.rid: tuple(r.out) for r in reqs}

        _, ref = serve(False)
        eng, got = serve(None)
        assert got == ref
        assert 0 < eng.ticks_ahead < eng.kv_pool_ticks

    def test_an_empty_plan_lands_the_tick_in_flight(self, dense):
        """A step that plans nothing still syncs and commits the tick in
        flight."""
        cfg, model, params = dense
        eng = Engine(model, params, batch_slots=2, max_len=64, paged=True,
                     eos_id=-1, warmup=False)
        req = Request(0, _prompt(cfg, 0), max_new=6)
        eng.submit(req)
        eng.step()
        eng.step()
        assert eng._inflight is not None and len(req.out) == 1
        plan = eng._plan
        eng._plan = lambda: (None, False)
        eng.step()
        assert eng._inflight is None and len(req.out) == 2
        eng._plan = plan
        eng.run()
        _, ref = _outs(cfg, model, params, n_req=1, max_new=6, paged=True,
                       overlap=False)
        assert tuple(req.out) == ref[0]
