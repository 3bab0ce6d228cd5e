"""KVCacheManager (repro.serve.cache)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models.model import Model
from repro.serve.cache import NO_AXIS, KVCacheManager


@pytest.fixture(scope="module")
def dense():
    cfg = registry.get("llama3.2-1b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


class TestKVCacheManager:
    def test_probes_batch_axis_on_every_leaf(self, dense):
        _, model, _ = dense
        mgr = KVCacheManager(model, slots=3, max_len=32)
        axes = jax.tree_util.tree_leaves(mgr.batch_axes)
        assert axes and all(a != NO_AXIS for a in axes)
        # probing is structural: the axis must actually carry slot count
        for ax, leaf in zip(axes, jax.tree_util.tree_leaves(mgr.cache)):
            assert leaf.shape[ax] == 3

    def test_slot_lifecycle_and_recycling(self, dense):
        _, model, _ = dense
        mgr = KVCacheManager(model, slots=2, max_len=32)
        a = mgr.allocate(5)
        b = mgr.allocate(7)
        assert sorted([a, b]) == [0, 1] and mgr.free_slots == []
        mgr.advance([a], [5])
        assert mgr.pos[a] == 5
        mgr.free(a)
        assert a in mgr.free_slots and mgr.pos[a] == 0
        c = mgr.allocate(3)  # recycled, no new arrays
        assert c == a

    def test_free_guards_double_free_and_bad_slot(self, dense):
        """Regression (§9 satellite): a double free used to push the slot
        onto the free list twice, handing the same slot to two requests."""
        _, model, _ = dense
        mgr = KVCacheManager(model, slots=2, max_len=32)
        s = mgr.allocate(4)
        mgr.free(s)
        with pytest.raises(ValueError, match="double free"):
            mgr.free(s)
        with pytest.raises(ValueError, match="invalid slot"):
            mgr.free(2)
        with pytest.raises(ValueError, match="invalid slot"):
            mgr.free(-1)
        # the free list stayed sane: both slots allocate exactly once
        assert sorted([mgr.allocate(1), mgr.allocate(1)]) == [0, 1]
        assert mgr.free_slots == []

    def test_free_invalidates_pos_ids_row(self, dense):
        _, model, _ = dense
        mgr = KVCacheManager(model, slots=2, max_len=16)
        s = mgr.allocate(4)
        # mark some positions valid, then free: the row must go to -1
        mgr.cache["stack"]["pos_ids"] = (
            mgr.cache["stack"]["pos_ids"].at[:, s, :4].set(
                jnp.arange(4, dtype=jnp.int32)))
        mgr.free(s)
        assert (np.asarray(mgr.cache["stack"]["pos_ids"][:, s]) == -1).all()

    def test_page_accounting(self, dense):
        _, model, _ = dense
        mgr = KVCacheManager(model, slots=2, max_len=32, page_size=8)
        assert mgr.total_pages == 8 and mgr.pages_in_use == 0
        s = mgr.allocate(1)
        mgr.advance([s], [9])  # 9 tokens -> 2 pages
        assert mgr.pages_in_use == 2
        assert mgr.peak_pages == 2
        mgr.free(s)
        assert mgr.pages_in_use == 0 and mgr.peak_pages == 2

    def test_incremental_pages_pinned_against_recount(self, dense):
        """pages_in_use is maintained incrementally (O(1) per advance);
        pin it against the from-scratch recount through a full slot
        lifecycle including preemption restore."""
        _, model, _ = dense
        mgr = KVCacheManager(model, slots=3, max_len=32, page_size=8)
        assert mgr.pages_in_use == mgr.recount_pages() == 0
        a = mgr.allocate(3)
        b = mgr.allocate(9)
        assert mgr.pages_in_use == mgr.recount_pages() == 2
        mgr.advance([a, b], [3, 9])       # b crosses into page 2
        assert mgr.pages_in_use == mgr.recount_pages() == 3
        mgr.advance([b], [8])             # page 3
        assert mgr.pages_in_use == mgr.recount_pages() == 4
        rows, pos = mgr.read_rows([b]), int(mgr.pos[b])
        mgr.free(b)
        assert mgr.pages_in_use == mgr.recount_pages() == 1
        c = mgr.allocate(1)
        mgr.restore(c, rows, pos)         # resume rewinds the page count
        assert mgr.pages_in_use == mgr.recount_pages() == 4
        mgr.free(a)
        mgr.free(c)
        assert mgr.pages_in_use == mgr.recount_pages() == 0
        assert mgr.peak_pages == 4

    def test_write_rows_scatters_one_request(self, dense):
        _, model, params = dense
        mgr = KVCacheManager(model, slots=3, max_len=16)
        toks = jnp.arange(4, dtype=jnp.int32)[None]
        _, rows = model.prefill(params, {"tokens": toks}, max_len=16)
        mgr.write_rows([2], rows)
        got = np.asarray(mgr.cache["stack"]["k"], np.float32)
        ref = np.asarray(rows["stack"]["k"], np.float32)[:, 0]
        np.testing.assert_allclose(got[:, 2], ref, rtol=1e-6)
        assert (got[:, 0] == 0).all()  # other slots untouched
