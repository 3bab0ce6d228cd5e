"""DeepSeek-V2's mechanisms in the program, on the CPU: YaRN rope, the
group-limited gate, the dropless expert layer over the experts a chip
holds, and latent attention kept in the page pool."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.attention import mla_softmax_scale
from repro.models.model import Model
from repro.models.params import materialize
from repro.serve.engine import Engine, Request
from repro.sharding.plan import make_plan

V2 = registry.get("deepseek-v2-236b")


class TestYarn:
    """DeepSeek-V2's published rope: factor 40 over an original 4,096
    positions, beta 32/1, mscale = mscale_all_dim = 0.707, theta 10,000,
    on the 64 rotary dimensions of each head."""

    def test_frequencies_by_hand(self):
        # dims whose wavelength turns more than 32 times over 4,096 positions
        # are kept, fewer than once interpolated: 64 ln(4096 / (32 2pi)) /
        # (2 ln 1e4) = 10.47 -> 10, 64 ln(4096 / 2pi) / (2 ln 1e4) = 22.51
        # -> 23, and a linear ramp between
        got = np.asarray(L.rope_freqs(V2, 64), np.float64)
        base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
        ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
        want = base * (1 - ramp) + base / 40 * ramp
        # float32 powers of 1e4 against float64: a few ulps
        np.testing.assert_allclose(got, want, rtol=2e-6)
        assert got[10] == pytest.approx(base[10], rel=2e-6)   # kept
        assert got[23] == pytest.approx(base[23] / 40, rel=2e-6)  # divided

    def test_softmax_scale_and_rotation_amplitude(self):
        m = 0.1 * 0.707 * math.log(40) + 1  # 1.2608...
        assert mla_softmax_scale(V2) == pytest.approx(192 ** -0.5 * m * m,
                                                      rel=1e-12)
        assert mla_softmax_scale(V2) == pytest.approx(0.11472139, rel=1e-7)
        # mscale / mscale_all_dim = 1: rotation keeps each pair's length
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 1, 64))
        y = L.apply_rope(x, jnp.arange(5)[None] * 997, V2)
        np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                                   np.linalg.norm(x, axis=-1), rtol=1e-5)

    def test_models_without_yarn_are_unchanged(self):
        q = registry.get("qwen3-1.7b")
        assert q.yarn is None and V2.yarn["factor"] == 40
        want = 1.0 / (q.rope_theta ** (np.arange(0, 128, 2) / 128))
        np.testing.assert_allclose(np.asarray(L.rope_freqs(q, 128)), want,
                                   rtol=2e-6)


def _numpy_gate(logits, k, n_group, topk_group, scale):
    """DeepSeek-V2's ``group_limited_greedy`` gate, written plainly."""
    z = logits - logits.max(-1, keepdims=True)
    p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    T, E = p.shape
    picks, weights = [], []
    for t in range(T):
        groups = p[t].reshape(n_group, E // n_group).max(-1)
        keep = np.argsort(-groups, kind="stable")[:topk_group]
        masked = np.zeros(E)
        for g in keep:
            s = slice(g * E // n_group, (g + 1) * E // n_group)
            masked[s] = p[t, s]
        top = np.argsort(-masked, kind="stable")[:k]
        picks.append(sorted(top))
        weights.append(sorted(masked[top] * scale))
    return np.asarray(picks), np.asarray(weights)


class TestGate:
    def test_group_limited_router_matches_numpy(self):
        logits = np.asarray(jax.random.normal(jax.random.PRNGKey(3),
                                              (64, 160)), np.float32) * 3
        w, idx, _, _ = moe_lib.router_topk(jnp.asarray(logits), 6,
                                           **moe_lib.gate_args(V2))
        want_idx, want_w = _numpy_gate(logits, 6, 8, 3, 16.0)
        np.testing.assert_array_equal(np.sort(np.asarray(idx), -1), want_idx)
        np.testing.assert_allclose(np.sort(np.asarray(w), -1), want_w,
                                   rtol=1e-5)  # float32 softmax
        # every pick lies in one of 3 groups of 20
        assert all(len({int(e) // 20 for e in row}) <= 3 for row in idx)

    def test_plain_gate_is_unchanged(self):
        # no group limit, renormalised: the mixtral gate as before
        logits = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
        w, idx, _, _ = moe_lib.router_topk(logits, 2)
        p = jax.nn.softmax(logits, -1)
        top, ti = jax.lax.top_k(p, 2)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ti))
        np.testing.assert_allclose(np.asarray(w),
                                   np.asarray(top / top.sum(-1, keepdims=True)),
                                   rtol=1e-6)


def _layer(cfg, seed=0, layers=3, layer=1):
    """An expert layer's params with the routed experts' weights of
    ``layers`` layers stacked, as the decode scan hands them over, and
    those of layer ``layer`` alone."""
    plan = make_plan(cfg)
    ps = [materialize(moe_lib.moe_params(cfg, plan),
                      jax.random.PRNGKey(seed + i), "float32")
          for i in range(layers)]
    p = {**ps[layer], **{n: jnp.stack([q[n] for q in ps])
                         for n in ("wg", "wu", "wd")}}
    return plan, p, ps[layer]


class TestDroplessLayer:
    CFG = V2.reduced().replace(dtype="float32", param_dtype="float32",
                               num_experts=16, held_experts=4,
                               held_expert_start=4, num_experts_per_tok=3)

    def test_matches_a_dense_sum_over_held_experts(self):
        cfg = self.CFG
        plan, p, one = _layer(cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, cfg.d_model))
        out, stats = moe_lib.moe_dropless(p, x, cfg, plan, 1)
        h = x.reshape(-1, cfg.d_model)
        w, idx, _, _ = moe_lib.router_topk(h @ p["router"], 3,
                                           **moe_lib.gate_args(cfg))
        want = L.mlp_apply(p["shared"], h, cfg, plan)
        for e in range(4):
            ex = {n: one[n][e] for n in ("wg", "wu", "wd")}
            gate = jnp.sum(jnp.where(idx == 4 + e, w, 0.0), -1)[:, None]
            want = want + gate * L.mlp_apply(ex, h, cfg, plan)
        # float32 throughout; the sums run in another order
        np.testing.assert_allclose(np.asarray(out).reshape(h.shape),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)
        held = (idx >= 4) & (idx < 8)
        assert int(stats["routed_local"]) == int(held.sum())
        assert int(stats["experts_touched"]) == len(set(
            np.asarray(idx)[np.asarray(held)].tolist()))

    def test_padded_rows_route_nothing(self):
        """A ragged tick's padded tails (outside ``valid``) take no held
        pick and leave the valid rows' outputs as they are."""
        cfg = self.CFG
        plan, p, _ = _layer(cfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
        valid = jnp.asarray([[1, 1, 0, 0, 0], [1, 1, 1, 1, 1]], bool)
        out, stats = moe_lib.moe_dropless(p, x, cfg, plan, 1, valid)
        full, _ = moe_lib.moe_dropless(p, x, cfg, plan, 1)
        v = np.asarray(valid)
        np.testing.assert_array_equal(np.asarray(out)[v], np.asarray(full)[v])
        _, idx, _, _ = moe_lib.router_topk(
            x.reshape(-1, cfg.d_model) @ p["router"], 3,
            **moe_lib.gate_args(cfg))
        held = ((idx >= 4) & (idx < 8)) & v.reshape(-1, 1)
        assert int(stats["routed_local"]) == int(held.sum())


class TestLatentPool:
    @pytest.fixture(scope="class")
    def mla(self):
        cfg = V2.reduced()
        model = Model(cfg)
        return cfg, model, model.init(jax.random.PRNGKey(2))

    def test_pool_holds_one_latent_leaf_per_layer(self, mla):
        cfg, model, params = mla
        eng = Engine(model, params, batch_slots=2, max_len=64, page_size=8,
                     paged=True, warmup=False)
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        shapes = jax.tree_util.tree_map(lambda a: a.shape, eng.mgr.pool)
        pages = eng.mgr.total_pages + 1
        assert shapes == {
            "dense0": {"latent": (pages, 8, width), "pos_ids": (pages, 8)},
            "stack": {"latent": (cfg.num_layers - 1, pages, 8, width),
                      "pos_ids": (cfg.num_layers - 1, pages, 8)}}
        assert model.decodes_in_pool and eng._kv_pool

    def test_write_near_max_len_keeps_live_entries(self, mla):
        """A slot decoding at position 54 of 64 keeps every cached latent,
        in the dense layer and the expert layers, through another slot's
        chunk-wide prompt tick, and adds the 55th (the contiguous write
        clamps an S-wide row to ``T - S`` over live entries)."""
        cfg, model, params = mla
        eng = Engine(model, params, batch_slots=2, max_len=64,
                     prefill_chunk=16, page_size=16, paged=True, eos_id=-1,
                     warmup=False)
        a = Request(0, (np.arange(40) * 3 % cfg.vocab_size).astype(np.int32),
                    max_new=20)
        eng.submit(a)
        slot = None
        while slot is None or eng.mgr.pos[slot] < 54:
            eng.step()
            slot = eng.slot_req.index(a)
        b = Request(1, (np.arange(20) * 7 % cfg.vocab_size).astype(np.int32),
                    max_new=4)
        eng.submit(b)
        eng.step()
        assert b.fed == 16  # the tick was chunk-wide
        rows = eng.mgr.read_rows([slot])
        for ids in [np.asarray(rows["dense0"]["pos_ids"])[0]] + list(
                np.asarray(rows["stack"]["pos_ids"])[:, 0]):
            assert sorted(ids[ids >= 0]) == list(range(55))
        assert eng.kv_pool_ticks == eng.ticks
