"""The DeepSeek-V2 configuration at a CPU size: the program's in-pool path
against the plain reference, the expert share against the uncut layer,
the counts, the per-layer readers and the cell's result line.

The configuration keeps every key of the benchmark's file and shrinks only
the sizes: 3 layers (the dense one and 2 expert layers), hidden 64, 4 heads,
4 of 16 experts held, 3 picks per token from 2 of 4 groups.
"""
import copy
import json
import os
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import compare, harness, weights
from benchmarks.chip import counts_mla_moe as C
from benchmarks.chip import trace as T
from benchmarks.chip.peaks import PEAKS, peaks
from benchmarks.chip.tests import chipbench_small as S

CONFIG = "deepseek-v2-serve"
CELL = "deepseek-v2-serve.decode_long16"
REF = harness.load_module(os.path.join(S.ROOT, "reference", "deepseek_v2.py"),
                          "bench_ref_deepseek_v2")
SMALL = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts": 4, "n_group": 4, "topk_group": 2,
         "num_experts_per_tok": 3, "vocab_size": 256}
FULL = harness.load_json(os.path.join(S.ROOT, "configs", f"{CONFIG}.json"))


def small_config(held=4, published=16, start=0, **kw) -> dict:
    c = copy.deepcopy(FULL)
    c.update(SMALL, n_routed_experts=held, **kw)
    c["deployment"].update(experts_published=published,
                           held_expert_start=start)
    c["program"]["fields"].update(num_experts=published,
                                  held_expert_start=start)
    return c


def program(c, dtype="float32"):
    from repro.models.model import Model
    c = copy.deepcopy(c)
    c["program"]["fields"].update(param_dtype=dtype, dtype=dtype)
    return Model(harness.program_config(c))


SEED = 2**32 + 29


@pytest.fixture(scope="module")
def f32():
    c = small_config()
    model = program(c)
    specs = REF.param_specs(c)
    params = weights.program_tree(specs, SEED, "float32", c["program_params"],
                                  model.abstract_params())
    return c, model, params, weights.canonical(specs, SEED, "float32")


def test_in_pool_path_matches_reference_logits(f32):
    """Chunked prefill, then decode, through the page pool of latents
    (``Model.decode_paged`` on ``PagedKVCacheManager``'s pool, as the
    engine's fused step calls it): every fed position's logits against the
    reference's one forward pass.  Float32 on both sides; the absorbed
    decode and the expanded prefill sum in other orders than the
    reference, so 1e-4 of the logits' scale."""
    from repro.serve.cache import PagedKVCacheManager
    c, model, params, w = f32
    mgr = PagedKVCacheManager(model, 2, 64, page_size=8)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 256, 40).astype(np.int32),
            rng.integers(0, 256, 23).astype(np.int32)]
    prompts = [17, 9]
    slots = [mgr.allocate(p) for p in prompts]
    step = jax.jit(model.decode_paged)
    fed = [0, 0]
    got = [[], []]
    while any(f < len(s) for f, s in zip(fed, seqs)):
        # prompts in chunks of 8, then one token per tick
        width = 8 if any(f < p for f, p in zip(fed, prompts)) else 1
        toks = np.zeros((2, width), np.int32)
        nv = np.zeros(2, np.int32)
        for b, slot in enumerate(slots):
            n = min(width, len(seqs[b]) - fed[b],
                    max(prompts[b] - fed[b], 1))
            toks[slot, :n] = seqs[b][fed[b]:fed[b] + n]
            nv[slot] = n
            mgr.extend(slot, fed[b] + n)
        logits, mgr.pool, stats = step(
            params, jnp.asarray(toks), mgr.pool,
            jnp.asarray(mgr.block_table), jnp.asarray(mgr.pos), jnp.asarray(nv))
        for b, slot in enumerate(slots):
            got[b].append(np.asarray(logits)[slot, :nv[slot]])
            fed[b] += int(nv[slot])
            mgr.pos[slot] += int(nv[slot])
    assert int(stats["routed_local"]) >= 0
    for b in range(2):
        have = np.concatenate(got[b])
        want = np.asarray(REF.logits(w, seqs[b], c))
        scale = np.abs(want).max()
        np.testing.assert_allclose(have, want, atol=1e-4 * scale, rtol=0)


def test_engine_serves_the_reference_argmax(f32):
    """The engine (``paged=True``, greedy) on every tick in the pool; each
    served token is the reference's best at its position up to float32
    round-off."""
    from repro.serve.engine import Engine, Request
    c, model, params, w = f32
    eng = Engine(model, params, batch_slots=3, max_len=64, page_size=8,
                 prefill_chunk=8, paged=True, eos_id=-1, warmup=False)
    rng = np.random.default_rng(6)
    reqs = [Request(i, rng.integers(0, 256, n).astype(np.int32), max_new=m)
            for i, (n, m) in enumerate([(13, 9), (5, 14), (21, 6), (3, 10)])]
    for r in reqs:
        eng.submit(r)
    while eng.step():
        pass
    assert eng.kv_pool_ticks == eng.ticks > 0
    for r in reqs:
        gaps = compare.served_gaps(REF, w, c, r.prompt, r.out, 64)
        assert gaps.max() <= 1e-4, (r.rid, gaps.max())


def _expert_layer(c, w, start=0, held=None):
    """The program's expert layer for configuration ``c`` holding ``held``
    experts from ``start``, with the reference weights ``w`` of the uncut
    layer (the first expert layer's), as the program lays them out."""
    from repro.models import moe
    cfg = harness.program_config(c)
    held = cfg.n_held if held is None else held
    canon = {"wg": "gate_proj", "wu": "up_proj", "wd": "down_proj"}
    # one expert layer's experts, stacked as the decode scan hands them
    p = {k: w[f"moe.experts.{v}"][:1, start:start + held]
         for k, v in canon.items()}
    p["router"] = w["moe.gate"][0]
    p["shared"] = {k: w[f"moe.shared_experts.{v}"][0]
                   for k, v in canon.items()}
    model = program(c)
    return (lambda h: moe.moe_dropless(p, h[None], model.cfg, model.plan,
                                       0)), p


def _reference_layer(w, h, c):
    p = {k[len("moe."):]: v[0] for k, v in w.items() if k.startswith("moe.")}
    return REF._experts(p, h, c, REF.exact)[0]


def _uncut(seed=3):
    c = small_config(held=32, published=32)
    w = weights.canonical(REF.param_specs(c), SEED, "float32")
    h = jax.random.normal(jax.random.PRNGKey(seed), (12, c["hidden_size"]))
    return c, w, h


def test_shares_sum_to_the_uncut_layer():
    """16 chips hold 2 of 32 experts each: their layers' outputs, with the
    shared experts (which every chip computes alike) counted once, add up
    to the uncut reference layer.  Float32; 16 partial sums in another
    order, so 1e-5 of the output's scale."""
    from repro.models import layers
    c_full, w, h = _uncut()
    want = np.asarray(_reference_layer(w, h, c_full))
    total = 0.0
    for share in range(16):
        c = small_config(held=2, published=32, start=2 * share)
        layer, p = _expert_layer(c, w, 2 * share)
        out, stats = layer(h)
        total = total + np.asarray(out[0])
    model = program(c)
    shared = np.asarray(layers.mlp_apply(p["shared"], h, model.cfg,
                                         model.plan))
    np.testing.assert_allclose(total - 15 * shared, want,
                               atol=1e-5 * np.abs(want).max(), rtol=0)


def test_dropless_under_skew():
    """Every token routed to one held expert (its router column made to
    win through a constant input feature): nothing is dropped, as there is
    no capacity, and the layer matches the reference's dense sum."""
    from repro.models import moe
    c = small_config(held=4, published=16)
    w = dict(weights.canonical(REF.param_specs(c), SEED, "float32"))
    h = jax.random.normal(jax.random.PRNGKey(4), (12, c["hidden_size"]))
    h = h.at[:, 0].set(1.0)
    w["moe.gate"] = w["moe.gate"].at[:, 0, :].set(0.0).at[:, 0, 1].set(50.0)
    layer, p = _expert_layer(c, w)
    out, stats = layer(h)
    _, idx, _, _ = moe.router_topk(h @ p["router"], 3,
                                   **moe.gate_args(harness.program_config(c)))
    assert (np.asarray(idx) == 1).any(-1).all()  # all 12 tokens on expert 1
    # a capacity of 1.25 x 12 x 3 / 16 would have kept 2 of them
    assert int(stats["routed_local"]) >= 12
    want = np.asarray(_reference_layer(w, h, c))
    np.testing.assert_allclose(np.asarray(out[0]), want,
                               atol=1e-5 * np.abs(want).max(), rtol=0)


def test_fp8_control_departs_from_reference(f32):
    c, _, _, w = f32
    toks = np.arange(24, dtype=np.int32) * 7 % 256
    exact = REF.logits(w, toks, c)
    low = REF.logits(w, toks, c, mm=REF.fp8)
    rel = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    # the control departs, and stays finite (at this width fp8's rounding
    # also moves routing decisions, so the departure is wide)
    assert rel > 1e-3 and np.isfinite(np.asarray(low)).all()


def test_reference_routes_are_the_gate_picks(f32):
    c, model, params, w = f32
    toks = np.arange(20, dtype=np.int32) * 5 % 256
    r = np.asarray(REF.routes(w, toks, c))
    assert r.shape == (2, 20, 3) and r.max() < 16
    # 2 of 4 groups of 4: every token's picks lie in at most 2 groups
    assert all(len({int(e) // 4 for e in row}) <= 2 for row in r[0])


def _upcast():
    return harness.load_module(os.path.join(S.ROOT, "drivers",
                                            "serve_upcast.py"),
                               "bench_driver_serve_upcast")


def test_limits_part_program_and_control_by_the_p90():
    """The configuration's limits on gaps shaped as the chip's readings:
    the program's (most tokens the reference's best, a tenth a little
    below it, a flipped expert's wide gap at a few positions) pass; a
    lower precision's (every position moved, its widest under the guard)
    fail by the p90 alone; a wrong token fails by the guard."""
    drv = _upcast()
    lim = FULL["limits"]
    prog = np.zeros(1490)
    prog[:160] = 0.04
    prog[:15] = 0.5
    prog[:2] = 2.14
    ctrl = np.linspace(0.0, 3.0, 1490)
    assert drv.p90(prog) == pytest.approx(0.04)
    assert drv.within(prog, lim)
    assert not drv.within(ctrl, lim)
    assert drv.within(ctrl, {**lim, "p90_logit_gap": np.inf})
    assert not drv.within(np.append(prog, 5.0), lim)
    assert not drv.within(np.zeros(0), lim)


def test_check_weights_are_drawn_in_the_served_dtype():
    """The check's weights stay in the served dtype whatever the serving
    driver asks for, and only float32 (or nothing) may be asked for."""
    drv = _upcast()
    specs = {"a": ((2, 3), ("normal", 1.0))}
    w = drv._served(specs, 7, "bfloat16", "float32")
    assert w["a"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(w["a"], np.float32),
        np.asarray(weights.canonical(specs, 7, "bfloat16", "float32")["a"]))
    with pytest.raises(AssertionError):
        drv._served(specs, 7, "bfloat16", "float16")


def test_calibration_counts_routing_differences(f32):
    """The calibration's routing count: the program's gate through its
    chunked decode path and the reference's gate agree on every decision
    in float32, and a changed pick on a held expert is counted as one."""
    drv = _upcast()
    c, model, params, w = f32
    seq = (np.arange(37) * 11 % 256).astype(np.int32)
    got = drv.program_routes(model, params, seq, chunk=16)
    want = np.sort(np.asarray(REF.routes(w, seq, c)), -1)
    assert got.shape == want.shape == (2, 37, 3)
    diff = drv.routing_differences(model.cfg, got, want)
    assert diff == {"decisions": 74, "differ": 0, "differ_on_held": 0,
                    "first_held_position": None}
    moved = got.copy()
    moved[0, 5] = [1, 14, 15] if 1 not in got[0, 5] else [2, 14, 15]
    assert drv.routing_differences(model.cfg, moved, want)["differ_on_held"] \
        == 1


# -- counts -------------------------------------------------------------------

def test_parameters_match_the_program():
    c = param = C.param_counts(FULL)
    # attention: q_a 5120x1536 + 1536, q_b 1536x128x192, kv_a 5120x576 +
    # 512, k_b and v_b 512x128x128 each, o 128x128x5120
    assert c["attn"] == 149_227_520
    assert c["expert"] == 3 * 5120 * 1536 == 23_592_960
    assert c["total"] == 4_851_942_400
    model = program(FULL, "bfloat16")
    assert model.n_params() == param["total"]


def test_latent_bytes_and_least_time():
    assert C.latent_bytes_per_token(FULL) == 9 * 576 * 2 == 10_368
    p = peaks("TPU v5 lite")
    c = C.param_counts(FULL)
    t = C.decode_tick_least(FULL, p, [1000] * 16, touched=36, routed=40)
    fixed = c["total"] - c["embed"] - 8 * 10 * c["expert"]
    bytes_ = ((fixed + 36 * c["expert"] + 16 * 5120) * 2
              + 10_368 * (16 * 1000 + 16))
    assert t == pytest.approx(bytes_ / 819e9)  # memory bound
    assert C.experts_least(FULL, p, 4, 6) == pytest.approx(
        4 * 23_592_960 * 2 / 819e9)


def test_serve_flops_sums_tokens():
    want = sum(C.token_flops(FULL, q + 1) for q in range(300, 556))
    assert C.serve_flops(FULL, 300, 256) == pytest.approx(want)


# -- per-layer readers ------------------------------------------------------------

def _reader(name):
    return harness.load_module(os.path.join(S.ROOT, "metrics", f"{name}.py"),
                               f"bench_metric_{name}")


def _run(commit_stats):
    """Two traced decode ticks of 10 ms, each with a 2 ms ragged-dot op and
    a commit span carrying ``commit_stats``."""
    spans, ops, ticks = [], [], []
    for i in range(2):
        a = i * 20e-3
        spans += [T.Span("serve.step", a, a + 12e-3, {"tick": i}),
                  T.Span("serve.engine.commit", a + 10e-3, a + 11e-3,
                         dict(commit_stats))]
        ops += [(a + 1e-3, a + 9e-3, "%fusion.1 = x"),
                (a + 2e-3, a + 4e-3, "%ragged-dot.3 = y")]
        ticks.append(types.SimpleNamespace(index=i, prefill=False,
                                           contexts=[500] * 16,
                                           fed=[(500, 1)] * 16, end=a + 12e-3))
    run = types.SimpleNamespace(
        trace=T.Trace([T.Device("/device:TPU:0", ops)], spans),
        src=FULL, peak=peaks("TPU v5 lite"), ticks=ticks, t0=0.0, t1=0.04)
    run.traced_ticks = lambda prefill=None: [
        (s, ticks[s.stats["tick"]]) for s in spans if s.name == "serve.step"]
    return run


def test_readers_read_the_commit_counters():
    run = _run({"routed_local": 6, "experts_touched": 5})
    p = run.peak
    roof = _reader("moe_experts_roofline.mla_moe").read(run)
    assert roof == pytest.approx(100 * C.experts_least(FULL, p, 5, 6)
                                 / 2e-3)
    tick = _reader("decode_tick_roofline.mla_moe").read(run)
    assert tick == pytest.approx(100 * C.decode_tick_least(
        FULL, p, [500] * 16, 5, 6) / 8e-3)
    mfu = _reader("mfu.serve.mla_moe").read(run)
    flops = 2 * 16 * C.serve_flops(FULL, 500, 1) + C.expert_flops(FULL, 12)
    assert mfu == pytest.approx(100 * flops / (0.04 * p["bf16_flops_per_s"]))


def test_readers_are_silent_without_the_counters():
    run = _run({})
    for name in ("moe_experts_roofline.mla_moe",
                 "decode_tick_roofline.mla_moe", "mfu.serve.mla_moe"):
        assert _reader(name).read(run) is None, name


# -- the cell (last: its check frees every device array, the fixtures' too) -----

def _tree(tmp):
    root = os.path.join(str(tmp), "chip")
    shutil.copytree(S.ROOT, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    # served in float32: at this width bfloat16 moves routing decisions
    # (the chip's limit is set at the published widths)
    c = small_config()
    c["serve_dtype"] = "float32"
    c["program"]["fields"].update(param_dtype="float32", dtype="float32")
    with open(os.path.join(root, "configs", f"{CONFIG}.json"), "w") as f:
        json.dump(c, f)
    mix = harness.load_json(os.path.join(S.ROOT, "mixes",
                                         "decode_long16.json"))
    mix["arrivals"].update(clients=4, requests=12)
    mix["prompt_len"].update(min=4, max=20)
    mix["output_len"].update(min=8, max=24)
    mix["engine"].update(batch_slots=4, max_len=64, page_size=8,
                         prefill_chunk=16)
    mix["check"]["tokens"] = 48
    with open(os.path.join(root, "mixes", "decode_long16.json"), "w") as f:
        json.dump(mix, f)
    return root


def test_cell_result_line(tmp_path, monkeypatch):
    monkeypatch.setitem(PEAKS, "cpu", PEAKS["TPU v5 lite"])  # counts only
    root = _tree(tmp_path)
    cell = harness.load_cell(S.bench(), CELL, root=root)
    # tokens/s is left out: a stall of about a second costs a closed loop
    # 2 % of its tokens (PERF.md section 2)
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "tick_host_gap_ms", "decode_tick_device_ms", "tick_host_work_ms",
        "decode_tick_roofline.mla_moe", "mfu.serve.mla_moe",
        "moe_experts_roofline.mla_moe"}
    for trace in (False, True):
        ctx = harness.Context(cell, 2**33 + 11, 2.0, trace,
                              jax.devices()[:1], time.perf_counter(),
                              harness.CompileClock())
        result, checks = cell.driver().run(ctx)
        assert result["correct"] is True and result["failed"] == 0
        gap, limit = checks["max_logit_gap"]
        assert 0 <= gap <= 1e-4  # float32 round-off
        q, limit = checks["p90_logit_gap"]
        assert 0 <= q <= gap and limit == FULL["limits"]["p90_logit_gap"]
        if not trace:
            assert set(result["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_cell_is_not_correct_past_the_p90_limit(tmp_path, monkeypatch):
    """A p90 limit below every reading fails the run, with the widest gap
    inside its own limit: the run's ``correct`` holds both."""
    monkeypatch.setitem(PEAKS, "cpu", PEAKS["TPU v5 lite"])
    root = _tree(tmp_path)
    path = os.path.join(root, "configs", f"{CONFIG}.json")
    c = harness.load_json(path)
    c["limits"]["p90_logit_gap"] = -1.0
    with open(path, "w") as f:
        json.dump(c, f)
    cell = harness.load_cell(S.bench(), CELL, root=root)
    ctx = harness.Context(cell, 2**33 + 13, 2.0, False, jax.devices()[:1],
                          time.perf_counter(), harness.CompileClock())
    result, checks = cell.driver().run(ctx)
    assert checks["max_logit_gap"][0] <= checks["max_logit_gap"][1]
    assert checks["p90_logit_gap"][1] == -1.0
    assert result["correct"] is False and result["failed"] == 0

