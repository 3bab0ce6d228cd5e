"""The serving engine's own tracing, on the CPU: the ``serve.engine.*``
spans of each tick (order, nesting, stats), served tokens unchanged by a
running trace, the requests' ``perf_counter`` stamps, the named scopes in
the fused step's op metadata, and the per-layer readers of the chip
benchmark that read the spans and stamps."""
import glob
import os
import re
import types

import jax
import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace as T
from benchmarks.chip.peaks import PEAKS
from benchmarks.chip.tests import chipbench_small as S
from repro.configs import registry
from repro.models.model import Model
from repro.serve.engine import Engine, Request

PHASES = ["admit", "compose", "upload", "dispatch", "sync", "commit"]
CHUNK = 8


@pytest.fixture(scope="module")
def dense():
    cfg = registry.get("llama3.2-1b").reduced()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def swa():
    cfg = registry.get("mixtral-8x7b").reduced()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1))


def _engine(dense, **kw):
    _, model, params = dense
    kw = {"batch_slots": 3, "max_len": 64, "prefill_chunk": CHUNK,
          "page_size": 8, "paged": True, "eos_id": -1, "warmup": False,
          **kw}
    return Engine(model, params, **kw)


def _requests(cfg, n=4):
    return [Request(i, (np.arange(5 + 6 * i) * (i + 2)) % cfg.vocab_size,
                    max_new=3 + i) for i in range(n)]


def _serve(eng, reqs, trace_dir=None):
    """Serve ``reqs`` to the end, each ``Engine.step`` inside a
    ``serve.step`` span as the benchmark's serving loop does, under a profiler
    trace when ``trace_dir`` is given; returns the served tokens by rid and
    the loaded trace (or None)."""
    for r in reqs:
        eng.submit(r)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    tick, more = 0, True
    while more:
        with harness.span("serve.step", tick=tick):
            more = eng.step()
        tick += 1
    tr = None
    if trace_dir:
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        tr = T.load(path)
    return {r.rid: list(r.out) for r in reqs}, tr


def _reader(name):
    return harness.load_module(
        os.path.join(os.path.dirname(T.__file__), "metrics", f"{name}.py"),
        f"bench_metric_{name}")


def test_every_tick_holds_the_phases_in_order(dense, tmp_path):
    cfg = dense[0]
    reqs = _requests(cfg)
    _, tr = _serve(_engine(dense), reqs, str(tmp_path))
    steps = tr.spans_named("serve.step")
    spans = [s for s in tr.spans if s.name.startswith("serve.engine.")]
    assert steps and len(spans) >= len(PHASES) * len(steps)
    released, fed, sent = [], 0, []
    for st in steps:
        inside = [s for s in spans if st.start <= s.start and s.end <= st.end]
        top = [s for s in inside if s.name != "serve.engine.release"]
        assert [s.name.rsplit(".", 1)[1] for s in top] == PHASES
        assert all(a.end <= b.start for a, b in zip(top, top[1:]))
        commit = top[-1]
        for s in inside:
            if s.name == "serve.engine.release":
                assert commit.start <= s.start and s.end <= commit.end
                released.append(s.stats["pages"])
        d = top[PHASES.index("dispatch")].stats
        assert d["prefill"] + d["decode"] >= 1
        assert d["width"] == (CHUNK if d["prefill"] else 1)
        assert d["prompt_tokens"] <= CHUNK * d["prefill"]
        assert (d["prompt_tokens"] > 0) == (d["prefill"] > 0)
        assert d["kv_pool"] == 1  # dense GQA: K/V stays in the page pool
        fed += d["prompt_tokens"]
        sent.append(top[PHASES.index("upload")].stats["bt_sent"])
    # every request releases its pages once, inside the commit that ends it
    assert len(released) == len(reqs) and min(released) >= 1
    assert fed == sum(len(r.prompt) for r in reqs)
    # the block table goes up on the first tick, and not on every tick
    assert sent[0] == 1 and 0 in sent


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_served_tokens_do_not_depend_on_a_trace(dense, tmp_path,
                                                temperature):
    cfg = dense[0]
    kw = {"temperature": temperature, "seed": 7}
    plain, _ = _serve(_engine(dense, **kw), _requests(cfg))
    traced, tr = _serve(_engine(dense, **kw), _requests(cfg), str(tmp_path))
    assert tr.spans_named("serve.engine.dispatch")
    assert traced == plain
    assert all(len(v) == 3 + rid for rid, v in plain.items())


def test_request_stamps_are_ordered_through_preemption(dense):
    cfg = dense[0]
    eng = _engine(dense)
    reqs = _requests(cfg)
    reject = Request(99, np.zeros(64, np.int32), max_new=2)
    for r in reqs + [reject]:
        eng.submit(r)
    eng.step()
    eng.step()
    first_admit = {r.rid: r.t_admit for r in eng.slot_req if r is not None}
    assert eng.preempt_to(1) >= 1
    preempted = [r for r in reqs if r.preempts]
    assert preempted and all(r.t_admit == first_admit[r.rid]
                             for r in preempted)
    while eng.step():
        pass
    for r in reqs:
        assert r.done and r.error is None
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    # a resume after preemption keeps the first admission's stamp
    assert all(r.t_admit == first_admit[r.rid] for r in preempted)
    assert reject.error == "prompt_too_long"
    assert reject.t_admit is None and reject.t_first is None
    assert reject.t_submit <= reject.t_done


@pytest.mark.parametrize("paged, spec, scopes", [
    (True, False, ("decode", "kv_write", "kv_read", "sample")),
    (True, True, ("decode", "kv_write", "kv_read", "sample")),
    (False, False, ("decode", "sample")),
])
def test_fused_step_op_metadata_names_its_layers(dense, paged, spec, scopes):
    names = _fused_op_names(_engine(dense, paged=paged,
                                    speculate=2 if spec else 0), spec)
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope
    # K/V stays in the pool (dense GQA): no whole-cache gather or scatter
    for scope in ("kv_gather", "kv_scatter"):
        assert not any(f"/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("spec", [False, True])
def test_gather_path_op_metadata_names_its_layers(swa, spec):
    """A sliding-window stack keeps gather -> decode -> scatter, and the
    step's op metadata names those layers."""
    eng = _engine(swa, speculate=2 if spec else 0)
    assert not eng._kv_pool
    names = _fused_op_names(eng, spec)
    for scope in ("kv_gather", "decode", "sample", "kv_scatter"):
        assert any(f"/{scope}/" in n for n in names), scope
    for scope in ("kv_write", "kv_read"):
        assert not any(f"/{scope}/" in n for n in names), scope


@pytest.fixture(scope="module")
def mla():
    cfg = registry.get("deepseek-v2-236b").reduced()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(2))


def test_latent_expert_step_names_its_layers(mla):
    """DeepSeek-V2 (latent attention, expert layers) decodes in the pool:
    its latents' reads and writes sit under ``kv_read`` and ``kv_write``,
    like GQA K/V, and each expert layer's parts under ``moe_route``,
    ``moe_experts`` and ``moe_shared``."""
    eng = _engine(mla)
    assert eng._kv_pool
    names = _fused_op_names(eng, False)
    for scope in ("decode", "kv_write", "kv_read", "moe_route",
                  "moe_experts", "moe_shared", "sample"):
        assert any(f"/{scope}/" in n for n in names), scope
    for scope in ("kv_gather", "kv_scatter"):
        assert not any(f"/{scope}/" in n for n in names), scope


def test_commit_spans_carry_the_expert_counters(mla, tmp_path):
    """Each in-pool tick of an expert model returns its counters with its
    output, read at the tick's one sync: the commit span's
    ``routed_local`` and ``experts_touched``, which add up to the engine's
    totals; a dense model's commit spans carry none."""
    cfg = mla[0]
    eng = _engine(mla)
    _, tr = _serve(eng, _requests(cfg), str(tmp_path))
    commits = tr.spans_named("serve.engine.commit")
    dispatch = tr.spans_named("serve.engine.dispatch")
    assert commits and all(d.stats["kv_pool"] == 1 for d in dispatch)
    assert sum(c.stats["routed_local"] for c in commits) == \
        eng.routed_local > 0
    assert sum(c.stats["experts_touched"] for c in commits) == \
        eng.experts_touched > 0
    # at most every held expert in every expert layer, each tick
    layers = cfg.num_layers - cfg.first_k_dense
    assert all(0 <= c.stats["experts_touched"] <= cfg.n_held * layers
               for c in commits)


def test_overlapped_ticks_land_in_the_next_step(mla, tmp_path):
    """``overlap``: a step dispatches its tick before it syncs and commits
    the one before, so the first step holds no sync, the last no dispatch,
    and every step keeps the phases' order; the tokens are the
    synchronous engine's, and the commit spans' counters (the landed
    tick's) still add up to the engine's totals."""
    cfg = mla[0]
    plain, _ = _serve(_engine(mla), _requests(cfg))
    eng = _engine(mla, overlap=True)
    got, tr = _serve(eng, _requests(cfg), str(tmp_path))
    assert got == plain
    spans = [s for s in tr.spans if s.name.startswith("serve.engine.")
             and s.name != "serve.engine.release"]
    phases = [[s.name.rsplit(".", 1)[1] for s in spans
               if st.start <= s.start and s.end <= st.end]
              for st in tr.spans_named("serve.step")]
    launch, land = PHASES[:4], PHASES[:2] + PHASES[4:]
    assert phases[0] == launch and phases[-1] == land
    assert all(p in (PHASES, land) for p in phases[1:-1])
    assert phases.count(PHASES) >= len(phases) - 4
    commits = tr.spans_named("serve.engine.commit")
    assert sum(c.stats["routed_local"] for c in commits) == \
        eng.routed_local > 0


def test_dense_commit_spans_carry_no_counters(dense, tmp_path):
    eng = _engine(dense)
    _, tr = _serve(eng, _requests(dense[0]), str(tmp_path))
    assert all("routed_local" not in c.stats
               for c in tr.spans_named("serve.engine.commit"))
    assert eng.routed_local == eng.experts_touched == 0


def _fused_op_names(eng, spec):
    """The op names in the compiled fused step's metadata."""
    fn = eng._fused_spec if spec else eng._fused
    B, S_ = eng.B, 3 if spec else 1
    args = (jax.numpy.zeros((B, S_), jax.numpy.int32),
            jax.numpy.zeros((B,), jax.numpy.int32),
            jax.numpy.zeros((B,), jax.numpy.int32), eng.key)
    if eng._paged:
        lowered = fn.lower(eng.params, eng.mgr.pool, *eng._bt_device(), *args)
    else:
        lowered = fn.lower(eng.params, eng.mgr.cache, *args)
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


def test_small_cell_reads_the_engine_stamps(tmp_path, monkeypatch):
    monkeypatch.setitem(PEAKS, "cpu", PEAKS["TPU v5 lite"])  # counts only
    root = S.make_tree(tmp_path)
    result, _ = S.run_cell(root, seconds=2.0, trace=True)
    assert result["correct"] is True
    m = result["metrics"]
    assert m["engine_queue_p90_ms"]["value"] >= 0
    assert m["prefill_latency_p90_ms"]["value"] > 0
    # no device plane on the CPU: the trace readers stay silent
    assert "tick_host_work_ms" not in m


def _span(name, a, b, **stats):
    return T.Span(name, a * 1e-3, b * 1e-3, stats)


def test_tick_host_work_is_the_union_of_non_sync_phases():
    def step(t, sync_ms, tick):
        return [_span("serve.step", t, t + 12, tick=tick),
                _span("serve.engine.admit", t, t + 1),
                _span("serve.engine.compose", t + 1, t + 2),
                _span("serve.engine.upload", t + 2, t + 3),
                _span("serve.engine.dispatch", t + 3, t + 4),
                _span("serve.engine.sync", t + 4, t + 4 + sync_ms),
                _span("serve.engine.commit", t + 10, t + 11.5),
                _span("serve.engine.release", t + 10.5, t + 11)]
    dev = T.Device("/device:TPU:0", [(0.0, 1.0, "%fusion.1 = x")])
    spans = step(0, 6, 0) + step(20, 2, 1) + step(40, 5, 2)
    run = types.SimpleNamespace(trace=T.Trace([dev], spans))
    # 4 one-ms phases and a 1.5 ms commit holding the release; sync left out
    assert _reader("tick_host_work_ms").read(run) == pytest.approx(5.5)


def test_readers_are_silent_without_engine_spans_or_stamps():
    """What the benchmark reads of the program when the program lacks the
    spans and stamps: every new reader returns nothing and raises not."""
    dev = T.Device("/device:TPU:0", [(0.0, 1.0, "%fusion.1 = x")])
    bare = types.SimpleNamespace(rid=0, done=True, error=None, out=[1])
    rec = types.SimpleNamespace(req=bare, due=0.5, admitted=1.0)
    run = types.SimpleNamespace(
        trace=T.Trace([dev], [_span("serve.step", 0, 12, tick=0)]),
        recs=[rec], t0=0.0, t1=10.0)
    for name in ("engine_queue_p90_ms", "prefill_latency_p90_ms",
                 "tick_host_work_ms"):
        assert _reader(name).read(run) is None, name
