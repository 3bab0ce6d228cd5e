"""The serving driver on the CPU at a small size, through the functions the
command uses (the command itself refuses to run without a TPU).

It checks the result line's shape, that a timed path broken underneath
comes out not correct, and that the harness finds a new mix and a new
metric by name with no edit to a file it already has.
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import harness
from benchmarks.chip.peaks import PEAKS
from benchmarks.chip.tests import chipbench_small as S

KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return S.make_tree(tmp_path_factory.mktemp("serve"))


def test_result_line(tree):
    result, checks = S.run_cell(tree, seconds=2.0)
    harness.finish(result, checks)
    assert set(result) == KEYS and list(result)[-1] == "check"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert set(result["metrics"]) == {"output_tokens_per_s", "ttft_p90_ms",
                                      "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    gap = result["check"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert json.loads(json.dumps(result)) == result


def _altered_token(monkeypatch):
    from repro.serve import engine
    orig = engine.sample
    monkeypatch.setattr(engine, "sample", lambda *a, **k: (
        orig(*a, **k) + 1) % S.SMALL["vocab_size"])


def _state_unchanged(monkeypatch):
    from repro.serve import cache
    monkeypatch.setattr(cache.PagedKVCacheManager, "scatter_all",
                        lambda self, pool, logical, inv: pool)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_broken_timed_path_is_not_correct(tree, monkeypatch, fault):
    fault(monkeypatch)
    result, checks = S.run_cell(tree, seconds=2.0)
    assert result["correct"] is False
    value, limit = checks["max_logit_gap"]
    assert value > limit


def test_new_mix_and_metric_found_by_name(tmp_path, monkeypatch):
    monkeypatch.setitem(PEAKS, "cpu", PEAKS["TPU v5 lite"])  # counts only
    root = S.make_tree(tmp_path)
    mix = S.small_mix()
    mix["arrivals"] = {"kind": "closed", "clients": 2, "requests": 6}
    with open(os.path.join(root, "mixes", "dummy.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "metrics", "dummy_count.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.recs))\n")
    bench = copy.deepcopy(S.bench())
    bench["workloads"].append({"name": "qwen3-1.7b-serve.dummy",
                               "config": "qwen3-1.7b-serve",
                               "traffic": "dummy", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("qwen3-1.7b-serve.dummy")
    bench["per_layer"].append({"name": "dummy_count", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "ttft_p90_ms",
                               "workloads": ["qwen3-1.7b-serve.dummy"]})
    result, _ = S.run_cell(root, "qwen3-1.7b-serve.dummy", seconds=2.0,
                           trace=True, bench_json=bench)
    assert result["correct"] is True
    # the closed loop's 2 clients served at least their first requests;
    # trace readers find no device plane on the CPU and stay silent
    assert result["metrics"]["dummy_count"]["value"] >= 2
    assert "queue_wait_p50_ms" in result["metrics"]
    assert "decode_tick_device_ms" not in result["metrics"]


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.run", "--workload",
         "qwen3-1.7b-serve.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.repo_root(), env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_nothing_compiles_in_the_window(tree):
    # set-up serves a request ending at each position where the traffic's
    # requests end, through the engine's public entry points only
    cell = harness.load_cell(S.bench(), "qwen3-1.7b-serve.chat", root=tree)
    ctx = harness.Context(cell, 5, 2.0, False, S.jax.devices()[:1],
                          S.time.perf_counter(), harness.CompileClock())
    drv, cfg = cell.driver(), cell.config
    items = drv.traffic.generate(cell.mix, ctx.seed, ctx.seconds,
                                 cfg["vocab_size"])
    _, _, _, eng = drv.build(ctx, cfg, cell.mix)
    drv.warm(eng, items, cfg["vocab_size"])
    assert ctx.clock.counts()["compiles"][0] > 0
    recs, ticks, t0, t1, _ = drv.drive(ctx, eng, items)
    assert len(ticks) > 10 and any(r.req.done for r in recs)
    assert {k: n for k, (n, _) in ctx.clock.counts(t0, t1).items()} == \
        {"traces": 0, "compiles": 0, "cache_loads": 0}
