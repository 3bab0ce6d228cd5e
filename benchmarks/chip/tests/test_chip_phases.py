"""The engine's own spans on a trace recorded on a TPU v5e
(``record_serve_phases.py``): four ``serve.step`` ticks of a two-layer
engine at qwen3-1.7b width, then one ``serve.wait_arrival``.  Tick 0 feeds
two prompts' first 64-token chunks beside two decoding slots; tick 1 feeds
the rest of the prompts and releases a finished request's two pages; ticks
2 and 3 decode three slots."""
import os
import types

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace as T

HERE = os.path.dirname(__file__)
DATA = os.path.join(HERE, "data", "serve_phases.xplane.pb")
PHASES = ["admit", "compose", "upload", "dispatch", "sync", "commit"]
ENGINE = "serve.engine."


@pytest.fixture(scope="module")
def tr():
    return T.load(DATA)


def _inside(tr, step):
    return [s for s in tr.spans if s.name.startswith(ENGINE)
            and step.start <= s.start and s.end <= step.end]


def test_engine_spans_nest_in_each_step(tr):
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    steps = tr.spans_named("serve.step")
    assert [int(s.stats["tick"]) for s in steps] == [0, 1, 2, 3]
    engine = [s for s in tr.spans if s.name.startswith(ENGINE)]
    assert sum(len(_inside(tr, s)) for s in steps) == len(engine)
    dispatch, release = [], []
    for step in steps:
        inside = _inside(tr, step)
        top = [s for s in inside if s.name != ENGINE + "release"]
        assert [s.name[len(ENGINE):] for s in top] == PHASES
        assert all(a.end <= b.start for a, b in zip(top, top[1:]))
        dispatch.append({k: int(v) for k, v in top[3].stats.items()})
        for s in inside:
            if s.name == ENGINE + "release":
                assert top[-1].start <= s.start and s.end <= top[-1].end
                release.append((int(step.stats["tick"]), int(s.stats["pages"])))
    assert dispatch == [
        {"width": 64, "prefill": 2, "decode": 2, "prompt_tokens": 128},
        {"width": 64, "prefill": 2, "decode": 2, "prompt_tokens": 72},
        {"width": 1, "prefill": 0, "decode": 3, "prompt_tokens": 0},
        {"width": 1, "prefill": 0, "decode": 3, "prompt_tokens": 0}]
    assert release == [(1, 2)]
    # the device works inside the tick's sync, after its dispatch opened
    for step in steps:
        d, sync = _inside(tr, step)[3:5]
        assert tr.busy_in(sync.start, sync.end) > 0
        assert tr.busy_in(step.start, d.start) < tr.busy_in(d.start, step.end)


def test_idle_gaps_are_named_by_engine_phases(tr):
    names = {n for n, _ in tr.breakdown()["idle_gaps"]}
    assert "serve.step" not in names
    # inside the ticks the device waits while the host uploads the tick's
    # inputs, and inside the sync: at the fused program's head and tail
    assert names == {ENGINE + "upload", ENGINE + "sync", "serve.wait_arrival"}


def test_tick_host_work_reads_from_the_trace(tr):
    reader = harness.load_module(
        os.path.join(HERE, "..", "metrics", "tick_host_work_ms.py"),
        "bench_metric_tick_host_work_ms")
    got = reader.read(types.SimpleNamespace(trace=tr))
    # per tick: the five non-sync phases that follow one another, the
    # release nested in the commit
    want = np.median([sum(s.end - s.start for s in _inside(tr, step)
                          if s.name not in (ENGINE + "sync",
                                            ENGINE + "release"))
                      for step in tr.spans_named("serve.step")]) * 1e3
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got < np.median([s.end - s.start for s in
                                tr.spans_named("serve.step")]) * 1e3
