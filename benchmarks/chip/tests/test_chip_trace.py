"""The trace reduction, on a small trace recorded on a TPU v5e: four
``serve.step`` ticks of a two-layer engine at qwen3-1.7b width, then one
``serve.wait_arrival``.  Tick 0 feeds two prompts (S = 64) beside two
decoding slots; ticks 1 and 2 decode, and tick 1 also releases two
finished requests' pages, whose first release compiles on the host (a long
span with little device time); tick 3 finds no work."""
import os

import numpy as np
import pytest

from benchmarks.chip import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_steps.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return T.load(DATA)


def test_planes_and_spans(tr):
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    steps = tr.spans_named("serve.step")
    assert [int(s.stats["tick"]) for s in steps] == [0, 1, 2, 3]
    assert len(tr.spans_named("serve.wait_arrival")) == 1
    assert tr.t0 == steps[0].start and tr.window_s > 0


def test_busy_and_idle(tr):
    assert 0 < tr.busy_s < tr.window_s
    d = tr.devices[0]
    # the union never exceeds the sum of the operations, nor the window
    total = sum(e - s for s, e, _ in d.ops)
    assert T.covered(d.busy, tr.t0, tr.t1) <= total
    busy = [tr.busy_in(s.start, s.end) for s in tr.spans_named("serve.step")]
    steps = tr.spans_named("serve.step")
    assert all(0 < b <= s.end - s.start for b, s in zip(busy[:3], steps))
    assert busy[3] == 0.0  # an idle engine step runs nothing on the device


def test_device_time_per_span(tr):
    steps = tr.spans_named("serve.step")
    busy = [tr.busy_in(s.start, s.end) for s in steps]
    # the tick that feeds prompt chunks (S = chunk) outweighs a decode tick
    assert busy[0] > max(busy[1:])
    # tick 1 spends most of its span on the host (the release's compile)
    assert busy[1] < 0.05 * (steps[1].end - steps[1].start)
    # device work of a tick lies inside its span: the host syncs at its end
    inside = sum(busy)
    assert inside >= 0.9 * tr.busy_s


def test_breakdown(tr):
    b = tr.breakdown()
    ops, gaps = b["device_ops"], b["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(not n.startswith("%") and n not in T.CONTAINERS
               for n, _ in ops)
    assert {n for n, _ in gaps} <= {"serve.step", "serve.wait_arrival",
                                    "host.outside_spans"}
    assert gaps[0][0] == "serve.step" and gaps[0][1] > 0.1
    assert sum(t for _, t in gaps) <= tr.window_s - tr.busy_s + 1e-9


def test_no_collectives_on_one_chip(tr):
    assert tr.exposed_collective_s() == 0.0


def test_exposed_collectives():
    d = T.Device("/device:TPU:0", [
        (0.0, 10.0, "%all-gather-start.1 = x"), (2.0, 5.0, "%fusion.2 = y"),
        (4.0, 6.0, "%convolution.3 = z"), (12.0, 14.0, "%reduce-scatter.4 = w")])
    t = T.Trace([d], [T.Span("train.step", 0.0, 20.0)])
    assert t.exposed_collective_s() == pytest.approx(10 - 4 + 2)
    assert t.busy_s == pytest.approx(12.0)


def test_merge_and_covered():
    m = T.merge(np.array([[3.0, 5.0], [0.0, 1.0], [0.5, 2.0], [4.0, 6.0]]))
    assert m.tolist() == [[0.0, 2.0], [3.0, 6.0]]
    assert T.covered(m, 1.0, 4.0) == pytest.approx(2.0)
    assert T.covered(m, 7.0, 9.0) == 0.0
