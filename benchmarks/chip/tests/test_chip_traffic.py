"""The traffic generator: every seed gets the same sizes, gaps and order,
with other token ids."""
import numpy as np
import pytest

from benchmarks.chip import traffic
from benchmarks.chip.tests import chipbench_small as S

CHAT = S.harness.load_json(S.ROOT + "/mixes/chat.json")


ARRIVALS = {
    "poisson": CHAT["arrivals"],
    "gamma": {"kind": "gamma", "rate_per_s": 2.0, "cv": 3.0},
    "closed": {"kind": "closed", "clients": 4, "requests": 12},
}


@pytest.mark.parametrize("kind", sorted(ARRIVALS))
@pytest.mark.parametrize("seeds", [(1, 2), (2**33 + 5, 7)])
def test_schedule_fixed_by_the_mix(kind, seeds):
    # every seed sends the same sizes at the same times, so what the window
    # finishes is the same work; only the token ids differ
    mix = dict(CHAT, arrivals=ARRIVALS[kind])
    a = traffic.generate(mix, seeds[0], 50, 151936)
    b = traffic.generate(mix, seeds[1], 50, 151936)
    assert [(len(i.prompt), i.max_new, i.due, i.client) for i in a] == \
        [(len(i.prompt), i.max_new, i.due, i.client) for i in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_same_seed_same_requests():
    a = traffic.generate(CHAT, 99, 50, 151936)
    b = traffic.generate(CHAT, 99, 50, 151936)
    assert all(x.due == y.due and np.array_equal(x.prompt, y.prompt)
               and x.max_new == y.max_new for x, y in zip(a, b))


def test_chat_shape():
    items = traffic.generate(CHAT, 3, 50, 151936)
    rate = CHAT["arrivals"]["rate_per_s"]
    assert len(items) == round(rate * 50)
    assert items[0].due == 0.0 and items[-1].due < 50
    p = np.array([len(i.prompt) for i in items])
    o = np.array([i.max_new for i in items])
    assert p.min() >= 16 and p.max() <= 768 and o.min() >= 8 and o.max() <= 255
    assert abs(np.median(p) - 256) <= 8 and abs(np.median(o) - 128) <= 4
    # every request fits the engine's max_len with its output
    assert (p + o).max() < CHAT["engine"]["max_len"]


def test_gamma_and_closed_loops():
    bursty = dict(CHAT, arrivals={"kind": "gamma", "rate_per_s": 2.0,
                                  "cv": 3.0})
    a = traffic.generate(bursty, 1, 50, 100)
    gaps = np.diff([i.due for i in a])
    assert gaps.std() / gaps.mean() > 1.5
    closed = dict(CHAT, arrivals={"kind": "closed", "clients": 4,
                                  "requests": 12},
                  output_len={"kind": "uniform", "min": 768, "max": 1536})
    c = traffic.generate(closed, 1, 50, 100)
    assert len(c) == 12 and all(i.due is None for i in c)
    assert sorted({i.client for i in c}) == [0, 1, 2, 3]
