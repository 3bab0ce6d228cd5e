"""The closed-loop long-answer mixes: their schedule, and the qwen3 cell at
a CPU size reporting what BENCHMARK.json gives it."""
import json
import os

import numpy as np
import pytest

from benchmarks.chip import harness, traffic
from benchmarks.chip.tests import chipbench_small as S

MIXES = {"decode_long": "qwen3-1.7b-serve.decode_long",
         "decode_long16": "deepseek-v2-serve.decode_long16"}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_schedule(name):
    mix = harness.load_json(os.path.join(S.ROOT, "mixes", f"{name}.json"))
    eng, arr = mix["engine"], mix["arrivals"]
    assert arr["kind"] == "closed" and arr["clients"] == eng["batch_slots"]
    assert eng["overlap"] is True  # each tick launched before the last lands
    a = traffic.generate(mix, 4160000011, 50, 1000)
    b = traffic.generate(mix, 2**33 + 3, 50, 1000)
    assert [(len(i.prompt), i.max_new, i.client) for i in a] == \
        [(len(i.prompt), i.max_new, i.client) for i in b]
    p = np.array([len(i.prompt) for i in a])
    o = np.array([i.max_new for i in a])
    assert p.min() >= 64 and p.max() <= 256
    assert o.min() >= 768 and o.max() <= 1536
    # every request fits its slot with its answer
    assert (p + o).max() < eng["max_len"]
    # no client runs dry in a window: at 100 tokens/s a slot (above both
    # cells' decode rates, about 96 and 72) a client ends 50 x 100 / 1,152
    # answers of the mean length, with one more in flight
    per_client = len(a) / arr["clients"]
    assert per_client >= 1 + 50 * 100 / o.mean()


def test_qwen3_cell_reports_its_metrics(tmp_path):
    mix = harness.load_json(os.path.join(S.ROOT, "mixes", "decode_long.json"))
    mix["arrivals"].update(clients=2, requests=6)
    mix["prompt_len"].update(min=4, max=12)
    mix["output_len"].update(min=8, max=20)
    mix["engine"].update(batch_slots=2, max_len=64, page_size=8,
                         prefill_chunk=16)
    mix["check"]["tokens"] = 24
    root = S.make_tree(tmp_path)
    with open(os.path.join(root, "mixes", "decode_long.json"), "w") as f:
        json.dump(mix, f)
    result, checks = S.run_cell(root, MIXES["decode_long"], seconds=2.0)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"itl_p95_ms", "setup_s"}
    gap, limit = checks["max_logit_gap"]
    assert gap <= limit
