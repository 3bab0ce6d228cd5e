"""The check's control, at a size the CPU holds: the plain reference put in
the program's place and computed in fp8 (the precision below the served
bfloat16) fails the limit that the served tokens keep, on three seeds.

The sample is the one a serving run's check takes (the driver's own
window, then ``compare.pick``); at each of its positions the control ranks
the tokens itself and its first choice is read against the float32
reference.  Eight layers of width 128 carry enough fp8 error to show; two
layers of width 64 (the other tests' size) carry too little.
"""
import time

import jax
import pytest

from benchmarks.chip import compare, harness, traffic, weights
from benchmarks.chip.tests import chipbench_small as S

CONTROL_SIZE = {"num_hidden_layers": 8, "hidden_size": 128,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 32, "intermediate_size": 256, "vocab_size": 2048,
                "max_window_layers": 8}
# every request the short window finishes (about a hundred tokens): at this
# size fp8 error shows on some positions only, so the check reads them all
CHECK_TOKENS = 160


@pytest.mark.parametrize("seed", [41, 2**32 + 42, 43])
def test_control_fails_where_the_program_passes(tmp_path, seed):
    cfg = dict(S.small_config(), **CONTROL_SIZE)
    mix = S.small_mix()
    mix["check"]["tokens"] = CHECK_TOKENS
    root = S.make_tree(tmp_path, config=cfg, mix=mix)
    cell = harness.load_cell(S.bench(), "qwen3-1.7b-serve.chat", root=root)
    ctx = harness.Context(cell, seed, 2.0, False, jax.devices()[:1],
                          time.perf_counter(), harness.CompileClock())
    drv, mix = cell.driver(), cell.mix
    items = traffic.generate(mix, seed, ctx.seconds, cfg["vocab_size"])
    ref, specs, model, eng = drv.build(ctx, cfg, mix)
    drv.warm(eng, items, cfg["vocab_size"])
    recs, _, _, t1, _ = drv.drive(ctx, eng, items)
    sample = compare.pick(drv.served(recs, t1), seed, mix["check"]["tokens"])
    w = weights.canonical(specs, seed, cfg["serve_dtype"], "float32")
    L = mix["engine"]["max_len"]
    limit = cfg["limits"]["max_logit_gap"]
    assert compare.max_served_gap(ref, w, cfg, sample, L) <= limit
    assert compare.max_served_gap(ref, w, cfg, sample, L, mm=ref.fp8) > limit
