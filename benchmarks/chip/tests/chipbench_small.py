"""A serving cell cut to a size the CPU runs in seconds, for the tests.

The configuration keeps every key of the benchmark's file and shrinks only
the sizes; the mix keeps the chat mix's shape with small lengths.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys
import time

import jax

from benchmarks.chip import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(harness.repo_root(), "src"))
SMALL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 256, "max_window_layers": 2}


def small_config(name="qwen3-1.7b-serve") -> dict:
    c = harness.load_json(os.path.join(ROOT, "configs", f"{name}.json"))
    c.update(SMALL)
    return c


def small_mix(name="chat") -> dict:
    m = copy.deepcopy(harness.load_json(os.path.join(ROOT, "mixes",
                                                     f"{name}.json")))
    m["arrivals"]["rate_per_s"] = 6.0
    m["prompt_len"].update(median=20, min=4, max=40)
    m["output_len"].update(median=8, min=2, max=16)
    m["engine"].update(batch_slots=4, max_len=64, page_size=8,
                       prefill_chunk=16)
    m["check"]["tokens"] = 48
    return m


def make_tree(tmp, config=None, mix=None, bench_extra=None) -> str:
    """A copy of the benchmark's directory with the small configuration and
    mix written over the real ones; returns its root."""
    root = os.path.join(str(tmp), "chip")
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(root, "configs", "qwen3-1.7b-serve.json"), "w") as f:
        json.dump(config or small_config(), f)
    with open(os.path.join(root, "mixes", "chat.json"), "w") as f:
        json.dump(mix or small_mix(), f)
    return root


def bench() -> dict:
    return harness.load_json(os.path.join(os.path.dirname(ROOT), "..",
                                          "BENCHMARK.json"))


def run_cell(root, workload="qwen3-1.7b-serve.chat", seed=2**33 + 7,
             seconds=3.0, trace=False, bench_json=None):
    """Drive the cell as ``run.py`` does, minus the look for a chip."""
    cell = harness.load_cell(bench_json or bench(), workload, root=root)
    ctx = harness.Context(cell, seed, seconds, trace, jax.devices()[:1],
                          time.perf_counter(), harness.CompileClock())
    return cell.driver().run(ctx)
