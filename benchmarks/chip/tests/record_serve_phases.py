"""Record ``data/serve_phases.xplane.pb`` on a TPU: four ``serve.step``
ticks of a two-layer engine at qwen3-1.7b width, then one
``serve.wait_arrival``, with the engine's own ``serve.engine.*`` spans.

    python3 -m benchmarks.chip.tests.record_serve_phases [out.xplane.pb]

Two slots decode (A, B) when the trace starts.  Tick 0 admits two prompts
of 100 tokens (C, D) and feeds their first 64-token chunks beside A and B;
tick 1 feeds the rest of C and D, and A takes its last token, so the tick
releases A's pages; ticks 2 and 3 decode B, C and D.  The same day is
served once untraced first, so nothing compiles in the traced ticks.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "serve_phases.xplane.pb")


def day(eng, rid0, trace_dir=None):
    import jax
    import numpy as np

    from benchmarks.chip.harness import span
    from repro.serve.engine import Request

    vocab = eng.model.cfg.vocab_size
    prompt = lambda n, k: (np.arange(n, dtype=np.int32) * (k + 3)) % vocab
    eng.submit(Request(rid0, prompt(20, 0), max_new=3))
    eng.submit(Request(rid0 + 1, prompt(20, 1), max_new=8))
    eng.step()  # A and B take their first token
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    eng.submit(Request(rid0 + 2, prompt(100, 2), max_new=4))
    eng.submit(Request(rid0 + 3, prompt(100, 3), max_new=4))
    for tick in range(4):
        with span("serve.step", tick=tick):
            eng.step()
    with span("serve.wait_arrival"):
        time.sleep(0.003)
    if trace_dir:
        jax.profiler.stop_trace()
    while eng.step():
        pass


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    out = argv[0] if argv else OUT
    import jax

    from benchmarks.chip import harness
    sys.path.insert(0, os.path.join(harness.repo_root(), "src"))
    from repro.configs import registry
    from repro.models.model import Model
    from repro.serve.engine import Engine

    harness.devices_or_exit(1)
    model = Model(registry.get("qwen3-1.7b").replace(num_layers=2))
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_slots=4, max_len=256, prefill_chunk=64,
                 page_size=16, paged=True, eos_id=-1)
    day(eng, 0)
    trace_dir = tempfile.mkdtemp(prefix="serve_phases_")
    day(eng, 10, trace_dir)
    (found,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    shutil.copyfile(found, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
