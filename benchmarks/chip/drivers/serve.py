"""Serving cells: load from the mix on ``serve.Engine``, then the check.

Set-up builds the configuration's program, makes its weights from the seed
(one jitted call), builds the engine (which compiles its tick widths), and
serves a request ending at each position where the traffic's requests end,
so nothing compiles in the window.  The harness uses only the engine's
public entry points (``submit``, ``step``) and the requests' own fields.  The window offers the mix's requests as
they fall due (open loop) or as each client's previous request finishes
(closed loop), one ``Engine.step`` at a time; every step ends in a host
sync, so a token's time is the end of the step that produced it.

After the window: device memory is read, the program's arrays are freed,
and the plain reference is run over a sample of the finished requests (see
``compare.py``).  A traced run (``--trace 1``) takes a profiler trace of a
stretch in the middle of the same window, and its line carries the cell's
per-layer metrics in place of the end-to-end ones.
"""
from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from benchmarks.chip import compare, harness, traffic, weights
from benchmarks.chip import trace as trace_mod
from benchmarks.chip.harness import span
from benchmarks.chip.peaks import peaks

TRACE_AT = 0.4         # the traced stretch starts at this share of the window
TRACE_SECONDS = 8.0    # and lasts this long (or a fifth of the window)


@dataclass
class Rec:
    """One request's life on the host clock (seconds, perf_counter)."""
    item: traffic.Item
    req: object
    due: float
    submitted: float
    admitted: Optional[float] = None
    tokens: List[float] = field(default_factory=list)
    fed: int = 0


@dataclass
class Tick:
    index: int
    start: float
    end: float
    prefill: bool
    contexts: List[int]      # each decoding slot's cache length before
    fed: List[Tuple[int, int]]  # (first position, tokens) fed per slot
    generated: int
    finished: int            # requests that ended (and freed their slot)
    slept: float             # seconds waited for arrivals since the last


@dataclass
class Run:
    """What the per-layer readers see."""
    cell: object
    src: dict
    peak: dict
    t0: float
    t1: float
    recs: List[Rec]
    ticks: List[Tick]
    trace: Optional[trace_mod.Trace] = None

    def traced_ticks(self, prefill=None):
        """(span, tick) pairs of the traced ``serve.step`` spans; ``prefill``
        True / False keeps the ticks that did / did not feed prompt
        tokens."""
        if self.trace is None:
            return []
        by_index = {t.index: t for t in self.ticks}
        out = []
        for s in self.trace.spans_named("serve.step"):
            t = by_index.get(int(s.stats.get("tick", -1)))
            if t is not None and (prefill is None or t.prefill == prefill):
                out.append((s, t))
        return out


def build(ctx, cfg_json, mix):
    from repro.models.model import Model
    from repro.serve.engine import Engine
    ref = harness.load_module(
        os.path.join(ctx.cell.root, "reference", f"{cfg_json['reference']}.py"),
        f"bench_ref_{cfg_json['reference']}")
    model = Model(harness.program_config(cfg_json))
    specs = ref.param_specs(cfg_json)
    params = weights.program_tree(specs, ctx.seed, cfg_json["serve_dtype"],
                                  cfg_json["program_params"],
                                  model.abstract_params())
    eng = Engine(model, params, seed=ctx.seed & 0x7FFFFFFF, warmup=True,
                 **mix["engine"])
    return ref, specs, model, eng


def warm(eng, items, vocab):
    """Drive what the window will call through the engine's public entry
    points: the fused step's widths, and the release of a slot at every
    position at which the traffic's requests end.  A request of prompt P
    and max_new M ends at position P + M - 1; a warm request of max_new 2
    and a prompt one shorter than that position ends there too, so
    whatever the engine runs to free it is run here, with no rule of the
    engine's copied."""
    from repro.serve.engine import Request
    ends = sorted({len(i.prompt) + i.max_new - 1 for i in items})
    for k, end in enumerate(ends):
        prompt = np.arange(end - 1, dtype=np.int32) % vocab
        eng.submit(Request(-1 - k, prompt, max_new=2))
    while eng.step():
        pass


def drive(ctx, eng, items):
    """The measured window.  Returns (recs, ticks, t0, t1, trace_dir).

    A traced run closes its window when the traced stretch ends, so its
    host-clock readings cover the same time as its trace and never the
    profiler's writing of the trace."""
    import jax
    from repro.serve.engine import Request
    mix = ctx.cell.mix
    closed = mix["arrivals"]["kind"] == "closed"
    if closed:
        per_client = {}
        for it in items:
            per_client.setdefault(it.client, deque()).append(it)
    else:
        pending = deque(sorted(items, key=lambda i: i.due))
    recs, live, ticks = [], [], []
    trace_dir, tracing = None, False
    t0 = time.perf_counter()
    close = t0 + ctx.seconds
    trace_from = t0 + TRACE_AT * ctx.seconds
    trace_to = trace_from + min(TRACE_SECONDS, ctx.seconds / 5)

    def submit(it, due):
        req = Request(it.rid, it.prompt, max_new=it.max_new)
        with span("serve.submit", rid=it.rid):
            eng.submit(req)
        rec = Rec(it, req, due, time.perf_counter())
        recs.append(rec)
        live.append(rec)

    if closed:
        for q in per_client.values():
            submit(q.popleft(), t0)
    k, slept = 0, 0.0
    while True:
        now = time.perf_counter()
        if ctx.trace and not tracing and trace_dir is None and now >= trace_from:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # harness spans only, no call graph
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        if now >= close or (tracing and now >= trace_to):
            break
        if not closed:
            while pending and t0 + pending[0].due <= now:
                it = pending.popleft()
                submit(it, t0 + it.due)
        if not live:  # every request offered so far has ended
            nxt = close if closed or not pending else min(
                close, t0 + pending[0].due)
            if ctx.trace:  # wake to start or stop the trace on time
                nxt = min(nxt, trace_to if tracing else
                          trace_from if trace_dir is None else nxt)
            w0 = time.perf_counter()
            with span("serve.wait_arrival"):
                time.sleep(max(0.0, nxt - w0))
            slept += time.perf_counter() - w0
            continue
        # a slot's cache length: the prompt fed, and every served token
        # but the last, which the next decode tick feeds
        pos_before = {id(r.req): r.fed + max(len(r.tokens) - 1, 0)
                      for r in live if r.admitted is not None}
        start = time.perf_counter()
        with span("serve.step", tick=k):
            eng.step()
        end = time.perf_counter()
        fed, gen, fed_any, done = [], 0, False, 0
        for rec in list(live):
            req = rec.req
            # the step that admits a request feeds it its first chunk
            if rec.admitted is None and (req.done or req.fed):
                rec.admitted = end
            new_fed = req.fed - rec.fed
            if new_fed:
                fed_any = True
                fed.append((pos_before.get(id(req), 0), new_fed))
                rec.fed = req.fed
            new_out = len(req.out) - len(rec.tokens)
            if new_out:
                gen += new_out
                if not new_fed:  # a decode tick fed the previous token
                    fed.append((pos_before[id(req)], 1))
                rec.tokens.extend([end] * new_out)
            done += req.done
            if req.done and closed and per_client[rec.item.client]:
                submit(per_client[rec.item.client].popleft(), end)
        live[:] = [r for r in live if not r.req.done]
        # a tick without prompt tokens decodes every slot active before it
        ticks.append(Tick(k, start, end, fed_any,
                          [] if fed_any else list(pos_before.values()),
                          fed, gen, done, slept))
        k, slept = k + 1, 0.0
    t1 = max(now, ticks[-1].end) if ticks else now
    if tracing:
        jax.profiler.stop_trace()
    return recs, ticks, t0, t1, trace_dir


def percentile(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def end_to_end(recs, ticks, t0, t1, setup_s) -> dict:
    due_in = [r for r in recs if t0 <= r.due <= t1]
    ttft = [((r.tokens[0] if r.tokens and r.tokens[0] <= t1 else t1) - r.due)
            * 1e3 for r in due_in]
    itl = [(b - a) * 1e3 for r in recs
           if r.req.done and r.tokens and r.tokens[-1] <= t1
           for a, b in zip(r.tokens, r.tokens[1:])]
    out_tokens = sum(sum(1 for t in r.tokens if t0 <= t <= t1) for r in recs)
    return {
        "output_tokens_per_s": (out_tokens / (t1 - t0), "tokens/s"),
        "ttft_p90_ms": (percentile(ttft, 90), "ms"),
        "itl_p95_ms": (percentile(itl, 95), "ms"),
        "setup_s": (setup_s, "s"),
    }, {"due": len(due_in), "ttft_samples": len(ttft),
        "itl_samples": len(itl), "output_tokens": out_tokens,
        "ttft_p50_ms": percentile(ttft, 50), "ttft_p75_ms": percentile(ttft, 75),
        "itl_p50_ms": percentile(itl, 50)}


def host_report(ticks, clock, gcs, t0, t1, excess=0.3) -> str:
    """What the host did inside the window besides serving: JAX's compile
    events (there should be none), Python's collections, and each tick
    that took ``excess`` seconds more than the median tick of its kind,
    with the collections and compile events that ended inside it."""
    ev = clock.counts(t0, t1)
    out = ["window: " + ", ".join(f"{k} {n} ({s:.3f} s)"
                                  for k, (n, s) in ev.items())]
    win = [g for g in gcs.log if t0 <= g[0] <= t1]
    out.append(f"gc: {len(win)} collections, generation 2: "
               f"{sum(g[2] == 2 for g in win)}, longest "
               f"{max((b - a for a, b, _ in win), default=0) * 1e3:.3f} ms")
    for kind in (True, False):
        ts = [t for t in ticks if t.prefill == kind]
        if not ts:
            continue
        med = float(np.median([t.end - t.start for t in ts]))
        for t in ts:
            if t.end - t.start > med + excess:
                g = [b - a for a, b, _ in gcs.log if t.start <= a <= t.end]
                e = [(k, d) for end, k, d in clock.log
                     if t.start <= end <= t.end]
                out.append(
                    f"slow tick {t.index} ({'prefill' if kind else 'decode'}"
                    f"): {(t.end - t.start) * 1e3:.1f} ms against a median "
                    f"of {med * 1e3:.1f}; {t.finished} requests ended in "
                    f"it; gc {[round(x * 1e3, 1) for x in g]} ms; compile "
                    f"events {e}")
    gaps = [(b.start - a.end - b.slept, a.index)
            for a, b in zip(ticks, ticks[1:])]
    if gaps:
        gap, i = max(gaps)
        out.append(f"longest host gap between ticks {gap * 1e3:.1f} ms "
                   f"(after tick {i})")
    return "; ".join(out)


def served(recs, t1):
    """(prompt, served tokens) of each request that finished without error
    inside the window: what the check samples from."""
    return [(r.item.prompt, list(r.req.out)) for r in recs
            if r.req.done and r.req.error is None
            and r.tokens and r.tokens[-1] <= t1]


def free_device():
    """Free every device array left once the caller has dropped the
    program's objects, so the reference runs on an empty chip."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def run(ctx):
    import jax
    cell, cfg_json, mix = ctx.cell, ctx.cell.config, ctx.cell.mix
    items = traffic.generate(mix, ctx.seed, ctx.seconds,
                             cfg_json["vocab_size"])
    ref, specs, model, eng = build(ctx, cfg_json, mix)
    warm(eng, items, cfg_json["vocab_size"])
    setup_s = time.perf_counter() - ctx.t_start
    ctx.note(f"setup_s {setup_s!r}; in set-up: " + ", ".join(
        f"{k} {n} ({s:.3f} s)" for k, (n, s) in ctx.clock.counts().items()))
    gcs = harness.GcClock()
    recs, ticks, t0, t1, trace_dir = drive(ctx, eng, items)
    gcs.close()
    device = harness.device_info(ctx.devices)
    e2e, n = end_to_end(recs, ticks, t0, t1, setup_s)
    late = [r.submitted - r.due for r in recs]
    ctx.note(f"serve: {len(recs)} requests submitted, {n['due']} due in the "
             f"{t1 - t0:.3f} s window, {len(ticks)} ticks "
             f"({sum(t.prefill for t in ticks)} with prompt tokens), "
             f"{n['output_tokens']} output tokens; ttft samples "
             f"{n['ttft_samples']}, itl samples {n['itl_samples']}; "
             f"submit lateness max {max(late, default=0) * 1e3:.3f} ms "
             f"(includes the step in flight); peak_bytes_in_use {device['memory_peak_bytes']}; "
             f"rate {mix['arrivals'].get('rate_per_s')} /s; ttft p50 "
             f"{n['ttft_p50_ms']} ms, p75 {n['ttft_p75_ms']} ms; itl p50 "
             f"{n['itl_p50_ms']} ms")
    ctx.note(host_report(ticks, ctx.clock, gcs, t0, t1))
    finished = served(recs, t1)
    failed = sum(r.req.error is not None for r in recs)
    del eng, model
    free_device()
    sample = compare.pick(finished, ctx.seed, mix["check"]["tokens"])
    w = weights.canonical(specs, ctx.seed, cfg_json["serve_dtype"], "float32")
    gap = compare.max_served_gap(ref, w, cfg_json, sample,
                                 mix["engine"]["max_len"])
    limit = cfg_json["limits"]["max_logit_gap"]
    ctx.note(f"check: {len(sample)} requests, "
             f"{sum(len(o) for _, o in sample)} served tokens compared")
    correct = (bool(sample) and gap is not None and gap <= limit
               and failed == 0)
    result = {"correct": correct, "attempted": len(recs), "failed": failed,
              "metrics": {}, "device": device}
    if ctx.trace:
        tr = trace_mod.load(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if tr.devices and tr.spans:
            result["device"]["busy_s"] = tr.busy_s
            result["device"]["window_s"] = tr.window_s
            result["breakdown"] = tr.breakdown()
        else:
            tr = None  # no device plane (a CPU run) or no span: nothing
        run_ = Run(cell, cfg_json, peaks(device["kind"]), t0, t1, recs, ticks, tr)
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(run_)
            if v is not None:
                result["metrics"][m["name"]] = harness.metric(v, m["unit"])
    else:
        for m in cell.end_to_end:
            v, unit = e2e[m["name"]]
            if v is not None:
                result["metrics"][m["name"]] = harness.metric(v, unit)
    return result, {"max_logit_gap": (gap, limit), "failed": (failed, 0)}
