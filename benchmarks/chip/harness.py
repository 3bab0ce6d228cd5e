"""What every cell shares: finding its files by name, the device, the
compile clock, harness spans, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the published sizes (source keys), how the
  program is built from them, the reference, the limits of the check;
- ``mixes/<traffic>.json``: the traffic or job, read by ``traffic.py`` and
  by the driver that the mix names (``drivers/<driver>.py``);
- ``metrics/<metric>.py``: a per-layer metric's reader, ``read(run)``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str = HERE

    def driver(self):
        d = self.mix["driver"]
        return load_module(os.path.join(self.root, "drivers", f"{d}.py"),
                           f"bench_driver_{d}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.root, "metrics", f"{metric}.py"),
                           f"bench_metric_{metric}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(bench: dict, name: str, root: str = HERE) -> Cell:
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(wl)}")
    w = wl[name]
    config = load_json(os.path.join(root, "configs", f"{w['config']}.json"))
    mix = load_json(os.path.join(root, "mixes", f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in moved]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer, root)


def program_config(config: dict):
    """The program's ModelConfig: the registry entry the configuration
    names, with every published size it maps set from the source keys, and
    the program's own fields (dtypes) set as the configuration states."""
    from repro.configs import registry
    prog = config["program"]
    kw = {field_: config[key] for key, field_ in prog["from_source"].items()}
    kw.update(prog.get("fields", {}))
    return registry.get(prog["registry"]).replace(**kw)


class CompileClock:
    """JAX's compile work on the host clock, from its monitoring events:
    ``traces`` (a function traced to a jaxpr), ``compiles`` (an XLA compile
    or, since the event wraps ``compile_or_get_cached``, a load from the
    persistent cache) and ``cache_loads`` (persistent-cache hits alone).
    Each is logged with the ``perf_counter`` at which it ended."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads"}

    def __init__(self):
        import jax
        self.log = []  # (end, kind, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        kind = self.EVENTS.get(event)
        if kind is not None:
            self.log.append((time.perf_counter(), kind, duration))

    def counts(self, since=-math.inf, until=math.inf) -> Dict[str, list]:
        """{kind: [count, seconds]} of the events that ended in the span."""
        out = {k: [0, 0.0] for k in self.EVENTS.values()}
        for end, kind, dur in self.log:
            if since <= end <= until:
                out[kind][0] += 1
                out[kind][1] += dur
        return out


class GcClock:
    """Python's garbage collections, as (start, end, generation) on the
    ``perf_counter`` clock; ``close()`` stops recording."""

    def __init__(self):
        self.log = []
        self._start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.log.append((self._start, time.perf_counter(),
                             info["generation"]))
            self._start = None

    def close(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


def span(name: str, **stats):
    """A harness span: a profiler TraceAnnotation on the host's timeline
    (nearly free while no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)


def devices_or_exit(chips: int):
    """The chips the cell asks for, or exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX found {devs[0].platform}); nothing "
              "was run", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def open_cell(workload: str):
    """What every command that holds the chip does first: find the cell in
    the checkout's ``BENCHMARK.json``, take its chips (or exit with no
    result), and keep compiled programs in the checkout's compile cache.
    Returns (cell, devices)."""
    root = repo_root()
    sys.path.insert(0, os.path.join(root, "src"))
    cell = load_cell(load_json(os.path.join(root, "BENCHMARK.json")), workload)
    devs = devices_or_exit(cell.chips)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # every program, however quick to compile, comes from the cache on the
    # second run, so set-up is the same from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell, devs


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak(devs)}


@dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, the clocks."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float
    clock: CompileClock

    def note(self, msg: str):
        print(msg, file=sys.stderr, flush=True)


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def finish(result: dict, checks: Dict[str, tuple]) -> None:
    """Print every compared number beside its limit as the last lines on
    stderr, then the result line, with the checks as its last key."""
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
