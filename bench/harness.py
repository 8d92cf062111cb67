"""Resolve a cell of ``BENCHMARK.json`` to its files and run it once.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the traffic's ``kind`` picks the driver.  The
end-to-end metrics the cell reports are those of ``end_to_end`` whose
``workloads`` list it (or that have no list); its per-layer metrics are the
``per_layer`` entries that list it, each read by ``metrics/<name>.py``.
The driver gets the cell's chips; in a traced run the program's own spans
and counters (``repro.utils.trace``) are recorded too, and handed to the
readers beside the benchmark's.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Mapping, Optional

from . import tracing
from .drivers import Check, driver_for

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(entry: Mapping, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(bm: Mapping, name: str) -> Dict:
    """The cell's entry with its configuration, traffic and metric entries."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = dict(cells[name])
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    w["config_file"] = load_json(os.path.join(ROOT, conf["file"]))
    w["traffic_file"] = load_json(os.path.join(HERE, "traffic",
                                               w["traffic"] + ".json"))
    w["end_to_end"] = [m for m in bm["end_to_end"] if _applies(m, name)]
    w["per_layer"] = [m for m in bm["per_layer"] if _applies(m, name)]
    return w


def use_compile_cache(path: str) -> None:
    """Keep every compiled program in ``path`` (a fixed directory in the
    checkout), so that only a checkout's first run compiles, whatever
    limit the machine's environment sets."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def peaks_for(kind: str) -> Dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read: the cell's sizes and traffic, the
    benchmark's spans of the traced window, the reduced trace, the window's
    counters, the chip's peaks, and the program's spans that lie in the
    window with its counters' change over the window."""
    cfg: Mapping
    traffic: Mapping
    spans: tracing.Spans
    trace: Optional[Dict]
    counters: Mapping
    peaks: Optional[Mapping]
    program: tracing.Spans = dataclasses.field(default_factory=tracing.Spans)
    program_counters: Mapping = dataclasses.field(default_factory=dict)


class ProgramTrace:
    """The program's spans and counters (``repro.utils.trace``) over one
    window: recording is switched on at ``start`` and back to what it was
    at ``stop``.  ``spans(lo, hi)`` are those that lie within [lo, hi] on
    the host clock, ``counters`` each counter's change."""

    def __init__(self):
        from repro.utils import trace

        self.trace, self.was = trace, trace.enabled()
        self.c0: Dict[str, int] = {}
        self.counters: Dict[str, int] = {}

    def start(self) -> None:
        self.trace.enable()
        self.c0 = self.trace.counters()

    def stop(self) -> None:
        c1 = self.trace.counters()
        self.counters = {k: v - self.c0.get(k, 0) for k, v in c1.items()}
        if not self.was:
            self.trace.disable()

    def spans(self, lo: float, hi: float) -> tracing.Spans:
        out = tracing.Spans()
        out.records = [(s.name, s.t0, s.t1) for s in self.trace.records()
                       if lo <= s.t0 and s.t1 <= hi]
        return out


class CompileCount:
    """Counts compilations and persistent-cache loads while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax.monitoring as mon

        self.active = False
        self.n = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if self.active and name in self.EVENTS:
            self.n += 1

    def _duration(self, name, secs, **kw):
        self._event(name)


def _num(x: float):
    return x if math.isfinite(x) else str(x)


def run(cell: Mapping, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices, peaks: Optional[Mapping] = None,
        modes: tuple = ()) -> Dict[str, Any]:
    """Run ``cell`` once; return the result line's object.  ``devices`` are
    the chips the cell uses; ``modes`` adds the readings of what may be put
    in the program's place (calibration only)."""
    import jax

    traffic = cell["traffic_file"]
    spans = tracing.Spans(annotate=trace)
    drv = driver_for(traffic["kind"])(cell["config_file"], traffic, seed,
                                      spans, devices)
    compiles = CompileCount()
    program = ProgramTrace() if trace else None
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        drv.setup()
        setup_s = time.perf_counter() - t_start
        print(f"[bench] setup_s {setup_s:.3f}", file=sys.stderr, flush=True)
        length = min(seconds, float(traffic["trace_seconds"])) if trace else seconds
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no event per Python call
            opts.host_tracer_level = 1  # the benchmark's spans, not the runtime's
            jax.profiler.start_trace(logdir, create_perfetto_trace=True,
                                     profiler_options=opts)
            program.start()
        compiles.active = True
        try:
            with spans.span(tracing.WINDOW):
                win = drv.window(length)
        finally:
            compiles.active = False
            if trace:
                program.stop()
                jax.profiler.stop_trace()
        if compiles.n:
            print(f"[bench] warning: {compiles.n} compilations or cache loads "
                  "inside the window", file=sys.stderr, flush=True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        drv.release()
        checks: List[Check] = drv.check()
        extra = {m: {c.name: c.value for c in drv.check(m)} for m in modes}
        if modes and hasattr(drv, "look"):
            extra["look"] = drv.look()
        red = None
        if trace:
            lo, hi = spans.window()
            prog = program.spans(lo, hi)
            path = tracing.find_trace(logdir)
            red = tracing.reduce_trace(
                tracing.load_events(path),
                {n for n, _, _ in spans.records + prog.records})
    finally:
        drv.close()
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    values = dict(win.values, setup_s=setup_s)
    metrics: Dict[str, Dict] = {}
    if trace:
        ctx = Context(cell["config_file"]["as_run"], traffic,
                      spans.inside(), red, win.counters, peaks,
                      program=prog, program_counters=program.counters)
        for m in cell["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {
        "correct": bool(all(c.ok for c in checks) and win.failed == 0),
        "attempted": win.attempted, "failed": win.failed,
        "metrics": metrics, "device": device,
    }
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = tracing.breakdown(red)
    if extra:
        out["modes"] = extra
    out["checks"] = {c.name: {"value": _num(c.value), "limit": c.limit}
                     for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=sys.stderr, flush=True)
    return out
