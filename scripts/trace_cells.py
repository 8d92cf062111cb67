#!/usr/bin/env python3
"""Read the program's spans and counters in the benchmark's cells, on a TPU.

    python3 scripts/trace_cells.py [--cells queue.roberta-base,...] \\
        [--seed 3141592653] [--seconds 15] [--out trace_cells.json]

Run from the root of a checkout.  Each cell of ``BENCHMARK.json`` runs
three times in this one process, through ``bench.harness.run``:

* ``off``: tracing off, as the benchmark's untraced run takes it;
* ``on``: the program's recorder (``repro.utils.trace``) on, the profiler
  off; against ``off`` it gives what recording costs;
* ``traced``: the recorder on in a ``--trace 1`` run.

From the traced run it writes, inside the benchmark's window: each span's
count and host seconds (``program``), each counter's increase
(``counters``), the benchmark's own spans (``bench_spans``), the driver's
window counters (``window``: rounds, steps, calls), and from the profiler
trace ``module_s``/``module_calls`` (device seconds and events of every
program on the device's "XLA Modules" line, fingerprint stripped) and
``idle_by_span`` (each idle gap on the device cut at every host span edge,
each piece credited to the innermost span open over all of it, ``none``
where none is), with the longest gaps split the same way.  It also times
a disabled and an enabled ``trace.span`` on the host.

The benchmark's harness does not read the program's spans; this script
reaches them from outside it.  It wraps ``bench.tracing.load_events`` (to
keep the trace's events), ``bench.tracing.Spans.span`` (to find the window
and read the counters at its edges), the harness's driver lookup (to keep
the window's counters), and opens spans of its own around calls that open
none: ``generate`` (the queue driver's off-clock ``Queue._host_rows``),
``client.sketch`` and ``client.write`` (``row_sketch_host`` and the npz
write in ``serve.cold_service``), ``finetune.init``, ``finetune.batch`` and
``finetune.dispatch`` (the optimizer's init, the next batch and the train
step in ``train.finetune.finetune``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import re
import sys
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("queue.roberta-base", "inproc.roberta-base",
         "finetune.roberta-large", "finetune.roberta-base")
MODULES_LINE = "XLA Modules"


def _base_name(name: str) -> str:
    """An annotation with attributes may carry them after a ``#``."""
    return name.split("#", 1)[0]


def _strip_fingerprint(name: str) -> str:
    """``jit_flat_flatten(1234)`` -> ``jit_flat_flatten``."""
    return re.sub(r"\(\d+\)$", "", name)


def _lines(events: Sequence[dict]):
    """Device pids, and their threads by name."""
    from bench.tracing import _DEVICE

    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name":
            procs[e.get("pid")] = name
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = name
    devs = {p for p, n in procs.items() if _DEVICE.search(n or "")}
    return devs, threads


def _window(events: Sequence[dict], window: str) -> Tuple[float, float]:
    """The host span ``window`` in seconds, else the extent of every event."""
    ws = [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6) for e in events
          if e.get("ph") == "X" and "dur" in e
          and _base_name(e.get("name", "")) == window]
    if ws:
        return ws[0][0], ws[-1][1]
    xs = [e for e in events if e.get("ph") == "X" and "dur" in e]
    return (min(e["ts"] for e in xs) * 1e-6,
            max(e["ts"] + e["dur"] for e in xs) * 1e-6)


def module_times(events: Sequence[dict], window: str = "bench_window"
                 ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Device seconds and events of each program on the "XLA Modules" line
    of every device, clipped to the window, fingerprint stripped."""
    devs, threads = _lines(events)
    lo, hi = _window(events, window)
    secs: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    for e in events:
        if (e.get("ph") != "X" or "dur" not in e or e.get("pid") not in devs
                or threads.get((e["pid"], e.get("tid"))) != MODULES_LINE):
            continue
        a = max(e["ts"] * 1e-6, lo)
        b = min((e["ts"] + e["dur"]) * 1e-6, hi)
        if b > a:
            name = _strip_fingerprint(e.get("name", ""))
            secs[name] += b - a
            calls[name] += 1
    return dict(secs), dict(calls)


def idle_split(events: Sequence[dict], span_names: Iterable[str],
               window: str = "bench_window", top: int = 8) -> Dict:
    """Each idle gap of each device inside the window, cut at every edge
    of a host span in ``span_names``; a piece goes to the shortest span
    open over all of it, or ``none``.  Returns the window, busy and idle
    seconds (averaged over devices), ``idle_by_span`` summed by name, and
    the ``top`` longest gaps with their split."""
    from bench.tracing import OPS_LINE, union

    names = set(span_names) - {window}
    devs, threads = _lines(events)
    lo, hi = _window(events, window)
    host: List[Tuple[float, float, str]] = []
    ops: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        a, b = e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6
        if e.get("pid") in devs:
            if threads.get((e["pid"], e.get("tid"))) == OPS_LINE:
                ops[e["pid"]].append((a, b))
        elif _base_name(e.get("name", "")) in names:
            host.append((a, b, _base_name(e["name"])))
    # shortest first, so the first span found over a piece is the innermost
    host.sort(key=lambda s: s[1] - s[0])
    starts = np.array([a for a, _, _ in host], dtype=np.float64)
    ends = np.array([b for _, b, _ in host], dtype=np.float64)
    by_span: Dict[str, float] = defaultdict(float)
    gaps, busy = [], []
    for pid, ivs in ops.items():
        merged = union((max(a, lo), min(b, hi)) for a, b in ivs
                       if b > lo and a < hi)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            over = [host[i] for i in
                    np.flatnonzero((starts < g1) & (ends > g0))]
            cuts = sorted({g0, g1} | {x for a, b, _ in over for x in (a, b)
                                       if g0 < x < g1})
            split: Dict[str, float] = defaultdict(float)
            for a, b in zip(cuts, cuts[1:]):
                name = next((n for s, t, n in over if s <= a and b <= t),
                            "none")
                split[name] += b - a
            for n, s in split.items():
                by_span[n] += s
            gaps.append({"s": g1 - g0, "at": g0 - lo, "split": dict(split)})
    gaps.sort(key=lambda g: -g["s"])
    n = max(len(busy), 1)
    return {"window_s": hi - lo, "busy_s": sum(busy) / n,
            "idle_s": sum(g["s"] for g in gaps) / n,
            "idle_by_span": {k: v / n for k, v in by_span.items()},
            "longest_gaps": gaps[:top]}


def totals(records: Iterable[Tuple[str, float, float]], lo: float, hi: float
           ) -> Dict[str, List[float]]:
    """{name: [count, seconds]} of the spans that lie within [lo, hi]."""
    out: Dict[str, List[float]] = {}
    for name, a, b in records:
        if lo <= a and b <= hi:
            c = out.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += b - a
    return out


def span_cost_ns(n: int = 200_000) -> Tuple[float, float]:
    """Host nanoseconds of one ``with trace.span(...)`` off, then on."""
    from repro.utils import trace

    def loop():
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("repo.stage", iteration=1):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    trace.disable()
    off = loop()
    trace.enable()
    try:
        on = loop()
    finally:
        trace.disable()
        trace.reset()
    return off, on


class Probe:
    """The wrappers: what a run leaves behind for the reductions."""

    def __init__(self):
        self.events = None
        self.spans = None
        self.c0 = self.c1 = {}
        self.window = {}

    def install(self):
        from bench import harness, tracing
        from bench.drivers import fuse
        from repro.checkpoint import io as ckpt
        from repro.serve import cold_service as cs
        from repro.train import finetune as FT
        from repro.utils import trace

        probe = self
        load, span = tracing.load_events, tracing.Spans.span

        def load_events(path):
            probe.events = load(path)
            return probe.events

        @contextlib.contextmanager
        def bench_span(spans, name):
            if name == tracing.WINDOW:
                probe.spans, probe.c0 = spans, trace.counters()
            with span(spans, name):
                yield
            if name == tracing.WINDOW:
                probe.c1 = trace.counters()

        tracing.load_events, tracing.Spans.span = load_events, bench_span

        lookup = harness.driver_for

        def driver_for(kind):
            cls = lookup(kind)

            class Kept(cls):
                def window(self, seconds):
                    w = super().window(seconds)
                    probe.window = dict(w.counters)
                    return w

            return Kept

        harness.driver_for = driver_for

        def wrap(fn, name):
            def wrapped(*a, **kw):
                with trace.span(name):
                    return fn(*a, **kw)
            return wrapped

        fuse.Queue._host_rows = wrap(fuse.Queue._host_rows, "generate")
        cs.row_sketch_host = wrap(cs.row_sketch_host, "client.sketch")

        class Writes:
            """``checkpoint.io`` as ``serve.cold_service`` sees it: the
            client's writes open ``client.write``."""

            def __getattr__(self, attr):
                fn = getattr(ckpt, attr)
                if attr in ("save_flat", "save_flat_delta",
                            "save_flat_shards"):
                    return wrap(fn, "client.write")
                return fn

        cs.ckpt = Writes()

        steps, batches = FT._steps, FT.batches

        def spanned_steps(*a, **kw):
            opt, step, ev = steps(*a, **kw)
            opt = dataclasses.replace(opt, init=wrap(opt.init,
                                                     "finetune.init"))
            return opt, wrap(step, "finetune.dispatch"), ev

        def spanned_batches(*a, **kw):
            it = batches(*a, **kw)
            while True:
                with trace.span("finetune.batch"):
                    b = next(it, None)
                if b is None:
                    return
                yield b

        FT._steps, FT.batches = spanned_steps, spanned_batches


def run_cell(harness, bm, name: str, seed: int, seconds: float, devices,
             peaks, probe: Probe) -> Dict:
    from repro.utils import trace

    cell = harness.resolve(bm, name)
    devs = devices[:cell["chips"]]
    out: Dict = {"seed": seed}
    probe.events = None
    for mode, on, traced in (("off", False, False), ("on", True, False),
                             ("traced", True, True)):
        trace.reset()
        (trace.enable if on else trace.disable)()
        try:
            out[mode] = harness.run(cell, seed, seconds, traced,
                                    t_start=time.perf_counter(),
                                    devices=devs, peaks=peaks)
        finally:
            trace.disable()
        print(f"{name} {mode} {json.dumps(out[mode].get('metrics'))}",
              flush=True)
    lo, hi = next((a, b) for n, a, b in reversed(probe.spans.records)
                  if n == "bench_window")
    recs = [(s.name, s.t0, s.t1) for s in trace.records()]
    bench_names = {n for n, _, _ in probe.spans.records}
    out["window"] = probe.window
    out["program"] = totals(recs, lo, hi)
    out["bench_spans"] = totals(
        [r for r in probe.spans.records if r[0] != "bench_window"], lo, hi)
    out["counters"] = {k: v - probe.c0.get(k, 0) for k, v in probe.c1.items()}
    if probe.events is not None:
        names = bench_names | {n for n, _, _ in recs}
        red = idle_split(probe.events, names)
        red["module_s"], red["module_calls"] = module_times(probe.events)
        out["trace"] = red
    trace.reset()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seed", type=int, default=3141592653)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default="trace_cells.json")
    args = ap.parse_args(argv)
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("trace_cells: no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(cache)
    peaks = harness.peaks_for(devices[0].device_kind)
    bm = harness.benchmark()
    probe = Probe()
    probe.install()
    off_ns, on_ns = span_cost_ns()
    res = {"span_off_ns": off_ns, "span_on_ns": on_ns,
           "device": devices[0].device_kind, "cells": {}}
    for i, name in enumerate(args.cells.split(",")):
        res["cells"][name] = run_cell(harness, bm, name, args.seed + i,
                                      args.seconds, devices, peaks, probe)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    print(f"span off {off_ns:.0f} ns, on {on_ns:.0f} ns; wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
