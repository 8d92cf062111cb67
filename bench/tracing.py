"""The benchmark's host spans and its reduction of a profiler trace.

``Spans`` times the benchmark's own calls into the program on the host
clock (and holds the program's own spans, read from ``repro.utils.trace``,
in a traced run).  In a traced run each span is also a ``jax.profiler.TraceAnnotation``
of the same name, so the trace carries it on the device's clock and an idle
gap on the device can be named by the span that was open on the host.

``reduce_trace`` turns the profiler's Chrome-format trace
(``perfetto_trace.json.gz``) into the numbers the per-layer metrics read:
the union of device-op intervals (busy time), the idle gaps, device time per
op name, and the host spans.  It needs nothing but the standard library, so
it is tested on a small recorded trace without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# the span that brackets the traced window
WINDOW = "bench_window"
_DEVICE = re.compile(r"/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
# device-plane lines that summarise rather than run ops
_SUMMARY_LINES = ("Steps", "XLA Modules", "Async XLA Ops", "Framework",
                  "Source", "TensorFlow", "Launch Stats", "SparseCore",
                  "TC Overlay")


class Spans:
    """Host-clock spans (name, start, end) around the benchmark's calls."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> List[float]:
        return [b - a for n, a, b in self.records if n == name]

    def window(self, name: str = WINDOW) -> Tuple[float, float]:
        """Start and end of the last span called ``name``."""
        return next((a, b) for n, a, b in reversed(self.records) if n == name)

    def inside(self, name: str = WINDOW) -> "Spans":
        """The spans that lie within the last span called ``name``."""
        lo, hi = self.window(name)
        out = Spans(self.annotate)
        out.records = [r for r in self.records
                       if r[0] != name and lo <= r[1] and r[2] <= hi]
        return out


def find_trace(logdir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(logdir, "**", "perfetto_trace.json.gz"),
                            recursive=True))
    return hits[-1] if hits else None


def load_events(path: str) -> List[dict]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _base_name(name: str) -> str:
    """A host annotation with attributes carries them after a ``#``
    (``repo.finalize#iteration=3#``)."""
    return name.split("#", 1)[0]


def _strip_suffix(name: str) -> str:
    """``fusion.12`` and ``fusion.13`` are one kind of op; a kernel keeps
    its own name (``_cold_fuse_impl``)."""
    return re.sub(r"\.\d+$", "", name)


def reduce_trace(events: Sequence[dict], span_names: Iterable[str] = ()) -> Dict:
    """Reduce a Chrome-format trace (times in microseconds) to seconds.

    The window is the ``bench_window`` host span when present, else the
    extent of the device ops.  Device ops are the complete events on a
    ``/device:TPU:N`` process, on every line but the summary lines.
    Returns::

        devices   number of devices with ops
        window_s  length of the window
        busy_s    union of op intervals inside the window, averaged over devices
        op_s      {op name: device seconds}, summed over devices
        op_calls  {op name: number of events}
        gaps      [(seconds, span open at the gap's middle or "none")],
                  longest first, per device
    """
    span_names = set(span_names) | {WINDOW}
    procs: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name":
            procs[e.get("pid")] = name
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = name
    dev_pids = {pid for pid, n in procs.items() if _DEVICE.search(n or "")}
    # a TPU plane runs its ops on the "XLA Ops" line; elsewhere take every
    # line that is not a summary
    op_lines = {pid: {t for (p, t), n in threads.items()
                      if p == pid and n == OPS_LINE} for pid in dev_pids}
    ops: Dict[int, List[Tuple[float, float, str]]] = defaultdict(list)
    spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        pid = e.get("pid")
        t0 = float(e["ts"]) * 1e-6
        t1 = t0 + float(e["dur"]) * 1e-6
        if pid in dev_pids:
            if op_lines[pid]:
                if e.get("tid") not in op_lines[pid]:
                    continue
            elif threads.get((pid, e.get("tid")), "").startswith(_SUMMARY_LINES):
                continue
            ops[pid].append((t0, t1, e.get("name", "")))
        elif _base_name(e.get("name", "")) in span_names:
            spans[_base_name(e["name"])].append((t0, t1))
    if spans.get(WINDOW):
        lo, hi = spans[WINDOW][0][0], spans[WINDOW][-1][1]
    elif ops:
        lo = min(a for v in ops.values() for a, _, _ in v)
        hi = max(b for v in ops.values() for _, b, _ in v)
    else:
        lo = hi = 0.0
    window = hi - lo
    op_s: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    busy = []
    gaps: List[Tuple[float, str]] = []
    host = sorted(((a, b, n) for n, v in spans.items() if n != WINDOW
                   for a, b in v), key=lambda s: s[1] - s[0])
    for pid, evs in ops.items():
        inside = [(max(a, lo), min(b, hi), n) for a, b, n in evs
                  if b > lo and a < hi]
        for a, b, n in inside:
            op_s[_strip_suffix(n)] += b - a
            op_calls[_strip_suffix(n)] += 1
        merged = union((a, b) for a, b, _ in inside)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > 0:
                mid = 0.5 * (a + b)
                # the innermost (shortest) span open at the gap's middle
                name = next((n for s, t, n in host if s <= mid <= t), "none")
                gaps.append((b - a, name))
    gaps.sort(key=lambda g: -g[0])
    return {
        "devices": len(ops),
        "window_s": window,
        "busy_s": (sum(busy) / len(busy)) if busy else 0.0,
        "op_s": dict(op_s),
        "op_calls": dict(op_calls),
        "gaps": gaps,
    }


def kernel_time(red: Dict, kernel: str) -> Tuple[float, int]:
    """Device seconds and event count of every op whose name contains
    ``kernel``."""
    s = sum(v for n, v in red["op_s"].items() if kernel in n)
    c = sum(v for n, v in red["op_calls"].items() if kernel in n)
    return s, c


def breakdown(red: Dict, top: int = 10) -> Dict[str, list]:
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for s, n in red["gaps"][:top]]}
