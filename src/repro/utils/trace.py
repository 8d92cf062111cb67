"""Program spans and counters, on the host clock and the profiler's.

The fuse path and the service loop open spans at their layer boundaries
(``span("repo.stage", iteration=3)``), and the checkpoint I/O counts the
bytes its files move (``count("io.write_bytes", n)``).  Recording is off
by default; ``enable()`` turns it on for the whole process.

* Off, ``span`` returns one shared null context after a single flag check
  and ``count`` returns at once: no clock is read, no annotation is made.
* On, a span opens a ``jax.profiler.TraceAnnotation`` of its name and
  attributes, so it lands in a profiler trace on the device trace's clock,
  and records ``(name, t0, t1, parent, attrs)`` on ``time.perf_counter``.
  ``parent`` is the span open around it on the same thread.

Spans wrap host work and dispatches as they are: a span never waits on the
device.  Records live in a ring of ``RING`` entries, so a long-lived daemon
with tracing on stays bounded; appends are safe from any thread (the
repository's spill executor persists bases on its own threads).
``docs/observability.md`` lists every span and counter the program opens.
"""
from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

# spans kept in memory; the oldest are dropped first
RING = 65536


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[str]
    attrs: Dict[str, Any]


_on = False
_lock = threading.Lock()
_records: "collections.deque[Span]" = collections.deque(maxlen=RING)
_counters: Dict[str, int] = {}
_local = threading.local()
_NULL = contextlib.nullcontext()


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def reset() -> None:
    """Forget every span and counter recorded so far."""
    with _lock:
        _records.clear()
        _counters.clear()


class _Open:
    __slots__ = ("name", "attrs", "parent", "ann", "t0")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        rec = Span(self.name, self.t0, t1, self.parent, self.attrs)
        with _lock:
            _records.append(rec)
        return False


def span(name: str, **attrs):
    """A context manager timing the work inside it as ``name``."""
    if not _on:
        return _NULL
    return _Open(name, attrs)


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name``."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def records() -> List[Span]:
    """The spans recorded (at most ``RING``), in the order they closed."""
    with _lock:
        return list(_records)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def dump(path: str) -> None:
    """Write the spans, then the counters, one JSON object a line."""
    spans, totals = records(), counters()
    with open(path, "w") as f:
        for s in spans:
            f.write(json.dumps({"span": s.name, "t0": s.t0, "t1": s.t1,
                                "parent": s.parent, "attrs": s.attrs},
                               default=str) + "\n")
        for name, value in sorted(totals.items()):
            f.write(json.dumps({"counter": name, "value": value}) + "\n")
