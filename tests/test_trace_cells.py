"""The reductions of ``scripts/trace_cells.py``: device time per program on
the "XLA Modules" line of a trace recorded on a TPU v5e, the split of each
idle gap among the host spans open over it, and span totals in a window."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "scripts")]

import trace_cells as tc  # noqa: E402
from bench import tracing  # noqa: E402

RECORDED = os.path.join(ROOT, "bench", "tests", "data", "tpu_v5e_trace.json")


def test_module_times_find_the_fuse_kernel_on_the_recorded_trace():
    events = tracing.load_events(RECORDED)
    secs, calls = tc.module_times(events)
    # three calls of the kernel, each program named without its fingerprint
    assert calls["jit__cold_fuse_impl"] == 3
    assert all("(" not in n for n in secs)
    # a program spans at least its kernel's op on the "XLA Ops" line
    red = tracing.reduce_trace(events)
    kernel_s, _ = tracing.kernel_time(red, "_cold_fuse_impl")
    assert secs["jit__cold_fuse_impl"] >= kernel_s > 0


def _meta(pid, tid, proc, thread):
    return [{"ph": "M", "name": "process_name", "pid": pid,
             "args": {"name": proc}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
             "args": {"name": thread}}]


def _x(pid, tid, name, t0, t1):
    """A complete event from t0 to t1 microseconds."""
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": t0,
            "dur": t1 - t0}


def _synthetic():
    """Window 0-100 us; ops at 0-10 and 90-100; one idle gap 10-90 under
    host spans: a (5-50) holding its child b (20-40), then c (60-80)
    carrying attributes after a ``#``."""
    return (_meta(1, 1, "/device:TPU:0", "XLA Ops")
            + _meta(1, 2, "/device:TPU:0", "XLA Modules")
            + _meta(2, 1, "/host:CPU", "python")
            + [_x(2, 1, "bench_window", 0, 100),
               _x(1, 1, "fusion.1", 0, 10), _x(1, 1, "fusion.2", 90, 100),
               _x(1, 2, "jit_stage_stack(123)", 0, 10),
               _x(1, 2, "jit_stage_stack(123)", 90, 100),
               _x(2, 1, "a", 5, 50), _x(2, 1, "b", 20, 40),
               _x(2, 1, "c#iteration=3#", 60, 80),
               _x(2, 1, "not_listed", 10, 90)])


def test_an_idle_gap_is_split_among_the_spans_open_over_it():
    red = tc.idle_split(_synthetic(), ["a", "b", "c"])
    us = {k: round(v * 1e6, 6) for k, v in red["idle_by_span"].items()}
    # 10-20 and 40-50 under a, 20-40 under its child b, 50-60 and 80-90
    # under none, 60-80 under c; a span not listed names nothing
    assert us == {"a": 20, "b": 20, "c": 20, "none": 20}
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(20e-6)
    assert red["idle_s"] == pytest.approx(80e-6)
    (gap,) = red["longest_gaps"]
    assert gap["s"] == pytest.approx(80e-6) and gap["at"] == pytest.approx(10e-6)


def test_module_times_clip_to_the_window_and_strip_fingerprints():
    events = _synthetic() + [_x(1, 2, "jit_flat_flatten(9)", 95, 130)]
    secs, calls = tc.module_times(events)
    assert calls == {"jit_stage_stack": 2, "jit_flat_flatten": 1}
    assert secs["jit_stage_stack"] == pytest.approx(20e-6)
    assert secs["jit_flat_flatten"] == pytest.approx(5e-6)


def test_totals_keep_the_spans_inside_the_window():
    recs = [("repo.stage", 1.0, 2.0), ("repo.stage", 3.0, 3.5),
            ("repo.stage", 9.0, 11.0), ("repo.persist", 0.5, 1.5)]
    assert tc.totals(recs, 1.0, 10.0) == {"repo.stage": [2, 1.5]}
