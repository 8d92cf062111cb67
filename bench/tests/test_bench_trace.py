"""The trace reduction on a small trace recorded on a TPU v5e: three calls
of the ``cold_fuse`` kernel and a matmul, with host spans around them."""
import os

import pytest

from bench import tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_v5e_trace.json")
SPANS = ("round", "fuse_pending", "upload")


@pytest.fixture(scope="module")
def events():
    return tracing.load_events(DATA)


def _device_ops(events):
    """(start, end, name) in seconds of the ops on the TPU's "XLA Ops" line,
    found by hand: process 3 is /device:TPU:0, its thread 3 "XLA Ops"."""
    return sorted((e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6, e["name"])
                  for e in events if e.get("ph") == "X" and e.get("pid") == 3
                  and e.get("tid") == 3)


def test_busy_and_window_without_a_window_span(events):
    ops = _device_ops(events)
    red = tracing.reduce_trace(events, SPANS)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(ops[-1][1] - ops[0][0])
    # the recorded ops do not overlap, so their union is their sum; the
    # "XLA Modules" line, which spans whole programs, is not counted
    assert all(a[1] <= b[0] for a, b in zip(ops, ops[1:]))
    assert red["busy_s"] == pytest.approx(sum(b - a for a, b, _ in ops))
    assert red["busy_s"] < red["window_s"]


def test_kernel_time_by_name(events):
    red = tracing.reduce_trace(events, SPANS)
    secs, calls = tracing.kernel_time(red, "_cold_fuse_impl")
    assert calls == 3
    assert secs == pytest.approx((685.947656 + 685.963906 + 685.715) * 1e-6)
    # op names lose their numeric suffix, kernels keep their own name
    assert "_cold_fuse_impl" in red["op_s"]
    assert "convolution_reduce_fusion" in red["op_s"]


def test_window_span_sets_the_window_and_the_idle_share(events):
    window = {"ph": "X", "pid": 701, "tid": 952937413, "ts": 45000.0,
              "dur": 45000.0, "name": tracing.WINDOW}
    red = tracing.reduce_trace(list(events) + [window], SPANS)
    ops = _device_ops(events)
    assert red["window_s"] == pytest.approx(0.045)
    busy = sum(b - a for a, b, _ in ops)
    assert red["busy_s"] == pytest.approx(busy)
    # idle gaps cover the rest of the window
    assert sum(g for g, _ in red["gaps"]) == pytest.approx(0.045 - busy)


def test_idle_gaps_are_named_by_the_innermost_open_span(events):
    red = tracing.reduce_trace(events, SPANS)
    longest, name = red["gaps"][0]
    # the ~11.9 ms between a fuse and the next matmul lies inside "round"
    # and outside both of its inner spans
    assert longest == pytest.approx(58785.192656e-6 - 46871.53515e-6, rel=1e-4)
    assert name == "round"
    bd = tracing.breakdown(red)
    assert bd["idle_gaps"][0] == ["round", longest]
    assert bd["device_ops"][0][0] == "_cold_fuse_impl"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_union_merges_overlaps():
    assert tracing.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]


def test_spans_inside_the_window():
    s = tracing.Spans()
    with s.span("upload"):
        pass
    with s.span(tracing.WINDOW):
        with s.span("upload"):
            pass
    inner = s.inside()
    assert [n for n, _, _ in inner.records] == ["upload"]
    assert len(s.durations("upload")) == 2
