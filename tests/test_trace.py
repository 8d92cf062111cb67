"""The program's spans and counters (``repro.utils.trace``): nothing is
recorded while off; while on, each span keeps its name, parent and
attributes, the ring stays bounded and appends are safe from any thread;
a spilled queue round opens the spans docs/observability.md lists, and the
byte counters match the files."""
import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.repository import Repository
from repro.serve.cold_service import (QUEUE_DIR, AdmissionPolicy, ColdService,
                                      ContributorClient)
from repro.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on():
    """Tracing on for one test, from an empty record; off again after."""
    trace.reset()
    trace.enable()
    try:
        yield
    finally:
        trace.disable()
        trace.reset()


def _m(v, n=64):
    return {"w": jnp.full((n,), float(v)), "b": jnp.full((5,), float(v))}


def test_off_returns_the_shared_null_context_and_records_nothing():
    trace.reset()
    assert not trace.enabled()
    a, b = trace.span("repo.stage", iteration=1), trace.span("x")
    assert a is b
    with a:
        trace.count("io.write_bytes", 10)
    assert trace.records() == [] and trace.counters() == {}


def test_on_records_name_parent_attrs_and_order(on):
    with trace.span("outer", iteration=3):
        with trace.span("inner", sub="c0-000001"):
            pass
        with trace.span("inner2"):
            pass
    recs = trace.records()
    # records close innermost first
    assert [r.name for r in recs] == ["inner", "inner2", "outer"]
    inner, inner2, outer = recs
    assert inner.parent == "outer" and inner2.parent == "outer"
    assert outer.parent is None
    assert outer.attrs == {"iteration": 3} and inner.attrs == {"sub": "c0-000001"}
    assert outer.t0 <= inner.t0 <= inner.t1 <= inner2.t0 <= inner2.t1 <= outer.t1


def test_a_span_closed_by_an_exception_is_recorded(on):
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    with trace.span("after"):
        pass
    assert [(r.name, r.parent) for r in trace.records()] == [
        ("boom", None), ("after", None)]


def test_the_ring_is_bounded(on):
    for i in range(trace.RING + 10):
        with trace.span("s", i=i):
            pass
    recs = trace.records()
    assert len(recs) == trace.RING
    # the oldest went first
    assert recs[0].attrs["i"] == 10 and recs[-1].attrs["i"] == trace.RING + 9


def test_counters_add_and_reset(on):
    trace.count("io.write_bytes", 5)
    trace.count("io.write_bytes", 7)
    trace.count("io.read_bytes", 1)
    assert trace.counters() == {"io.write_bytes": 12, "io.read_bytes": 1}
    trace.reset()
    assert trace.counters() == {} and trace.records() == []


def test_appends_from_executor_threads_are_safe(on):
    """Spill-executor threads record spans and counts concurrently: no
    record or count is lost, and each thread's parents are its own."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(t):
            for _ in range(200):
                with trace.span("repo.persist", thread=t):
                    with trace.span("child", thread=t):
                        trace.count("io.write_bytes", 1)
            return t

        with ThreadPoolExecutor(max_workers=8) as ex:
            done = list(ex.map(work, range(16), timeout=60))
    finally:
        sys.setswitchinterval(old)
    assert done == list(range(16))
    recs = trace.records()
    assert len(recs) == 16 * 200 * 2
    assert trace.counters() == {"io.write_bytes": 16 * 200}
    assert all(r.parent == "repo.persist" for r in recs if r.name == "child")
    assert all(r.parent is None for r in recs if r.name == "repo.persist")


def test_dump_writes_spans_then_counters(on, tmp_path):
    with trace.span("a", iteration=2):
        trace.count("io.read_bytes", 9)
    path = str(tmp_path / "trace.jsonl")
    trace.dump(path)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["span"] == "a" and lines[0]["attrs"] == {"iteration": 2}
    assert lines[0]["t1"] >= lines[0]["t0"] and lines[0]["parent"] is None
    assert lines[1] == {"counter": "io.read_bytes", "value": 9}


# the spans one queue round opens, in the order they start: the admitting
# cycle (stage + dispatch), the publishing cycle (finalize, persist, GC,
# status)
def _round_spans(k):
    return (["service.admit", "repo.stage"]
            + ["repo.spill_read", "repo.h2d"] * k
            + ["repo.stack", "service.bookkeep",
               "service.admit", "repo.finalize", "repo.screen_sync",
               "repo.publish", "repo.persist", "service.bookkeep",
               "service.bookkeep"])


def test_a_spilled_queue_round_opens_every_span_in_order(on, tmp_path,
                                                         monkeypatch):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=True)
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    written = []
    real_replace = os.replace

    def replace(src, dst):
        written.append(os.path.getsize(src))
        real_replace(src, dst)

    monkeypatch.setattr(os, "replace", replace)
    rounds = 2
    for r in range(rounds):
        trace.reset()
        written.clear()
        subs = [client.submit(_m(r + 1 + 0.5 * i), sketch=True,
                              base_iteration=r) for i in range(2)]
        qdir = os.path.join(root, QUEUE_DIR)
        queued = sum(os.path.getsize(os.path.join(qdir, s + ".npz"))
                     for s in subs)
        for _ in range(10):
            if svc.run_once()["iteration"] > r:
                break
        recs = sorted(trace.records(), key=lambda s: s.t0)
        assert [s.name for s in recs] == _round_spans(2)
        # one iteration for every repository and service span of the round
        tagged = {s.attrs["iteration"] for s in recs
                  if s.name.split(".")[0] in ("repo", "service")}
        assert tagged == {r}
        parent = {s.name: s.parent for s in recs}
        assert parent["repo.spill_read"] == parent["repo.h2d"] == "repo.stage"
        assert parent["repo.stack"] == "repo.stage"
        assert parent["repo.screen_sync"] == parent["repo.publish"] == "repo.finalize"
        c = trace.counters()
        assert c["io.write_bytes"] == sum(written)
        # the round reads back exactly its spilled rows
        assert c["io.read_bytes"] == queued
    assert repo.iteration == rounds


def test_persist_on_a_spill_thread_keeps_its_iteration(on, tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, spill_workers=2)
    for v in (1.0, 2.0):
        repo.upload(_m(v))
    repo.fuse_pending()
    repo.flush()
    persist = [s for s in trace.records() if s.name == "repo.persist"]
    assert len(persist) == 1
    assert persist[0].attrs == {"iteration": 0} and persist[0].parent is None
    assert os.path.exists(os.path.join(root, "base_iter0001.npz"))


@pytest.mark.parametrize("tracing", [False, True])
def test_staging_spans_read_the_iteration_only_while_tracing(tracing,
                                                             monkeypatch):
    """Off, the staging spans cost a flag check: the iteration they are
    tagged with is not looked up."""
    reads = []
    real = Repository._staging_iteration

    def counted(self):
        reads.append(1)
        return real(self)

    monkeypatch.setattr(Repository, "_staging_iteration", counted)
    trace.reset()
    if tracing:
        trace.enable()
    try:
        repo = Repository(_m(0))  # no spill: nothing else reads it
        for v in (1.0, 2.0):
            repo.upload(_m(v))
        repo.fuse_pending()
    finally:
        trace.disable()
    staged = [s for s in trace.records() if s.name.startswith("repo.st")]
    trace.reset()
    if tracing:
        assert reads and {s.attrs["iteration"] for s in staged} == {0}
    else:
        assert reads == [] and staged == []


def test_fuse_record_host_times_with_tracing_off(tmp_path):
    """``stage_s`` and ``finalize_s`` are measured with the recorder off,
    and survive a reopen."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True)
    repo.upload(_m(1.0))
    rec = repo.fuse_pending()
    assert rec.stage_s > 0 and rec.finalize_s > 0
    back = Repository.open(root).history[-1]
    assert (back.stage_s, back.finalize_s) == (rec.stage_s, rec.finalize_s)


def test_the_daemon_writes_its_trace_on_exit(tmp_path):
    root = str(tmp_path / "repo")
    Repository(_m(0), root=root, spill=True)
    ContributorClient(root, name="c0").submit(_m(1.0))
    out = str(tmp_path / "trace.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_repository", "--root", root,
         "--max-iterations", "1", "--idle-timeout", "60", "--trace-out", out],
        env=env, capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stderr
    lines = [json.loads(l) for l in open(out)]
    spans = {l["span"] for l in lines if "span" in l}
    assert {"service.admit", "repo.stage", "repo.finalize",
            "repo.persist"} <= spans
    counters = {l["counter"]: l["value"] for l in lines if "counter" in l}
    assert counters["io.write_bytes"] > os.path.getsize(
        os.path.join(root, "base_iter0001.npz"))
