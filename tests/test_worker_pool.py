"""Serving scale-out (repro/serve/{router,worker_pool}.py,
docs/serving.md): router policy units (least-loaded + round-robin,
drain-on-swap, dead-marking with exactly-once re-route, shed failover),
the multi-worker version-pinning interleaving property suite, the
daemon's worker-state namespace aggregation, and the slow cross-process
pool tests — adoption over the socket protocol, batched coalescing, and
kill -9 of a member (including at a swap seam) with the router
converging to zero failed requests."""
import os
import shutil
import tempfile
import threading
import time
import types

import numpy as np
import pytest

from _faults import wait_until
from _hypothesis_compat import given, settings, st
from repro.core.repository import Repository
from repro.serve.cold_service import AdmissionPolicy, ColdService
from repro.serve.hot_swap import ServingWorker
from repro.serve.router import EndpointDied, LocalEndpoint, Router
from repro.serve.scheduler import RequestRejected
from repro.serve.worker_pool import WorkerPool
from repro.utils import faults

PROMPT = np.zeros((2,), np.int32)   # one [T] row (routers take rows)


def _m(v, n=64):
    import jax.numpy as jnp
    return {"w": jnp.full((n,), float(v)), "b": jnp.full((5,), float(v))}


def _repo(root, **kw):
    kw.setdefault("screen", False)
    return Repository(_m(0), root=str(root), spill=True, **kw)


def _publish(repo, v) -> int:
    repo.upload(_m(v))
    repo.fuse_pending()
    repo.flush()
    return repo.iteration


class _ValueEngine:
    """Generation returns the served tree's scalar w value — a token
    mismatch IS a version tear (same fake as the hot_swap suite)."""

    def __init__(self, cfg, params, max_len):
        self.params = params

    def generate(self, prompts, *, max_new_tokens=16, params=None):
        p = self.params if params is None else params
        val = float(np.asarray(p["w"]).reshape(-1)[0])
        toks = np.full((prompts.shape[0], prompts.shape[1] + max_new_tokens),
                       val, np.float32)
        return types.SimpleNamespace(tokens=toks,
                                     prompt_len=int(prompts.shape[1]),
                                     steps=int(max_new_tokens))


def _fake(cfg, params, max_len):
    return _ValueEngine(cfg, params, max_len)


# ---------------------------------------------------------------------------
# router policy units (programmable endpoints)
# ---------------------------------------------------------------------------


class _Ep:
    """Programmable endpoint: health and failure modes set per test."""

    def __init__(self, eid, value=1.0):
        self.id = eid
        self.value = float(value)
        self.swapping = False
        self.alive = True          # health() returns None when False
        self.stale = False         # health older than HEALTH_STALE_S
        self.fail_next = None      # exception instance raised ONCE
        self.calls = 0

    def health(self):
        if not self.alive:
            return None
        age = 99.0 if self.stale else 0.0
        return {"iteration": 0, "swapping": self.swapping,
                "updated_at": time.time() - age}

    def generate(self, prompt, *, max_new_tokens, deadline_s=None):
        self.calls += 1
        if self.fail_next is not None:
            err, self.fail_next = self.fail_next, None
            raise err
        return {"tokens": np.full(len(prompt) + max_new_tokens, self.value),
                "iteration": 0, "steps": max_new_tokens,
                "batch_size": 1, "latency_s": 0.001}


def test_router_spreads_equal_load_round_robin():
    a, b = _Ep("a"), _Ep("b")
    r = Router([a, b])
    for _ in range(6):
        r.route(PROMPT)
    st = r.stats()
    assert st["per_worker"]["a"] > 0 and st["per_worker"]["b"] > 0
    assert st["routed_total"] == 6 and st["failed_total"] == 0


def test_router_drains_swapping_worker():
    """A mid-swap worker is deprioritized (drained), not excluded — and
    re-joins as soon as its swap ends."""
    a, b = _Ep("a"), _Ep("b")
    a.swapping = True
    r = Router([a, b])
    for _ in range(4):
        assert r.route(PROMPT).worker_id == "b"
    a.swapping = False
    for _ in range(4):
        r.route(PROMPT)
    assert r.stats()["per_worker"]["a"] >= 1, "drained worker never re-joined"


def test_router_serves_even_when_all_swapping():
    a, b = _Ep("a"), _Ep("b")
    a.swapping = b.swapping = True
    r = Router([a, b])
    assert r.route(PROMPT).worker_id in ("a", "b")
    assert r.stats()["failed_total"] == 0


def test_router_reroutes_died_endpoint_exactly_once():
    """An in-flight transport death re-routes that request exactly once;
    the endpoint is dead-marked, and fresh health re-admits it (the
    restarted-worker path)."""
    a, b = _Ep("a"), _Ep("b")
    a.fail_next = EndpointDied("killed mid-request")
    r = Router([a, b], max_reroutes=1)
    results = [r.route(PROMPT) for _ in range(4)]
    st = r.stats()
    assert st["failed_total"] == 0
    assert st["reroutes_total"] == 1          # the one in-flight failure
    assert sum(x.rerouted for x in results) == 1
    # a's health stayed fresh, so it was re-admitted and served again
    assert a.calls >= 2
    assert "a" not in st["dead"]


def test_router_skips_endpoint_with_no_health_then_readmits():
    a, b = _Ep("a"), _Ep("b")
    a.alive = False
    r = Router([a, b])
    for _ in range(3):
        assert r.route(PROMPT).worker_id == "b"
    assert "a" in r.stats()["dead"]
    a.alive = True   # restarted worker heartbeats its state file again
    for _ in range(4):
        r.route(PROMPT)
    st = r.stats()
    assert st["per_worker"]["a"] >= 1 and "a" not in st["dead"]


def test_router_treats_stale_health_as_dead():
    a, b = _Ep("a"), _Ep("b")
    a.stale = True
    r = Router([a, b])
    for _ in range(3):
        assert r.route(PROMPT).worker_id == "b"
    assert r.stats()["failed_total"] == 0


def test_router_fails_over_a_shed_without_dead_marking():
    """queue_full means alive-and-bounded: fail over under the same
    single-retry budget, but never mark the worker dead."""
    a, b = _Ep("a"), _Ep("b")
    a.fail_next = RequestRejected("queue_full")
    r = Router([a, b], max_reroutes=1)
    results = [r.route(PROMPT) for _ in range(4)]
    st = r.stats()
    assert st["failed_total"] == 0 and st["shed_total"] == 0
    assert "a" not in st["dead"]
    assert sum(x.rerouted for x in results) == 1


def test_router_surfaces_pool_saturation():
    a, b = _Ep("a"), _Ep("b")
    a.fail_next = RequestRejected("queue_full")
    b.fail_next = RequestRejected("queue_full")
    r = Router([a, b], max_reroutes=1)
    with pytest.raises(RequestRejected):
        r.route(PROMPT)
    st = r.stats()
    assert st["failed_total"] == 1 and st["shed_total"] == 1


def test_router_raises_when_no_live_endpoint():
    a = _Ep("a")
    a.alive = False
    r = Router([a])
    with pytest.raises(EndpointDied):
        r.route(PROMPT)
    assert r.stats()["failed_total"] == 1
    with pytest.raises(ValueError):
        Router([])


# ---------------------------------------------------------------------------
# multi-worker version-pinning property suite (ISSUE satellite)
# ---------------------------------------------------------------------------


@settings(max_examples=10)
@given(st.data())
def test_pool_interleavings_serve_only_pinned_published_weights(data):
    """Any interleaving of publish / rollback / per-worker poll / route
    across a 2-worker pool: EVERY routed response was computed by the
    exact weights the repository published as that response's pinned
    iteration at the moment its worker adopted it — workers poll
    repository.json independently (cross-process watch mode), so they
    may sit at different iterations; the router must never blend them
    within one request."""
    ops = data.draw(st.lists(
        st.sampled_from(["publish", "rollback", "poll0", "poll1",
                         "route", "route", "route"]),
        min_size=6, max_size=18))
    root = tempfile.mkdtemp(prefix="pool_prop_")
    try:
        repo = _repo(root)
        repo.flush()   # iteration 0 durable before the workers look
        workers = [ServingWorker(None, root, engine_factory=_fake,
                                 worker_id=f"w{i}", name=f"w{i}")
                   for i in range(2)]
        for w in workers:
            assert w.poll_once()
        router = Router([LocalEndpoint(w) for w in workers])
        live = {0: 0.0}       # iteration -> value published AS it (now)
        adopted = {w.worker_id: (0, 0.0) for w in workers}
        next_v = 1.0
        for op in ops:
            if op == "publish":
                it = _publish(repo, next_v)
                live[it] = next_v
                next_v += 1.0
            elif op == "rollback":
                if repo.iteration == 0:
                    continue
                target = data.draw(st.integers(0, repo.iteration - 1))
                repo.rollback(target)
                live = {k: v for k, v in live.items() if k <= target}
            elif op in ("poll0", "poll1"):
                w = workers[int(op[-1])]
                if w.poll_once():
                    adopted[w.worker_id] = (w.current_iteration,
                                            live[w.current_iteration])
            else:
                r = router.route(PROMPT, max_new_tokens=2)
                it, val = adopted[r.worker_id]
                assert r.iteration == it, (
                    f"{r.worker_id} re-labelled a response "
                    f"({r.iteration} != adopted {it})")
                assert float(r.tokens[-1]) == val, (
                    f"{r.worker_id} served weights never published as "
                    f"its adopted iteration {it}")
        assert router.stats()["failed_total"] == 0
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# daemon status aggregation over the worker-state namespace
# ---------------------------------------------------------------------------


def test_status_aggregates_worker_state_namespace(tmp_path):
    repo = _repo(tmp_path)
    _publish(repo, 4.0)
    workers = [ServingWorker(None, str(tmp_path), engine_factory=_fake,
                             worker_id=f"w{i}", name=f"w{i}")
               for i in range(2)]
    for w in workers:
        assert w.poll_once() and w.current_iteration == 1
        w.generate(PROMPT[None, :], max_new_tokens=2)
        w._persist_state()
    assert os.path.exists(os.path.join(str(tmp_path),
                                       "serving_state-w0.json"))
    svc = ColdService(repo, policy=AdmissionPolicy())
    st = svc.status()
    svc.close()
    serving = st["serving"]
    assert serving["n_workers"] == 2
    assert set(serving["workers"]) == {"w0", "w1"}
    assert serving["iteration"] == 1      # every member agrees
    assert serving["requests_total"] == 2
    assert serving["swaps_total"] == 2
    assert serving["versions_served"] == [1]
    assert serving["swapping"] is False


def test_status_iteration_none_when_workers_diverge(tmp_path):
    repo = _repo(tmp_path)
    workers = [ServingWorker(None, str(tmp_path), engine_factory=_fake,
                             worker_id=f"w{i}", name=f"w{i}")
               for i in range(2)]
    assert workers[0].poll_once() and workers[1].poll_once()
    _publish(repo, 4.0)
    assert workers[0].poll_once()   # only w0 adopted iteration 1
    for w in workers:
        w._persist_state()
    svc = ColdService(repo, policy=AdmissionPolicy())
    serving = svc.status()["serving"]
    svc.close()
    assert serving["iteration"] is None, "mid-divergence must not pick one"
    assert serving["versions_served"] == [0, 1]


def test_worker_id_rejects_path_characters():
    from repro.serve.cold_service import serving_state_filename
    assert serving_state_filename(None) == "serving_state.json"
    assert serving_state_filename("w3") == "serving_state-w3.json"
    for bad in ("a/b", "a\\b", "a.b", ""):
        with pytest.raises(ValueError):
            serving_state_filename(bad)


def test_pool_refuses_to_spawn_from_a_tpu_parent(tmp_path, monkeypatch):
    """A parent holding the TPU must not start JAX children: start()
    raises before spawning anything."""
    import jax
    from repro.serve.worker_pool import WorkerPool

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = WorkerPool(str(tmp_path), 2, engine="value")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        pool.start()
    assert pool._procs == {}


# ---------------------------------------------------------------------------
# cross-process pool (slow): socket protocol, kill -9, swap-seam crash
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pool_cross_process_adoption_and_kill9(tmp_path):
    """Two worker processes adopt publishes via repository.json; kill -9
    of one member mid-traffic re-routes in-flight-failed requests exactly
    once and the router converges to zero failed requests."""
    root = str(tmp_path)
    repo = _repo(root)
    repo.flush()
    pool = WorkerPool(root, 2, engine="value", poll=0.01).start()
    try:
        pool.wait_ready(iteration=0)
        router = pool.router()
        r = router.route(PROMPT, max_new_tokens=2)
        assert r.iteration == 0 and float(r.tokens[-1]) == 0.0
        it = _publish(repo, 5.0)
        pool.wait_ready(iteration=it)
        for _ in range(4):
            r = router.route(PROMPT, max_new_tokens=2)
            assert r.iteration == 1 and float(r.tokens[-1]) == 5.0
        assert {s["iteration"] for s in pool.states().values()} == {1}

        pool.kill("w0")
        results = [router.route(PROMPT, max_new_tokens=2)
                   for _ in range(6)]
        assert all(float(r.tokens[-1]) == 5.0 for r in results)
        # only the survivor can have served them
        assert all(r.worker_id == "w1" for r in results)
        assert router.stats()["failed_total"] == 0
    finally:
        codes = pool.stop()
    assert codes["w0"] == -9 and codes["w1"] == 0


@pytest.mark.slow
def test_pool_worker_killed_at_swap_seam_router_converges(tmp_path):
    """One member armed to die at the post_transfer_pre_flip seam — a
    kill -9 mid-swap by construction.  Its state file must never name a
    half-adopted base, and the router converges to zero failed requests
    on the survivor, across a further publish."""
    root = str(tmp_path)
    repo = _repo(root)
    repo.flush()
    pool = WorkerPool(
        root, 2, engine="value", poll=0.01,
        child_env={"w1": {faults.ENV: "worker.post_transfer_pre_flip"}})
    pool.start()
    try:
        wait_until(lambda: "w1" not in pool.alive(),
                   desc="armed crash point firing mid-swap")
        assert pool._procs["w1"].returncode == faults.EXIT_CODE
        pool.wait_ready(iteration=0)    # skips the dead member
        router = pool.router()
        results = [router.route(PROMPT, max_new_tokens=2)
                   for _ in range(8)]
        assert all(float(r.tokens[-1]) == 0.0 for r in results)
        assert all(r.worker_id == "w0" for r in results)
        assert router.stats()["failed_total"] == 0
        # the crashed member registered its port but died BEFORE the
        # flip: its state file must not claim an adopted iteration
        h = pool.endpoints[1].health()
        assert h is not None and h["iteration"] is None
        # the pool keeps following publishes on the survivor
        it = _publish(repo, 3.0)
        pool.wait_ready(iteration=it)
        r = router.route(PROMPT, max_new_tokens=2)
        assert r.iteration == it and float(r.tokens[-1]) == 3.0
        assert router.stats()["failed_total"] == 0
    finally:
        pool.stop()


@pytest.mark.slow
def test_pool_batched_worker_coalesces_cross_process(tmp_path):
    root = str(tmp_path)
    repo = _repo(root)
    repo.flush()
    pool = WorkerPool(root, 1, engine="value", poll=0.01, batch=True,
                      batch_wait_s=0.05).start()
    try:
        pool.wait_ready(iteration=0)
        router = pool.router()
        results, errors = [], []

        def client():
            try:
                results.append(router.route(PROMPT, max_new_tokens=2))
            except Exception as err:  # noqa: BLE001
                errors.append(err)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errors and len(results) == 6
        assert all(float(r.tokens[-1]) == 0.0 for r in results)
        assert any(r.batch_size > 1 for r in results), "nothing coalesced"
    finally:
        codes = pool.stop()
    assert codes == {"w0": 0}
