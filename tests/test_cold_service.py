"""Contributor service loop: queue submit/admission/fuse behaviour, spill
compaction, property tests over submit/poll/fuse interleavings, and the
kill-at-checkpoint fault-injection suite (exactly-once fusion across every
parametrized crash window — see docs/service_loop.md's crash matrix)."""
import os
import shutil
import tempfile
import time

import jax.numpy as jnp
import numpy as np
import pytest

from _faults import run_child, wait_until
from _hypothesis_compat import given, settings, st
from repro.checkpoint import io as ckpt
from repro.core.repository import Repository
from repro.serve.cold_service import (ERROR_RING, QUEUE_DIR, QUEUE_MANIFEST,
                                      STATUS_FILE, AdmissionPolicy,
                                      ColdService, ContributorClient)
from repro.serve.probes import ProbeSuite, RegressionGate
from repro.utils.flat import (FlatSpec, ShardedFlatSpec, delta_encode,
                              row_checksum)


def _m(v, n=64):
    return {"w": jnp.full((n,), float(v)), "b": jnp.full((5,), float(v))}


def _make(root, **kw):
    kw.setdefault("screen", False)
    repo = Repository(_m(0), root=root, spill=True, **kw)
    return repo


def _drain(svc, max_cycles=100):
    """Run service cycles until quiescent (bounded — never an open loop)."""
    for _ in range(max_cycles):
        st = svc.run_once()
        if (st["queue_depth"] == 0 and st["staged"] == 0
                and not st["inflight"]):
            return st
    raise AssertionError(f"service did not drain in {max_cycles} cycles: {st}")


# ---------------------------------------------------------------------------
# queue submit -> admit -> fuse -> GC
# ---------------------------------------------------------------------------


def test_submit_admit_fuse_roundtrip(tmp_path):
    """Queue-driven ingest publishes the same base as direct upload, and a
    consumed submission leaves neither a queue file nor a manifest entry."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=3))
    client = ContributorClient(root, name="c0")
    for v, w in ((1.0, 2.0), (3.0, 1.0), (5.0, 1.0)):
        client.submit(_m(v), weight=w)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 3
    # weighted mean (2·1 + 3 + 5) / 4
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 2.5)
    direct = Repository(_m(0), screen=False)
    for v, w in ((1.0, 2.0), (3.0, 1.0), (5.0, 1.0)):
        direct.upload(_m(v), weight=w)
    direct.fuse_pending()
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]),
                               np.asarray(direct.download()["w"]))
    qdir = os.path.join(root, QUEUE_DIR)
    assert [f for f in os.listdir(qdir) if f.endswith(".npz")] == []
    assert ckpt.load_json(os.path.join(qdir, QUEUE_MANIFEST))["entries"] == []


def test_min_cohort_batches_arrivals(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=3))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    client.submit(_m(2.0))
    st = svc.run_once()
    assert st["iteration"] == 0 and st["staged"] == 2  # undersized: held
    client.submit(_m(3.0))
    st = _drain(svc)
    assert st["iteration"] == 1
    assert svc.repo.history[0].n_contributions == 3  # one cohort, not three


def test_max_wait_fuses_undersized_cohort(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root),
                      policy=AdmissionPolicy(min_cohort=5, max_wait_s=0.05))
    ContributorClient(root, name="c0").submit(_m(4.0))
    svc.run_once()
    assert svc.repo.iteration == 0  # not yet: below min_cohort, too young
    wait_until(lambda: svc.run_once()["iteration"] >= 1,
               timeout=10.0, desc="timeout-triggered fuse")
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 4.0)


def test_dispatch_overlaps_queue_drain(tmp_path):
    """wait=False dispatch: while a cohort fuses on device, the next
    arrivals are admitted into the fresh front buffer."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    client.submit(_m(3.0))
    st = svc.run_once()
    assert st["inflight"]  # dispatched, not yet published
    client.submit(_m(10.0))
    client.submit(_m(20.0))
    st = svc.run_once()  # finalizes cohort 1, dispatches cohort 2
    assert st["iteration"] >= 1
    st = _drain(svc)
    assert st["iteration"] == 2
    assert [r.n_contributions for r in svc.repo.history] == [2, 2]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 15.0)


def test_idempotent_retry_same_seq(tmp_path):
    """A contributor retrying a submission (same name+seq) atomically
    replaces the same queue file — it can never fuse twice."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    a = client.submit(_m(2.0), seq=0)
    b = client.submit(_m(2.0), seq=0)  # retry
    assert a == b
    client.submit(_m(6.0))
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 2
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 4.0)


def test_garbage_and_inflight_tmp_files_ignored(tmp_path):
    """A torn enqueue can only exist as a .tmp-* file (invisible) or as
    garbage bytes under the final name (quarantined at admission) —
    neither reaches the fuse, and the daemon survives both."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    qdir = os.path.join(root, QUEUE_DIR)
    with open(os.path.join(qdir, "torn-000000.npz"), "wb") as f:
        f.write(b"PK\x03\x04 truncated garbage")
    with open(os.path.join(qdir, "c9-000001.npz.tmp-123"), "wb") as f:
        f.write(b"half an npz")
    ContributorClient(root, name="c0").submit(_m(7.0))
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 1
    assert st["rejected_total"] == 1
    assert "unreadable" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 7.0)


def test_remark_of_staged_row_is_not_budget_starved(tmp_path):
    """Regression (review): a row ingested pre-crash but never marked in
    the queue manifest must be re-marked even when max_cohort leaves no
    admission budget — a starved re-mark would let it fuse unmarked and
    later be re-ingested (double-fused)."""
    root = str(tmp_path / "repo")
    repo = _make(root)
    client = ContributorClient(root, name="c0")
    client.submit(_m(9.0))  # z: will be staged but never queue-marked
    z_path = os.path.join(root, QUEUE_DIR, "c0-000000.npz")
    repo.ingest_spilled(z_path)  # simulates crash at service.post_ingest
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=1, max_cohort=1))
    client.submit(_m(1.0))
    client.submit(_m(2.0))
    st = _drain(svc)
    fused = sum(r.n_contributions for r in svc.repo.history)
    assert fused == 3, f"z double-fused or dropped: {svc.repo.history}"
    assert st["iteration"] == 3  # max_cohort=1: three single-row cohorts
    qdir = os.path.join(root, QUEUE_DIR)
    assert [f for f in os.listdir(qdir) if f.endswith(".npz")] == []


def test_max_wait_covers_recovered_rows(tmp_path):
    """Regression (review): rows recovered from the staging manifest at
    service start must start the cohort clock — an undersized recovered
    cohort fuses by max_wait_s without needing a fresh arrival."""
    root = str(tmp_path / "repo")
    _make(root).upload(_m(3.0))  # staged + spilled, then "crash"
    reopened = Repository.open(root, spill=True, screen=False)
    assert reopened.n_staged == 1
    svc = ColdService(reopened,
                      policy=AdmissionPolicy(min_cohort=5, max_wait_s=0.05))
    wait_until(lambda: svc.run_once()["iteration"] >= 1,
               timeout=10.0, desc="recovered-cohort timeout fuse")
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 3.0)


def test_serve_forever_exits_on_stalled_undersized_cohort(tmp_path):
    """Regression (review): idle_timeout means 'no progress', so a daemon
    holding an undersized cohort below min_cohort exits (rows stay durable
    in the manifest) instead of busy-spinning forever."""
    import threading
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=4))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    client.submit(_m(2.0))
    out = {}
    t = threading.Thread(target=lambda: out.update(
        svc.serve_forever(poll_interval=0.01, idle_timeout=0.3)))
    t.start()
    t.join(timeout=30.0)
    assert not t.is_alive(), "serve_forever hung on a stalled cohort"
    assert out["iteration"] == 0 and out["staged"] == 2
    # the stalled rows survive for the next service instance
    again = Repository.open(root, spill=True)
    assert again.n_staged == 2


def test_admission_rejects_stale_submission(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root),
                      policy=AdmissionPolicy(min_cohort=1, max_staleness=1))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0), base_iteration=0)
    _drain(svc)
    client.submit(_m(2.0), base_iteration=1)
    _drain(svc)
    assert svc.repo.iteration == 2
    client.submit(_m(9.0), base_iteration=0)  # finetuned from a stale base
    st = _drain(svc)
    assert st["iteration"] == 2  # never fused
    assert st["rejected_total"] == 1
    assert "stale" in st["recent_rejects"][0]["reason"]


def test_admission_rejects_mismatched_spec(tmp_path):
    """A row from a different architecture is refused at the queue
    boundary; the daemon keeps serving."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    wrong = {"other": jnp.zeros((13,))}
    ContributorClient(root, name="bad").submit(wrong)
    ContributorClient(root, name="good").submit(_m(3.0))
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rejected_total"] == 1
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 3.0)


def test_checksum_verification(tmp_path):
    """verify_checksums re-reads the row at admission and rejects a file
    whose content no longer matches the contributor's CRC."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, verify_checksums=True))
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0), checksum=True)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rejected_total"] == 0
    # now corrupt a submission in place: right spec, wrong bytes vs CRC
    spec = FlatSpec.from_tree(_m(0))
    row = np.asarray(spec.flatten(_m(5.0)))
    path = os.path.join(root, QUEUE_DIR, "c0-000001.npz")
    ckpt.save_flat(path, row, spec,
                   extra={"id": "c0-000001", "checksum": row_checksum(row + 1)})
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rejected_total"] == 1
    assert "checksum" in st["recent_rejects"][-1]["reason"]


def test_sharded_slice_submission(tmp_path):
    """Per-shard submissions (ShardedFlatSpec.shard_slices) fuse to the
    same base as whole-row submissions, even on a meshless repository
    (portable fallback)."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    spec = FlatSpec.from_tree(_m(0))
    sspec = ShardedFlatSpec.from_spec(spec, 4)
    client.submit(_m(2.0))
    client.submit(row=spec.flatten(_m(6.0)), spec=spec, sspec=sspec)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 2
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 4.0)
    np.testing.assert_allclose(np.asarray(svc.repo.download()["b"]), 4.0)


def test_screen_outlier_diluted_not_fatal(tmp_path):
    """§9 at service level: a lone outlier cohort all-rejects (publish
    abandoned, daemon survives), later arrivals dilute it, and the re-pass
    fuses with the outlier weight-zeroed."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=True)
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=1))
    client = ContributorClient(root, name="c0")
    client.submit({"w": jnp.full((64,), jnp.inf), "b": jnp.full((5,), 1.0)})
    st = svc.run_once()  # dispatch
    st = svc.run_once()  # finalize -> all rejected -> cohort restored
    assert st["iteration"] == 0 and st["last_error"] is not None
    assert "rejected" in st["last_error"]
    for v in (1.0, 1.2, 0.8, 1.1):
        client.submit(_m(v))
    st = _drain(svc)
    assert st["iteration"] == 1
    rec = svc.repo.history[0]
    assert rec.n_contributions == 5 and rec.n_accepted == 4
    assert np.isfinite(np.asarray(svc.repo.download()["w"])).all()


@pytest.mark.parametrize("seam", ["fuse_pending", "flush"])
def test_device_error_in_fuse_propagates(tmp_path, monkeypatch, seam):
    """Only the repository's own refusal (NothingToFuse) is a per-cohort
    outcome: a device error raised by the fuse (a compile failure, an HBM
    out-of-memory) leaves run_once instead of landing in the error ring."""
    import jax

    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    ContributorClient(root, name="c0").submit(_m(1.0))
    if seam == "flush":
        svc.run_once()  # dispatch; the next cycle's flush publishes
        assert svc.repo.inflight

    def boom(*a, **k):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: injected")

    monkeypatch.setattr(svc.repo, seam, boom)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        svc.run_once()
    assert svc.status()["last_error"] is None


def test_status_endpoint_fields(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    st = svc.run_once()
    for key in ("iteration", "queue_depth", "staged", "inflight", "fuses",
                "fused_contributions", "rejected_total", "fuse_latency_s",
                "last_fuse", "pid", "running", "updated_at"):
        assert key in st, key
    assert st["staged"] == 1 and st["running"] and st["last_fuse"] is None
    # the client reads the same thing, atomically published
    assert client.status()["staged"] == 1
    assert os.path.exists(os.path.join(root, STATUS_FILE))
    client.submit(_m(3.0))
    st = _drain(svc)
    assert st["last_fuse"]["n_accepted"] == 2
    # the fuse's host time: its staging and dispatch plus its finalize,
    # not the cadence from dispatch to publish
    rec = svc.repo.history[-1]
    assert rec.stage_s > 0 and rec.finalize_s > 0
    assert st["fuse_latency_s"] == pytest.approx(rec.stage_s + rec.finalize_s)
    final = svc.close()
    assert final["running"] is False
    assert client.iteration() == 1


def test_wait_for_iteration_bounded(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root))
    svc.run_once()
    client = ContributorClient(root, name="c0")
    with pytest.raises(TimeoutError):
        client.wait_for_iteration(1, timeout=0.1, interval=0.01)
    client.submit(_m(2.0))
    _drain(svc)
    st = client.wait_for_iteration(1, timeout=5.0)
    assert st["iteration"] == 1
    np.testing.assert_allclose(np.asarray(client.download_base()["w"]), 2.0)


def test_service_requires_spill(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, screen=False)  # spill=False
    with pytest.raises(ValueError, match="spill=True"):
        ColdService(repo)
    with pytest.raises(ValueError, match="on-disk"):
        ColdService(Repository(_m(0), screen=False))


def test_ingest_spilled_direct_api(tmp_path):
    """The queue-ingest entry point registers an on-disk row by reference:
    no copy, manifest-tracked, recovered like any spilled upload."""
    root = str(tmp_path / "repo")
    repo = _make(root)
    spec = FlatSpec.from_tree(_m(0))
    path = os.path.join(root, QUEUE_DIR, "x-000000.npz")
    ckpt.save_flat(path, spec.flatten(_m(8.0)), spec)
    repo.ingest_spilled(path, weight=2.0)
    assert repo.n_staged == 1
    assert "queue/x-000000.npz" in repo.staged_spill_files()
    # crash here would recover it: reopen instead of fusing
    again = Repository.open(root, spill=True)
    assert again.n_staged == 1 and again._pending_weights == [2.0]
    again.fuse_pending()
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 8.0)
    with pytest.raises(ValueError, match="outside"):
        repo.ingest_spilled(os.path.join(str(tmp_path), "elsewhere.npz"))


# ---------------------------------------------------------------------------
# novelty admission screen (docs/service_loop.md)
# ---------------------------------------------------------------------------


def test_novelty_screen_rejects_replay_and_near_duplicate(tmp_path):
    """Exact replays (same content, different id) and near-duplicates are
    rejected at the queue boundary; distinct contributions are admitted."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=2, novelty_threshold=0.05, sketch_window=8))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    client.submit(_m(1.0))             # exact replay, new submission id
    client.submit(_m(1.0 + 1e-6))      # near-duplicate
    client.submit(_m(4.0))             # distinct
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 2
    assert st["rejected_total"] == 2 and st["novelty_rejected_total"] == 2
    assert all("near-duplicate" in r["reason"] for r in st["recent_rejects"])
    assert st["novelty_screen"] is True and st["sketch_entries"] == 2
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 2.5)


def test_novelty_screen_survives_restart(tmp_path):
    """The sketch window is durable: a replay of a row fused BEFORE a
    daemon restart is still rejected by the restarted daemon."""
    root = str(tmp_path / "repo")
    pol = AdmissionPolicy(min_cohort=1, novelty_threshold=0.05,
                          sketch_window=8)
    svc = ColdService(_make(root), policy=pol)
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0))
    _drain(svc)
    svc.close()
    svc2 = ColdService(Repository.open(root, spill=True), policy=pol)
    ContributorClient(root, name="c1").submit(_m(2.0))  # replay, new name
    st = _drain(svc2)
    assert st["iteration"] == 1 and st["novelty_rejected_total"] == 1


def test_novelty_screen_off_by_default(tmp_path):
    """Without novelty_threshold the replay fuses (PR 4 behaviour) and no
    sketch state is created."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    client.submit(_m(1.0))
    st = _drain(svc)
    assert st["fused_contributions"] == 2 and st["novelty_rejected_total"] == 0
    assert st["sketch_entries"] is None
    assert not os.path.exists(os.path.join(root, "cohort_sketch.json"))


def test_novelty_screen_without_rider_sketch(tmp_path):
    """Rows enqueued without a rider sketch (foreign writers) are sketched
    from the file at admission — the screen still catches the replay."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, novelty_threshold=0.05, sketch_window=8))
    spec = FlatSpec.from_tree(_m(0))
    row = np.asarray(spec.flatten(_m(6.0)))
    qdir = os.path.join(root, QUEUE_DIR)
    ckpt.save_flat(os.path.join(qdir, "f-000000.npz"), row, spec,
                   extra={"id": "f-000000"})
    _drain(svc)
    ckpt.save_flat(os.path.join(qdir, "f-000001.npz"), row, spec,
                   extra={"id": "f-000001"})
    st = _drain(svc)
    assert st["iteration"] == 1 and st["novelty_rejected_total"] == 1


def test_novelty_screen_not_bypassed_by_forged_rider_id(tmp_path):
    """Regression (review): the self-match skip is keyed by id AND queue
    file — a replay that forges a previously admitted submission's rider
    id under a new file cannot talk its way past the screen."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, novelty_threshold=0.05, sketch_window=8))
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0))
    _drain(svc)
    spec = FlatSpec.from_tree(_m(0))
    ckpt.save_flat(os.path.join(root, QUEUE_DIR, "forger-000000.npz"),
                   np.asarray(spec.flatten(_m(2.0))), spec,
                   extra={"id": "c0-000000"})  # the fused row's id, replayed
    st = _drain(svc)
    assert st["iteration"] == 1 and st["novelty_rejected_total"] == 1, st


def test_novelty_screen_distrusts_rider_under_verify(tmp_path):
    """With verify_checksums the service recomputes the sketch from the
    file: a rider sketch that lies about duplicate content cannot evade
    the screen."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, novelty_threshold=0.05, sketch_window=8,
        verify_checksums=True))
    client = ContributorClient(root, name="c0")
    client.submit(_m(3.0))
    _drain(svc)
    spec = FlatSpec.from_tree(_m(0))
    row = np.asarray(spec.flatten(_m(3.0)))  # duplicate content...
    fake = np.asarray(spec.flatten(_m(99.0)))  # ...novel-looking rider sketch
    from repro.utils.flat import row_sketch_host
    ckpt.save_flat(os.path.join(root, QUEUE_DIR, "liar-000000.npz"), row, spec,
                   extra={"id": "liar-000000",
                          "sketch": row_sketch_host(fake).tolist()})
    st = _drain(svc)
    assert st["iteration"] == 1 and st["novelty_rejected_total"] == 1


# ---------------------------------------------------------------------------
# admit-path hardening (malformed riders, torn reads, re-mark dedupe)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad_extra", [
    {"base_iteration": "garbage"},
    {"base_iteration": [1, 2]},
    {"weight": "heavy"},
    {"weight": {"x": 1}},
    {"weight": "nan"},   # finite-ness: NaN·w/Σw would publish a NaN base
    {"weight": "inf"},
    {"id": {"not": "a string"}},
])
def test_malformed_rider_is_per_file_rejection(tmp_path, bad_extra):
    """Regression: a garbage rider must be a per-file rejection with a
    reason — not a daemon last_error that stalls the whole admit pass."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, max_staleness=2))
    spec = FlatSpec.from_tree(_m(0))
    ckpt.save_flat(os.path.join(root, QUEUE_DIR, "bad-000000.npz"),
                   np.asarray(spec.flatten(_m(9.0))), spec,
                   extra={"id": "bad-000000", **bad_extra})
    ContributorClient(root, name="good").submit(_m(5.0), base_iteration=0)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["last_error"] is None
    assert st["rejected_total"] == 1
    assert "malformed rider" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 5.0)


def _corrupt_buffer_entry(path):
    """Rewrite a flat npz so its metadata entries stay readable but the
    buffer entry's bytes are garbage (CRC fails on access) — a torn file
    that passes the admission meta peek and dies on the full-row read."""
    import zipfile
    tmp = path + ".rewrite"
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(tmp, "w", zipfile.ZIP_STORED) as zout:
        for info in zin.infolist():
            zout.writestr(info.filename, zin.read(info.filename))
            if info.filename.startswith("__flat_buffer__"):
                # poison the central directory's recorded CRC: zipfile
                # raises BadZipFile ("Bad CRC-32") when the entry is read
                zout.infolist()[-1].CRC = 0xDEADBEEF
    os.replace(tmp, path)


def test_torn_row_between_meta_and_checksum_read_quarantined(tmp_path):
    """Regression: _checksum_ok raising (file torn between the meta peek
    and the full-row read) must reject that one file, not abort the
    whole admit pass."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, verify_checksums=True))
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0), checksum=True)
    _corrupt_buffer_entry(os.path.join(root, QUEUE_DIR, "c0-000000.npz"))
    client.submit(_m(7.0), checksum=True)  # healthy row behind the torn one
    st = _drain(svc)
    assert st["iteration"] == 1 and st["last_error"] is None
    assert st["rejected_total"] == 1
    assert "unreadable" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 7.0)


def test_remark_dedupes_by_file_when_rider_id_differs(tmp_path):
    """Regression: a submission whose rider id differs from its filename
    stem, ingested pre-crash but never queue-marked, must end up under ONE
    queue-manifest entry after the re-mark — and fuse exactly once."""
    root = str(tmp_path / "repo")
    repo = _make(root)
    spec = FlatSpec.from_tree(_m(0))
    path = os.path.join(root, QUEUE_DIR, "stem-000000.npz")
    ckpt.save_flat(path, np.asarray(spec.flatten(_m(4.0))), spec,
                   extra={"id": "rider-id-x", "weight": 2.0})
    repo.ingest_spilled(path, weight=2.0)  # crash at service.post_ingest
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=1))
    st = svc.run_once()
    files = [e["file"] for e in svc._entries.values()]
    assert files.count("stem-000000.npz") <= 1, svc._entries
    st = _drain(svc)
    assert st["iteration"] == 1
    assert sum(r.n_contributions for r in svc.repo.history) == 1
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 4.0)
    qdir = os.path.join(root, QUEUE_DIR)
    assert [f for f in os.listdir(qdir) if f.endswith(".npz")] == []
    assert ckpt.load_json(os.path.join(qdir, QUEUE_MANIFEST))["entries"] == []


def test_rejection_counters_survive_restart(tmp_path):
    """Counters persist in the queue manifest even on reject-only passes,
    so a restarted daemon's totals match what the status reported."""
    root = str(tmp_path / "repo")
    pol = AdmissionPolicy(min_cohort=1, novelty_threshold=0.05)
    svc = ColdService(_make(root), policy=pol)
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0))
    _drain(svc)
    client.submit(_m(2.0))  # replay: a reject-only admit pass
    st = _drain(svc)
    assert st["rejected_total"] == 1 and st["novelty_rejected_total"] == 1
    svc.close()
    svc2 = ColdService(Repository.open(root, spill=True), policy=pol)
    st2 = svc2.status()
    assert st2["rejected_total"] == 1 and st2["novelty_rejected_total"] == 1


# ---------------------------------------------------------------------------
# property tests: queue/cohort invariants under arbitrary interleavings
# ---------------------------------------------------------------------------


# NOTE: @settings below @given so the shim's given() sees the settings
# (decorators apply bottom-up; real hypothesis accepts either order)
@given(st.lists(st.sampled_from(["submit", "cycle", "burst"]),
                min_size=1, max_size=8))
@settings(max_examples=8, deadline=None)
def test_interleavings_preserve_monotonicity_and_drop_nothing(ops):
    """Any interleaving of submit / poll-cycle / burst keeps the published
    iteration monotone and fuses every submission exactly once."""
    root = tempfile.mkdtemp(prefix="cold_prop_")
    try:
        svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
        client = ContributorClient(root, name="p")
        submitted, last_it = 0, 0
        for op in ops:
            if op == "submit":
                client.submit(_m(float(submitted)))
                submitted += 1
            elif op == "burst":
                client.submit(_m(float(submitted)))
                client.submit(_m(float(submitted + 1)))
                submitted += 2
            st = svc.run_once()
            assert st["iteration"] >= last_it, "iteration went backwards"
            last_it = st["iteration"]
        svc.policy.min_cohort = 1  # drain stragglers below the cohort bar
        st = _drain(svc)
        assert st["iteration"] >= last_it
        fused = sum(r.n_contributions for r in svc.repo.history)
        assert fused == submitted, f"dropped/duplicated: {fused} != {submitted}"
        assert st["iteration"] == len(svc.repo.history)
        qdir = os.path.join(root, QUEUE_DIR)
        assert [f for f in os.listdir(qdir) if f.endswith(".npz")] == []
    finally:
        shutil.rmtree(root, ignore_errors=True)


# NOTE: @settings below @given so the shim's given() sees the settings
@given(st.lists(st.sampled_from(["submit", "dup", "near", "cycle", "burst"]),
                min_size=1, max_size=8))
@settings(max_examples=8, deadline=None)
def test_interleavings_with_duplicates_screen_consistently(ops):
    """Any interleaving of distinct submits, exact replays, and
    near-duplicates: every distinct contribution fuses exactly once, every
    planted duplicate is rejected exactly once, and the counters stay
    consistent with the history."""
    root = tempfile.mkdtemp(prefix="cold_prop_nov_")
    try:
        svc = ColdService(_make(root), policy=AdmissionPolicy(
            min_cohort=2, novelty_threshold=0.02, sketch_window=64))
        client = ContributorClient(root, name="p")
        distinct = dups = 0
        last_val = None
        for op in ops:
            if op in ("submit", "burst"):
                for _ in range(2 if op == "burst" else 1):
                    distinct += 1
                    last_val = float(distinct)
                    client.submit(_m(last_val))
            elif op == "dup" and last_val is not None:
                client.submit(_m(last_val))            # exact replay
                dups += 1
            elif op == "near" and last_val is not None:
                client.submit(_m(last_val + 1e-7))     # near-duplicate
                dups += 1
            st = svc.run_once()
        svc.policy.min_cohort = 1  # drain stragglers below the cohort bar
        st = _drain(svc)
        fused = sum(r.n_contributions for r in svc.repo.history)
        assert fused == distinct, f"{fused} fused != {distinct} distinct"
        assert st["novelty_rejected_total"] == dups, st
        assert st["rejected_total"] == dups, st
        assert st["iteration"] == len(svc.repo.history)
        qdir = os.path.join(root, QUEUE_DIR)
        assert [f for f in os.listdir(qdir) if f.endswith(".npz")] == []
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# spill compaction / GC
# ---------------------------------------------------------------------------


def _fuse_rounds(repo, n):
    for it in range(n):
        repo.upload(_m(float(it + 1)))
        repo.fuse_pending()


def test_compact_keeps_current_base_and_staged_rows(tmp_path):
    root = str(tmp_path / "repo")
    repo = _make(root)
    _fuse_rounds(repo, 4)  # bases 0..4 on disk, 4 archived rows
    repo.upload(_m(9.0))   # staged, manifest-referenced
    out = repo.compact(keep_bases=2)
    assert out == {"bases_removed": 3, "rows_removed": 4}
    bases = sorted(f for f in os.listdir(root) if f.startswith("base_iter"))
    assert bases == ["base_iter0003.npz", "base_iter0004.npz"]
    again = Repository.open(root, spill=True)
    assert again.iteration == 4 and again.n_staged == 1
    again.fuse_pending()
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 9.0)


@pytest.mark.parametrize("survive_removes", [0, 1, 3])
def test_compact_crash_midway_never_breaks_recovery(tmp_path, monkeypatch,
                                                    survive_removes):
    """Kill compact after N deletions, for several N: recovery must never
    reference a deleted file — open() + fuse still work."""
    root = str(tmp_path / "repo")
    repo = _make(root)
    _fuse_rounds(repo, 3)
    repo.upload(_m(7.0))
    real_remove, calls = os.remove, []

    def flaky_remove(path):
        if len(calls) >= survive_removes:
            raise RuntimeError("injected crash mid-compact")
        calls.append(path)
        real_remove(path)

    monkeypatch.setattr(os, "remove", flaky_remove)
    with pytest.raises(RuntimeError, match="mid-compact"):
        repo.compact(keep_bases=1)
    monkeypatch.setattr(os, "remove", real_remove)
    again = Repository.open(root, spill=True)
    assert again.iteration == 3 and again.n_staged == 1
    again.fuse_pending()
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 7.0)
    # a clean re-run finishes the job
    again.compact(keep_bases=1)
    assert sorted(f for f in os.listdir(root) if f.startswith("base_iter")) \
        == ["base_iter0004.npz"]


def test_compact_validations(tmp_path):
    with pytest.raises(ValueError, match="on-disk"):
        Repository(_m(0)).compact()
    repo = _make(str(tmp_path / "repo"))
    with pytest.raises(ValueError, match="keep_bases"):
        repo.compact(keep_bases=0)


def test_service_compacts_after_publish(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, compact_keep_bases=1))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0))
    _drain(svc)
    client.submit(_m(2.0))
    _drain(svc)
    assert svc.repo.iteration == 2
    bases = [f for f in os.listdir(root) if f.startswith("base_iter")]
    assert bases == ["base_iter0002.npz"]


# ---------------------------------------------------------------------------
# fault injection: exactly-once fusion across kill-at-checkpoint crashes
# ---------------------------------------------------------------------------

_SCENARIO = '''
import os, sys
sys.path.insert(0, "src")
import numpy as np
import jax.numpy as jnp
from repro.core.repository import Repository
from repro.serve.cold_service import AdmissionPolicy, ColdService, ContributorClient

root, phase = sys.argv[1], sys.argv[2]

def m(v):
    return {"w": jnp.full((96,), float(v)), "b": jnp.full((7,), float(v))}

if phase == "prep":
    Repository(m(0.0), root=root, spill=True, screen=False)
    client = ContributorClient(root, name="c")
    for v, w in ((1.0, 2.0), (3.0, 1.0), (5.0, 1.0)):
        client.submit(m(v), weight=w, base_iteration=0)
    print("PREP_OK", flush=True)
    sys.exit(0)

if phase == "client_crash":
    # killed mid-submit: nothing durable may appear under the final name
    client = ContributorClient(root, name="late")
    client.submit(m(9.0), weight=1.0, seq=0)
    raise AssertionError("unreachable: client.mid_submit must fire")

if phase == "client_retry":
    client = ContributorClient(root, name="late")
    print("RETRY", client.submit(m(9.0), weight=1.0, seq=0), flush=True)
    sys.exit(0)

# phase == "serve": poll to quiescence (or die at the armed crash point)
repo = Repository.open(root, spill=True)
svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=3))
for _ in range(200):
    st = svc.run_once()
    if (st["iteration"] >= 1 and not st["inflight"] and st["staged"] == 0
            and st["queue_depth"] == 0):
        break
else:
    print("NO_CONVERGENCE", st, flush=True)
    sys.exit(3)
st = svc.close()
w = np.asarray(repo.download()["w"])
n_q = len([f for f in os.listdir(svc.queue_dir) if f.endswith(".npz")])
print(f"DONE it={st['iteration']} fused={st['fused_contributions']} "
      f"w={w[0]:.6f} qfiles={n_q}", flush=True)
'''

# the crash windows of docs/service_loop.md's matrix, in lifecycle order:
# after a row enters the staging manifest but before the queue manifest
# marks it; after the fuse dispatch but before any publish; after the base
# publish but before the staging-manifest rewrite; after the full publish
# but before queue GC; and mid-GC between file delete and entry drop.
CRASH_POINTS = [
    "service.post_ingest",
    "service.post_dispatch",
    "repo.post_publish_pre_manifest",
    "service.post_publish",
    "service.mid_gc",
]


def _done_line(res):
    line = [l for l in res.stdout.splitlines() if l.startswith("DONE")][0]
    return dict(kv.split("=") for kv in line.split()[1:])


@pytest.mark.slow
@pytest.mark.parametrize("point", CRASH_POINTS)
def test_exactly_once_fusion_across_crash_points(tmp_path, point):
    """kill -9 the daemon at any crash window, restart it: every submitted
    contribution fuses exactly once and the published base equals the
    uninterrupted run's (weighted mean 2.5)."""
    root = str(tmp_path / "repo")
    run_child(_SCENARIO, [root, "prep"])
    run_child(_SCENARIO, [root, "serve"], crash_at=point)
    res = run_child(_SCENARIO, [root, "serve"])  # restart, run to completion
    done = _done_line(res)
    assert done["it"] == "1", done       # ONE publish total — never two
    assert done["fused"] == "3", done    # every submission, exactly once
    assert abs(float(done["w"]) - 2.5) < 1e-5, done
    assert done["qfiles"] == "0", done   # queue fully GC'd


@pytest.mark.slow
def test_uninterrupted_reference_run(tmp_path):
    """The oracle the crash tests compare against: prep + serve with no
    crash lands on the same DONE line."""
    root = str(tmp_path / "repo")
    run_child(_SCENARIO, [root, "prep"])
    done = _done_line(run_child(_SCENARIO, [root, "serve"]))
    assert done == {"it": "1", "fused": "3", "w": "2.500000", "qfiles": "0"}


# the novelty-screen variant of the crash matrix: three distinct prepped
# submissions plus a planted exact replay of one of them, served with the
# screen armed.  Every window of the original matrix plus the new
# sketch-persist window (service.post_sketch) must converge to the same
# duplicate-free base with consistent rejection counters.
_NOVELTY_SCENARIO = '''
import os, sys
sys.path.insert(0, "src")
import numpy as np
import jax.numpy as jnp
from repro.core.repository import Repository
from repro.serve.cold_service import AdmissionPolicy, ColdService, ContributorClient

root, phase = sys.argv[1], sys.argv[2]

def m(v):
    return {"w": jnp.full((96,), float(v)), "b": jnp.full((7,), float(v))}

if phase == "prep":
    Repository(m(0.0), root=root, spill=True, screen=False)
    client = ContributorClient(root, name="c")
    for v, w in ((1.0, 2.0), (3.0, 1.0), (5.0, 1.0)):
        client.submit(m(v), weight=w, base_iteration=0)
    # the planted replay: same content as c-000001, different contributor
    ContributorClient(root, name="d").submit(m(3.0), weight=1.0,
                                             base_iteration=0)
    print("PREP_OK", flush=True)
    sys.exit(0)

repo = Repository.open(root, spill=True)
svc = ColdService(repo, policy=AdmissionPolicy(
    min_cohort=3, novelty_threshold=0.02, sketch_window=8))
for _ in range(200):
    st = svc.run_once()
    if (st["iteration"] >= 1 and not st["inflight"] and st["staged"] == 0
            and st["queue_depth"] == 0):
        break
else:
    print("NO_CONVERGENCE", st, flush=True)
    sys.exit(3)
st = svc.close()
w = np.asarray(repo.download()["w"])
n_q = len([f for f in os.listdir(svc.queue_dir) if f.endswith(".npz")])
print(f"DONE it={st['iteration']} fused={st['fused_contributions']} "
      f"w={w[0]:.6f} rej={st['rejected_total']} "
      f"nov={st['novelty_rejected_total']} qfiles={n_q}", flush=True)
'''

_NOVELTY_DONE = {"it": "1", "fused": "3", "w": "2.500000",
                 "rej": "1", "nov": "1", "qfiles": "0"}


@pytest.mark.slow
@pytest.mark.parametrize("point", ["service.post_sketch"] + CRASH_POINTS)
def test_novelty_screen_exactly_once_across_crash_points(tmp_path, point):
    """kill -9 the screened daemon at any window (including the new
    sketch-persist window), restart: every distinct submission fuses
    exactly once, the replay is rejected exactly once, and the counters
    agree with the uninterrupted run."""
    root = str(tmp_path / "repo")
    run_child(_NOVELTY_SCENARIO, [root, "prep"])
    run_child(_NOVELTY_SCENARIO, [root, "serve"], crash_at=point)
    done = _done_line(run_child(_NOVELTY_SCENARIO, [root, "serve"]))
    assert done == _NOVELTY_DONE, done


@pytest.mark.slow
def test_novelty_uninterrupted_reference_run(tmp_path):
    root = str(tmp_path / "repo")
    run_child(_NOVELTY_SCENARIO, [root, "prep"])
    done = _done_line(run_child(_NOVELTY_SCENARIO, [root, "serve"]))
    assert done == _NOVELTY_DONE, done


# fault-harness regression for the re-mark dedupe: a submission whose rider
# id differs from its filename stem, killed at service.post_ingest (staged
# but never queue-marked), must re-mark into ONE entry and fuse once.
_ODD_ID_SCENARIO = '''
import os, sys
sys.path.insert(0, "src")
import numpy as np
import jax.numpy as jnp
from repro.checkpoint import io as ckpt
from repro.core.repository import Repository
from repro.serve.cold_service import AdmissionPolicy, ColdService
from repro.utils.flat import FlatSpec

root, phase = sys.argv[1], sys.argv[2]

def m(v):
    return {"w": jnp.full((96,), float(v)), "b": jnp.full((7,), float(v))}

if phase == "prep":
    Repository(m(0.0), root=root, spill=True, screen=False)
    spec = FlatSpec.from_tree(m(0.0))
    ckpt.save_flat(os.path.join(root, "queue", "stem-000000.npz"),
                   np.asarray(spec.flatten(m(4.0))), spec,
                   extra={"id": "rider-id-x", "weight": 1.0})
    print("PREP_OK", flush=True)
    sys.exit(0)

repo = Repository.open(root, spill=True)
svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=1))
for _ in range(200):
    st = svc.run_once()
    if (st["iteration"] >= 1 and not st["inflight"] and st["staged"] == 0
            and st["queue_depth"] == 0):
        break
else:
    print("NO_CONVERGENCE", st, flush=True)
    sys.exit(3)
st = svc.close()
qman = ckpt.load_json(os.path.join(root, "queue", "queue_manifest.json"))
w = np.asarray(repo.download()["w"])
n_q = len([f for f in os.listdir(svc.queue_dir) if f.endswith(".npz")])
print(f"DONE it={st['iteration']} fused={st['fused_contributions']} "
      f"w={w[0]:.6f} entries={len(qman['entries'])} qfiles={n_q}", flush=True)
'''


@pytest.mark.slow
def test_odd_rider_id_remark_across_post_ingest_crash(tmp_path):
    root = str(tmp_path / "repo")
    run_child(_ODD_ID_SCENARIO, [root, "prep"])
    run_child(_ODD_ID_SCENARIO, [root, "serve"],
              crash_at="service.post_ingest")
    done = _done_line(run_child(_ODD_ID_SCENARIO, [root, "serve"]))
    assert done == {"it": "1", "fused": "1", "w": "4.000000",
                    "entries": "0", "qfiles": "0"}, done


@pytest.mark.slow
def test_client_killed_mid_submit_then_retry(tmp_path):
    """A contributor killed mid-enqueue leaves nothing under the final
    name; the retry (same name+seq) enqueues exactly one row."""
    root = str(tmp_path / "repo")
    run_child(_SCENARIO, [root, "prep"])
    run_child(_SCENARIO, [root, "client_crash"], crash_at="client.mid_submit")
    qdir = os.path.join(root, QUEUE_DIR)
    files = [f for f in os.listdir(qdir) if f.endswith(".npz")]
    assert not any(f.startswith("late-") for f in files), files
    run_child(_SCENARIO, [root, "client_retry"])
    files = [f for f in os.listdir(qdir) if f.startswith("late-")]
    assert files == ["late-000000.npz"]
    # 3 prepped + 1 retried row fuse in one cohort: (2·1+3+5+9)/5
    res = run_child(_SCENARIO, [root, "serve"])
    done = _done_line(res)
    assert done["fused"] == "4" and abs(float(done["w"]) - 3.8) < 1e-5, done


# ---------------------------------------------------------------------------
# forgetting regression gate: probes -> rollback -> quarantine -> metrics
# ---------------------------------------------------------------------------

def _gate(tolerance=0.5):
    # _m trees flatten to 64 + 5 = 69 elements
    return RegressionGate(ProbeSuite(69, seed=0), tolerance=tolerance)


def _harmful(client, base_iteration, n=2, scale=10.0, seed=7):
    """Submit n rows of large uniform-norm noise: invisible to the MAD
    screen (all norms agree), harmful to the probe readouts."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        client.submit(
            {"w": (0.2 + rng.normal(0, scale, 64)).astype(np.float32),
             "b": (0.2 + rng.normal(0, scale, 5)).astype(np.float32)},
            base_iteration=base_iteration)


def test_gate_clean_publish_rebaselines(tmp_path):
    """Benign cohorts pass the gate and move the baseline with them — the
    tolerance is on the per-fuse delta, so benign drift never accumulates
    into a false trip."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2),
                      gate=_gate())
    client = ContributorClient(root, name="c")
    for v in (0.1, 0.3):
        client.submit(_m(v), base_iteration=0)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rollbacks_total"] == 0
    assert st["gate"] and st["last_gate"]["ok"] is True
    assert ckpt.load_json(os.path.join(root, "gate_state.json"))["iteration"] == 1
    for v in (0.2, 0.4):
        client.submit(_m(v), base_iteration=1)
    st = _drain(svc)
    assert st["iteration"] == 2 and st["rollbacks_total"] == 0
    assert ckpt.load_json(os.path.join(root, "gate_state.json"))["iteration"] == 2


def test_gate_trips_rolls_back_and_quarantines(tmp_path):
    root = str(tmp_path / "repo")
    repo = _make(root)
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=2), gate=_gate())
    client = ContributorClient(root, name="c")
    for v in (0.1, 0.3):
        client.submit(_m(v), base_iteration=0)
    _drain(svc)
    good = np.array(repo.flat_base_host(), copy=True)
    _harmful(ContributorClient(root, name="bad"), base_iteration=1)
    st = _drain(svc)
    assert st["iteration"] == 1, st
    assert st["rollbacks_total"] == 1 and st["quarantined_total"] == 2
    assert st["last_gate"]["ok"] is False and st["last_gate"]["regressed"]
    np.testing.assert_array_equal(repo.flat_base_host(), good)
    qdir = os.path.join(root, "quarantine")
    assert len([f for f in os.listdir(qdir) if f.endswith(".npz")]) == 2
    # quarantined rows never re-enter the queue: more cycles change nothing
    st = _drain(svc)
    assert st["quarantined_total"] == 2 and st["iteration"] == 1
    # the verdicts landed in the metrics time series
    events = [r["event"] for r in
              ckpt.read_jsonl(os.path.join(root, "metrics.jsonl"))]
    assert "quarantine" in events and "rollback" in events
    # ... and a benign cohort after the rollback still fuses cleanly
    for v in (0.2, 0.4):
        client.submit(_m(v), base_iteration=1)
    st = _drain(svc)
    assert st["iteration"] == 2 and st["rollbacks_total"] == 1
    svc.close()
    # counters and gate state survive restart
    svc2 = ColdService(Repository.open(root, spill=True), gate=_gate())
    st2 = svc2.status()
    assert st2["rollbacks_total"] == 1 and st2["quarantined_total"] == 2
    svc2.close()


def test_gate_requires_retained_baseline_bases(tmp_path):
    """Arming the gate with compaction keeping <2 bases would delete the
    rollback target; the service widens the floor instead."""
    root = str(tmp_path / "repo")
    with pytest.warns(UserWarning, match="keep_bases"):
        svc = ColdService(_make(root),
                          policy=AdmissionPolicy(compact_keep_bases=1),
                          gate=_gate())
    assert svc.policy.compact_keep_bases == 2


def test_recent_errors_ring_bounded_and_persisted(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root))
    for i in range(ERROR_RING + 9):
        svc._note_error(RuntimeError(f"boom {i}"))
    errs = svc.status()["recent_errors"]
    assert len(errs) == ERROR_RING
    assert f"boom {ERROR_RING + 8}" in errs[-1]["error"]
    assert all("t" in e for e in errs)
    svc.close()
    errs2 = ColdService(Repository.open(root, spill=True)).status()["recent_errors"]
    assert len(errs2) == ERROR_RING
    assert f"boom {ERROR_RING + 8}" in errs2[-1]["error"]


def test_wait_for_iteration_total_wait_bounded_by_timeout(tmp_path):
    """Regression test for the backoff: even with a poll interval far
    above the timeout, every sleep is clamped to the remaining budget."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root))
    svc.run_once()
    client = ContributorClient(root)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        client.wait_for_iteration(5, timeout=0.2, interval=5.0,
                                  max_interval=60.0)
    assert time.monotonic() - t0 < 1.0


def test_serve_forever_idle_backoff_capped(tmp_path):
    """The no-progress sleep backs off but stays capped, so idle_timeout
    is honored promptly rather than overshot by a runaway interval."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root))
    t0 = time.monotonic()
    st = svc.serve_forever(poll_interval=0.01, idle_timeout=0.3,
                           max_poll_interval=0.05)
    elapsed = time.monotonic() - t0
    assert st["iteration"] == 0
    assert 0.3 <= elapsed < 2.0, elapsed


def test_metrics_emitted_on_state_change_only(tmp_path):
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    mpath = os.path.join(root, "metrics.jsonl")
    client = ContributorClient(root, name="c")
    client.submit(_m(1.0))
    _drain(svc)
    recs = ckpt.read_jsonl(mpath)
    n = len(recs)
    assert n >= 1
    assert all("t" in r and r["event"] == "cycle" for r in recs)
    for _ in range(10):
        svc.run_once()  # idle cycles may not grow the series
    assert len(ckpt.read_jsonl(mpath)) == n
    # a writer killed mid-append leaves a torn tail: readers skip it, and
    # the next service start repairs it so appends never weld mid-file
    with open(mpath, "a") as f:
        f.write('{"event": "cyc')
    assert len(ckpt.read_jsonl(mpath, warn=False)) == n
    svc.close()
    with pytest.warns(UserWarning, match="torn"):
        svc2 = ColdService(Repository.open(root, spill=True),
                           policy=AdmissionPolicy(min_cohort=1))
    client.submit(_m(2.0))
    _drain(svc2)
    recs = ckpt.read_jsonl(mpath)  # parses end to end: no welded line
    assert len(recs) > n
    assert all(r["event"] == "cycle" for r in recs)


# the gate variant of the crash matrix: a clean benign publish establishes
# the baseline, then a harmful cohort (large uniform-norm noise — admitted
# by every screen) is served with the gate armed.  kill -9 anywhere inside
# publish -> probe -> quarantine -> rollback, restart, and the run must
# converge to the benign fixed point with the harmful rows quarantined
# exactly once and the counters exact.
_GATE_SCENARIO = '''
import os, sys
sys.path.insert(0, "src")
import numpy as np
import jax.numpy as jnp
from repro.core.repository import Repository
from repro.serve.cold_service import AdmissionPolicy, ColdService, ContributorClient
from repro.serve.probes import ProbeSuite, RegressionGate

root, phase = sys.argv[1], sys.argv[2]

def m(v):
    return {"w": jnp.full((96,), float(v)), "b": jnp.full((7,), float(v))}

def gate():
    return RegressionGate(ProbeSuite(103, seed=0), tolerance=0.5)

def serve(stop):
    repo = Repository.open(root, spill=True)
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=3), gate=gate())
    for _ in range(200):
        st = svc.run_once()
        if stop(st):
            break
    else:
        print("NO_CONVERGENCE", st, flush=True)
        sys.exit(3)
    st = svc.close()
    w = np.asarray(repo.download()["w"])
    n_q = len([f for f in os.listdir(svc.queue_dir) if f.endswith(".npz")])
    n_quar = (len([f for f in os.listdir(svc.quarantine_dir)
                   if f.endswith(".npz")])
              if os.path.isdir(svc.quarantine_dir) else 0)
    print(f"DONE it={st['iteration']} fused={st['fused_contributions']} "
          f"w={w[0]:.6f} qfiles={n_q} quar={n_quar} "
          f"quarc={st['quarantined_total']} rb={st['rollbacks_total']}",
          flush=True)

if phase == "prep":
    Repository(m(0.0), root=root, spill=True, screen=False)
    client = ContributorClient(root, name="c")
    for v in (0.1, 0.3, 0.5):
        client.submit(m(v), weight=1.0, base_iteration=0)
    print("PREP_OK", flush=True)
    sys.exit(0)

if phase == "serve_clean":
    serve(lambda st: st["iteration"] >= 1 and not st["inflight"]
          and st["staged"] == 0 and st["queue_depth"] == 0)
    sys.exit(0)

if phase == "plant":
    client = ContributorClient(root, name="bad")
    rng = np.random.default_rng(99)
    for j in range(3):
        client.submit({"w": (0.3 + rng.normal(0, 10.0, 96)).astype(np.float32),
                       "b": (0.3 + rng.normal(0, 10.0, 7)).astype(np.float32)},
                      weight=1.0, base_iteration=1)
    print("PLANT_OK", flush=True)
    sys.exit(0)

# phase == "serve": drive the harmful cohort through
# publish -> probe -> quarantine -> rollback to quiescence
serve(lambda st: st["rollbacks_total"] >= 1 and st["iteration"] == 1
      and not st["inflight"] and st["staged"] == 0
      and st["queue_depth"] == 0)
'''

# every window of the harmful cohort's lifecycle, in order: staging, fuse
# dispatch, the two publish windows, then the three gate seams — verdict
# computed but unapplied (post_probe), cohort quarantined but base not yet
# rolled back (post_quarantine), base restored on disk but spill manifest
# not yet rewritten (mid_rollback).
GATE_CRASH_POINTS = [
    "service.post_ingest",
    "service.post_dispatch",
    "repo.post_publish_pre_manifest",
    "service.post_publish",
    "service.post_probe",
    "service.post_quarantine",
    "repo.mid_rollback",
]

_GATE_DONE = {"it": "1", "fused": "3", "w": "0.300000", "qfiles": "0",
              "quar": "3", "quarc": "3", "rb": "1"}


@pytest.mark.slow
@pytest.mark.parametrize("point", GATE_CRASH_POINTS)
def test_gate_exactly_once_across_crash_points(tmp_path, point):
    """kill -9 the daemon anywhere inside the gate's verdict path and
    restart: the harmful cohort is quarantined exactly once, the base
    converges to the benign fixed point, and no admitted row is lost or
    double-fused."""
    root = str(tmp_path / "repo")
    run_child(_GATE_SCENARIO, [root, "prep"])
    run_child(_GATE_SCENARIO, [root, "serve_clean"])
    run_child(_GATE_SCENARIO, [root, "plant"])
    run_child(_GATE_SCENARIO, [root, "serve"], crash_at=point)
    done = _done_line(run_child(_GATE_SCENARIO, [root, "serve"]))
    assert done == _GATE_DONE, (point, done)
    # the metrics series survived the kill -9 parseable end to end.  The
    # series is best-effort (the counters in the queue manifest are the
    # source of truth): a kill between the rollback's on-disk commit and
    # its append — exactly the repo.mid_rollback window — loses that one
    # record, and the restart correctly does NOT replay the (already
    # applied) verdict just to re-log it.
    recs = ckpt.read_jsonl(os.path.join(root, "metrics.jsonl"), warn=False)
    events = [r["event"] for r in recs]
    assert "quarantine" in events, events
    if point != "repo.mid_rollback":
        assert "rollback" in events, events
    assert recs[-1]["rollbacks_total"] == 1, recs[-1]


@pytest.mark.slow
def test_gate_uninterrupted_reference_run(tmp_path):
    """The oracle the gate crash tests compare against."""
    root = str(tmp_path / "repo")
    run_child(_GATE_SCENARIO, [root, "prep"])
    run_child(_GATE_SCENARIO, [root, "serve_clean"])
    run_child(_GATE_SCENARIO, [root, "plant"])
    done = _done_line(run_child(_GATE_SCENARIO, [root, "serve"]))
    assert done == _GATE_DONE, done


# ---------------------------------------------------------------------------
# delta-compressed submissions (docs/service_loop.md): admission, vintage
# pin, checksum-over-encoded-bytes, novelty from the decoded delta, and the
# mixed compressed+dense crash matrix
# ---------------------------------------------------------------------------

# uniform deltas with k_per_block covering every live entry reconstruct to
# float32 rounding (~1e-7 relative), so the dense closed forms carry over
_KB = 128  # > 69 live entries of _m: nothing is dropped by top-k


def _submit_compressed(client, v, *, weight=None, base_iteration=0,
                       base_v=0.0, **kw):
    return client.submit(_m(v), weight=weight, base_iteration=base_iteration,
                         compress=True, base=_m(base_v), k_per_block=_KB,
                         **kw)


def test_compressed_submit_fuse_roundtrip(tmp_path):
    """Compressed submissions fuse to the dense closed form, never leave a
    dense row in the queue, and GC like any other submission."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=2))
    client = ContributorClient(root, name="c0")
    _submit_compressed(client, 3.0, weight=1.0)
    _submit_compressed(client, 9.0, weight=3.0)
    qdir = os.path.join(root, QUEUE_DIR)
    for f in os.listdir(qdir):
        if f.endswith(".npz"):  # encoded payloads on the wire, never dense
            assert ckpt.is_flat_compressed(os.path.join(qdir, f)), f
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 2
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]),
                               (1 * 3.0 + 3 * 9.0) / 4.0, atol=1e-5)
    assert [f for f in os.listdir(qdir) if f.endswith(".npz")] == []


def test_compressed_mixed_cohort_matches_dense(tmp_path):
    """A cohort mixing dense rows and compressed deltas publishes the same
    weighted mean as the all-dense equivalent."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=4))
    client = ContributorClient(root, name="c0")
    client.submit(_m(1.0), weight=2.0)
    client.submit(_m(3.0), weight=1.0)
    _submit_compressed(client, 5.0, weight=1.0)
    _submit_compressed(client, 7.0, weight=2.0)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 4
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]),
                               (2 * 1 + 1 * 3 + 1 * 5 + 2 * 7) / 6.0,
                               atol=1e-5)


def test_compressed_vintage_pin_rejects_stale(tmp_path):
    """A delta declared against any iteration but the current one is a
    per-file rejection — it can only mis-decode against the wrong base."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0))
    _drain(svc)
    assert svc.repo.iteration == 1
    _submit_compressed(client, 5.0, base_iteration=0)  # yesterday's base
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rejected_total"] == 1
    assert "stale" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 2.0)
    # ... and future vintages are equally undecodable
    _submit_compressed(client, 5.0, base_iteration=7)
    st = _drain(svc)
    assert st["rejected_total"] == 2
    assert "stale" in st["recent_rejects"][-1]["reason"]


def test_compressed_without_base_iteration_is_malformed(tmp_path):
    """A compressed file with no declared vintage is undecodable by
    construction: per-file malformed-rider rejection, daemon unharmed."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    spec = FlatSpec.from_tree(_m(0))
    base = np.asarray(spec.flatten(_m(0.0)), np.float32)
    pay = delta_encode(np.asarray(spec.flatten(_m(4.0)), np.float32), base,
                       k_per_block=_KB)
    ckpt.save_flat_delta(os.path.join(root, QUEUE_DIR, "f-000000.npz"), pay,
                         spec, extra={"id": "f-000000"})
    ContributorClient(root, name="good").submit(_m(5.0))
    st = _drain(svc)
    assert st["iteration"] == 1 and st["last_error"] is None
    assert st["rejected_total"] == 1
    assert "malformed rider" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 5.0)


def test_compressed_nonfinite_scale_is_malformed(tmp_path):
    """Non-finite quantization scales would decode to a non-finite delta:
    rejected at the boundary, not dispatched into the fuse."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    client = ContributorClient(root, name="c0")
    sub = _submit_compressed(client, 4.0)
    path = os.path.join(root, QUEUE_DIR, sub + ".npz")
    with np.load(path) as data:
        arrays = {k: data[k] for k in data.files}
    arrays["__delta_scales__"] = np.full_like(arrays["__delta_scales__"],
                                              np.inf)
    np.savez(path, **arrays)
    ContributorClient(root, name="good").submit(_m(5.0))
    st = _drain(svc)
    assert st["iteration"] == 1 and st["last_error"] is None
    assert st["rejected_total"] == 1
    assert "non-finite quantization scale" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 5.0)


def test_compressed_checksum_over_encoded_bytes(tmp_path):
    """Regression: verify_checksums recomputes over the ENCODED payload
    bytes.  A liar rider stamping the decoded row's CRC is a per-file
    rejection — matching on the decoded row would let a corrupted payload
    through whenever it still decoded cleanly."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, verify_checksums=True))
    client = ContributorClient(root, name="c0")
    _submit_compressed(client, 2.0, checksum=True)
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rejected_total"] == 0
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 2.0,
                               atol=1e-5)
    # the liar: a hand-built file whose rider CRC is of the decoded row
    spec = FlatSpec.from_tree(_m(0))
    base = np.asarray(svc.repo.flat_base_host())
    row = np.asarray(spec.flatten(_m(6.0)), np.float32)
    pay = delta_encode(row, base, k_per_block=_KB)
    ckpt.save_flat_delta(
        os.path.join(root, QUEUE_DIR, "liar-000000.npz"), pay, spec,
        extra={"id": "liar-000000", "base_iteration": 1,
               "checksum": row_checksum(row)})
    st = _drain(svc)
    assert st["iteration"] == 1 and st["rejected_total"] == 1
    assert "checksum" in st["recent_rejects"][-1]["reason"]


def test_compressed_replay_caught_by_novelty_screen(tmp_path):
    """Two same-content compressed submissions from different contributors
    (no rider sketch — the screen must sketch from the decoded delta,
    without materializing a dense host row): one fuses, one rejects."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(
        min_cohort=1, novelty_threshold=0.05, sketch_window=8))
    spec = FlatSpec.from_tree(_m(0))
    base = np.asarray(spec.flatten(_m(0.0)), np.float32)
    pay = delta_encode(np.asarray(spec.flatten(_m(6.0)), np.float32), base,
                       k_per_block=_KB)
    for name in ("a-000000", "b-000000"):
        ckpt.save_flat_delta(os.path.join(root, QUEUE_DIR, f"{name}.npz"),
                             pay, spec,
                             extra={"id": name, "base_iteration": 0})
    st = _drain(svc)
    assert st["iteration"] == 1 and st["fused_contributions"] == 1
    assert st["novelty_rejected_total"] == 1
    assert "near-duplicate" in st["recent_rejects"][0]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 6.0,
                               atol=1e-5)


def test_compressed_deferred_while_inflight_then_vintage_checked(tmp_path):
    """While a fuse is in flight the base is already moving: a compressed
    arrival is DEFERRED (neither staged nor rejected), and once the
    publish lands its vintage is re-checked against the new iteration."""
    root = str(tmp_path / "repo")
    svc = ColdService(_make(root), policy=AdmissionPolicy(min_cohort=1))
    client = ContributorClient(root, name="c0")
    client.submit(_m(2.0))
    st = svc.run_once()
    assert st["inflight"]
    _submit_compressed(client, 5.0, base_iteration=0)
    st = svc.run_once()  # defers the delta, then finalizes the publish
    assert st["iteration"] == 1
    assert st["queue_depth"] == 1 and st["rejected_total"] == 0
    st = _drain(svc)  # now at vintage 1: the 0-vintage delta is stale
    assert st["rejected_total"] == 1
    assert "stale" in st["recent_rejects"][-1]["reason"]
    np.testing.assert_allclose(np.asarray(svc.repo.download()["w"]), 2.0)


def test_compressed_rejected_after_gate_rollback(tmp_path):
    """Regression: the PR 6 gate rolls the base back, so a delta declared
    against the rolled-back-away vintage must be rejected as stale — never
    decoded against the restored (different) base."""
    root = str(tmp_path / "repo")
    repo = _make(root)
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=2),
                      gate=_gate())
    client = ContributorClient(root, name="c")
    for v in (0.1, 0.3):
        client.submit(_m(v), base_iteration=0)
    _drain(svc)
    assert svc.repo.iteration == 1
    good = np.array(repo.flat_base_host(), copy=True)
    _harmful(ContributorClient(root, name="bad"), base_iteration=1)
    st = _drain(svc)
    assert st["rollbacks_total"] == 1 and st["iteration"] == 1
    # a rider finetuned from the transient (rolled-back) iteration-2 base
    _submit_compressed(client, 9.0, base_iteration=2)
    st = _drain(svc)
    assert st["iteration"] == 1 and "stale" in st["recent_rejects"][-1]["reason"]
    np.testing.assert_array_equal(repo.flat_base_host(), good)


# the mixed variant of the crash matrix: two dense + two compressed
# submissions, all declared against vintage 0, served through every kill
# window of the original matrix.  Exactly-once must hold for BOTH row
# kinds, and the published base must match the all-dense closed form.
_COMPRESSED_SCENARIO = '''
import os, sys
sys.path.insert(0, "src")
import numpy as np
import jax.numpy as jnp
from repro.core.repository import Repository
from repro.serve.cold_service import AdmissionPolicy, ColdService, ContributorClient

root, phase = sys.argv[1], sys.argv[2]

def m(v):
    return {"w": jnp.full((96,), float(v)), "b": jnp.full((7,), float(v))}

if phase == "prep":
    Repository(m(0.0), root=root, spill=True, screen=False)
    client = ContributorClient(root, name="c")
    client.submit(m(1.0), weight=2.0, base_iteration=0)
    client.submit(m(3.0), weight=1.0, base_iteration=0)
    for v, w in ((5.0, 1.0), (7.0, 2.0)):
        client.submit(m(v), weight=w, base_iteration=0, compress=True,
                      base=m(0.0), k_per_block=128)
    print("PREP_OK", flush=True)
    sys.exit(0)

# phase == "serve": poll to quiescence (or die at the armed crash point)
repo = Repository.open(root, spill=True)
svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=4))
for _ in range(200):
    st = svc.run_once()
    if (st["iteration"] >= 1 and not st["inflight"] and st["staged"] == 0
            and st["queue_depth"] == 0):
        break
else:
    print("NO_CONVERGENCE", st, flush=True)
    sys.exit(3)
st = svc.close()
w = np.asarray(repo.download()["w"])
n_q = len([f for f in os.listdir(svc.queue_dir) if f.endswith(".npz")])
print(f"DONE it={st['iteration']} fused={st['fused_contributions']} "
      f"w={w[0]:.6f} qfiles={n_q}", flush=True)
'''


def _assert_compressed_done(done):
    assert done["it"] == "1", done       # ONE publish total — never two
    assert done["fused"] == "4", done    # both kinds, each exactly once
    # weighted mean (2·1 + 3 + 5 + 2·7) / 6, to int8-codec reconstruction
    assert abs(float(done["w"]) - 4.0) < 1e-5, done
    assert done["qfiles"] == "0", done   # queue fully GC'd


@pytest.mark.slow
@pytest.mark.parametrize("point", CRASH_POINTS)
def test_compressed_exactly_once_fusion_across_crash_points(tmp_path, point):
    """kill -9 the daemon at any crash window with a mixed compressed+dense
    cohort staged, restart it: every submission of either kind fuses
    exactly once and the base equals the uninterrupted run's."""
    root = str(tmp_path / "repo")
    run_child(_COMPRESSED_SCENARIO, [root, "prep"])
    run_child(_COMPRESSED_SCENARIO, [root, "serve"], crash_at=point)
    done = _done_line(run_child(_COMPRESSED_SCENARIO, [root, "serve"]))
    _assert_compressed_done(done)


@pytest.mark.slow
def test_compressed_uninterrupted_reference_run(tmp_path):
    """The oracle the mixed crash tests compare against."""
    root = str(tmp_path / "repo")
    run_child(_COMPRESSED_SCENARIO, [root, "prep"])
    done = _done_line(run_child(_COMPRESSED_SCENARIO, [root, "serve"]))
    _assert_compressed_done(done)
