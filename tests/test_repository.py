"""Repository behaviour: screening, fusion, versioning, disk persistence,
the async double-buffered staging path, and crash recovery of spilled
staged-but-unfused rows (kill-and-reopen subprocess tests).

Flake audit (PR 4): no test here (or in test_sharded_fuse.py) waits on a
``PendingFusion`` with sleeps or wall-clock timing — async fuses are
synchronized deterministically through ``flush()`` / the next
``fuse_pending`` / ``download()``, which block until the publish.  Keep it
that way: anything that genuinely needs to poll (e.g. the service loop)
must use ``tests/_faults.wait_until`` (bounded, described) rather than
``time.sleep``; global RNGs are pinned per-test by the autouse
``_seed_global_rngs`` fixture in conftest.py."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt
from repro.core import Repository, screen_contributions
from repro.core.repository import MANIFEST, PendingFusion
from repro.utils.flat import StagedBuffer


def _m(v):
    return {"w": jnp.full((16,), float(v))}


def test_fuse_average_and_iteration_advance():
    repo = Repository(_m(0))
    repo.upload(_m(1))
    repo.upload(_m(3))
    rec = repo.fuse_pending()
    assert rec.iteration == 0 and repo.iteration == 1
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 2.0)


def test_screening_rejects_nan_and_outliers():
    repo = Repository(_m(0), mad_threshold=5.0)
    for v in (1.0, 1.1, 0.9, 1.05):
        repo.upload(_m(v))
    repo.upload({"w": jnp.full((16,), jnp.nan)})
    repo.upload(_m(1e5))
    rec = repo.fuse_pending()
    assert rec.n_accepted == 4 and rec.n_contributions == 6
    assert abs(float(repo.download()["w"][0]) - 1.0125) < 1e-4


def test_screening_disabled():
    repo = Repository(_m(0), screen=False)
    repo.upload(_m(1))
    repo.upload(_m(1e5))
    rec = repo.fuse_pending()
    assert rec.n_accepted == 2


def test_all_rejected_raises():
    repo = Repository(_m(0))
    repo.upload({"w": jnp.full((16,), jnp.inf)})
    with pytest.raises(RuntimeError):
        repo.fuse_pending()


def test_empty_fuse_raises():
    with pytest.raises(RuntimeError):
        Repository(_m(0)).fuse_pending()


def test_damped_fusion_op():
    repo = Repository(_m(0), fusion_op="damped", fusion_kwargs={"alpha": 0.5})
    repo.upload(_m(2))
    repo.fuse_pending()
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 1.0)


def test_rollback():
    repo = Repository(_m(0), keep_history=True)
    repo.upload(_m(2)); repo.fuse_pending()
    repo.upload(_m(4)); repo.fuse_pending()
    assert repo.iteration == 2
    repo.rollback(1)
    assert repo.iteration == 1
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 2.0)


def test_rollback_on_disk_without_history(tmp_path):
    """Crash-safe rollback: with keep_history=False the base is restored
    from the compact-retained base_iterNNNN.npz, the manifest and
    iteration update atomically, and a reopened repository agrees."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, screen=False)
    repo.upload(_m(2)); repo.fuse_pending()
    repo.upload(_m(4)); repo.fuse_pending()
    assert repo.iteration == 2 and not repo.keep_history
    repo.rollback(1)
    assert repo.iteration == 1
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 2.0)
    again = Repository.open(root)
    assert again.iteration == 1
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 2.0)
    # rolling forward again from the restored base still works
    again.upload(_m(6)); again.fuse_pending()
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 6.0)


def test_rollback_validations(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, screen=False)
    repo.upload(_m(2)); repo.fuse_pending()
    with pytest.raises(ValueError, match="iteration"):
        repo.rollback(5)
    with pytest.raises(ValueError, match="iteration"):
        repo.rollback(-1)
    # a compacted-away base cannot be a rollback target
    os.remove(os.path.join(root, "base_iter0000.npz"))
    with pytest.raises(ValueError, match="keep_bases"):
        repo.rollback(0)
    # no root and no history: rollback has nothing to restore from
    mem = Repository(_m(0), screen=False)
    mem.upload(_m(2)); mem.fuse_pending()
    with pytest.raises(RuntimeError, match="keep_history"):
        mem.rollback(0)


def test_disk_persistence(tmp_path):
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root)
    repo.upload(_m(2))
    repo.fuse_pending()
    again = Repository.open(root)
    assert again.iteration == 1
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 2.0)


def test_screen_zero_diff_rejected():
    base = _m(1)
    rep = screen_contributions(base, [_m(1), _m(1.2), _m(0.8), _m(1.1)])
    assert 0 in rep.rejected and "no-op" in rep.reasons[0]


def test_fisher_fusion_via_repository():
    """fusion_op='fisher' consumes per-contribution Fishers (§8 beyond-paper)."""
    repo = Repository(_m(0), fusion_op="fisher", screen=False)
    repo.upload(_m(1), fisher={"w": jnp.ones((16,))})
    repo.upload(_m(3), fisher={"w": 3 * jnp.ones((16,))})
    repo.fuse_pending()
    # (1*1 + 3*3) / (1+3) = 2.5
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 2.5, rtol=1e-5)


def test_fisher_fusion_missing_fisher_raises():
    repo = Repository(_m(0), fusion_op="fisher", screen=False)
    repo.upload(_m(1))
    with pytest.raises(RuntimeError):
        repo.fuse_pending()


def test_weighted_uploads():
    """§8 contributor weights: weight by (e.g.) dataset size."""
    repo = Repository(_m(0), screen=False)
    repo.upload(_m(1), weight=3)
    repo.upload(_m(5), weight=1)
    repo.fuse_pending()
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 2.0)  # (3*1+1*5)/4


def test_async_contribution():
    """§8 asynchronous repository updates via damped task arithmetic."""
    repo = Repository(_m(0), screen=False)
    rec = repo.contribute_async(_m(4))  # alpha = 1/(1+0) = 1
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 4.0)
    assert rec.op.startswith("async")
    repo.contribute_async(_m(0))  # alpha = 1/2 -> (4+0)/2
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 2.0)
    repo.contribute_async(_m(8), alpha=0.25)  # 2 + 0.25*(8-2) = 3.5
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 3.5)
    assert repo.iteration == 3


def test_async_screens_nan():
    repo = Repository(_m(1))
    with pytest.raises(RuntimeError):
        repo.contribute_async({"w": jnp.full((16,), jnp.nan)})


# ---------------------------------------------------------------------------
# async double-buffered fuse (docs/async_repository.md)
# ---------------------------------------------------------------------------


def test_fuse_pending_async_matches_sync():
    """wait=False must publish the same bases as the blocking path, with
    uploads of the next cohort landing in the front buffer while the back
    cohort's fuse is in flight."""
    repo, sync = Repository(_m(0), screen=False), Repository(_m(0), screen=False)
    for v in (1, 3):
        repo.upload(_m(v)); sync.upload(_m(v))
    pf = repo.fuse_pending(wait=False)
    assert isinstance(pf, PendingFusion) and not pf.done
    sync.fuse_pending()
    for v in (5, 7):  # staged during the in-flight fuse
        repo.upload(_m(v)); sync.upload(_m(v))
    assert len(repo._pending) == 2  # front buffer, untouched by the fuse
    repo.fuse_pending()  # finalizes (1,3), then fuses (5,7)
    sync.fuse_pending()
    rec = repo.flush()
    assert pf.done and pf.record.n_accepted == 2
    assert repo.iteration == sync.iteration == 2
    np.testing.assert_allclose(
        np.asarray(repo.download()["w"]), np.asarray(sync.download()["w"]))
    assert rec is None or rec.iteration == 1  # flush after final fuse_pending(wait=True)


def test_download_finalizes_inflight():
    repo = Repository(_m(0), screen=False)
    repo.upload(_m(4))
    repo.fuse_pending(wait=False)
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 4.0)
    assert repo.iteration == 1 and repo._inflight is None


def test_flush_idle_returns_none():
    assert Repository(_m(0)).flush() is None


def test_async_all_rejected_raises_at_finalize_and_keeps_cohort():
    repo = Repository(_m(0))
    repo.upload({"w": jnp.full((16,), jnp.inf)})
    repo.fuse_pending(wait=False)
    with pytest.raises(RuntimeError, match="all contributions rejected"):
        repo.flush()
    # base untouched, cohort restored to the front buffer for retry
    assert repo.iteration == 0 and len(repo._pending) == 1
    np.testing.assert_array_equal(np.asarray(repo.download()["w"]), 0.0)


def test_fuse_pending_explicit_buffer():
    """fuse_pending(buffer=...) fuses a caller-staged operand without
    touching the front staging buffer."""
    repo = Repository(_m(0), screen=False)
    repo.upload(_m(9))  # stays staged
    buf = StagedBuffer.from_rows(
        [jnp.full((16,), 2.0), jnp.full((16,), 4.0)])
    rec = repo.fuse_pending(buffer=buf)
    assert rec.n_contributions == 2 and repo.iteration == 1
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 3.0)
    assert len(repo._pending) == 1  # the staged upload is still there


def test_fuse_pending_buffer_shape_mismatch_raises():
    repo = Repository(_m(0), screen=False)
    with pytest.raises(ValueError, match="does not match"):
        repo.fuse_pending(buffer=jnp.zeros((2, 7)))


# ---------------------------------------------------------------------------
# resumable spill: kill-and-reopen crash recovery
# ---------------------------------------------------------------------------

_CRASH_STAGE = '''
import os, sys
sys.path.insert(0, "src")
import jax.numpy as jnp
from repro.core.repository import Repository
root = sys.argv[1]
def m(v):
    return {"w": jnp.full((64,), float(v))}
repo = Repository(m(0), root=root, spill=True, screen=False)
repo.upload(m(1), weight=2.0)
repo.upload(m(3), weight=1.0)
repo.upload(m(5), weight=1.0)
# a torn write that never got atomically published: not in the manifest
with open(os.path.join(root, "iter0000_contrib099.npz"), "wb") as f:
    f.write(b"PK\\x03\\x04 truncated garbage")
print("STAGED", flush=True)
os._exit(1)  # crash before fuse_pending
'''


def _run_crash_child(root, extra_env=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    res = subprocess.run(
        [sys.executable, "-c", _CRASH_STAGE, root],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert res.returncode == 1 and "STAGED" in res.stdout, (
        res.stdout + "\n" + res.stderr)


def test_spill_crash_recovery_reopen(tmp_path):
    """A repository killed mid-staging reopens with zero lost uploaded
    rows: manifest entries are re-staged (with their weights) and fuse to
    the same base an uncrashed repository would have published."""
    root = str(tmp_path / "repo")
    _run_crash_child(root)
    again = Repository.open(root, spill=True)
    assert len(again._pending) == 3
    assert again._pending_weights == [2.0, 1.0, 1.0]
    rec = again.fuse_pending()
    assert rec.n_accepted == 3
    # parity with the never-crashed in-memory flow
    mem = Repository({"w": jnp.full((64,), 0.0)}, screen=False)
    for v, w in ((1, 2.0), (3, 1.0), (5, 1.0)):
        mem.upload({"w": jnp.full((64,), float(v))}, weight=w)
    mem.fuse_pending()
    np.testing.assert_allclose(
        np.asarray(again.download()["w"]), np.asarray(mem.download()["w"]))
    # the cohort left the manifest once the publish landed
    assert json.load(open(os.path.join(root, MANIFEST)))["entries"] == []


def test_spill_recovery_ignores_partial_and_missing_rows(tmp_path):
    """Manifest entries whose row file is torn or missing are skipped with
    a warning; row files not in the manifest are ignored entirely."""
    root = str(tmp_path / "repo")
    _run_crash_child(root)
    # corrupt the manifest's view: one entry pointing at the torn npz, one
    # at a file that does not exist
    mpath = os.path.join(root, MANIFEST)
    manifest = json.load(open(mpath))
    good = dict(manifest["entries"][0])
    manifest["entries"].append(dict(good, file="iter0000_contrib099.npz"))
    manifest["entries"].append(dict(good, file="iter0000_contrib777.npz"))
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.warns(UserWarning, match="skipping unreadable staged row"):
        again = Repository.open(root, spill=True)
    assert len(again._pending) == 3  # the three real rows, nothing else
    assert again.fuse_pending().n_accepted == 3


def test_spill_recovery_on_pytree_engine(tmp_path):
    """Recovered rows re-enter as pytrees when the repository reopens on
    the per-leaf engine (use_flat=False)."""
    root = str(tmp_path / "repo")
    _run_crash_child(root)
    again = Repository.open(root, use_flat=False, screen=False)
    assert len(again._pending) == 3
    assert isinstance(again._pending[0], dict)
    again.fuse_pending()
    # weighted mean (2·1 + 1·3 + 1·5) / 4
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 2.5)


def test_open_rejects_base_spec_mismatch(tmp_path):
    """Regression: open() validated nothing about the stored base, so a
    swapped/corrupted checkpoint silently accepted the recorded
    fusion_kwargs (dtype/N mismatch).  It must now raise clearly."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, fusion_kwargs={"weights": [1.0]})
    repo.upload(_m(2))
    repo.fuse_pending()
    # clobber the latest base with a different architecture
    ckpt.save(os.path.join(root, "base_iter0001.npz"),
              {"other": jnp.zeros((7, 3))})
    with pytest.raises(ValueError, match="does not match the recorded"):
        Repository.open(root)


def test_recovery_rejects_spec_mismatched_rows(tmp_path):
    """A spilled row from a different model (dtype/N) must raise, not fuse."""
    root = str(tmp_path / "repo")
    _run_crash_child(root)
    # replace one staged row with a row of the wrong width
    entries = json.load(open(os.path.join(root, MANIFEST)))["entries"]
    from repro.utils.flat import FlatSpec
    wrong = {"w": jnp.zeros((32,))}
    spec = FlatSpec.from_tree(wrong)
    ckpt.save_flat(os.path.join(root, entries[0]["file"]),
                   spec.flatten(wrong), spec)
    with pytest.raises(ValueError, match="refusing to recover"):
        Repository.open(root, spill=True)


_CRASH_STAGE_MESH = '''
import os, sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core.repository import Repository
from repro.launch.mesh import make_mesh
root, phase = sys.argv[1], sys.argv[2]
assert jax.device_count() == 8, jax.device_count()
mesh = make_mesh((8,), ("model",))
def m(v):
    return {"w": jnp.full((3000,), float(v)), "b": jnp.full((17,), float(v))}
if phase == "stage":
    repo = Repository(m(0), mesh=mesh, root=root, spill=True, screen=False)
    repo.upload(m(2.0))
    repo.upload(m(6.0))
    print("STAGED", flush=True)
    os._exit(1)  # crash before fuse_pending
# phase == "recover": reopen under the same mesh, forbid full-row loads
from repro.checkpoint import io as ckpt
from repro.utils import flat as F
def boom(*a, **k):
    raise AssertionError("full [N] row materialized on host")
F.ShardedFlatSpec.unshard_slices = boom
ckpt.FlatShardReader.full_row = boom
ckpt.load_flat = boom
repo = Repository.open(root, mesh=mesh, spill=True)
assert len(repo._pending) == 2, repo._pending
rec = repo.fuse_pending()
assert rec.n_accepted == 2
import numpy as np
np.testing.assert_allclose(np.asarray(repo.download()["w"]), 4.0, rtol=1e-6)
print("RECOVERED", flush=True)
'''


@pytest.mark.slow
def test_spill_crash_recovery_sharded_8dev(tmp_path):
    """Kill-and-reopen under the forced 8-fake-device mesh: per-shard
    spilled rows recover into their shard placement with zero loss and no
    host-side full-row reassembly."""
    root = str(tmp_path / "repo")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-c", _CRASH_STAGE_MESH, root, "stage"],
        capture_output=True, text=True, env=env, timeout=900, cwd=cwd)
    assert res.returncode == 1 and "STAGED" in res.stdout, (
        res.stdout + "\n" + res.stderr)
    res = subprocess.run(
        [sys.executable, "-c", _CRASH_STAGE_MESH, root, "recover"],
        capture_output=True, text=True, env=env, timeout=900, cwd=cwd)
    assert res.returncode == 0 and "RECOVERED" in res.stdout, (
        res.stdout + "\n" + res.stderr)


def test_recovery_skips_cohort_whose_publish_landed(tmp_path):
    """Crash window between base publish and manifest rewrite: the
    recorded iteration has moved past the entries' staged_at, so recovery
    must skip them — re-applying a fused cohort would corrupt the base."""
    root = str(tmp_path / "repo")
    _run_crash_child(root)
    stale = json.load(open(os.path.join(root, MANIFEST)))
    again = Repository.open(root, spill=True)
    again.fuse_pending()  # publishes iteration 1, manifest rewritten empty
    base_after = np.asarray(again.download()["w"]).copy()
    # simulate the lost rewrite: restore the pre-publish manifest in the
    # state the dispatch left it on disk — back cohort marked in-flight
    for e in stale["entries"]:
        e["fusing"] = True
    with open(os.path.join(root, MANIFEST), "w") as f:
        json.dump(stale, f)
    third = Repository.open(root, spill=True)
    assert len(third._pending) == 0  # staged_at < iteration -> consumed
    np.testing.assert_array_equal(np.asarray(third.download()["w"]), base_after)


def test_recovery_reopen_without_spill_kwarg(tmp_path):
    """open() restores spill from repository.json, and recovery works even
    when the caller does not repeat the construction kwargs."""
    root = str(tmp_path / "repo")
    _run_crash_child(root)
    again = Repository.open(root)  # no spill=True: restored from the meta
    assert again.spill and len(again._pending) == 3
    assert again.fuse_pending().n_accepted == 3


def test_pending_rows_survive_interleaved_async_publish(tmp_path):
    """A publish that does not consume the staged rows (contribute_async)
    must not make them look consumed to crash recovery."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=False)
    repo.upload(_m(2), weight=1.0)
    repo.contribute_async(_m(8), alpha=1.0)  # iteration 0 -> 1, row still staged
    again = Repository.open(root, spill=True)
    assert len(again._pending) == 1  # staged row recovered, not skipped
    again.fuse_pending()
    # fused against the async-published base: mean of one row = 2
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 2.0)


def test_unconsumed_rows_recovered_after_async_publish_crash_window(tmp_path):
    """Crash between a contribute_async publish and its manifest rewrite:
    the staged row's entry is stale (old staged_at) but carries no
    in-flight mark, so recovery must keep it — only marked (dispatched)
    cohorts may be skipped as consumed."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, screen=False)
    repo.upload(_m(2), weight=1.0)
    stale = json.load(open(os.path.join(root, MANIFEST)))  # staged_at=0
    repo.contribute_async(_m(8), alpha=1.0)  # publishes iteration 1
    # simulate the lost rewrite: stale manifest + advanced repository.json
    with open(os.path.join(root, MANIFEST), "w") as f:
        json.dump(stale, f)
    again = Repository.open(root, spill=True)
    assert len(again._pending) == 1  # unmarked entry: never skipped
    again.fuse_pending()
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 2.0)


def test_spill_workers_async_writes(tmp_path):
    """spill_workers=N drains npz writes off the upload path; fuse and
    recovery semantics are unchanged."""
    root = str(tmp_path / "repo")
    repo = Repository(_m(0), root=root, spill=True, spill_workers=2,
                      screen=False)
    for v in (1.0, 3.0, 5.0):
        repo.upload(_m(v))
    rec = repo.fuse_pending()
    assert rec.n_accepted == 3
    repo.flush()
    np.testing.assert_allclose(np.asarray(repo.download()["w"]), 3.0)
    assert json.load(open(os.path.join(root, MANIFEST)))["entries"] == []
    # the published base landed on disk despite the executor-drained write
    again = Repository.open(root)
    assert again.iteration == 1
    np.testing.assert_allclose(np.asarray(again.download()["w"]), 3.0)
