"""Fuse cells run end to end on the CPU at a tiny size: a sound run is
correct, and the control and each fault planted under the timed path make
``correct`` false."""
import pytest

from .conftest import RESULT_KEYS, run_tiny

FUSE_CELLS = ["inproc.roberta-base", "queue.roberta-base"]


def _fuse_fault(monkeypatch, fault):
    """Break ``ops.fuse_flat``, which the repository's fuse calls."""
    from repro.kernels import ops

    orig = ops.fuse_flat

    def broken(base, contribs, weights, alpha=1.0, *, donate=False):
        rows = getattr(contribs, "data", contribs)
        fused, sq = orig(base, rows, weights, alpha)
        if fault == "unchanged":
            return base, sq
        if fault == "half_batch":
            k = rows.shape[0] // 2
            return orig(base, rows[:k], weights[:k], alpha)[0], sq
        if fault == "altered":
            return fused.at[0].add(1.0), sq
        raise ValueError(fault)

    monkeypatch.setattr(ops, "fuse_flat", broken)


@pytest.mark.parametrize("name", FUSE_CELLS)
def test_sound_run_is_correct_and_the_control_is_not(tiny_cell, name):
    cell = tiny_cell(name, contributors=4, policy={"min_cohort": 4,
                                                   "novelty_threshold": 0.0})
    out = run_tiny(cell, modes=("control",))
    modes = out.pop("modes")
    assert list(out) == RESULT_KEYS
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert {"setup_s"} < set(out["metrics"])
    limits = cell["traffic_file"]["limits"]
    control = modes["control"]
    assert any(v > limits[k] for k, v in control.items()), control


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("name", FUSE_CELLS)
def test_fault_makes_the_run_incorrect(tiny_cell, monkeypatch, name, fault):
    cell = tiny_cell(name, contributors=4, policy={"min_cohort": 4,
                                                   "novelty_threshold": 0.0})
    _fuse_fault(monkeypatch, fault)
    out = run_tiny(cell)
    assert out["correct"] is False, out["checks"]
