"""Finetune cells run end to end on the CPU at a tiny size: a sound run is
correct, and the control and each fault planted under the timed path make
``correct`` false."""
import pytest

from .conftest import RESULT_KEYS, run_tiny

FT_CELLS = ["finetune.roberta-base", "finetune.roberta-large"]


def _step_fault(monkeypatch, fault):
    """Break the contributor's step or its feed (``train.finetune``)."""
    from repro.train import finetune as FT

    if fault == "unchanged":
        orig = FT._steps

        def steps(*a, **kw):
            opt, step, ev = orig(*a, **kw)

            def stuck(trainable, opt_state, static_body, batch):
                out = step(trainable, opt_state, static_body, batch)
                return (trainable, opt_state) + tuple(out[2:])

            return opt, stuck, ev

        monkeypatch.setattr(FT, "_steps", steps)
        return
    orig_batches = FT.batches

    def batches(*a, **kw):
        for b in orig_batches(*a, **kw):
            if fault == "half_batch":
                h = len(b["labels"]) // 2
                yield {k: v[:h] for k, v in b.items()}
            elif fault == "token_altered":
                t = b["tokens"].copy()
                t[0, 0] = (t[0, 0] + 1) % 256
                yield dict(b, tokens=t)
            else:
                raise ValueError(fault)

    monkeypatch.setattr(FT, "batches", batches)


@pytest.mark.parametrize("name", FT_CELLS)
def test_sound_run_is_correct_and_the_control_is_not(tiny_cell, name):
    cell = tiny_cell(name)
    out = run_tiny(cell, modes=("control", "half_batch"))
    modes = out.pop("modes")
    assert list(out) == RESULT_KEYS
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 6 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    limits = cell["traffic_file"]["limits"]
    for mode in ("control", "half_batch"):
        readings = modes[mode]
        assert any(v > limits[k] for k, v in readings.items()), (mode, readings)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "token_altered"])
def test_fault_makes_the_run_incorrect(tiny_cell, monkeypatch, fault):
    cell = tiny_cell(FT_CELLS[0])
    _step_fault(monkeypatch, fault)
    out = run_tiny(cell)
    assert out["correct"] is False, out["checks"]
