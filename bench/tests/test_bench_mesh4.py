"""The four-chip fuse cell on four virtual CPU devices at a tiny size (in a
process of its own, ``mesh4_runs.py``), the blocked reference it is
compared with, and the readers of its per-layer metrics."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, tracing
from bench.reference import fuse as ref
from bench.reference.common import max_bf16_steps

from .conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, os.path.join(HERE, "mesh4_runs.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_trees_are_split_over_the_four_devices(runs):
    assert runs["sharded"] is True


def test_sound_run_is_correct_and_the_control_is_not(runs):
    sound = runs["sound"]
    assert sound["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    limits = {k: c["limit"] for k, c in sound["checks"].items()}
    control = sound["control"]
    assert any(v > limits[k] for k, v in control.items()), control


def test_traced_run_reads_the_program_spans(runs):
    traced = runs["traced"]
    assert traced["correct"] is True
    # the CPU trace has no device plane, so only the host readers read:
    # the benchmark's span and the program's own
    assert traced["metrics"]["finalize_ms.mesh4"]["value"] > 0
    assert traced["metrics"]["upload_ms.inproc"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered",
                                   "no_exchange"])
def test_fault_on_the_sharded_fuse_makes_the_run_incorrect(runs, fault):
    assert runs[fault]["correct"] is False, runs[fault]["checks"]


def _cohort(k=5):
    key = jax.random.PRNGKey(3)
    base = {"a": jax.random.normal(key, (12, 5, 7)).astype(jnp.bfloat16),
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (33,)).astype(jnp.bfloat16)}
    rows = [jax.tree.map(
        lambda x, i=i: (x.astype(jnp.float32) + 0.05 * jax.random.normal(
            jax.random.fold_in(key, 10 + i), x.shape)).astype(x.dtype), base)
        for i in range(k)]
    return base, rows


def _flat(tree):
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(tree)])


@pytest.mark.parametrize("prec", ["f32", "fp8"])
def test_blocked_reference_equals_the_whole_row_one(monkeypatch, prec):
    base, rows = _cohort()
    w = np.array([1, 0, 1, 1, 1], np.float32)
    want, want_sq = ref.fuse(_flat(base), jnp.stack([_flat(r) for r in rows]),
                             jnp.asarray(w), 1.0, prec=prec)
    # blocks of two rows of the leading axis: six blocks of "a", and "b"
    # in one block
    monkeypatch.setattr(ref, "BLOCK", 70)
    assert ref.leading_block((12, 5, 7), ref.BLOCK) == 2
    got, sq = ref.fuse_tree(base, rows, w, 1.0, prec=prec)
    assert jax.tree.structure(got) == jax.tree.structure(base)
    # the same rows in host memory give the same bytes
    host_got, host_sq = ref.fuse_tree(base, jax.device_get(rows), w, 1.0,
                                      prec=prec)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(host_got), jax.tree.leaves(got)))
    np.testing.assert_array_equal(host_sq, sq)
    # the same arithmetic, summed in another order: at most one rounding
    # step of bfloat16 apart, and the distances to float32 rounding
    assert float(max_bf16_steps(_flat(got), want)) <= 1.0
    np.testing.assert_allclose(sq, np.asarray(want_sq), rtol=1e-5)


def test_leading_block_divides_the_leading_axis():
    assert ref.leading_block((49155, 1024), 1 << 24) == 9831
    assert ref.leading_block((24, 32, 1024, 512), 1 << 24) == 1
    assert ref.leading_block((1024,), 1 << 24) == 1024
    assert ref.leading_block((7, 1 << 25), 1 << 24) == 1


def _ctx(**kw):
    base = dict(cfg={}, traffic={}, spans=tracing.Spans(), trace=None,
                counters={}, peaks={"hbm_bytes_per_s": 800e9,
                                    "bf16_flops_per_s": 200e12})
    base.update(kw)
    return harness.Context(**base)


def test_mesh4_readers():
    k, n, chips = 8, 1000, 4
    counters = {"k": k, "n": n, "chips": chips, "rounds": 5, "window_s": 1.0}
    red = {"devices": 4, "window_s": 1.0, "busy_s": 0.5,
           "op_s": {"_cold_fuse_impl": 4 * 1e-6, "all-reduce": 0.002,
                    "all-gather-done": 0.004, "fusion": 0.3},
           "op_calls": {"_cold_fuse_impl": 4, "all-reduce": 4,
                        "all-gather-done": 8, "fusion": 40}, "gaps": []}
    ctx = _ctx(trace=red, counters=counters)
    # one call on each of four chips: 10 * 250 * 2 bytes a chip in 1 us
    roof = harness.metric_reader("cold_fuse_roofline.inproc")(ctx)
    assert roof == pytest.approx(100.0 * 5000 / 800e9 / 1e-6)
    mfu = harness.metric_reader("fuse_round_mfu.inproc")(ctx)
    assert mfu == pytest.approx(100.0 * 5 * (10 * n * 2 / 800e9) / 4 / 1.0)
    # on one chip the same readers count the whole row, to the digit
    one = dict(counters, chips=1)
    red1 = dict(red, devices=1, op_s={"_cold_fuse_impl": 1e-6},
                op_calls={"_cold_fuse_impl": 1})
    ctx1 = _ctx(trace=red1, counters=one)
    assert harness.metric_reader("cold_fuse_roofline.inproc")(ctx1) == (
        100.0 * (1 * 10 * n * 2) / 800e9 / 1e-6)
    assert harness.metric_reader("fuse_round_mfu.inproc")(ctx1) == (
        100.0 * (5 * (10 * n * 2 / 800e9)) / 1.0)
    coll = harness.metric_reader("collective_ms.mesh4")(ctx)
    assert coll == pytest.approx(1e3 * 0.006 / 4 / 5)
    program = tracing.Spans()
    program.records = [("repo.finalize", 0.0, 0.002),
                       ("repo.finalize", 1.0, 1.004),
                       ("repo.screen_sync", 1.0, 1.001)]
    fin = harness.metric_reader("finalize_ms.mesh4")(_ctx(program=program))
    assert fin == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["cold_fuse_roofline.inproc",
                                  "fuse_round_mfu.inproc",
                                  "collective_ms.mesh4", "finalize_ms.mesh4"])
def test_mesh4_reader_with_nothing_to_read_reads_nothing(name):
    empty = {"devices": 0, "window_s": 0.0, "busy_s": 0.0, "op_s": {},
             "op_calls": {}, "gaps": []}
    assert harness.metric_reader(name)(_ctx(trace=empty)) is None


def test_program_spans_with_attributes_name_the_idle_gaps():
    dev = [{"ph": "M", "name": "process_name", "pid": 1,
            "args": {"name": "/device:TPU:0"}},
           {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
            "args": {"name": "XLA Ops"}},
           {"ph": "X", "pid": 1, "tid": 2, "ts": 0.0, "dur": 10.0,
            "name": "fusion.1"},
           {"ph": "X", "pid": 1, "tid": 2, "ts": 110.0, "dur": 10.0,
            "name": "fusion.2"},
           {"ph": "X", "pid": 9, "tid": 9, "ts": 5.0, "dur": 110.0,
            "name": "fuse_pending"},
           {"ph": "X", "pid": 9, "tid": 9, "ts": 20.0, "dur": 80.0,
            "name": "repo.finalize#iteration=3#"}]
    red = tracing.reduce_trace(dev, {"fuse_pending", "repo.finalize"})
    assert red["gaps"][0] == (pytest.approx(100e-6), "repo.finalize")
