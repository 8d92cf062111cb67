"""Train-step machinery: microbatch-accumulation equivalence, loss descent,
linear probing freezes the body, and the finetune loop that runs one step
ahead of the device matches a loop that syncs after every step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models.transformer import init_lm
from repro.optim.optimizers import constant_lr, make_optimizer, sgd
from repro.train import finetune as FT
from repro.train.step import make_train_state, make_train_step
from repro.models import encoder as E
from repro.utils import trace


def test_microbatch_equals_full_batch_grads(key):
    """SGD step with 4 microbatches == single-batch step (linear loss in
    grads => averaging microbatch grads is exact)."""
    cfg = reduce_config(get_config("gemma3-1b"))
    params = init_lm(cfg, key)
    opt = sgd(constant_lr(0.1))
    batch = {"tokens": jax.random.randint(key, (8, 16), 3, cfg.vocab_size)}
    s1, m1 = jax.jit(make_train_step(cfg, opt, microbatches=1))(make_train_state(params, opt), batch)
    s4, m4 = jax.jit(make_train_step(cfg, opt, microbatches=4))(make_train_state(params, opt), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s4["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_lm_loss_decreases(key):
    cfg = reduce_config(get_config("mistral-nemo-12b"))
    params = init_lm(cfg, key)
    opt = make_optimizer("adamw", constant_lr(3e-3))
    step = jax.jit(make_train_step(cfg, opt))
    state = make_train_state(params, opt)
    batch = {"tokens": jax.random.randint(key, (4, 16), 3, cfg.vocab_size)}
    losses = []
    for _ in range(12):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_linear_probe_freezes_body(tiny_cfg, key):
    body = E.init_encoder_body(tiny_cfg, key)
    head = E.init_cls_head(tiny_cfg, key, 3)
    x = np.random.default_rng(0).integers(3, 64, (64, 16)).astype(np.int32)
    y = np.random.default_rng(1).integers(0, 3, 64).astype(np.int32)
    body2, head2, _ = FT.finetune(tiny_cfg, body, head, x, y, steps=5, frozen_body=True)
    for a, b in zip(jax.tree.leaves(body), jax.tree.leaves(body2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(head), jax.tree.leaves(head2))
    )
    assert changed


def test_full_finetune_changes_body(tiny_cfg, key):
    body = E.init_encoder_body(tiny_cfg, key)
    head = E.init_cls_head(tiny_cfg, key, 2)
    x = np.random.default_rng(0).integers(3, 64, (64, 16)).astype(np.int32)
    y = np.random.default_rng(1).integers(0, 2, 64).astype(np.int32)
    body2, _, _ = FT.finetune(tiny_cfg, body, head, x, y, steps=5)
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(body), jax.tree.leaves(body2))
    )
    assert changed


def _task(tiny_cfg, key, classes=3):
    body = E.init_encoder_body(tiny_cfg, key)
    head = E.init_cls_head(tiny_cfg, key, classes)
    x = np.random.default_rng(0).integers(3, 64, (64, 16)).astype(np.int32)
    y = np.random.default_rng(1).integers(0, classes, 64).astype(np.int32)
    return body, head, x, y


def _synced_finetune(cfg, body, head, x, y, *, steps, frozen_body, seed=0):
    """``FT.finetune``'s steps with a host read of each step's loss and
    accuracy before the next is dispatched, and the body passed beside the
    trainable state as well."""
    num_classes = int(head["out"].shape[-1])
    opt, train_step, _ = FT._steps(cfg, num_classes, frozen_body, 5e-4, 0.0)
    trainable = {"head": head} if frozen_body else {"head": head, "body": body}
    opt_state = opt.init(trainable)
    it = FT.batches(x, y, 32, rng=np.random.default_rng(seed), epochs=10_000)
    losses, accs = [], []
    for _ in range(steps):
        trainable, opt_state, loss, acc = train_step(trainable, opt_state,
                                                     body, next(it))
        losses.append(float(loss))
        accs.append(float(acc))
    return trainable.get("body", body), trainable["head"], {
        "loss": losses, "train_acc": accs}


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("frozen_body", [False, True])
def test_finetune_matches_a_loop_that_syncs_every_step(tiny_cfg, key,
                                                      frozen_body):
    body, head, x, y = _task(tiny_cfg, key)
    got = FT.finetune(tiny_cfg, body, head, x, y, steps=6,
                      frozen_body=frozen_body, seed=3)
    want = _synced_finetune(tiny_cfg, body, head, x, y, steps=6,
                            frozen_body=frozen_body, seed=3)
    assert got[2] == want[2]
    _assert_trees_equal(got[0], want[0])
    _assert_trees_equal(got[1], want[1])


def test_finetune_leaves_the_callers_arrays_and_repeats(tiny_cfg, key):
    body, head, x, y = _task(tiny_cfg, key)
    body0, head0 = jax.device_get((body, head))
    first = FT.finetune(tiny_cfg, body, head, x, y, steps=4)
    _assert_trees_equal(body, body0)
    _assert_trees_equal(head, head0)
    second = FT.finetune(tiny_cfg, body, head, x, y, steps=4)
    _assert_trees_equal(first[0], second[0])
    _assert_trees_equal(first[1], second[1])
    assert first[2] == second[2]


def test_finetune_metrics_are_one_python_float_a_step(tiny_cfg, key):
    body, head, x, y = _task(tiny_cfg, key)
    _, _, metrics = FT.finetune(tiny_cfg, body, head, x, y, steps=5)
    assert set(metrics) == {"loss", "train_acc"}
    for values in metrics.values():
        assert len(values) == 5
        assert all(type(v) is float for v in values)


def test_finetune_counts_its_steps_and_host_waits(tiny_cfg, key):
    body, head, x, y = _task(tiny_cfg, key)
    trace.reset()
    trace.enable()
    try:
        FT.finetune(tiny_cfg, body, head, x, y, steps=5)
        c = trace.counters()
    finally:
        trace.disable()
        trace.reset()
    assert c["finetune.steps"] == 5
    assert 5 <= c["finetune.host_waits"] <= 5 + 1
    assert 0 <= c.get("finetune.overlapped", 0) <= 4


def test_finetune_queues_at_most_one_step_behind_the_running_one(
        tiny_cfg, key, monkeypatch):
    """When step n is dispatched, step n-2 has finished: the loop holds at
    most one step queued behind the running one."""
    body, head, x, y = _task(tiny_cfg, key)
    orig, outs, seen = FT._steps, [], []

    def steps(*a, **kw):
        opt, step, ev = orig(*a, **kw)

        def watched(trainable, opt_state, static_body, batch):
            if len(outs) >= 2:
                seen.append(outs[-2][2].is_ready())
            outs.append(step(trainable, opt_state, static_body, batch))
            return outs[-1]

        return opt, watched, ev

    monkeypatch.setattr(FT, "_steps", steps)
    FT.finetune(tiny_cfg, body, head, x, y, steps=6)
    assert seen == [True] * 4
