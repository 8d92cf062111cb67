"""Contributor finetuning: ``Contributor.contribute`` calls back to back
from the same base, each ``steps_per_call`` AdamW steps over one epoch of
a seeded classification set (``steps_per_call * batch`` distinct rows of
random token ids, random labels).

Set-up builds the one ``Contributor`` the window drives and makes its first
call, which compiles the step and whose first ``check_steps`` steps are
recorded on the way through: the batch each step was fed, its loss, the
optimizer's first moment after step 1 (from which the clipped gradient is
read back: its per-leaf norms and a seeded sample of its elements) and the
parameters after the last recorded step.  After the window the float32
reference (``reference.roberta``) takes the same steps from the same
seeded weights on the same batches (the reference module the
configuration file names: ``reference.roberta`` for the encoders).

Compared (PERF.md gives the readings each limit was set from): the first
step's loss (``loss1_gap``; the later steps' losses swing by seed, since
the loss climbs several-fold in three steps at this learning rate), the
gradient's per-leaf norms (``grad_gap``) and its sampled elements
(``grad_err``, the number that the float8 control fails), the per-leaf
norms of the parameters' change over the recorded steps (``change_gap``)
and the feed (``feed_bad``).  A traffic file's null limit leaves a number
uncompared.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from ..reference.common import (change_norms, leaf_errors, leaf_gaps,
                                leaf_norms, named_leaves, sample_index,
                                seed_key, take_samples, worst_leaf_gap)
from . import Window, checks_from, program_config, reference_module

_PARAMS = 1


def make_data(seed: int, n: int, seq: int, vocab: int, classes: int):
    """``n`` rows of ``seq`` token ids and a label each, from the seed."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, size=(n, seq), dtype=np.int32)
    y = rng.integers(0, classes, size=(n,), dtype=np.int32)
    return x, y


class StepRecorder:
    """Wraps the program's compiled train step (``train.finetune._steps``)
    while open: the first ``n`` steps' inputs and results are noted and
    passed through unchanged."""

    def __init__(self, ft_module, n: int, theta0, index):
        self.ft, self.n, self.theta0, self.index = ft_module, n, theta0, index
        self.batches: List[Dict[str, np.ndarray]] = []
        self.losses: List = []
        self.m_norms = self.m_sample = None
        self.change = None

    def __enter__(self):
        self._orig = self.ft._steps

        def patched(*a, **kw):
            opt, step, ev = self._orig(*a, **kw)
            return opt, self._wrap(step), ev

        self.ft._steps = patched
        return self

    def __exit__(self, *exc):
        self.ft._steps = self._orig

    def _wrap(self, step):
        def recorded(trainable, opt_state, static_body, batch):
            out = step(trainable, opt_state, static_body, batch)
            i = len(self.batches)
            if i < self.n:
                self.batches.append({k: np.array(v) for k, v in batch.items()})
                self.losses.append(out[2])
                if i == 0:
                    self.m_norms = leaf_norms(out[1]["m"])
                    self.m_sample = take_samples(out[1]["m"], self.index)
                if i == self.n - 1:
                    self.change = change_norms(out[0], self.theta0)
            return out

        return recorded


class Finetune:
    def __init__(self, conf, traffic, seed: int, spans, devices=None):
        # one chip: the default device (a finetune cell asks for one)
        self.conf, self.t, self.seed, self.spans = conf, traffic, seed, spans
        self.R = R = reference_module(conf)
        self.sz = R.Sizes.of(conf["as_run"], int(traffic["num_classes"]))
        o = traffic["optimizer"]
        self.hp = R.AdamW(float(traffic["lr"]), float(o["b1"]), float(o["b2"]),
                          float(o["eps"]), float(o["weight_decay"]),
                          float(o["clip_norm"]))
        self.key = jax.random.fold_in(seed_key(seed), _PARAMS)
        self.steps = int(traffic["steps_per_call"])
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self._refs = {}

    def _params0(self):
        return self.R.init_params(self.key, sz=self.sz)

    def setup(self):
        from repro.core.contributor import Contributor
        from repro.train import finetune as FT

        params = self._params0()
        self.x, self.y = make_data(self.seed, self.steps * self.batch, self.seq,
                                   self.sz.vocab_size, self.sz.num_classes)
        self.body0 = params["body"]
        c = Contributor(program_config(self.conf), task_id=0,
                        num_classes=self.sz.num_classes, x=self.x, y=self.y,
                        steps=self.steps, batch_size=self.batch,
                        lr=self.hp.lr, seed=self.seed % (2 ** 31))
        c._head = params["head"]
        self.index = sample_index(self.seed, params)
        rec = StepRecorder(FT, int(self.t["check_steps"]), params, self.index)
        with rec:
            jax.block_until_ready(c.contribute(self.body0))
        self.contributor = c
        self.rec = {"batches": rec.batches,
                    "losses": [float(v) for v in rec.losses],
                    "grad": {k: float(v) / (1.0 - self.hp.b1)
                             for k, v in named_leaves(rec.m_norms).items()},
                    "grad_sample": {k: np.asarray(v) / (1.0 - self.hp.b1)
                                    for k, v in named_leaves(
                                        jax.device_get(rec.m_sample)).items()},
                    "change": {k: float(v)
                               for k, v in named_leaves(rec.change).items()}}

    def window(self, seconds: float) -> Window:
        c, calls, bad = self.contributor, 0, 0
        t0 = time.perf_counter()
        while True:
            with self.spans.span("contribute"):
                body = c.contribute(self.body0)
            calls += 1
            bad += int(np.sum(~np.isfinite(c.last_metrics["loss"])))
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(body)
        total = time.perf_counter() - t0
        steps = calls * self.steps
        tokens = steps * self.batch * self.seq
        return Window(attempted=steps, failed=bad,
                      values={"train_tokens_per_s": tokens / total},
                      counters={"steps": steps, "calls": calls,
                                "tokens": tokens, "window_s": total,
                                "batch": self.batch, "seq": self.seq,
                                "num_classes": self.sz.num_classes})

    def release(self):
        self.contributor = self.body0 = None
        gc.collect()

    def close(self):
        pass

    def _feed_bad(self) -> float:
        """Rows of the recorded batches that are not a row of the data set
        with its label, or that repeat within the epoch."""
        index = {r.tobytes(): i for i, r in enumerate(self.x)}
        seen, bad = set(), 0
        for b in self.rec["batches"]:
            if b["tokens"].shape != (self.batch, self.seq):
                bad += self.batch
            for row, lab in zip(b["tokens"], b["labels"]):
                i = index.get(np.asarray(row, np.int32).tobytes())
                if i is None or i in seen or self.y[i] != lab:
                    bad += 1
                seen.add(i)
        return float(bad)

    def reference(self, prec: str = "f32", half_batch: bool = False):
        key = (prec, half_batch)
        if key not in self._refs:
            self._refs[key] = self._reference(prec, half_batch)
        return self._refs[key]

    def _reference(self, prec: str, half_batch: bool):
        batches = self.rec["batches"]
        if half_batch:
            h = self.batch // 2
            batches = [{k: v[:h] for k, v in b.items()} for b in batches]
        p0 = self._params0()
        losses, g, gs, p = self.R.train_steps(self.sz, self.hp, p0,
                                              batches, self.index, prec)
        return {"losses": losses,
                "grad": {k: float(v) for k, v in named_leaves(g).items()},
                "grad_sample": named_leaves(gs),
                "change": {k: float(v) for k, v in
                           named_leaves(change_norms(p, p0)).items()}}

    def readings(self, mode: str = "program") -> Dict[str, float]:
        """The numbers compared, for ``mode``: "program", or what is put in
        its place: "control" (the reference in float8) or "half_batch" (the
        reference over half of each batch)."""
        want = self.reference("f32")
        if mode == "program":
            got = self.rec
        elif mode == "control":
            got = self.reference("fp8")
        elif mode == "half_batch":
            got = self.reference("f32", half_batch=True)
        else:
            raise ValueError(mode)
        loss1 = abs(got["losses"][0] - want["losses"][0]) / abs(want["losses"][0])
        return {"loss1_gap": loss1 if np.isfinite(loss1) else float("inf"),
                "grad_gap": worst_leaf_gap(got["grad"], want["grad"], want["grad"]),
                "grad_err": max(leaf_errors(got["grad_sample"],
                                            want["grad_sample"],
                                            want["grad"]).values()),
                "change_gap": worst_leaf_gap(got["change"], want["change"],
                                             want["grad"]),
                "feed_bad": self._feed_bad()}

    def check(self, mode: str = "program"):
        return checks_from(self.t["limits"], self.readings(mode))

    def look(self) -> Dict:
        """Each recorded step's relative loss gap, for the program and what
        may be put in its place, and the program's leaves that read the
        largest gaps of gradient and change (calibration only)."""
        want = self.reference("f32")
        out = {"reference": want["losses"]}
        for name, got in (("program", self.rec),
                          ("control", self.reference("fp8")),
                          ("half_batch", self.reference("f32", half_batch=True))):
            out[name] = [abs(a - b) / abs(b)
                         for a, b in zip(got["losses"], want["losses"])]
        for key in ("grad", "change"):
            gaps = leaf_gaps(self.rec[key], want[key], want["grad"])
            top = sorted(gaps, key=gaps.get, reverse=True)[:4]
            out[key + "_leaves"] = [[k, gaps[k], want[key][k]] for k in top]
            out[key + "_median_gap"] = float(np.median(list(gaps.values())))
        for name, got in (("program", self.rec),
                          ("control", self.reference("fp8"))):
            errs = leaf_errors(got["grad_sample"], want["grad_sample"],
                               want["grad"])
            top = sorted(errs, key=errs.get, reverse=True)[:4]
            out["err_leaves." + name] = [[k, errs[k]] for k in top]
            out["err_median." + name] = float(np.median(list(errs.values())))
        return out
