"""Fuse rounds: the in-process hub (``Repository.upload`` then
``fuse_pending``) and the durable queue (``ContributorClient.submit`` then
``ColdService.run_once`` until the next base is published).

Contribution rows are the seeded base plus a seeded Gaussian perturbation
of every element (standard deviation 1/32 of the leaf's RMS, or of 0.02
where that is larger), so every round's cohort is distinct and close to the
base, as finetuned models are.  The reference fuses the same rows
(``reference.fuse``) after the window; it takes the previous base as its
own fuse of the previous round's rows.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import fuse as ref
from ..reference.common import flat, max_bf16_steps, named_leaves, seed_key
from ..reference.roberta import Sizes, init_params
from . import Reservoir, Window, checks_from

# key streams derived from the seed
_PARAMS, _ROWS = 1, 2


def _sigma(x):
    rms = jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))
    return jnp.maximum(rms, 0.02) / 32.0


def _perturb(base, key):
    leaves, treedef = jax.tree.flatten(base)
    out = [(x.astype(jnp.float32) + _sigma(x) * jax.random.normal(
        jax.random.fold_in(key, j), x.shape, jnp.float32)).astype(x.dtype)
        for j, x in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


@jax.jit
def perturbed(base, key):
    """A perturbed copy of ``base`` (one jitted call per copy: a program
    that made many copies at once would take minutes to compile)."""
    return _perturb(base, key)


@jax.jit
def perturbed_row(base, key):
    """The same copy as a flat row ``[N]``."""
    return flat(_perturb(base, key))


def make_trees(base, key, k: int):
    return [perturbed(base, jax.random.fold_in(key, i)) for i in range(k)]


def make_rows(base, key, k: int):
    return [perturbed_row(base, jax.random.fold_in(key, i)) for i in range(k)]


@jax.jit
def _flat(tree):
    return flat(tree)


def read_npz_row(path: str, names: List[str], shapes) -> Optional[np.ndarray]:
    """A published base file read back as a flat bf16 row in the
    benchmark's leaf order: one array per leaf, named by the leaf's path
    with ``::`` separators and ``__bf16__`` after the bit pattern's name.
    None when a leaf is missing or misshapen."""
    parts = []
    with np.load(path) as z:
        for name, shape in zip(names, shapes):
            key = name.replace("/", "::") + "__bf16__"
            if key not in z.files or tuple(z[key].shape) != tuple(shape):
                return None
            parts.append(np.ravel(z[key]).view(jnp.bfloat16))
    return np.concatenate(parts)


class _FuseDriver:
    """What both kinds of round share: the seeded base, the sample of
    checked rounds and the comparison with the reference."""

    def __init__(self, conf, traffic, seed: int, spans):
        self.conf, self.t, self.seed, self.spans = conf, traffic, seed, spans
        self.k = int(traffic["contributors"])
        self.sz = Sizes.of(conf["as_run"], 2)
        self.key = seed_key(seed)
        self.sampled = Reservoir(int(traffic["check_rounds"]), seed)
        self.round = 0
        self.rounds: List[Dict] = []

    def _base0(self):
        return init_params(jax.random.fold_in(self.key, _PARAMS),
                           sz=self.sz)["body"]

    # -- subclass API --------------------------------------------------
    def rows_of(self, r: int):
        """Round ``r``'s rows, ``[K, N]``, on the device (reference side)."""
        raise NotImplementedError

    # -- comparison ----------------------------------------------------
    def _screened(self, base, rows, prec: str):
        """Fuse with every weight 1, then again with the screen's rejects at
        weight 0.  Returns (fused, sq of the first pass)."""
        w = np.ones((self.k,), np.float32)
        fused, sq = ref.fuse(base, rows, jnp.asarray(w), 1.0, prec=prec)
        keep = ref.screen(np.sqrt(np.asarray(sq, np.float64)), self.mad)
        if len(keep) < self.k:
            w[:] = 0.0
            w[keep] = 1.0
            fused, _ = ref.fuse(base, rows, jnp.asarray(w), 1.0, prec=prec)
        return fused, sq

    def _reference(self, base0_flat, r: int, prec: str):
        prev = base0_flat
        if r > 0:
            prev, _ = self._screened(base0_flat, self.rows_of(r - 1), "f32")
        return self._screened(prev, self.rows_of(r), prec)

    def readings(self, mode: str = "program") -> Dict[str, float]:
        """The numbers compared for the sampled rounds.  ``mode="control"``
        puts the reference computed in float8 in the program's place."""
        base0 = self._base0()
        base0_flat = _flat(base0)
        names = list(named_leaves(base0))
        shapes = [x.shape for x in jax.tree.leaves(base0)]
        del base0
        out = {"base_steps": 0.0, "sq_rel": 0.0, "screen_bad": 0.0}
        if self.npz_checked:
            out["npz_steps"] = 0.0
        for rec in self.sampled.sample():
            want, sq = self._reference(base0_flat, rec["round"], "f32")
            sq = np.asarray(sq, np.float64)
            if mode == "control":
                got, gsq = self._reference(base0_flat, rec["round"], "fp8")
                gsq = np.asarray(gsq, np.float64)
                accepted = len(ref.screen(np.sqrt(gsq), self.mad))
                npz = np.asarray(got)
                n_contrib = self.k
            else:
                got = _flat(rec["base"])
                gsq = np.square(np.asarray(rec["diff_norms"], np.float64))
                accepted, n_contrib = rec["n_accepted"], rec["n_contributions"]
                npz = (read_npz_row(rec["npz"], names, shapes)
                       if self.npz_checked else None)
            out["base_steps"] = max(out["base_steps"],
                                    float(max_bf16_steps(got, want)))
            if self.npz_checked:
                out["npz_steps"] = max(out["npz_steps"], float("inf") if npz is None
                                       else float(max_bf16_steps(jnp.asarray(npz), want)))
            if gsq.shape != sq.shape:
                out["sq_rel"] = float("inf")
            else:
                rel = np.max(np.abs(gsq - sq) / np.maximum(sq, 1e-30))
                out["sq_rel"] = max(out["sq_rel"], float(rel) if np.isfinite(rel)
                                    else float("inf"))
            want_acc = len(ref.screen(np.sqrt(sq), self.mad))
            out["screen_bad"] += float(accepted != want_acc) + float(n_contrib != self.k)
        return out

    def check(self, mode: str = "program"):
        return checks_from(self.t["limits"], self.readings(mode))

    def close(self):
        pass


class InProcess(_FuseDriver):
    """``Repository(base, screen=True)`` with no root: each round uploads
    one of two cohorts of ``contributors`` seeded pytrees, made once in
    set-up, in turn (so consecutive cohorts differ and each row is as far
    from the previous base as the others), and calls ``fuse_pending()``."""

    npz_checked = False

    def setup(self):
        from repro.core.repository import Repository

        self.mad = 5.0
        base0 = self._base0()
        self.pool = make_trees(base0, jax.random.fold_in(self.key, _ROWS),
                               2 * self.k)
        jax.block_until_ready(self.pool)
        self.repo = Repository(base0, screen=True, mad_threshold=self.mad)
        del base0
        for _ in range(int(self.t["warmup_rounds"])):
            self._round(timed=False)
        jax.block_until_ready(self.repo.download())

    def members(self, r: int) -> List[int]:
        return list(range((r % 2) * self.k, (r % 2 + 1) * self.k))

    def rows_of(self, r: int):
        return jnp.stack([_flat(self.pool[p]) for p in self.members(r)])

    def _round(self, timed: bool = True):
        r = self.round
        t0 = time.perf_counter()
        with self.spans.span("upload"):
            for p in self.members(r):
                self.repo.upload(self.pool[p])
        with self.spans.span("fuse_pending"):
            rec = self.repo.fuse_pending()
        dt = time.perf_counter() - t0
        self.round += 1
        if timed:
            self.rounds.append({"s": dt})
            self.sampled.offer({"round": r, "base": self.repo.download(),
                                "diff_norms": list(rec.diff_norms),
                                "n_accepted": rec.n_accepted,
                                "n_contributions": rec.n_contributions})
        return dt

    def window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._round()
        # every round's work has ended, the last publish included
        jax.block_until_ready(self.repo.download())
        total = time.perf_counter() - t0
        times = [x["s"] for x in self.rounds]
        times[-1] += total - sum(times)
        n = len(times)
        return Window(
            attempted=n, failed=0,
            values={"fuse_round_ms": 1e3 * total / n,
                    "fuse_round_p95_ms": 1e3 * float(np.percentile(times, 95))},
            counters={"rounds": n, "window_s": total, "k": self.k,
                      "n": int(sum(np.prod(x.shape) for x in
                                   jax.tree.leaves(self.pool[0])))})

    def release(self):
        self.repo = None
        gc.collect()


class Queue(_FuseDriver):
    """A spilled, screened ``Repository`` wrapped in ``ColdService`` with
    the novelty screen armed, on local disk under a temporary root.  A
    round: the generator makes ``contributors`` rows in host memory (off
    the clock); then, timed, each client submits one and the service runs
    ``run_once`` until the status shows the next iteration and its file is
    on disk."""

    npz_checked = True

    def setup(self):
        from repro.core.repository import Repository
        from repro.serve.cold_service import (AdmissionPolicy, ColdService,
                                              ContributorClient)
        from repro.utils.flat import FlatSpec

        self.mad = 5.0
        self.gen_s = 0.0
        base0 = self._base0()
        self.spec = FlatSpec.from_tree(base0)
        self.dir = tempfile.mkdtemp(prefix="bench-queue-")
        self.repo = Repository(base0, root=self.dir, spill=True, screen=True,
                               mad_threshold=self.mad)
        pol = self.t["policy"]
        self.svc = ColdService(self.repo, policy=AdmissionPolicy(
            min_cohort=int(pol["min_cohort"]),
            novelty_threshold=float(pol["novelty_threshold"])))
        self.clients = [ContributorClient(self.dir, name=f"c{i}")
                        for i in range(self.k)]
        self.base0 = base0
        for _ in range(int(self.t["warmup_rounds"])):
            self._round(timed=False)
        self.warm_rejected = self.svc.status()["rejected_total"]

    def _key(self, r: int):
        return jax.random.fold_in(jax.random.fold_in(self.key, _ROWS), r)

    def rows_of(self, r: int):
        return jnp.stack(make_rows(self._base0(), self._key(r), self.k))

    def _host_rows(self, r: int) -> List[np.ndarray]:
        t0 = time.perf_counter()
        host = [np.asarray(x) for x in make_rows(self.base0, self._key(r),
                                                  self.k)]
        self.gen_s += time.perf_counter() - t0
        return host

    def _round(self, timed: bool = True) -> Optional[float]:
        r = self.round
        rows = self._host_rows(r)
        it = self.repo.iteration
        t0 = time.perf_counter()
        for i, c in enumerate(self.clients):
            with self.spans.span("submit"):
                c.submit(row=rows[i], spec=self.spec, weight=1.0,
                         base_iteration=it)
        st = None
        for _ in range(int(self.t["max_cycles"])):
            with self.spans.span("run_once"):
                st = self.svc.run_once()
            if st["iteration"] > it:
                break
        dt = time.perf_counter() - t0
        self.round += 1
        npz = os.path.join(self.dir, f"base_iter{it + 1:04d}.npz")
        ok = (st is not None and st["iteration"] == it + 1
              and st["last_error"] is None and os.path.exists(npz))
        if not ok:
            print(f"[bench] round {r} not published: "
                  f"{None if st is None else st.get('last_error')}",
                  file=sys.stderr, flush=True)
        if timed:
            self.rounds.append({"s": dt, "ok": ok})
            if ok:
                rec = self.repo.history[-1]
                self.sampled.offer({"round": r, "base": self.repo.download(),
                                    "npz": npz,
                                    "diff_norms": list(rec.diff_norms),
                                    "n_accepted": rec.n_accepted,
                                    "n_contributions": rec.n_contributions})
        return dt

    def window(self, seconds: float) -> Window:
        total = 0.0
        while total < seconds:
            total += self._round()
        n = len(self.rounds)
        failed = sum(not x["ok"] for x in self.rounds)
        print(f"[bench] generator: {self.gen_s:.3f} s making rows off the "
              f"clock ({self.round} rounds)", file=sys.stderr, flush=True)
        self.rejected = self.svc.status()["rejected_total"] - self.warm_rejected
        return Window(
            attempted=n, failed=failed,
            values={"publish_s": total / n},
            counters={"rounds": n, "window_s": total, "k": self.k,
                      "n": self.spec.size, "generator_s": self.gen_s})

    def readings(self, mode: str = "program") -> Dict[str, float]:
        out = super().readings(mode)
        # every submitted row is distinct, so the reference rejects none
        out["rejected"] = 0.0 if mode == "control" else float(self.rejected)
        return out

    def release(self):
        self.svc = self.repo = self.clients = self.base0 = None
        gc.collect()

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)
