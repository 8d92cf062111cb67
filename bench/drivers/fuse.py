"""Fuse rounds: the in-process hub (``Repository.upload`` then
``fuse_pending``) and the durable queue (``ContributorClient.submit`` then
``ColdService.run_once`` until the next base is published).

Contribution rows are the seeded base plus a seeded Gaussian perturbation
of every element (standard deviation 1/32 of the leaf's RMS, or of 0.02
where that is larger), so every round's cohort is distinct and close to the
base, as finetuned models are.  The reference fuses the same rows
(``reference.fuse``) after the window; it takes the previous base as its
own fuse of the previous round's rows.  The seeded base comes from the
reference module the configuration file names.

On more than one chip the in-process hub is ``Repository(mesh=)`` over a
one-axis mesh of the cell's chips, and the base the benchmark makes is
split over that mesh, each leaf along its last axis that the chip count
divides.  Where the traffic says ``"arrive": "host"``, contributions are
numpy pytrees in host memory, as a hub receives them off the network;
otherwise they are made on, and stay on, the device.
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
from . import Reservoir, Window, checks_from, reference_module

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


def make_trees(base, key, k: int, host: bool = False):
    """``k`` perturbed copies of ``base``, each made on the device; with
    ``host``, each is read into host memory before the next is made."""
    get = jax.device_get if host else (lambda t: t)
    return [get(perturbed(base, jax.random.fold_in(key, i)))
            for i in range(k)]


def leaf_sharding(mesh, shape):
    """A leaf split over the mesh's one axis along its last axis that the
    axis's size divides (replicated where none does)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    (axis,), n = mesh.axis_names, mesh.devices.size
    spec = [None] * len(shape)
    for i in reversed(range(len(shape))):
        if shape[i] % n == 0:
            spec[i] = axis
            break
    return NamedSharding(mesh, P(*spec))


def make_rows(base, key, k: int):
    return [perturbed_row(base, jax.random.fold_in(key, i)) for i in range(k)]


@jax.jit
def _flat(tree):
    return flat(tree)


def _sq_rel(got: np.ndarray, want: np.ndarray) -> float:
    """The largest gap between two rounds' squared distances, relative to
    the reference's (``inf`` where the counts differ or a gap is not
    finite)."""
    if got.shape != want.shape:
        return float("inf")
    rel = np.max(np.abs(got - want) / np.maximum(want, 1e-30))
    return float(rel) if np.isfinite(rel) else float("inf")


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

    def __init__(self, conf, traffic, seed: int, spans, devices=None):
        self.conf, self.t, self.seed, self.spans = conf, traffic, seed, spans
        self.k = int(traffic["contributors"])
        self.ref = reference_module(conf)
        self.key = seed_key(seed)
        self.sampled = Reservoir(int(traffic["check_rounds"]), seed)
        self.round = 0
        self.rounds: List[Dict] = []
        self.mesh = None
        if devices is not None and len(devices) > 1:
            from repro.launch.mesh import make_mesh

            self.mesh = make_mesh((len(devices),), ("model",),
                                  devices=devices)

    def _init(self, key):
        return self.ref.init_body(key, self.conf["as_run"])

    def _base0(self):
        key = jax.random.fold_in(self.key, _PARAMS)
        if self.mesh is None:
            return self._init(key)
        # made in place: one jitted call whose leaves come out split
        out = jax.tree.map(lambda x: leaf_sharding(self.mesh, x.shape),
                           jax.eval_shape(self._init, key))
        return jax.jit(self._init, out_shardings=out)(key)

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
        puts the reference computed in float8 in the program's place.  On
        a mesh, where the cohort is larger than a chip's memory as one
        array, the reference reads the contribution pytrees in blocks
        (``reference.fuse.fuse_tree``) rather than stacked ``[K, N]``
        rows."""
        if self.mesh is not None:
            return self._readings_blocked(mode)
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
            out["sq_rel"] = max(out["sq_rel"], _sq_rel(gsq, sq))
            want_acc = len(ref.screen(np.sqrt(sq), self.mad))
            out["screen_bad"] += float(accepted != want_acc) + float(n_contrib != self.k)
        return out

    def trees_of(self, r: int) -> List:
        """Round ``r``'s contribution pytrees (the reference on a mesh)."""
        raise NotImplementedError

    def _round_blocked(self, base, r: int, prec: str):
        """The reference's round ``r`` from ``base``, screened as
        ``_screened`` screens: (fused pytree, sq [K], accepted indices)."""
        rows = self.trees_of(r)
        w = np.ones((self.k,), np.float32)
        fused, sq = ref.fuse_tree(base, rows, w, 1.0, prec=prec)
        keep = ref.screen(np.sqrt(sq), self.mad)
        if len(keep) < self.k:
            w[:] = 0.0
            w[keep] = 1.0
            fused, _ = ref.fuse_tree(base, rows, w, 1.0, prec=prec)
        return fused, sq, keep

    def _readings_blocked(self, mode: str) -> Dict[str, float]:
        base0 = self._base0()
        out = {"base_steps": 0.0, "sq_rel": 0.0, "screen_bad": 0.0}
        for rec in self.sampled.sample():
            r = rec["round"]
            prev = (base0 if r == 0 else
                    self._round_blocked(base0, r - 1, "f32")[0])
            want, sq, keep = self._round_blocked(prev, r, "f32")
            if mode == "control":
                got, gsq, gkeep = self._round_blocked(prev, r, "fp8")
                accepted, n_contrib = len(gkeep), self.k
            else:
                got = rec["base"]
                gsq = np.square(np.asarray(rec["diff_norms"], np.float64))
                accepted = rec["n_accepted"]
                n_contrib = rec["n_contributions"]
            del prev
            g, w = jax.tree.flatten(got), jax.tree.flatten(want)
            steps = (float("inf") if g[1] != w[1] else max(
                float(max_bf16_steps(a, b)) for a, b in zip(g[0], w[0])))
            del got, want, g, w
            out["base_steps"] = max(out["base_steps"], steps)
            out["sq_rel"] = max(out["sq_rel"], _sq_rel(gsq, sq))
            out["screen_bad"] += (float(accepted != len(keep))
                                  + float(n_contrib != self.k))
        return out

    def check(self, mode: str = "program"):
        return checks_from(self.t["limits"], self.readings(mode))

    def close(self):
        pass


class InProcess(_FuseDriver):
    """``Repository(base, screen=True)`` with no root (with ``mesh=`` over
    the cell's chips where it has more than one).  Set-up makes a pool of
    ``pool`` seeded pytrees (default two cohorts, ``2 * contributors``),
    in host memory where the traffic's ``arrive`` is ``host``;
    round ``r`` uploads the ``contributors`` trees from index
    ``r * stride`` on, modulo the pool (default stride ``contributors``:
    the two cohorts in turn), and calls ``fuse_pending()``.  So
    consecutive cohorts differ, and each row is as far from the previous
    base as the others."""

    npz_checked = False

    def setup(self):
        from repro.core.repository import Repository

        self.mad = 5.0
        self.pool_size = int(self.t.get("pool", 2 * self.k))
        self.stride = int(self.t.get("stride", self.k))
        base0 = self._base0()
        self.pool = make_trees(base0, jax.random.fold_in(self.key, _ROWS),
                               self.pool_size,
                               host=self.t.get("arrive") == "host")
        jax.block_until_ready(self.pool)
        self.repo = Repository(base0, screen=True, mad_threshold=self.mad,
                               mesh=self.mesh)
        del base0
        for _ in range(int(self.t["warmup_rounds"])):
            self._round(timed=False)
        base = self.repo.download()
        if self.mesh is not None:
            # made and run once here, so that keeping a sampled base in
            # the window compiles nothing
            self._split = jax.jit(lambda t: t, out_shardings=jax.tree.map(
                lambda x: leaf_sharding(self.mesh, x.shape), base))
            base = self._split(base)
        jax.block_until_ready(base)

    def _kept(self, tree):
        """A sampled base as the benchmark keeps it for the comparison
        after the window.  On a mesh the published base is whole on every
        chip, so it is split like the trees, on the chips: a
        ``jax.device_put`` to the split layout would read it to the host."""
        return tree if self.mesh is None else self._split(tree)

    def members(self, r: int) -> List[int]:
        return [(r * self.stride + i) % self.pool_size
                for i in range(self.k)]

    def rows_of(self, r: int):
        return jnp.stack([_flat(self.pool[p]) for p in self.members(r)])

    def trees_of(self, r: int) -> List:
        return [self.pool[p] for p in self.members(r)]

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
            item = {"round": r, "base": self.repo.download(),
                    "diff_norms": list(rec.diff_norms),
                    "n_accepted": rec.n_accepted,
                    "n_contributions": rec.n_contributions}
            self.sampled.offer(item)
            if any(x is item for x in self.sampled.items):
                item["base"] = self._kept(item["base"])
        return dt

    def window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._round()
        # every round's work has ended, the last publish included
        jax.block_until_ready(self.repo.download())
        total = time.perf_counter() - t0
        last = self.sampled.last
        last["base"] = self._kept(last["base"])
        times = [x["s"] for x in self.rounds]
        times[-1] += total - sum(times)
        n = len(times)
        return Window(
            attempted=n, failed=0,
            values={"fuse_round_ms": 1e3 * total / n,
                    "fuse_round_p95_ms": 1e3 * float(np.percentile(times, 95))},
            counters={"rounds": n, "window_s": total, "k": self.k,
                      "n": int(sum(np.prod(x.shape) for x in
                                   jax.tree.leaves(self.pool[0]))),
                      "chips": 1 if self.mesh is None else self.mesh.size})

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
