#!/usr/bin/env python3
"""Bring-up check on a TPU: one ColD Fusion round at RoBERTa-base width.

    python chip_smoke.py [--seed S]     # one chip (the default phase)
    python chip_smoke.py --chips 4      # the sharded fuse on a 4-chip host

One chip: the body and four private heads are built from ``--seed``
(RoBERTa-base, 123,969,792 body parameters, bf16), four ``Contributor``s
each take three finetune steps (batch 16, S=128) on synthetic tasks, and
submit through ``ContributorClient`` — two dense rows, two top-k/int8
compressed deltas — to a spill-enabled, screened ``Repository`` wrapped in
a ``ColdService`` with the novelty screen armed.  The service's own
``run_once`` publishes iteration 1.  The result is then checked against
the jnp oracles (``repro.kernels.ref``) on the same host-side inputs: the
fused base within one bf16 rounding step per element, every ``sq_diff`` to
a relative 1e-3, the kernel-made sketches against ``ref.row_sketch``, and
the published ``base_iter0001.npz`` read back bit-exactly.  The fuse,
decode and sketch must compile to Mosaic kernels (``tpu_custom_call``).

``--chips 4`` runs only the sharded path: the same kind of cohort (two
rounds: four dense rows, then two dense and two compressed; the rows are
seeded perturbations of the base, no finetuning) fused by
``Repository(mesh=<4-device mesh>)``, compared with the single-device
fuse of the same inputs, the sharded sketch compared with the
single-device sketch, and exactly one all-reduce in each sharded program
the rounds ran (recorded as they ran, then read back compiled).

Phase wall times are printed for information, and a line announces each
phase before it starts.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero without it.  A run still going after ``--deadline``
seconds dumps every thread's Python stack to standard error and exits 1.
Without a TPU (for example with ``JAX_PLATFORMS=cpu``), with
``REPRO_NO_KERNELS=1``, or outside a checkout of the repository, the
script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
STEPS, BATCH, SEQ = 3, 16, 128
N_CONTRIB = 4
K_PER_BLOCK = 64          # ContributorClient.submit's default top-k
SKETCH_RTOL = 1e-4
SQ_RTOL = 1e-3


class SmokeFailure(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Phases:
    """Wall time per phase (information only: host clock, compile included)."""

    def __init__(self):
        self.times = {}

    def run(self, name, fn, *a, **kw):
        print(f"[chip_smoke] phase {name} ...", flush=True)
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        self.times[name] = time.perf_counter() - t0
        print(f"[chip_smoke] phase {name}: {self.times[name]:.3f} s", flush=True)
        return out


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def bf16_steps(got, want):
    """Per-element |got − want| in units of one bf16 rounding step at the
    larger magnitude (both arrays are bf16 rows)."""
    import numpy as np

    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    step = np.spacing(np.maximum(np.abs(g), np.abs(w))) * np.float32(2 ** 16)
    return np.abs(g - w) / step


def check_close(name, got, want, rtol):
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    worst = float(np.max(err)) if err.size else 0.0
    print(f"[chip_smoke] {name}: max relative error {worst:.3e} "
          f"(limit {rtol:g})", flush=True)
    require(got.shape == want.shape and np.isfinite(got).all()
            and worst <= rtol, f"{name}: {got} vs oracle {want}")


def check_sketch(name, got, row):
    """A kernel-made sketch against ``ref.row_sketch`` of the same row.  The
    projection row is a signed sum, so its error is bounded relative to the
    bucket's sum of magnitudes."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref

    nb = np.asarray(got).shape[1]
    want = np.asarray(ref.row_sketch(row, nb), np.float64)
    scale = np.asarray(ref.row_sketch(jnp.abs(row.astype(jnp.float32)), nb),
                       np.float64)[0]
    err = np.abs(np.asarray(got, np.float64) - want)
    worst = float(np.max(err / (np.stack([scale, want[1]]) + 1e-30)))
    print(f"[chip_smoke] {name}: max error {worst:.3e} of the bucket "
          f"magnitude (limit {SKETCH_RTOL:g})", flush=True)
    require(np.isfinite(got).all() and worst <= SKETCH_RTOL,
            f"{name} disagrees with ref.row_sketch")


def mosaic_kernels(compiled_text: str):
    """Names of the Pallas kernels compiled to Mosaic custom calls."""
    return {m.group(1) for m in re.finditer(
        r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text)}


def perturbed(body, seed: int, scale: float = 1e-3):
    """A seeded stand-in for a finetuned body: base + N(0, scale²) per leaf."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(body)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [(x.astype(jnp.float32)
            + scale * jax.random.normal(k, x.shape, jnp.float32)).astype(x.dtype)
           for x, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def open_service(body, root, mesh=None):
    from repro.core.repository import Repository
    from repro.serve.cold_service import AdmissionPolicy, ColdService

    repo = Repository(body, root=root, spill=True, screen=True, mesh=mesh)
    # threshold 0: the screen rejects exact replays only, and it sketches
    # every admitted dense row on the device
    policy = AdmissionPolicy(min_cohort=N_CONTRIB, novelty_threshold=0.0)
    return repo, ColdService(repo, policy=policy)


def submit_cohort(root, rows, base_body, iteration, compressed, sspec=None):
    """Submit one contribution per body; ``compressed`` marks the positions
    that go in as top-k/int8 deltas against ``base_body``."""
    from repro.serve.cold_service import ContributorClient

    for i, body in enumerate(rows):
        kw = dict(weight=1.0, base_iteration=iteration, sketch=False,
                  sspec=sspec)
        if i in compressed:
            kw.update(compress=True, base=base_body, k_per_block=K_PER_BLOCK)
        ContributorClient(root, name=f"c{i}").submit(body, **kw)


def drive_round(svc, target: int, max_cycles: int = 50):
    for _ in range(max_cycles):
        st = svc.run_once()
        if st["iteration"] >= target:
            require(st["last_error"] is None, f"service error: {st['last_error']}")
            return st
    raise SmokeFailure(f"iteration {target} not published in {max_cycles} "
                       f"cycles: {st}")


# ---------------------------------------------------------------------------
# one chip: finetune -> submit -> fuse -> publish, checked against oracles
# ---------------------------------------------------------------------------


def finetune_cohort(cfg, body, seed: int):
    """Four contributors, one synthetic task each (tasks sharing a class
    count, so the train step compiles once), three steps each."""
    import numpy as np

    from repro.core.contributor import Contributor
    from repro.data.synthetic import SyntheticSuite

    suite = SyntheticSuite(seed=seed)
    counts = [t.num_classes for t in suite.tasks]
    nc = max(set(counts), key=counts.count)
    tasks = [t.task_id for t in suite.tasks if t.num_classes == nc][:N_CONTRIB]
    require(len(tasks) == N_CONTRIB, "synthetic suite has too few tasks")
    bodies = []
    for i, t in enumerate(tasks):
        d = suite.dataset(t, STEPS * BATCH, BATCH, SEQ, split_seed=seed)
        x = np.clip(d["x_train"], 0, cfg.vocab_size - 1)
        c = Contributor(cfg, task_id=t, num_classes=nc, x=x, y=d["y_train"],
                        steps=STEPS, batch_size=BATCH, seed=seed + i)
        bodies.append(c.contribute(body))
        losses = c.last_metrics["loss"]
        print(f"[chip_smoke] contributor {i} (task {t}, {nc} classes) "
              f"losses {losses}", flush=True)
        require(len(losses) == STEPS and np.isfinite(losses).all(),
                f"contributor {i}: non-finite finetune loss {losses}")
    return bodies


def one_chip(cfg, seed: int, phases: Phases):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import io as ckpt
    from repro.core.validation import norms_from_sq, screen_norms
    from repro.kernels import ref
    from repro.models import encoder as E
    from repro.utils.flat import FlatSpec, delta_encode

    body = phases.run("init", lambda: jax.block_until_ready(
        E.init_encoder_body(cfg, jax.random.PRNGKey(seed))))
    spec = FlatSpec.from_tree(body)
    print(f"[chip_smoke] {cfg.name}: {spec.size:,} body parameters "
          f"({spec.dtype})", flush=True)
    bodies = phases.run("finetune", finetune_cohort, cfg, body, seed)
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        repo, svc = phases.run("open", open_service, body, root)
        compressed = (2, 3)
        phases.run("submit", submit_cohort, root, bodies, body, 0, compressed)
        phases.run("round", drive_round, svc, 1)
        rec = repo.history[-1]
        require(rec.n_contributions == N_CONTRIB,
                f"fused {rec.n_contributions} contributions, want {N_CONTRIB}")
        published = repo.flat_base_host()

        def oracle():
            base_row = np.asarray(spec.flatten(body))
            rows = [np.asarray(spec.flatten(b)) for b in bodies]
            base_dev = jnp.asarray(base_row)
            full = []
            sq_comp = {}
            pays = {}
            for i, row in enumerate(rows):
                if i not in compressed:
                    full.append(jnp.asarray(row).astype(jnp.float32))
                    continue
                p = delta_encode(row, base_row, k_per_block=K_PER_BLOCK)
                pays[i] = p
                idx = jnp.asarray(p.indices, jnp.int32)[None]
                dv = jnp.asarray(p.values.astype(np.float32)
                                 * p.scales[:, None])[None]
                d, sq = jax.jit(ref.decode_accum, static_argnames=(
                    "size", "block"))(idx, dv, jnp.ones((1,)),
                                      size=spec.size, block=p.block)
                sq_comp[i] = float(sq[0])
                full.append(base_dev.astype(jnp.float32) + d)
            stack = jnp.stack(full)
            _, sq_all = jax.jit(ref.cold_fuse)(base_dev, stack,
                                               jnp.ones((N_CONTRIB,)))
            sq = np.asarray(sq_all, np.float64)
            for i, v in sq_comp.items():
                sq[i] = v  # Σ dv², exact: no (base + Δ) − base rounding
            report = screen_norms(norms_from_sq(sq),
                                  mad_threshold=repo.mad_threshold)
            w = np.zeros((N_CONTRIB,), np.float32)
            w[report.accepted] = 1.0
            fused, _ = jax.jit(ref.cold_fuse)(base_dev, stack, jnp.asarray(w))
            return (base_dev, rows, pays, stack, w, sq_all, sq, report,
                    np.asarray(fused))

        (base_dev, rows, pays, stack, w, sq_all, sq, report,
         want) = phases.run("oracle", oracle)
        require(rec.n_accepted == len(report.accepted),
                f"repository accepted {rec.n_accepted}, oracle screen "
                f"{len(report.accepted)}")
        check_close("sq_diff", np.square(rec.diff_norms), sq, SQ_RTOL)
        steps = bf16_steps(published, want)
        print(f"[chip_smoke] fused base: max {float(steps.max()):.3f} bf16 "
              f"steps from the oracle over {steps.size:,} elements "
              f"({int(np.count_nonzero(steps))} differ)", flush=True)
        require(bool(np.all(steps <= 1.0)),
                "fused base is more than one bf16 step from ref.cold_fuse")
        on_disk = ckpt.load(os.path.join(root, "base_iter0001.npz"),
                            as_jax=False)
        require(np.array_equal(np.asarray(spec.flatten(on_disk)).view(np.uint16),
                               published.view(np.uint16)),
                "base_iter0001.npz differs from the in-memory base")
        sk = repo.cohort_sketch
        check_sketch("sketch of the published base", sk.base,
                     jnp.asarray(published))
        check_sketch("sketch of the initial base", sk.base_at(0), base_dev)
        entries = {e[0]: e[2] for e in sk.entries}
        for i in range(N_CONTRIB):
            if i not in compressed:
                check_sketch(f"sketch of dense submission c{i}",
                             entries[f"c{i}-000000"], jnp.asarray(rows[i]))
        phases.run("kernel", check_dense_kernel, base_dev, stack, w,
                   want, sq_all)
        del stack
        phases.run("mosaic", check_mosaic_one_chip, base_dev, rows, pays,
                   compressed)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def check_dense_kernel(base_dev, stack, w, want, sq_want):
    """The dense fuse kernel (``ops.fuse_flat``) on the cohort's four full
    rows, decoded deltas included, against ``ref.cold_fuse`` at full
    width: the mixed round above runs only the decode kernel."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    fused, sq = ops.fuse_flat(base_dev, stack, jnp.asarray(w))
    steps = bf16_steps(np.asarray(fused), want)
    print(f"[chip_smoke] cold_fuse kernel: max {float(steps.max()):.3f} bf16 "
          f"steps from the oracle over {steps.size:,} elements "
          f"({int(np.count_nonzero(steps))} differ)", flush=True)
    require(bool(np.all(steps <= 1.0)),
            "cold_fuse kernel is more than one bf16 step from ref.cold_fuse")
    check_close("cold_fuse kernel sq_diff", sq, sq_want, SQ_RTOL)


def check_mosaic_one_chip(base_dev, rows, pays, compressed):
    """The dense fuse, the round's mixed fuse (dense rows + compressed
    deltas) and the sketch compile to Mosaic kernels at the round's
    shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    comp = [pays[i] for i in compressed]
    dense = jnp.stack([jnp.asarray(r) for i, r in enumerate(rows)
                       if i not in compressed])
    wd = jnp.ones((dense.shape[0],))
    args = (base_dev,
            jnp.asarray(np.stack([p.indices for p in comp])),
            jnp.asarray(np.stack([p.values for p in comp])),
            jnp.asarray(np.stack([p.scales for p in comp])),
            jnp.ones((len(comp),)), jnp.float32(1.0), dense, wd)

    def mixed(base, idx, val, scl, wc, alpha, dense, wd):
        return ops.fuse_flat_compressed(base, idx, val, scl, wc, alpha,
                                        block=comp[0].block, dense=dense,
                                        dense_weights=wd)

    def kernels(fn, *a):
        return mosaic_kernels(jax.jit(fn).lower(*a).compile().as_text())

    got = {"dense fuse": kernels(ops.fuse_flat, base_dev, dense, wd),
           "mixed fuse": kernels(mixed, *args),
           "sketch": kernels(ops.row_sketch, base_dev)}
    print("[chip_smoke] Mosaic kernels: " + ", ".join(
        f"{k} {sorted(v)}" for k, v in got.items()), flush=True)
    for name, kernel in (("dense fuse", "_cold_fuse_impl"),
                         ("mixed fuse", "_decode_accum_impl"),
                         ("sketch", "_row_sketch_impl")):
        require(kernel in got[name],
                f"{name} did not compile to a Mosaic kernel: "
                f"{sorted(got[name])}")


# ---------------------------------------------------------------------------
# four chips: Repository(mesh=) vs the single-device fuse, one all-reduce
# ---------------------------------------------------------------------------


class ShardedPrograms:
    """Records every sharded program the repository runs: ``ops`` builds
    each one once per layout (``_sharded_fuse_fn``, ``_compressed_sharded_fn``,
    ``_sharded_sketch_fn``), and while this context is open each call
    through them also notes the jitted function with the shapes, dtypes
    and shardings of the operands it was called with.  ``lower`` then
    reads those very programs back compiled, without staging anything."""

    BUILDERS = {"_sharded_fuse_fn": "dense fuse",
                "_compressed_sharded_fn": "compressed fuse",
                "_sharded_sketch_fn": "sketch"}

    def __init__(self):
        self.seen = {}  # (kind, builder args, operand types) -> (fn, avals)

    def __enter__(self):
        from repro.kernels import ops

        self._saved = {name: getattr(ops, name) for name in self.BUILDERS}
        for name, build in self._saved.items():
            setattr(ops, name, self._recording(self.BUILDERS[name], build))
        return self

    def __exit__(self, *exc):
        from repro.kernels import ops

        for name, build in self._saved.items():
            setattr(ops, name, build)

    def _recording(self, kind, build):
        import jax

        def built(*key):
            fn = build(*key)

            def call(*args):
                # uncommitted operands keep no sharding: jit places them
                avals = tuple(jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None)
                    for x in args)
                sig = (kind, key, tuple((a.shape, str(a.dtype), str(a.sharding))
                                        for a in avals))
                self.seen.setdefault(sig, (fn, avals))
                return fn(*args)

            return call

        return built

    def compiled(self):
        """(kind, compiled HLO text) of every distinct program recorded."""
        for (kind, _, _), (fn, avals) in self.seen.items():
            yield kind, fn.lower(*avals).compile().as_text()


def four_chips(cfg, seed: int, phases: Phases, devices):
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import encoder as E
    from repro.utils.flat import FlatSpec, ShardedFlatSpec

    mesh = make_mesh((len(devices),), ("model",), devices=devices)
    body = phases.run("init", lambda: jax.block_until_ready(
        E.init_encoder_body(cfg, jax.random.PRNGKey(seed))))
    spec = FlatSpec.from_tree(body)
    sspec = ShardedFlatSpec.from_spec(spec, len(devices))
    one = jax.sharding.SingleDeviceSharding(devices[0])
    root = tempfile.mkdtemp(prefix="chip_smoke_mesh_")
    try:
        with ShardedPrograms() as programs:
            repo, svc = phases.run("open", open_service, body, root, mesh)
            for rnd, compressed in ((1, ()), (2, (2, 3))):
                base = repo.download()
                base_row = repo.flat_base_host()
                rows = [perturbed(base, seed * 100 + rnd * 10 + i)
                        for i in range(N_CONTRIB)]
                phases.run(f"submit{rnd}", submit_cohort, root, rows, base,
                           rnd - 1, compressed, sspec)
                phases.run(f"round{rnd}", drive_round, svc, rnd)
                rec = repo.history[-1]
                require(rec.n_accepted == N_CONTRIB,
                        f"round {rnd}: screen rejected "
                        f"{N_CONTRIB - rec.n_accepted} seeded rows")
                want, sq = phases.run(f"single{rnd}", single_device_fuse,
                                      spec, rows, base_row, compressed, one)
                steps = bf16_steps(repo.flat_base_host(), want)
                print(f"[chip_smoke] round {rnd} sharded vs single-device "
                      f"fuse: max {float(steps.max()):.3f} bf16 steps "
                      f"({int(np.count_nonzero(steps))} of {steps.size:,} "
                      f"differ)", flush=True)
                require(bool(np.all(steps <= 1.0)),
                        f"round {rnd}: sharded fuse disagrees with one device")
                check_close(f"round {rnd} sq_diff (sharded vs single-device)",
                            np.square(rec.diff_norms), sq, SQ_RTOL)
        # the repository sketched its published base with the sharded
        # sketch (one psum); the single-device kernel must agree
        print("[chip_smoke] single-device sketch of the published base ...",
              flush=True)
        row = jax.device_put(repo.flat_base_host(), one)
        single = np.asarray(ops.row_sketch(row))
        sharded = repo.cohort_sketch.base
        check_close("sharded vs single-device sketch (sq row)", sharded[1],
                    single[1], SKETCH_RTOL)
        check_sketch("sharded sketch", sharded, row)
        phases.run("collectives", check_one_all_reduce, programs)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def single_device_fuse(spec, rows, base_row, compressed, one):
    """The single-device fuse of one round's inputs on the first chip:
    returns (fused row on the host, sq_diff in cohort order)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.utils.flat import delta_encode

    flat = [np.asarray(spec.flatten(r)) for r in rows]
    b1 = jax.device_put(base_row, one)
    dense = jax.device_put(np.stack(
        [f for i, f in enumerate(flat) if i not in compressed]), one)
    w = jnp.ones((N_CONTRIB,), jnp.float32)
    if not compressed:
        want, sq = ops.fuse_flat(b1, dense, w, 1.0)
        return np.asarray(want), np.asarray(sq)
    pays = [delta_encode(flat[i], base_row, k_per_block=K_PER_BLOCK)
            for i in compressed]
    want, sq = ops.fuse_flat_compressed(
        b1, *[jax.device_put(np.stack([getattr(p, a) for p in pays]), one)
              for a in ("indices", "values", "scales")],
        w[:len(pays)], 1.0, block=pays[0].block, dense=dense,
        dense_weights=w[:dense.shape[0]])
    # sq comes back (dense..., compressed...): cohort order here
    order = [i for i in range(N_CONTRIB) if i not in compressed]
    return (np.asarray(want),
            np.asarray(sq)[np.argsort(order + list(compressed))])


def check_one_all_reduce(programs: ShardedPrograms):
    """Exactly one all-reduce in each sharded program the rounds ran: the
    dense fuse (round 1), the compressed fuse (round 2, mixed) and the
    sketch."""
    from repro.utils.hlo import collect_collectives

    kinds = set()
    for kind, text in programs.compiled():
        stats = collect_collectives(text)
        print(f"[chip_smoke] {kind}: collectives {stats.count_by_kind}, "
              f"Mosaic kernels {sorted(mosaic_kernels(text))}", flush=True)
        require(stats.count_by_kind == {"all-reduce": 1},
                f"{kind}: want exactly one all-reduce, got "
                f"{stats.count_by_kind}")
        kinds.add(kind)
    require(kinds == set(ShardedPrograms.BUILDERS.values()),
            f"the rounds ran sharded programs {sorted(kinds)}, want "
            f"{sorted(ShardedPrograms.BUILDERS.values())}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the ColD round on one chip; 4: only the "
                         "sharded fuse on a 4-chip mesh")
    ap.add_argument("--deadline", type=float, default=1100.0,
                    help="seconds after which a run still going dumps every "
                         "thread's stack and exits 1")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(args.deadline, exit=True)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    import importlib.metadata

    import jax

    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "device_count": len(devices), "jax": jax.__version__,
                      "libtpu": libtpu}), flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.roberta_base import CONFIG
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    if not ops.kernels_enabled():
        print("chip_smoke: REPRO_NO_KERNELS disables the Pallas kernels",
              file=sys.stderr)
        return 2
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}", flush=True)
    phases = Phases()
    try:
        if args.chips == 4:
            four_chips(CONFIG, args.seed, phases, devices[:4])
        else:
            one_chip(CONFIG, args.seed, phases)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(f"[chip_smoke] phase times (s, information only): "
          f"{json.dumps({k: round(v, 3) for k, v in phases.times.items()})}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
