"""End-to-end Repository fuse benchmark — the ColD Fusion hot path.

Compares, for K=8 contributions of a ~1M-param model (non-block-aligned
leaf shapes, ~58 leaves), upload -> screen -> fuse -> publish wall time on:

* **seed per-leaf path** (``REPRO_NO_KERNELS`` oracle): ``upload`` keeps K
  live pytrees, ``screen_contributions`` re-reads every contribution for
  its diff norm, ``fusion.average`` re-reads everything again leaf by leaf
  — 3+ passes over the data and O(K x leaves) tiny device ops.
* **streaming flat engine**: ``upload`` folds each contribution into a flat
  staging row, ``fuse_pending`` issues ONE kernel launch that returns the
  fused model and the screening statistics together.

A third row covers the **mesh-sharded engine** (docs/sharding.md): the same
upload -> screen -> fuse -> publish flow with the staging buffer laid out
block-cyclically over a forced 8-device CPU mesh
(``--xla_force_host_platform_device_count``).  Because the fake devices
share one physical CPU this measures the sharding *overhead* (layout,
shard_map dispatch, the one all-reduce), not a speedup — the number to
watch is that overhead staying small relative to the fuse itself.  Run
directly with ``python -m benchmarks.fuse_e2e --mesh 8``; ``run()`` spawns
that subprocess automatically (device count must be fixed before jax
initializes) and the rows land in BENCH_kernels.json.

A fourth row measures the **async double-buffered repository**
(docs/async_repository.md): R rounds of K uploads each, synchronous
(``fuse_pending(wait=True)`` — every round blocks on its fuse) vs
double-buffered (``wait=False`` — the device fuses cohort i while the host
stages cohort i+1).  The overlap ratio is hardware-dependent: the upload
staging is host memcpy and the fuse is device streaming, so on a machine
with spare cores/bandwidth the async path approaches
``(upload + fuse) / max(upload, fuse)``; on a narrow container the two
contend and the ratio compresses toward 1.  Run directly with
``python -m benchmarks.fuse_e2e --async``.

The speedup is recorded in BENCH_kernels.json (benchmarks/run.py) so every
future PR inherits the perf trajectory.
"""
import argparse
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks import common as C
from repro.core.repository import Repository
from repro.kernels import ops
from repro.launch.mesh import make_mesh

K = 8
D = 100           # deliberately not a multiple of 8*128
N_BLOCKS = 8


def _model(key):
    """~1M params over ~58 non-aligned leaves (a small transformer's shape
    census, without the model code)."""
    ks = jax.random.split(key, 2 + N_BLOCKS)
    tree = {"embed": jax.random.normal(ks[0], (397, D), jnp.float32) * 0.02,
            "final_norm": jnp.ones((D,), jnp.float32), "blocks": {}}
    for b in range(N_BLOCKS):
        kb = jax.random.split(ks[2 + b], 6)
        tree["blocks"][f"b{b:02d}"] = {
            "wq": jax.random.normal(kb[0], (D, D)) * 0.02,
            "wk": jax.random.normal(kb[1], (D, D)) * 0.02,
            "wv": jax.random.normal(kb[2], (D, D)) * 0.02,
            "wo": jax.random.normal(kb[3], (D, D)) * 0.02,
            "w_up": jax.random.normal(kb[4], (D, 399)) * 0.02,
            "w_down": jax.random.normal(kb[5], (399, D)) * 0.02,
            "norm": jnp.ones((D,), jnp.float32),
        }
    return tree


def _contributions(base, k):
    out = []
    for i in range(k):
        key = jax.random.PRNGKey(1000 + i)
        out.append(jax.tree.map(
            lambda x: x + jax.random.normal(
                jax.random.fold_in(key, x.size), x.shape, jnp.float32) * 0.01,
            base))
    return out


def _run_once(base, contribs, *, flat: bool, mesh=None) -> float:
    t0 = time.time()
    repo = Repository(base, use_flat=flat if mesh is None else None, mesh=mesh)
    for c in contribs:
        repo.upload(c)
    repo.fuse_pending()
    jax.block_until_ready(jax.tree.leaves(repo.download()))
    return (time.time() - t0) * 1e6


def _best_of(base, contribs, *, flat: bool, mesh=None, reps: int = 3) -> float:
    _run_once(base, contribs, flat=flat, mesh=mesh)  # warm the jit caches
    return min(_run_once(base, contribs, flat=flat, mesh=mesh) for _ in range(reps))


ASYNC_ROUNDS = 6


def _run_rounds(base, cohorts, *, asynchronous: bool) -> float:
    """R rounds of (K uploads -> fuse): the synchronous path blocks on
    every fuse; the async path dispatches with ``wait=False`` so the device
    fuses cohort i while the host stages cohort i+1, and finalizes on the
    next round's ``fuse_pending`` (double buffering)."""
    t0 = time.time()
    repo = Repository(base, use_flat=True)
    for cohort in cohorts:
        for c in cohort:
            repo.upload(c)
        repo.fuse_pending(wait=not asynchronous)
    repo.flush()
    jax.block_until_ready(jax.tree.leaves(repo.download()))
    return (time.time() - t0) * 1e6


def _async_rows(rows: C.Rows, base, n_params: int, reps: int = 5) -> None:
    cohorts = [_contributions(base, K) for _ in range(ASYNC_ROUNDS)]
    for mode in (False, True):
        _run_rounds(base, cohorts, asynchronous=mode)  # warm the jit caches
    us_sync = min(_run_rounds(base, cohorts, asynchronous=False)
                  for _ in range(reps))
    us_async = min(_run_rounds(base, cohorts, asynchronous=True)
                   for _ in range(reps))
    overlap = us_sync / us_async
    rows.add("fuse_e2e/async_overlap", us_async,
             f"overlap={overlap:.2f}x;sync_us={us_sync:.1f};"
             f"rounds={ASYNC_ROUNDS};K={K};params={n_params}")


def run(rows: C.Rows):
    base = _model(jax.random.PRNGKey(0))
    contribs = _contributions(base, K)
    n_params = sum(x.size for x in jax.tree.leaves(base))
    n_leaves = len(jax.tree.leaves(base))

    prev = ops.kernels_enabled()
    try:
        ops.use_kernels(False)
        us_seed = _best_of(base, contribs, flat=False)
        ops.use_kernels(True)
        us_flat = _best_of(base, contribs, flat=True)
        _async_rows(rows, base, n_params)
    finally:
        ops.use_kernels(prev)

    speedup = us_seed / us_flat
    gb = (K + 2) * n_params * 4 / 1e9
    rows.add("fuse_e2e/seed_per_leaf", us_seed,
             f"K={K};params={n_params};leaves={n_leaves}")
    rows.add("fuse_e2e/flat_stream", us_flat,
             f"speedup={speedup:.2f}x;stream_GB={gb:.3f}")

    # mesh-sharded engine: the fake device count must be set before jax
    # initializes, so the measurement runs in a subprocess and its rows are
    # merged here (same CSV contract -> same BENCH_kernels.json entries)
    if jax.default_backend() == "tpu":
        print("fuse_e2e: mesh row skipped: it measures a forced host-device "
              "CPU mesh, and this process holds the TPU", file=sys.stderr)
        return
    for line in _mesh_bench_subprocess(8):
        name, us, derived = line.split(",", 2)
        rows.add(name, float(us), derived)


def _force_device_env(n_devices: int) -> dict:
    """Env with the forced host-platform device count APPENDED to any
    pre-existing XLA_FLAGS (so user tuning/determinism flags survive and
    the mesh rows are measured under the same XLA config as the rest)."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags + " " if flags else "") + \
        f"--xla_force_host_platform_device_count={n_devices}"
    return env


def _require_cpu_backend() -> None:
    """The mesh row is a CPU fake-device measurement by construction: on a
    TPU host this process holds the chip, so a JAX child would fail or
    hang reaching for it, and forced host devices are no TPU mesh."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "fuse_e2e's mesh row measures a forced host-device CPU mesh; "
            "it does not run on a TPU backend")


def _mesh_bench_subprocess(n_devices: int):
    _require_cpu_backend()
    env = _force_device_env(n_devices)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    try:
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.fuse_e2e", "--mesh", str(n_devices)],
            capture_output=True, text=True, env=env, timeout=600,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
    except subprocess.TimeoutExpired:
        return [f"fuse_e2e/mesh{n_devices}_ERROR,0.0,timeout"]
    if res.returncode != 0:
        return [f"fuse_e2e/mesh{n_devices}_ERROR,0.0,rc={res.returncode}"]
    return [l for l in res.stdout.splitlines() if l.startswith("fuse_e2e/")]


def _mesh_main(n_devices: int) -> None:
    """Entry for the subprocess: sharded vs single-device fuse on a forced
    n-device host-platform mesh.  Prints fuse_e2e/ CSV rows on stdout."""
    assert jax.device_count() == n_devices, (
        f"expected {n_devices} devices, got {jax.device_count()} — "
        "set XLA_FLAGS=--xla_force_host_platform_device_count before jax init")
    mesh = make_mesh((n_devices,), ("model",))
    base = _model(jax.random.PRNGKey(0))
    contribs = _contributions(base, K)
    n_params = sum(x.size for x in jax.tree.leaves(base))
    us_flat = _best_of(base, contribs, flat=True)
    us_mesh = _best_of(base, contribs, flat=True, mesh=mesh)
    overhead = us_mesh / us_flat
    print(f"fuse_e2e/mesh{n_devices}_sharded,{us_mesh:.1f},"
          f"K={K};params={n_params};shards={n_devices};"
          f"vs_1dev={overhead:.2f}x;collectives=1_allreduce")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="measure the sharded engine on N forced host devices "
                         "(requires XLA_FLAGS=--xla_force_host_platform_device_count=N; "
                         "set automatically when invoked via run())")
    ap.add_argument("--async", dest="asynchronous", action="store_true",
                    help="measure ONLY the async double-buffered overlap row "
                         "(sync vs wait=False over %d rounds)" % ASYNC_ROUNDS)
    args = ap.parse_args()
    rows = C.Rows()
    if args.asynchronous:
        base = _model(jax.random.PRNGKey(0))
        n_params = sum(x.size for x in jax.tree.leaves(base))
        _async_rows(rows, base, n_params)
        rows.emit()
        return
    if args.mesh:
        _require_cpu_backend()
        if (jax.device_count() != args.mesh
                and os.environ.get("_REPRO_MESH_REEXEC") != "1"):
            # direct CLI use without the flag: re-exec ONCE with it set (the
            # guard env var stops an exec loop on backends where forcing the
            # host-platform count cannot yield args.mesh devices, e.g. GPU)
            env = _force_device_env(args.mesh)
            env["_REPRO_MESH_REEXEC"] = "1"
            os.execvpe(sys.executable,
                       [sys.executable, "-m", "benchmarks.fuse_e2e",
                        "--mesh", str(args.mesh)], env)
        _mesh_main(args.mesh)
    else:
        run(rows)
        rows.emit()


if __name__ == "__main__":
    main()

