"""Fuse-to-serve load harness: concurrent inference + contribution traffic.

The paper's synergistic loop closes only when publishes reach requests:
this harness runs ONE repository with the full hot path live —

* a ``ColdService`` daemon fusing queue submissions (cohort per round),
* a ``ServingWorker`` (repro/serve/hot_swap.py) hot-swapping the engine
  onto every published base,
* N inference client threads generating continuously throughout,
* a contributor thread submitting a finetune each round and waiting for
  the worker to adopt the published result before the next round —

and then *proves* the swap seam: every request's tokens are recomputed
against the on-disk ``base_iterNNNN.npz`` of the iteration that served
it (compaction off, so every published base is retained).  A request is
**failed** if ``generate`` raised, and **version-torn** if its tokens
disagree with its served version's oracle — i.e. any part of the decode
ran against a different base than the one stamped on the result.  The
acceptance bar is zero failed and zero torn requests across >=3 live
swaps; only then does the ``serve_load/hot_swap`` row post
(us/request with swap + pinning counters in the derived column).

**Scale-out mode** (``--workers N``): the same contract, but through the
refactored serving stack — a ``WorkerPool`` of N single-process
``ServingWorker``s (each its own follower + engine + namespaced state
file) behind the least-loaded ``Router``, optionally with the
``BatchScheduler`` coalescing client requests per worker
(``--batch``).  Every routed response is tear-checked against the
oracle *at the executed batch shape* (bucketed batches tile identical
rows, and argmax ties may in principle resolve differently across XLA
batch tilings, so the oracle must replay the same ``[B, T]``).  The
``serve_load/scale_out`` row posts the workers x clients x batching
sweep: batched vs unbatched single-worker throughput, and 4-worker vs
1-worker aggregate throughput at equal client load — the 2.5x scale bar
is enforced on hosts with >= 4 CPU cores (a 1-core container cannot
scale CPU-bound work by adding processes; there the sweep instead
enforces a no-collapse floor and records the measured ratio, following
the async_overlap precedent).

``REPRO_HOST_TUNING=1`` additionally applies the host tuning recipe to
the pool children (tcmalloc preload when installed) and sweeps
``--xla_force_host_platform_device_count`` over ``--sweep-device-counts``,
recording the best setting in the row notes.

Run standalone (CI runs this at demo scale, forced 8-fake-device mesh):

  PYTHONPATH=src python -m benchmarks.serve_load --rounds 4 --clients 2
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m benchmarks.serve_load --mesh 8
  PYTHONPATH=src python -m benchmarks.serve_load --workers 2 --batch \\
      --clients 8 --rounds 3
"""
import argparse
import os
import sys
import tempfile
import threading
import time

import jax
import numpy as np

from benchmarks import common as C
from repro.checkpoint import io as ckpt
from repro.configs import get_config, reduce_config
from repro.core.repository import Repository
from repro.launch import host_tuning
from repro.launch.mesh import make_mesh
from repro.models.transformer import init_lm
from repro.serve.cold_service import AdmissionPolicy, ColdService, ContributorClient
from repro.serve.engine import Engine
from repro.serve.hot_swap import ServingWorker
from repro.serve.worker_pool import WorkerPool

PROMPT_LEN = 4
MAX_NEW = 4
MAX_LEN = 16
# the scale bar (>=2.5x aggregate throughput at 4 workers vs 1) is a
# statement about a host that can actually run 4 workers in parallel;
# below this core count the sweep enforces the no-collapse floor instead
SCALE_BAR_MIN_CORES = 4
SCALE_BAR = 2.5
SCALE_FLOOR = 0.45
BATCH_BAR = 1.5


def _wait(pred, *, timeout: float, desc: str, interval: float = 0.01):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() >= deadline:
            raise TimeoutError(f"serve_load: timed out waiting for {desc}")
        time.sleep(interval)


def harness(*, arch: str = "gemma3-1b", rounds: int = 4, clients: int = 2,
            mesh: int = 0, root: str = None, poll: float = 0.01,
            timeout: float = 300.0) -> dict:
    """Drive the loop; return stats (requests/failed/torn/swaps/...)."""
    cfg = reduce_config(get_config(arch))
    params = init_lm(cfg, jax.random.PRNGKey(0))
    kw = {}
    if mesh:
        if jax.device_count() < mesh:
            raise SystemExit(
                f"--mesh {mesh} needs {mesh} devices, have "
                f"{jax.device_count()} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={mesh})")
        kw["mesh"] = make_mesh((mesh,), ("model",))
    tmp = None
    if root is None:
        tmp = tempfile.TemporaryDirectory(prefix="serve_load_")
        root = tmp.name
    repo = Repository(params, root=root, spill=True, screen=False, **kw)
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=1))
    worker = ServingWorker(cfg, root, repo=repo, max_len=MAX_LEN)
    worker.poll_once()  # adopt iteration 0 before traffic starts

    prompts = np.arange(2, 2 + PROMPT_LEN, dtype=np.int32)[None, :]
    stop = threading.Event()
    lock = threading.Lock()
    served = []    # (iteration, tokens) per completed request
    failed = []    # exceptions out of generate()
    lat_us = []

    def infer_loop():
        # warm start included: the first request compiles the engine
        while not stop.is_set():
            try:
                r = worker.generate(prompts, max_new_tokens=MAX_NEW)
            except Exception as err:  # noqa: BLE001 - the bar is zero of these
                with lock:
                    failed.append(f"{type(err).__name__}: {err}")
                continue
            with lock:
                served.append((r.iteration, np.array(r.tokens)))
                lat_us.append(r.latency_s * 1e6)

    def service_loop():
        while not stop.is_set():
            try:
                svc.run_once()
            except Exception as err:  # noqa: BLE001
                with lock:
                    failed.append(f"service: {type(err).__name__}: {err}")
            time.sleep(poll)

    threads = [threading.Thread(target=service_loop, daemon=True)]
    threads += [threading.Thread(target=infer_loop, daemon=True)
                for _ in range(clients)]
    for t in threads:
        t.start()
    worker.start(interval=poll)

    # contributor: one finetune per round, each recycled from the previous
    # published base; the next round starts only after the worker ADOPTED
    # the publish, so every round is a live swap under open traffic
    client = ContributorClient(root, name="bench")
    t0 = time.time()
    try:
        for rnd in range(1, rounds + 1):
            prev = ckpt.load(os.path.join(root, f"base_iter{rnd-1:04d}.npz"))
            finetuned = jax.tree.map(lambda x, r=rnd: x + 0.003 * r, prev)
            client.submit(finetuned, base_iteration=rnd - 1)
            _wait(lambda r=rnd: worker.current_iteration == r,
                  timeout=timeout / rounds,
                  desc=f"worker adoption of iteration {rnd} "
                       f"(failed={failed[:3]})")
        # drain: every client sees at least one request on the final base
        n_done = len(served)
        _wait(lambda: len(served) >= n_done + clients or failed,
              timeout=30.0, desc="post-swap requests")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        wstate = worker.stop()
        svc.close()
    wall_s = time.time() - t0

    # -- tear check: recompute every served version's oracle ------------
    oracle = Engine(cfg, params, max_len=MAX_LEN)
    expected = {}
    for it in sorted({it for it, _ in served}):
        base = ckpt.load(os.path.join(root, f"base_iter{it:04d}.npz"))
        expected[it] = oracle.generate(prompts, max_new_tokens=MAX_NEW,
                                       params=base).tokens
    torn = sum(1 for it, toks in served
               if not np.array_equal(toks, expected[it]))
    stats = {
        "requests": len(served),
        "failed": len(failed),
        "failures": failed[:5],
        "torn": torn,
        "swaps_total": wstate["swaps_total"],
        "live_swaps": wstate["live_swaps"],
        "requests_pinned_across_swaps": wstate["requests_pinned_across_swaps"],
        "versions_served": wstate["versions_served"],
        "iteration": wstate["iteration"],
        "us_per_request": float(np.mean(lat_us)) if lat_us else 0.0,
        "wall_s": wall_s,
        "rounds": rounds,
        "clients": clients,
        "mesh": mesh,
    }
    if tmp is not None:
        tmp.cleanup()
    return stats


def check(stats: dict) -> None:
    """The acceptance bar: zero failed/torn requests across >=3 live
    swaps with inference traffic actually flowing the whole time."""
    assert stats["failed"] == 0, f"failed requests: {stats['failures']}"
    assert stats["torn"] == 0, f"{stats['torn']} version-torn requests"
    assert stats["live_swaps"] >= 3, f"only {stats['live_swaps']} live swaps"
    assert stats["requests"] > 0, "no inference traffic was served"
    assert stats["iteration"] == stats["rounds"], (
        f"worker ended on iteration {stats['iteration']}, "
        f"expected {stats['rounds']}")


def harness_pool(*, arch: str = "gemma3-1b", rounds: int = 3,
                 clients: int = 8, workers: int = 1, batch: bool = False,
                 poll: float = 0.01, timeout: float = 600.0,
                 root: str = None, measure_s: float = 4.0,
                 queue_depth: int = 64,
                 device_count: int = None) -> dict:
    """Scale-out harness: the daemon in-process, N worker PROCESSES
    (WorkerPool) behind the least-loaded Router, M client threads
    routing continuously while a contributor publishes each round.

    Throughput is measured over a steady-state window AFTER the last
    swap (jit warmup and adoption waits excluded — both cells of a
    ratio must measure the same regime); correctness (zero failed, zero
    torn) is asserted over the WHOLE run, swaps included.
    ``device_count`` forces ``--xla_force_host_platform_device_count``
    on the children (the host-tuning sweep's knob)."""
    cfg = reduce_config(get_config(arch))
    params = init_lm(cfg, jax.random.PRNGKey(0))
    tmp = None
    if root is None:
        tmp = tempfile.TemporaryDirectory(prefix="serve_scale_")
        root = tmp.name
    repo = Repository(params, root=root, spill=True, screen=False)
    repo.flush()   # iteration 0 durable before the children look
    svc = ColdService(repo, policy=AdmissionPolicy(min_cohort=1))
    env = {}
    if host_tuning.enabled():
        env = host_tuning.host_tuning_env(device_count=device_count)
    elif device_count is not None:
        env = {"XLA_FLAGS":
               f"--xla_force_host_platform_device_count={device_count}"}
    pool = WorkerPool(root, workers, arch=arch, engine="real",
                      max_len=MAX_LEN, poll=poll, batch=batch,
                      queue_depth=queue_depth, env=env,
                      warm=(PROMPT_LEN, MAX_NEW))
    pool.start(timeout=timeout)
    router = pool.router()

    prompt = np.arange(2, 2 + PROMPT_LEN, dtype=np.int32)
    stop = threading.Event()
    lock = threading.Lock()
    served = []    # (iteration, tokens[T+new], batch_size, t_done, lat_us)
    failed = []

    def client_loop():
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                r = router.route(prompt, max_new_tokens=MAX_NEW)
            except Exception as err:  # noqa: BLE001 - the bar is zero of these
                with lock:
                    failed.append(f"{type(err).__name__}: {err}")
                continue
            lat = (time.perf_counter() - t0) * 1e6
            with lock:
                served.append((r.iteration, np.array(r.tokens),
                               r.batch_size, time.monotonic(), lat))

    def service_loop():
        while not stop.is_set():
            try:
                svc.run_once()
            except Exception as err:  # noqa: BLE001
                with lock:
                    failed.append(f"service: {type(err).__name__}: {err}")
            time.sleep(poll)

    try:
        pool.wait_ready(iteration=0, timeout=timeout)
        threads = [threading.Thread(target=service_loop, daemon=True)]
        threads += [threading.Thread(target=client_loop, daemon=True)
                    for _ in range(clients)]
        for t in threads:
            t.start()

        contributor = ContributorClient(root, name="bench")
        for rnd in range(1, rounds + 1):
            prev = ckpt.load(os.path.join(root,
                                          f"base_iter{rnd-1:04d}.npz"))
            finetuned = jax.tree.map(lambda x, r=rnd: x + 0.003 * r, prev)
            contributor.submit(finetuned, base_iteration=rnd - 1)
            pool.wait_ready(iteration=rnd, timeout=timeout / rounds)
        # steady-state throughput window: all swaps done, caches warm,
        # and traffic demonstrably flowing post-swap (>= one request per
        # client since the final adoption)
        n_final = len(served)
        _wait(lambda: len(served) >= n_final + clients or failed,
              timeout=60.0, desc="post-swap traffic before measurement")
        t_m0 = time.monotonic()
        time.sleep(measure_s)
        t_m1 = time.monotonic()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        svc.close()
        worker_states = pool.states()
        pool.stop()

    # -- tear check at the EXECUTED batch shape -------------------------
    oracle = Engine(cfg, params, max_len=MAX_LEN)
    expected = {}
    torn = 0
    for it, toks, bsz, _t, _lat in served:
        key = (it, bsz)
        if key not in expected:
            base = ckpt.load(os.path.join(root, f"base_iter{it:04d}.npz"))
            tiled = np.repeat(prompt[None, :], bsz, axis=0)
            expected[key] = oracle.generate(
                tiled, max_new_tokens=MAX_NEW, params=base).tokens[0]
        if not np.array_equal(toks, expected[key]):
            torn += 1

    in_window = [(t, lat) for _it, _tk, _b, t, lat in served
                 if t_m0 <= t <= t_m1]
    window_s = max(t_m1 - t_m0, 1e-9)
    rstats = router.stats()
    live_swaps_total = sum(int((w or {}).get("live_swaps") or 0)
                           for w in worker_states.values())
    stats = {
        "requests": len(served),
        "failed": len(failed),
        "failures": failed[:5],
        "torn": torn,
        "workers": workers,
        "clients": clients,
        "batch": batch,
        "rounds": rounds,
        "live_swaps_total": live_swaps_total,
        "worker_iterations": {wid: (w or {}).get("iteration")
                              for wid, w in worker_states.items()},
        "requests_batched": sum(int((w or {}).get("requests_batched") or 0)
                                for w in worker_states.values()),
        "per_worker": rstats["per_worker"],
        "reroutes": rstats["reroutes_total"],
        "requests_measured": len(in_window),
        "throughput_rps": len(in_window) / window_s,
        "us_per_request": (float(np.mean([l for _t, l in in_window]))
                           if in_window else 0.0),
        "device_count": device_count,
    }
    if tmp is not None:
        tmp.cleanup()
    return stats


def check_pool(stats: dict, cell: str = "") -> None:
    """Per-cell acceptance: zero failed, zero torn, every worker ended
    on the final published base, every round was a live swap on every
    worker, and the measurement window actually saw traffic."""
    tag = f"[{cell}] " if cell else ""
    assert stats["failed"] == 0, (
        f"{tag}failed requests: {stats['failures']}")
    assert stats["torn"] == 0, f"{tag}{stats['torn']} version-torn requests"
    assert stats["live_swaps_total"] >= stats["rounds"] * stats["workers"], (
        f"{tag}only {stats['live_swaps_total']} live swaps across "
        f"{stats['workers']} workers x {stats['rounds']} rounds")
    bad = {w: it for w, it in stats["worker_iterations"].items()
           if it != stats["rounds"]}
    assert not bad, f"{tag}workers not on iteration {stats['rounds']}: {bad}"
    assert stats["requests_measured"] > 0, f"{tag}empty measurement window"
    if stats["batch"]:
        assert stats["requests_batched"] > 0, (
            f"{tag}batching enabled but no request was ever coalesced")


def run(rows: C.Rows):
    """Bench entry (benchmarks/run.py): the hot-swap row posts only after
    the zero-failed / zero-torn / >=3-live-swaps bar holds, then the
    scale-out sweep posts ``serve_load/scale_out`` — every swept cell
    must hold zero failed / zero torn, batched >= {BATCH_BAR}x unbatched
    at 1 worker, and 4-vs-1-worker aggregate throughput >= {SCALE_BAR}x
    on hosts with >= {SCALE_BAR_MIN_CORES} cores (no-collapse floor and
    an explicit note below that)."""
    rounds = {"quick": 4, "std": 5, "full": 8}[C.SCALE]
    stats = harness(rounds=rounds, clients=2)
    check(stats)
    rows.add(
        "serve_load/hot_swap", stats["us_per_request"],
        f"requests={stats['requests']};torn=0;failed=0;"
        f"live_swaps={stats['live_swaps']};"
        f"pinned={stats['requests_pinned_across_swaps']};"
        f"versions={len(stats['versions_served'])};"
        f"clients={stats['clients']}")

    # -- workers x clients x batching sweep -----------------------------
    # Two independent throughput axes, measured separately so each ratio
    # is apples-to-apples at equal client load: the BATCHING axis
    # (batched vs unbatched, 1 worker) and the SCALE-OUT axis (4 vs 1
    # workers, both unbatched — batching concentrates 8 clients into
    # near-full batches on 1 worker, so comparing batched cells across
    # worker counts conflates shrinking batch sizes with scaling).  The
    # combined cell (4 workers, batched) is the headline row.
    p_rounds = {"quick": 3, "std": 3, "full": 4}[C.SCALE]
    measure_s = {"quick": 4.0, "std": 8.0, "full": 12.0}[C.SCALE]
    clients = 8
    cells = {}
    for name, w, b in (("w1_unbatched", 1, False),
                       ("w1_batched", 1, True),
                       ("w4_unbatched", 4, False),
                       ("w4_batched", 4, True)):
        cells[name] = harness_pool(workers=w, clients=clients, batch=b,
                                   rounds=p_rounds, measure_s=measure_s)
        check_pool(cells[name], name)
    batch_ratio = (cells["w1_batched"]["throughput_rps"]
                   / max(cells["w1_unbatched"]["throughput_rps"], 1e-9))
    scale_ratio = (cells["w4_unbatched"]["throughput_rps"]
                   / max(cells["w1_unbatched"]["throughput_rps"], 1e-9))
    cores = os.cpu_count() or 1
    assert batch_ratio >= BATCH_BAR, (
        f"batched throughput only {batch_ratio:.2f}x unbatched at "
        f"{clients} clients (bar {BATCH_BAR}x)")
    if cores >= SCALE_BAR_MIN_CORES:
        scale_note = f"scale_bar={SCALE_BAR}x:enforced(cores={cores})"
        assert scale_ratio >= SCALE_BAR, (
            f"4-worker throughput only {scale_ratio:.2f}x 1-worker "
            f"(bar {SCALE_BAR}x on {cores} cores)")
    else:
        # a 1-core host cannot scale CPU-bound serving by adding
        # processes; enforce no-collapse and record the bar condition
        scale_note = (f"scale_bar={SCALE_BAR}x:needs>="
                      f"{SCALE_BAR_MIN_CORES}cores(have={cores})")
        assert scale_ratio >= SCALE_FLOOR, (
            f"4-worker throughput collapsed to {scale_ratio:.2f}x "
            f"1-worker (floor {SCALE_FLOOR}x)")
    tuning_note = ""
    if host_tuning.enabled():
        sweep = {}
        for n in (1, 2):
            st = harness_pool(workers=1, clients=clients, batch=True,
                              rounds=p_rounds, measure_s=measure_s,
                              device_count=n)
            check_pool(st, f"host_devices={n}")
            sweep[n] = st["throughput_rps"]
        best = max(sweep, key=sweep.get)
        tuning_note = (
            f";host_devices_best={best}"
            f";tcmalloc={'on' if host_tuning.tcmalloc_path() else 'absent'}")
    rows.add(
        "serve_load/scale_out", cells["w4_batched"]["us_per_request"],
        f"thr_w1={cells['w1_unbatched']['throughput_rps']:.1f}rps;"
        f"thr_w1_batched={cells['w1_batched']['throughput_rps']:.1f}rps;"
        f"thr_w4={cells['w4_unbatched']['throughput_rps']:.1f}rps;"
        f"thr_w4_batched={cells['w4_batched']['throughput_rps']:.1f}rps;"
        f"batch_ratio={batch_ratio:.2f};scale_ratio={scale_ratio:.2f};"
        f"{scale_note};clients={clients};torn=0;failed=0;"
        f"reroutes={cells['w4_batched']['reroutes']}{tuning_note}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="fuse-to-serve load harness")
    p.add_argument("--arch", default="gemma3-1b")
    p.add_argument("--rounds", type=int, default=4,
                   help="publish rounds (= live swaps; must be >=3)")
    p.add_argument("--clients", type=int, default=2,
                   help="concurrent inference client threads")
    p.add_argument("--mesh", type=int, default=0,
                   help="run the daemon's repository on an N-device mesh")
    p.add_argument("--root", default=None,
                   help="repository root (default: fresh temp dir)")
    p.add_argument("--workers", type=int, default=0,
                   help="scale-out mode: N worker PROCESSES behind the "
                        "router (0 = the classic in-process harness)")
    p.add_argument("--batch", action="store_true",
                   help="coalesce client requests per worker "
                        "(BatchScheduler; scale-out mode)")
    p.add_argument("--queue-depth", type=int, default=64,
                   help="per-worker bounded request queue (scale-out)")
    p.add_argument("--measure", type=float, default=4.0,
                   help="steady-state throughput window seconds "
                        "(scale-out)")
    p.add_argument("--sweep-device-counts", default=None, metavar="N,M",
                   help="also sweep --xla_force_host_platform_device_count "
                        "over these values on the pool children, printing "
                        "throughput per setting (scale-out)")
    args = p.parse_args(argv)
    if args.workers:
        stats = harness_pool(arch=args.arch, rounds=args.rounds,
                             clients=args.clients, workers=args.workers,
                             batch=args.batch, root=args.root,
                             queue_depth=args.queue_depth,
                             measure_s=args.measure)
        check_pool(stats)
        print(f"[serve_load] scale-out OK: {stats['requests']} requests "
              f"({stats['throughput_rps']:.1f} rps steady-state, "
              f"{stats['us_per_request']:.0f} us/req) across "
              f"{stats['workers']} workers x {stats['clients']} clients, "
              f"{stats['live_swaps_total']} live swaps, 0 failed, 0 torn "
              f"(batch={stats['batch']}, "
              f"coalesced={stats['requests_batched']}, "
              f"reroutes={stats['reroutes']}, "
              f"per_worker={stats['per_worker']})", flush=True)
        if args.sweep_device_counts:
            for n in (int(x) for x in args.sweep_device_counts.split(",")):
                st = harness_pool(arch=args.arch, rounds=args.rounds,
                                  clients=args.clients,
                                  workers=args.workers, batch=args.batch,
                                  queue_depth=args.queue_depth,
                                  measure_s=args.measure, device_count=n)
                check_pool(st, f"host_devices={n}")
                print(f"[serve_load]   host_devices={n}: "
                      f"{st['throughput_rps']:.1f} rps", flush=True)
        return 0
    stats = harness(arch=args.arch, rounds=args.rounds, clients=args.clients,
                    mesh=args.mesh, root=args.root)
    check(stats)
    print(f"[serve_load] OK: {stats['requests']} requests "
          f"({stats['us_per_request']:.0f} us/req) across "
          f"{stats['live_swaps']} live swaps, "
          f"{stats['requests_pinned_across_swaps']} pinned across a swap, "
          f"0 failed, 0 torn (versions={stats['versions_served']}, "
          f"mesh={args.mesh or 'none'}, {stats['wall_s']:.1f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
