"""Launch the queue-driven fusion daemon over an on-disk repository.

The operator-facing entry point for the contributor service loop
(docs/service_loop.md): opens (or initializes) a spill-enabled Repository
at ``--root``, wraps it in a ``ColdService``, and polls the contribution
queue until stopped — by SIGINT/SIGTERM (clean quiesce: in-flight fuse
finalized, final status published), by ``--max-iterations``, or by
``--idle-timeout`` seconds of empty queue.

  # serve an existing repository (spill restored from repository.json)
  PYTHONPATH=src python -m repro.launch.serve_repository --root repo/

  # initialize from a base checkpoint, fuse cohorts of >=2, stop after 3
  PYTHONPATH=src python -m repro.launch.serve_repository --root repo/ \\
      --init-npz base.npz --min-cohort 2 --max-iterations 3

``--mesh N`` opens the repository on an N-device mesh (the sharded fuse
path); the device count must already be available — under CPU testing,
export ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first.

``--serve-arch NAME`` additionally runs a fuse-to-serve hot-swap worker
(docs/serving.md) in the same process: a ``ServingWorker`` subscribed to
the repository's publishes keeps a serving ``Engine`` on the latest
published base (reduced NAME config), persisting ``serving_state.json``
and swap records alongside the daemon's status.  ``--serve-workers N``
scales that out instead: N worker PROCESSES (``serve/worker_pool.py``,
each with its own namespaced ``serving_state-<id>.json``) follow the
repository cross-process; ``--serve-batch`` enables the per-worker
``BatchScheduler``; ``--serve-queue-depth`` bounds each worker's
request queue (overload sheds explicitly instead of collapsing
latency).  ``status()`` aggregates the whole worker namespace.

``--trace-out PATH`` records the program's spans and counters
(``repro.utils.trace``) and writes them to PATH as JSONL when the daemon
stops; docs/observability.md names each one.

``REPRO_HOST_TUNING=1`` applies the opt-in host-throughput recipe
(``repro/launch/host_tuning.py``): tcmalloc ``LD_PRELOAD`` when
installed (the daemon re-execs itself once to pick it up, and pool
children inherit it).
"""
from __future__ import annotations

import argparse
import os
import signal
import sys

from repro.launch import host_tuning

# before jax (via the repro imports below) loads: LD_PRELOAD and
# XLA_FLAGS are read once at process/import start
host_tuning.maybe_reexec()

from repro.checkpoint import io as ckpt
from repro.core.repository import Repository, RepositoryFamily
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.serve.cold_service import AdmissionPolicy, ColdService
from repro.serve.probes import ProbeSuite, RegressionGate
from repro.utils import trace


def build_service(args) -> ColdService:
    mesh = None
    if args.mesh:
        import jax
        if jax.device_count() < args.mesh:
            raise SystemExit(
                f"--mesh {args.mesh} needs {args.mesh} devices, have "
                f"{jax.device_count()} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.mesh})")
        mesh = make_mesh((args.mesh,), ("model",))
    kw = dict(spill=True, spill_workers=args.spill_workers)
    if mesh is not None:
        kw["mesh"] = mesh
    routed = args.max_bases > 1
    family = None
    if routed:
        if os.path.exists(os.path.join(args.root, "repository.json")):
            family = RepositoryFamily.open(args.root, **kw)
        else:
            if not args.init_npz:
                raise SystemExit(f"{args.root} holds no repository.json — "
                                 "pass --init-npz to initialize a new "
                                 "repository")
            base = ckpt.load(args.init_npz)
            family = RepositoryFamily.create(
                base, root=args.root, screen=not args.no_screen,
                fusion_op=args.fusion_op, **kw)
        repo = family.members["main"]
    elif os.path.exists(os.path.join(args.root, "repository.json")):
        repo = Repository.open(args.root, **kw)
    else:
        if not args.init_npz:
            raise SystemExit(f"{args.root} holds no repository.json — pass "
                             "--init-npz to initialize a new repository")
        base = ckpt.load(args.init_npz)
        repo = Repository(base, root=args.root, screen=not args.no_screen,
                          fusion_op=args.fusion_op, **kw)
    policy = AdmissionPolicy(
        min_cohort=args.min_cohort,
        max_cohort=args.max_cohort,
        max_wait_s=args.max_wait,
        max_staleness=args.max_staleness,
        verify_checksums=args.verify_checksums,
        novelty_threshold=args.novelty_threshold,
        sketch_window=args.sketch_window,
        compact_keep_bases=args.compact_keep,
        max_bases=args.max_bases,
        split_threshold=args.split_threshold,
        cross_fuse_every=args.cross_fuse_every,
    )
    gate = None
    if args.gate:
        repo._ensure_flat_base()  # the probe pool is sized to the flat base
        gate = RegressionGate(
            ProbeSuite(repo._spec.size, n_tasks=args.probe_tasks,
                       n_examples=args.probe_examples,
                       seed=args.probe_seed),
            tolerance=args.probe_tolerance)
    if routed:
        return ColdService(family=family, policy=policy, gate=gate)
    return ColdService(repo, policy=policy, gate=gate)


def main(argv=None) -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser(
        description="queue-driven ColD Fusion daemon (docs/service_loop.md)")
    p.add_argument("--root", required=True, help="repository npz root")
    p.add_argument("--init-npz", default=None,
                   help="base checkpoint to initialize a NEW repository from")
    p.add_argument("--fusion-op", default="average")
    p.add_argument("--no-screen", action="store_true",
                   help="disable the §9 MAD screen (new repositories only)")
    p.add_argument("--mesh", type=int, default=0, metavar="N",
                   help="open on an N-device mesh (sharded fuse)")
    p.add_argument("--spill-workers", type=int, default=0)
    p.add_argument("--min-cohort", type=int, default=1)
    p.add_argument("--max-cohort", type=int, default=64)
    p.add_argument("--max-wait", type=float, default=0.0,
                   help="fuse an undersized cohort after this many seconds")
    p.add_argument("--max-staleness", type=int, default=None,
                   help="reject submissions finetuned from a base more than "
                        "this many iterations old")
    p.add_argument("--verify-checksums", action="store_true")
    p.add_argument("--novelty-threshold", type=float, default=None,
                   metavar="D",
                   help="reject submissions whose content sketch sits "
                        "within this relative distance of a recent "
                        "admission (the cohort novelty screen; default off)")
    p.add_argument("--sketch-window", type=int, default=32,
                   help="recent admissions the novelty screen remembers")
    p.add_argument("--compact-keep", type=int, default=None, metavar="M",
                   help="compact after each publish, keeping M bases")
    p.add_argument("--max-bases", type=int, default=1, metavar="B",
                   help="serve a base FAMILY of up to B members, routing "
                        "each submission to its nearest base by sketch "
                        "distance and spawning a new member when nothing "
                        "is near (docs/service_loop.md; default 1 = the "
                        "single-base loop)")
    p.add_argument("--split-threshold", type=float, default=0.8, metavar="D",
                   help="relative sketch distance beyond which a "
                        "submission founds a new family member "
                        "(--max-bases > 1)")
    p.add_argument("--cross-fuse-every", type=int, default=0, metavar="K",
                   help="after every K publishes, fuse the family members "
                        "into each other (inter-cluster merge; 0 = never)")
    p.add_argument("--gate", action="store_true",
                   help="arm the forgetting regression gate: probe every "
                        "publish against the pre-fuse baseline; on a "
                        "regression, roll the base back and quarantine the "
                        "offending cohort (docs/observability.md)")
    p.add_argument("--probe-tasks", type=int, default=4,
                   help="synthetic tasks in the gate's probe suite")
    p.add_argument("--probe-examples", type=int, default=32,
                   help="eval examples per probe task")
    p.add_argument("--probe-tolerance", type=float, default=0.5, metavar="T",
                   help="per-task probe-loss increase that counts as a "
                        "regression")
    p.add_argument("--probe-seed", type=int, default=0,
                   help="seed fixing the probe batches and readouts")
    p.add_argument("--serve-arch", default=None, metavar="NAME",
                   help="also serve the evolving base: run a hot-swap "
                        "ServingWorker for this arch (reduced config; the "
                        "repository base must be that arch's param tree)")
    p.add_argument("--serve-max-len", type=int, default=64,
                   help="serving engine KV-cache length (--serve-arch)")
    p.add_argument("--serve-workers", type=int, default=0, metavar="N",
                   help="scale the serving side out to N worker "
                        "PROCESSES behind namespaced state files "
                        "(requires --serve-arch; 0 = one in-process "
                        "worker)")
    p.add_argument("--serve-batch", action="store_true",
                   help="coalesce compatible requests per worker via "
                        "the BatchScheduler (--serve-workers)")
    p.add_argument("--serve-queue-depth", type=int, default=64,
                   help="bounded per-worker request queue; overflow is "
                        "shed as rejected:queue_full (--serve-workers)")
    p.add_argument("--poll", type=float, default=0.02, metavar="S",
                   help="idle poll interval (seconds)")
    p.add_argument("--max-iterations", type=int, default=None,
                   help="stop once this base iteration is published")
    p.add_argument("--idle-timeout", type=float, default=None,
                   help="stop after this many seconds without progress "
                        "(no admission, no publish, empty queue)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="record the program's spans and counters and write "
                        "them to PATH as JSONL on exit "
                        "(docs/observability.md)")
    args = p.parse_args(argv)
    if args.trace_out:
        trace.enable()

    svc = build_service(args)

    worker = None
    pool = None
    if args.serve_workers and not args.serve_arch:
        raise SystemExit("--serve-workers requires --serve-arch")
    if args.serve_workers:
        from repro.serve.worker_pool import WorkerPool
        env = host_tuning.host_tuning_env() if host_tuning.enabled() else {}
        pool = WorkerPool(svc.repo.root, args.serve_workers,
                          arch=args.serve_arch,
                          max_len=args.serve_max_len, poll=args.poll,
                          batch=args.serve_batch,
                          queue_depth=args.serve_queue_depth, env=env)
        pool.start()
        print(f"[cold-service] {args.serve_workers} pool workers serving "
              f"{args.serve_arch} (max_len={args.serve_max_len}, "
              f"batch={args.serve_batch}, "
              f"queue_depth={args.serve_queue_depth})", flush=True)
    elif args.serve_arch:
        from repro.configs import get_config, reduce_config
        from repro.serve.hot_swap import ServingWorker
        cfg = reduce_config(get_config(args.serve_arch))
        worker = ServingWorker(cfg, svc.repo.root, repo=svc.repo,
                               max_len=args.serve_max_len)
        worker.start(interval=args.poll)
        print(f"[cold-service] hot-swap worker serving {args.serve_arch} "
              f"(max_len={args.serve_max_len})", flush=True)

    def _stop(signum, frame):
        svc.request_stop()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    print(f"[cold-service] serving {args.root} from iteration "
          f"{svc.repo.iteration} (min_cohort={svc.policy.min_cohort}, "
          f"mesh={args.mesh or 'none'})", flush=True)
    st = svc.serve_forever(poll_interval=args.poll,
                           max_iterations=args.max_iterations,
                           idle_timeout=args.idle_timeout)
    if worker is not None:
        ws = worker.stop()
        print(f"[cold-service] worker stopped at iteration "
              f"{ws['iteration']}: {ws['swaps_total']} swaps "
              f"({ws['live_swaps']} live), {ws['requests_total']} requests "
              f"({ws['requests_pinned_across_swaps']} pinned across swaps)",
              flush=True)
    if pool is not None:
        states = pool.states()
        codes = pool.stop()
        detail = ", ".join(
            f"{wid}@it{(s or {}).get('iteration')}"
            f"({(s or {}).get('requests_total', 0)} req)"
            for wid, s in sorted(states.items()))
        print(f"[cold-service] pool stopped (exit={codes}): {detail}",
              flush=True)
    fams = st.get("families")
    if fams:
        detail = ", ".join(f"{n}@it{f['iteration']}"
                           for n, f in sorted(fams.items()))
        print(f"[cold-service] family: {detail} "
              f"({st['families_spawned_total']} spawned, "
              f"{st['cross_fuses_total']} cross-fuses)", flush=True)
    print(f"[cold-service] stopped at iteration {st['iteration']}: "
          f"{st['fuses']} fuses, {st['fused_contributions']} contributions "
          f"fused, {st['rejected_total']} rejected "
          f"({st['novelty_rejected_total']} near-duplicates), "
          f"{st['rollbacks_total']} rollbacks "
          f"({st['quarantined_total']} submissions quarantined)", flush=True)
    if args.trace_out:
        trace.dump(args.trace_out)
        print(f"[cold-service] trace written to {args.trace_out}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
