#!/usr/bin/env bash
# Tier-1 CI: the full test suite, docs consistency, a multi-device smoke of
# the sharded fusion engine, the kernel micro-bench in smoke mode, and the
# examples in --dry-run mode.
#
#   scripts/ci.sh
#
# pytest exits non-zero on COLLECTION errors as well as failures (exit code
# 2), and `set -e` propagates both — a module that fails to import cannot
# slip through as "0 tests ran".
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# opt-in host-throughput tuning (ROADMAP "Host-throughput tuning"):
# REPRO_HOST_TUNING=1 preloads tcmalloc for every stage below when the
# library is installed (existence-gated — containers without it run
# identically), and benchmarks/serve_load.py additionally sweeps
# --xla_force_host_platform_device_count, recording the winning setting
# in its bench row notes.
if [[ "${REPRO_HOST_TUNING:-}" == "1" ]]; then
    eval "$(python -m repro.launch.host_tuning)"
    echo "[ci] REPRO_HOST_TUNING=1: LD_PRELOAD=${LD_PRELOAD:-<tcmalloc absent>}"
fi

python -m pytest -q

# docs suite: every docs/*.md reachable from README, no dead relative
# links, fenced python blocks import-check against src/
python scripts/check_docs.py

# multi-device smoke: the sharded-fuse + novelty-sketch + delta-codec
# tests on a real (fake-)8-device mesh — under plain pytest above they ran
# on the single CPU device.  The sketch tests pin the sharded one-psum
# sketch (the novelty screen's distributed path) against the single-device
# oracle; the codec tests pin the sharded decode+accumulate fuse the same
# way (one psum, no all-gather).  The slow subprocess test forces its own
# 8 devices and already ran above: skip it.
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/test_sharded_fuse.py tests/test_sketch.py \
    tests/test_delta_codec.py -q -m "not slow"

# crash-recovery under the forced 8-fake-device config: kill-and-reopen
# spill recovery (per-shard placement, manifest validation) with the mesh
# tests running on a REAL 8-device mesh rather than the single CPU device.
# Includes the slow sharded kill-and-reopen subprocess test — it IS this
# stage's point (its children force their own 8 devices).
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/test_repository.py tests/test_sharded_fuse.py \
    -q -k "crash or recover"

# service-loop stage: the contributor service loop end-to-end — the demo
# driver (fusion daemon + 2 contributor subprocesses x 3 fusion rounds +
# 1 replaying shadow contributor, daemon on a forced 8-fake-device mesh
# with the novelty screen armed: planted near-duplicates must be rejected
# at the queue boundary while every distinct contribution fuses) plus the
# kill-at-checkpoint fault-injection suite (slow marker: exactly-once
# fusion across every parametrized crash window incl. the sketch-persist
# window, docs/service_loop.md)
python examples/cold_service_demo.py --contributors 2 --rounds 3 --mesh 8 \
    --duplicates 1
# ... and the delta-compressed round: contributors enqueue top-k int8
# payloads against their downloaded base; the sharded daemon decodes
# inside the fused kernel and the same closed form must come out
python examples/cold_service_demo.py --contributors 2 --rounds 3 --mesh 8 \
    --compress
# ... and the similarity-routed round (docs/service_loop.md): two
# dissimilar contributor streams against one daemon with --max-bases 3 —
# the family must separate into exactly two members (each matching its
# own stream's closed form, never the blend) and cross-fuse to the mean
python examples/cold_service_demo.py --contributors 2 --rounds 3 --mesh 8 \
    --tasks 2 --max-bases 3
python -m pytest tests/test_cold_service.py -q -m slow
# routing crash matrix + gate-isolation matrix + the 20-consecutive-run
# duplicates-demo soak (the novelty-count race regression test — runs
# WITHOUT retries by design: one flaky exit fails the stage)
python -m pytest tests/test_routing.py -q -m slow

# regression-gate stage: the forgetting gate end-to-end on the same forced
# 8-fake-device mesh — a planted saboteur's harmful cohort must publish,
# trip the post-publish task probes, roll the base back on disk, and land
# in <root>/quarantine/ while the benign closed form survives
# (docs/observability.md).  The gate fault matrix (kill -9 inside
# probe -> quarantine -> rollback) runs with the slow suite above.
python examples/cold_service_demo.py --contributors 2 --rounds 3 --mesh 8 \
    --regress 1

# fuse-to-serve stage (docs/serving.md): the hot-swap load harness at
# demo scale on a forced 8-fake-device mesh — concurrent inference +
# contribution traffic against one repository; zero failed or
# version-torn requests across >=3 live swaps is the bar — plus the
# swap-seam kill -9 crash matrix (slow marker: a worker restarted from
# any of the 3 kill windows must serve a published, uncorrupted base)
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m benchmarks.serve_load --rounds 4 --clients 2 --mesh 8
python -m pytest tests/test_hot_swap.py -q -m slow

# serving scale-out stage (docs/serving.md): 2 worker PROCESSES behind
# the least-loaded router with the batched scheduler coalescing client
# requests, daemon on the forced 8-fake-device mesh — swaps land
# mid-load and every routed response is closed-form verified against
# its pinned iteration's on-disk base at the executed batch shape
# (zero failed, zero torn), then the run's metrics.jsonl is charted
# (latency / swap / load series) so the plotting path cannot rot.
# The pool kill -9 matrix (worker death mid-swap, router converging to
# zero failed requests) runs with the slow suite.
SCALE_ROOT=$(mktemp -d)
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m benchmarks.serve_load --workers 2 --batch --clients 4 \
    --rounds 3 --measure 2 --root "$SCALE_ROOT"
python scripts/plot_metrics.py "$SCALE_ROOT" --out "$SCALE_ROOT/metrics.png"
test -s "$SCALE_ROOT/metrics.png"
rm -rf "$SCALE_ROOT"
python -m pytest tests/test_worker_pool.py -q -m slow

# the serving load bench (smoke scale): its hot-swap row asserts zero
# failed/torn requests across >=3 live swaps before posting
REPRO_BENCH_SCALE=quick python -m benchmarks.run --only serve_load

# examples cannot silently rot: both must run end-to-end at dry-run scale
python examples/cold_fusion_multitask.py --dry-run
python examples/federated_single_dataset.py --dry-run
