#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Set-up (weights from the seed, the
program's objects, a warm-up of every shape the window uses) is timed from
process start as ``setup_s``; then the window runs for ``--seconds``, and
the reference checks what it produced.  With ``--trace 1`` the window is
profiled (at most the traffic's ``trace_seconds``) and the cell's per-layer
metrics are printed instead of its end-to-end ones.  The last line of
standard output is the result, one JSON object; the numbers compared are
the last lines of standard error.  Without a TPU, with fewer chips than the
cell asks for, or without the program beside ``bench/``, the run exits 2
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the persistent compilation cache lives at a fixed path in the checkout
CACHE = os.path.join(ROOT, ".jax_cache")


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail(f"--seed {args.seed} is negative")
    # a run that hangs past the first run's allowance dumps its stacks
    faulthandler.dump_traceback_later(1150, exit=True)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"the program (src/repro) is not in {ROOT}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    cell = harness.resolve(harness.benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX found "
                    f"{len(devices)}")
    peaks = harness.peaks_for(devices[0].device_kind)
    harness.use_compile_cache(CACHE)
    out =harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, devices=devices[:cell["chips"]],
                      peaks=peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
