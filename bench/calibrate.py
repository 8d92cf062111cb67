#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for many seeds in
one process (compiled once).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed: one run of the cell (its window at the cell's own load, then
the comparison), followed by the readings of what may be put in the
program's place: the reference in float8 (``control``) and, for training,
the reference over half of each batch (``half_batch``).  Prints one JSON
object per seed.  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    harness.use_compile_cache(cache)
    cell = harness.resolve(harness.benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    modes = (("control", "half_batch")
             if cell["traffic_file"]["kind"] == "finetune" else ("control",))
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, False, t_start=t0,
                          devices=devices[:cell["chips"]], modes=modes)
        out["seed"] = seed
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
