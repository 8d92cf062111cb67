"""Runs of the four-chip fuse cell on four virtual CPU devices, at a tiny
size, for ``test_bench_mesh4.py``.  Not a test module: it sets
``XLA_FLAGS`` before JAX is imported, so it runs in a process of its own,
and prints one JSON object as its last line:

    sharded    the base is split over the four devices, the pool on the host
    sound      a run's ``correct`` and checks, and the float8 control's
    traced     the per-layer metrics of a ``--trace 1`` run
    <fault>    ``correct`` with the fault planted on the sharded fuse
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness, tracing  # noqa: E402
from bench.drivers import fuse  # noqa: E402

CELL = "inproc.granite-moe-1b-a400m"
# the configuration's layout at a tiny size: every kind of leaf, and
# leaves split along different axes (the vocabulary, 99, divides by none)
TINY_MOE = {"num_layers": 2, "d_model": 64, "num_heads": 2,
            "num_kv_heads": 1, "head_dim": 32, "d_ff": 32, "vocab_size": 99,
            "moe": {"num_experts": 4, "experts_per_token": 2}}
SEED = 2 ** 33 + 11
FAULTS = ("unchanged", "half_batch", "altered", "no_exchange")


def tiny_cell():
    cell = harness.resolve(harness.benchmark(), CELL)
    conf = cell["config_file"]
    cell["config_file"] = dict(conf, as_run=dict(conf["as_run"], **TINY_MOE))
    return cell


def run(cell, trace=False, modes=()):
    devices = jax.devices()[:cell["chips"]]
    return harness.run(cell, SEED, 0.3, trace, t_start=time.perf_counter(),
                       devices=devices, modes=modes)


def sharded(cell):
    """Whether the driver's base is split over the four devices (each leaf
    spread over all four, none whole on device 0) and its pool of
    contributions arrives in host memory, as the traffic states."""
    drv = fuse.InProcess(cell["config_file"], cell["traffic_file"], SEED,
                         tracing.Spans(), jax.devices()[:4])
    drv.t = dict(drv.t, warmup_rounds=0)
    drv.setup()
    ok = cell["traffic_file"]["arrive"] == "host"
    for x in jax.tree.leaves(drv._base0()):
        devs = {s.device for s in x.addressable_shards}
        whole = all(s.data.shape == x.shape for s in x.addressable_shards)
        # a leaf that no axis of divides by four stays whole on each
        ok &= len(devs) == 4 and (not whole or all(d % 4 for d in x.shape))
    for x in (leaf for t in drv.pool for leaf in jax.tree.leaves(t)):
        ok &= isinstance(x, np.ndarray)
    return ok


def plant(fault):
    """Break ``ops.fuse_flat_sharded``, the fuse ``Repository(mesh=)``
    calls; returns the original to put back."""
    from repro.kernels import ops

    orig = ops.fuse_flat_sharded

    def broken(base, contribs, weights, alpha=1.0, *, mesh, axes):
        rows = getattr(contribs, "data", contribs)
        fused, sq = orig(base, rows, weights, alpha, mesh=mesh, axes=axes)
        if fault == "unchanged":
            return base, sq
        if fault == "half_batch":
            k = rows.shape[0] // 2
            return orig(base, rows[:k], weights[:k], alpha, mesh=mesh,
                        axes=axes)[0], sq
        if fault == "altered":
            return fused.at[0, 0].add(1.0), sq
        if fault == "no_exchange":
            # the all-reduce of the squared distances left out: each row's
            # distance as the first chip's shard alone gives it
            d = rows[:, 0].astype(jnp.float32) - base[0].astype(jnp.float32)
            return fused, jnp.sum(jnp.square(d), axis=-1)
        raise ValueError(fault)

    ops.fuse_flat_sharded = broken
    return orig


def main():
    from repro.kernels import ops

    assert jax.device_count() == 4, jax.devices()
    cell = tiny_cell()
    out = {"sharded": sharded(cell)}
    res = run(cell, modes=("control",))
    out["sound"] = {"correct": res["correct"], "checks": res["checks"],
                    "control": res["modes"]["control"],
                    "count": res["device"]["count"]}
    res = run(cell, trace=True)
    out["traced"] = {"correct": res["correct"], "metrics": res["metrics"]}
    for fault in FAULTS:
        orig = plant(fault)
        try:
            res = run(cell)
        finally:
            ops.fuse_flat_sharded = orig
        out[fault] = {"correct": res["correct"], "checks": res["checks"]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
