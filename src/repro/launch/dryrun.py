import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e/g).

For every (architecture × input shape × mesh) combination this lowers and
compiles the real step function — train_step for train shapes, prefill /
serve steps for inference shapes — against ShapeDtypeStruct inputs (no
allocation), then records:

* ``compiled.memory_analysis()``  (per-chip fit proof),
* ``compiled.cost_analysis()``    (FLOPs / bytes for §Roofline),
* collective traffic parsed from the optimized per-device HLO,
* the derived roofline terms (repro.utils.roofline).

Artifacts land in ``artifacts/dryrun/<arch>__<shape>__<mesh>[__<strategy>].json``
and are consumed by ``benchmarks/roofline.py`` and EXPERIMENTS.md.

The 512 fake host devices are forced in the FIRST import line above, before
jax initializes; nothing else in the repo sets this flag (tests and benches
see the single real CPU device).

Usage:
  python -m repro.launch.dryrun --arch mistral-nemo-12b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh pod1|pod2|both] [--force]
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --strategy cold
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_shape
from repro.configs.base import ArchConfig, InputShape
from repro.configs.shapes import SHAPES
from repro.core.distributed import make_cold_train_step, make_fuse_step, ColdSchedule
from repro.kernels import ops as KOPS
from repro.launch import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_cold_mesh, make_production_mesh
from repro.launch.specs import (
    abstract_cache,
    abstract_params,
    abstract_state,
    auto_microbatches,
    input_specs,
)
from repro.optim.optimizers import constant_lr, make_optimizer
from repro.train.step import make_prefill_step, make_serve_step, make_train_step
from repro.utils.hlo_flops import analyze_hlo, wire_bytes as hlo_wire_bytes
from repro.utils.roofline import Roofline, model_flops_per_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

# long_500k eligibility (DESIGN.md §4): SSM / hybrid / windowed archs only.
LONG_CTX_ARCHS = {"rwkv6-7b", "jamba-1.5-large-398b", "mixtral-8x7b", "gemma3-1b"}

# Model-parallel submesh is fixed at 16 by the production mesh.
MODEL_AXIS = 16


def eligible(arch: str, shape: InputShape) -> bool:
    if shape.name == "long_500k":
        return arch in LONG_CTX_ARCHS
    return True


def _mesh(kind: str):
    if kind == "pod1":
        return make_production_mesh(multi_pod=False)
    if kind == "pod2":
        return make_production_mesh(multi_pod=True)
    if kind.startswith("cold"):
        # cold mesh: contributors x replicas x model; e.g. "cold8x2"
        spec = kind[4:] or "8x2"
        c, r = (int(x) for x in spec.split("x"))
        return make_cold_mesh(contributors=c, replicas=r, model=MODEL_AXIS)
    raise ValueError(kind)


def _data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data", "contrib", "replica") if a in mesh.axis_names)


def _dp_size(mesh) -> int:
    n = 1
    for a in _data_axes(mesh):
        n *= mesh.shape[a]
    return n


def _stash_hlo(cfg, shape, mesh, hlo: str, extra) -> None:
    """Gzip the optimized HLO next to the JSON so rooflines can be
    recomputed offline (``benchmarks.reanalyze``) without recompiling."""
    import gzip

    hlo_dir = os.path.join(ARTIFACT_DIR, "..", "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    tag = f"{cfg.name}__{shape.name}__{'x'.join(str(v) for v in mesh.shape.values())}"
    if extra and extra.get("strategy"):
        tag += f"__{extra['strategy']}"
    with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
        f.write(hlo)


def _analyze(compiled, mesh, cfg: ArchConfig, shape: InputShape, *, training: bool,
             wall_s: float, microbatches: int, extra: Optional[Dict] = None) -> Dict[str, Any]:
    # raw XLA numbers (NOTE: cost_analysis counts while/scan bodies ONCE —
    # kept for reference only)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    raw_flops = float(ca.get("flops", 0.0))
    raw_hbm = float(ca.get("bytes accessed", 0.0))
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "peak_memory_in_bytes"):
            mem[k] = int(getattr(ma, k, 0) or 0)
    hlo = compiled.as_text()
    _stash_hlo(cfg, shape, mesh, hlo, extra)
    # trip-count-aware per-chip analysis (repro.utils.hlo_flops)
    an = analyze_hlo(hlo)
    chips = mesh.devices.size
    tokens = shape.global_batch * (1 if shape.is_decode else shape.seq_len)
    mf_total = model_flops_per_step(cfg.active_param_count(), tokens, training=training)
    roof = Roofline(
        flops=an.flops,
        hbm_bytes=an.hbm_bytes,
        collective_bytes=float(hlo_wire_bytes(an)),
        model_flops=mf_total / chips,
        chips=chips,
    )
    out = {
        "ok": True,
        "arch": cfg.name,
        "shape": shape.name,
        "mesh_shape": dict(mesh.shape),
        "chips": chips,
        "kind": shape.kind,
        "microbatches": microbatches,
        "compile_wall_s": wall_s,
        "cost_analysis_raw": {"flops": raw_flops, "bytes_accessed": raw_hbm},
        "memory_analysis": mem,
        "collectives": {
            "bytes_by_kind": {k: float(v) for k, v in an.collective_bytes.items()},
            "count_by_kind": {k: int(v) for k, v in an.collective_count.items()},
            "total_bytes": float(an.total_collective_bytes),
            "dynamic_whiles": an.dynamic_whiles,
        },
        "roofline": roof.as_dict(),
        "hlo_chars": len(hlo),
    }
    if extra:
        out.update(extra)
    return out


def _dry_cfg(cfg: ArchConfig) -> ArchConfig:
    """Dry-run numerics policy: bf16 params/compute (DESIGN.md §5)."""
    return dataclasses.replace(cfg, param_dtype="bfloat16", compute_dtype="bfloat16")


def run_one(arch: str, shape_name: str, mesh_kind: str, *, strategy: str = "sync") -> Dict[str, Any]:
    cfg = _dry_cfg(get_config(arch))
    shape = get_shape(shape_name)
    if not eligible(arch, shape):
        return {"ok": False, "skipped": True,
                "reason": f"{arch} is full-attention; long_500k reserved for sub-quadratic archs"}
    mesh = _mesh(mesh_kind)
    t0 = time.time()
    # The CPU backend cannot lower Pallas; dry-runs use the pure-jnp paths.
    KOPS.use_kernels(False)

    # §Perf lever: "dp" layout — batch sharded over BOTH mesh axes, weights
    # replicated (no tensor parallelism).  The right regime for models whose
    # head counts / widths fit badly on a 16-way model axis (e.g. gemma3-1b:
    # 4 heads => attention otherwise runs 16x-replicated per chip).
    data_axis: Any = "data"
    model_axis: Any = "model"
    if strategy == "dp":
        data_axis = ("data", "model") if "pod" not in mesh.axis_names else ("pod", "data", "model")
        model_axis = None

    if shape.is_decode:
        params = abstract_params(cfg)
        cache = abstract_cache(cfg, shape)
        batch = input_specs(cfg, shape)
        params_sh = SH.params_shardings(mesh, params, cfg, data_axis=data_axis, model_axis=model_axis)
        cache_sh = SH.cache_shardings(mesh, cache, cfg, data_axis=data_axis, model_axis=model_axis)
        batch_sh = SH.batch_shardings(mesh, batch, data_axis=data_axis, model_axis=model_axis)
        rep = SH.replicated(mesh)
        serve = make_serve_step(cfg)
        with mesh:
            jitted = jax.jit(
                serve,
                in_shardings=(params_sh, cache_sh, batch_sh["tokens"], rep),
                out_shardings=(None, cache_sh),
            )
            lowered = jitted.lower(params, cache, batch["tokens"],
                                   jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        return _analyze(compiled, mesh, cfg, shape, training=False,
                        wall_s=time.time() - t0, microbatches=1)

    if shape.kind == "prefill":
        params = abstract_params(cfg)
        batch = input_specs(cfg, shape)
        params_sh = SH.params_shardings(mesh, params, cfg, data_axis=data_axis, model_axis=model_axis)
        batch_sh = SH.batch_shardings(mesh, batch, data_axis=data_axis, model_axis=model_axis)
        prefill = make_prefill_step(cfg)
        with mesh:
            jitted = jax.jit(prefill, in_shardings=(params_sh, batch_sh), out_shardings=None)
            lowered = jitted.lower(params, batch)
            compiled = lowered.compile()
        return _analyze(compiled, mesh, cfg, shape, training=False,
                        wall_s=time.time() - t0, microbatches=1)

    # --- training ---------------------------------------------------------
    # §Perf lever: force the factored optimizer (REPRO_OPT_ADAFACTOR=1) — the
    # pure-DP layout replicates optimizer state per chip, so Adam's f32 m+v
    # (8 bytes/param) is the peak-memory driver for ~1B models.
    opt_name = "adafactor" if os.environ.get("REPRO_OPT_ADAFACTOR", "0") == "1" else cfg.optimizer
    opt = make_optimizer(opt_name, constant_lr(1e-4))
    batch = input_specs(cfg, shape)

    if strategy == "cold":
        C = mesh.shape.get("contrib", 1) * mesh.shape.get("pod", 1)
        state1 = abstract_state(cfg, opt)
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((C,) + x.shape, x.dtype), state1
        )
        batch = {k: jax.ShapeDtypeStruct((C, v.shape[0] // C) + v.shape[1:], v.dtype)
                 for k, v in batch.items()}
        mb = auto_microbatches(cfg, shape, _dp_size(mesh))
        step = make_cold_train_step(cfg, opt, microbatches=mb)
        from repro.core.distributed import cold_shardings
        state_sh, batch_sh = cold_shardings(mesh, cfg, state, batch)
        with mesh:
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None))
            lowered = jitted.lower(state, batch)
            compiled = lowered.compile()
        res = _analyze(compiled, mesh, cfg, shape, training=True,
                       wall_s=time.time() - t0, microbatches=mb,
                       extra={"strategy": "cold", "contributors": C})
        # fuse step (the Repository collective), reported separately.
        # flat=False: the flat fuse currently pins its staging buffer to a
        # replicated sharding (GSPMD concat+mean workaround, see
        # make_fuse_step), which at pod scale would charge a full parameter
        # all-gather to the fuse budget; the per-leaf collective is the
        # honest pod-scale model until the sharded flat fuse lands (ROADMAP)
        t1 = time.time()
        fuse = make_fuse_step(cfg, mesh, ColdSchedule(), flat=False)
        with mesh:
            jf = jax.jit(fuse, in_shardings=(state_sh["params"],),
                         out_shardings=state_sh["params"])
            fc = jf.lower(state["params"]).compile()
        res["fuse"] = _analyze(fc, mesh, cfg, shape, training=True,
                               wall_s=time.time() - t1, microbatches=1)
        return res

    state = abstract_state(cfg, opt)
    params_sh = SH.params_shardings(mesh, state["params"], cfg, data_axis=data_axis, model_axis=model_axis)
    opt_sh = SH.opt_state_shardings(mesh, state["opt"], params_sh)
    state_sh = {"params": params_sh, "opt": opt_sh}
    batch_sh = SH.batch_shardings(mesh, batch, data_axis=data_axis, model_axis=model_axis)
    dp = mesh.devices.size if strategy == "dp" else _dp_size(mesh)
    mb = auto_microbatches(cfg, shape, dp)
    step = make_train_step(cfg, opt, microbatches=mb, grad_shardings=params_sh)
    with mesh:
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None))
        lowered = jitted.lower(state, batch)
        compiled = lowered.compile()
    return _analyze(compiled, mesh, cfg, shape, training=True,
                    wall_s=time.time() - t0, microbatches=mb)


def _artifact_path(arch: str, shape: str, mesh_kind: str, strategy: str) -> str:
    tag = f"{arch}__{shape}__{mesh_kind}"
    if strategy != "sync":
        tag += f"__{strategy}"
    return os.path.abspath(os.path.join(ARTIFACT_DIR, tag + ".json"))


def main() -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    p.add_argument("--shape", choices=list(SHAPES), default=None)
    p.add_argument("--mesh", choices=["pod1", "pod2", "both"], default="pod1")
    p.add_argument("--strategy", default="sync",
                   help="sync | cold (cold uses the contributor mesh; combine with --cold-mesh)")
    p.add_argument("--cold-mesh", default="8x2", help="contributors x replicas, e.g. 8x2")
    p.add_argument("--all", action="store_true", help="run every (arch, shape)")
    p.add_argument("--force", action="store_true", help="recompute existing artifacts")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    global ARTIFACT_DIR
    if args.out:
        ARTIFACT_DIR = args.out
    os.makedirs(ARTIFACT_DIR, exist_ok=True)

    archs = list(ARCH_IDS[:10]) if args.all else [args.arch]
    shapes = list(SHAPES) if args.all else [args.shape]
    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    if args.strategy.startswith("cold"):
        meshes = [f"cold{args.cold_mesh}"]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = _artifact_path(arch, shape, mesh_kind, args.strategy)
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {os.path.basename(path)}")
                    continue
                print(f"[dryrun] {arch} x {shape} x {mesh_kind} ({args.strategy}) ...", flush=True)
                try:
                    res = run_one(arch, shape, mesh_kind, strategy=args.strategy)
                except Exception as e:  # record failures as artifacts too
                    res = {"ok": False, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    failures += 1
                    print(f"  FAILED: {res['error']}")
                res.setdefault("arch", arch)
                res.setdefault("shape", shape)
                res.setdefault("mesh", mesh_kind)
                res["strategy"] = args.strategy
                with open(path, "w") as f:
                    json.dump(res, f, indent=2)
                if res.get("ok"):
                    r = res["roofline"]
                    print(
                        f"  ok in {res['compile_wall_s']:.0f}s: "
                        f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
                        f"collective={r['collective_s']*1e3:.2f}ms -> {r['bottleneck']} "
                        f"(useful={r['useful_flops_ratio']:.2f}, "
                        f"peak={res['memory_analysis'].get('peak_memory_in_bytes', 0)/2**30:.2f}GiB)"
                    )
                elif res.get("skipped"):
                    print(f"  skipped: {res['reason']}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
