"""Mesh-level ColD Fusion — the paper's schedule as a TPU training strategy.

The host-level `Repository`/`Contributor` objects exchange checkpoints; at
pod scale the same mathematics maps onto the device mesh (DESIGN.md §2):

* mesh ("pod"?, "contrib", "replica", "model");
* every parameter gains a leading contributor dim C sharded over
  ("pod", "contrib") — each contributor slab holds its own full replica of
  the model (sharded over its "replica" x "model" sub-mesh);
* ``cold_train_step`` = vmap of the ordinary train step over the contributor
  dim.  GSPMD inserts gradient all-reduces **only** over "replica"/"model"
  (params are sharded over "contrib", so no cross-contributor traffic);
* ``fuse_step`` = parameter mean over the contributor dim, broadcast back —
  a single all-reduce over ("pod", "contrib") every H steps.  With damping
  α it implements the paper-§8 "iteration learning rate".

Amortized collective traffic over contributor axes: 2·P/H bytes/step vs
2·P for synchronous data parallelism — the measurable systems win of the
paper's schedule, quantified from lowered HLO in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.launch import sharding as SH
from repro.optim.optimizers import Optimizer
from repro.train.step import make_train_step
from repro.utils.flat import ShardedFlatSpec, StagedBuffer


@dataclass(frozen=True)
class ColdSchedule:
    """Hyper-parameters of the distributed schedule."""

    fusion_interval: int = 50  # H: local steps between fusions
    alpha: float = 1.0         # damped-fusion coefficient (1.0 = paper)
    reset_opt_on_fuse: bool = False  # fresh optimizer each iteration (paper)


def contrib_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "contrib") if a in mesh.axis_names)


def num_contributors(mesh: Mesh) -> int:
    n = 1
    for a in contrib_axes_of(mesh):
        n *= mesh.shape[a]
    return n


def stack_for_contributors(tree, n: int):
    """Broadcast a pytree to a leading contributor dim of size n."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), tree)


def make_cold_train_step(
    cfg: ArchConfig,
    optimizer: Optimizer,
    *,
    microbatches: int = 1,
) -> Callable:
    """vmap(local_train_step) over the leading contributor dim.

    state: pytree with leading contributor dim C on every leaf;
    batch: {"tokens": [C, B_local, S], ...}.  Pair with ``cold_shardings``
    under ``jax.jit`` — params sharded over contrib ⇒ zero cross-contributor
    gradient traffic.
    """
    local = make_train_step(cfg, optimizer, microbatches=microbatches)
    return jax.vmap(local)


def shard_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    """Axes the flat fuse buffer is block-cyclically sharded over (the
    non-contributor part of the ColD mesh)."""
    return tuple(a for a in ("replica", "model") if a in mesh.axis_names)


def make_fuse_step(cfg: ArchConfig, mesh: Mesh, schedule: ColdSchedule,
                   *, flat: bool = True) -> Callable:
    """The Repository collective: θ ← θ_base + α·(mean_c θ_c − θ_base),
    broadcast back to every contributor slab.

    ``flat=True`` (default) runs the fuse over ONE ``[C, N]`` flat buffer
    instead of one reduction per leaf — the mesh-level face of the sharded
    flat engine (docs/sharding.md): the buffer is laid out block-cyclically
    (``ShardedFlatSpec``) with C over the contributor axes and N over the
    replica/model axes, and ``ops.cohort_fuse_sharded`` computes a
    per-device partial sum over its local slabs that exactly ONE psum over
    the contributor axes completes.  ``flat=False`` keeps the per-leaf path
    as the oracle.

    This shares the Repository fuse's implementation (the same layout, the
    same partial+one-all-reduce structure — only the reduced dim differs).
    The mean is computed manually under ``shard_map``, so GSPMD never
    lowers a ``concat -> mean`` over a sharded axis, no concat input needs
    a sharding pin, and each device holds only its ``1/S`` block-cyclic
    slice of the buffer through the fuse.
    """

    def leaf_fuse(x):
        mean = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True)
        if schedule.alpha != 1.0:
            # damped fusion: each slab relaxes toward the cohort mean
            mean = x.astype(jnp.float32) * (1 - schedule.alpha) + mean * schedule.alpha
        return jnp.broadcast_to(mean, x.shape).astype(x.dtype)

    def fuse_per_leaf(params):
        return jax.tree.map(leaf_fuse, params)

    contrib = contrib_axes_of(mesh)
    if not (flat and contrib):
        # no contributor axis (plain data/model mesh): nothing to fuse over
        # a mesh dim — the per-leaf reduction handles any mesh
        return fuse_per_leaf
    shard_axes = shard_axes_of(mesh)
    n_shards = SH.axes_extent(mesh, shard_axes)

    def fuse_flat(params):
        leaves, treedef = jax.tree.flatten(params)
        C = leaves[0].shape[0]
        shapes = [l.shape for l in leaves]
        dtypes = [l.dtype for l in leaves]
        sizes = [int(np.prod(s[1:])) for s in shapes]
        buf = jnp.concatenate(
            [l.reshape(C, -1).astype(jnp.float32) for l in leaves], axis=1)
        sspec = ShardedFlatSpec.for_size(buf.shape[1], n_shards)
        # hand the staged cohort to the fuse as an explicit buffer handle —
        # the same operand contract the async Repository uses
        fused = ops.cohort_fuse_sharded(
            StagedBuffer(sspec.shard(buf)), mesh=mesh, contrib_axes=contrib,
            shard_axes=shard_axes, alpha=schedule.alpha)
        fused = sspec.unshard(fused)
        outs = []
        off = 0
        for shape, dtype, n in zip(shapes, dtypes, sizes):
            outs.append(fused[:, off:off + n].reshape(shape).astype(dtype))
            off += n
        return jax.tree.unflatten(treedef, outs)

    return fuse_flat


def cold_shardings(mesh: Mesh, cfg: ArchConfig, state, batch):
    """Convenience: full (state, batch) NamedSharding trees for jit."""
    contrib = contrib_axes_of(mesh)
    contrib_spec: Tuple = (contrib if len(contrib) > 1 else contrib[0],)
    params_sh = SH.params_shardings(
        mesh, state["params"], cfg,
        data_axis="replica", model_axis="model", contrib_axes=contrib_spec,
    )
    opt_sh = SH.opt_state_shardings(mesh, state["opt"], params_sh)
    batch_sh = SH.batch_shardings(
        mesh, batch, data_axis="replica", contrib_axes=contrib_spec,
    )
    return {"params": params_sh, "opt": opt_sh}, batch_sh
