"""The central Repository (paper Fig. 1): versioned base-model store that
accepts contributions, screens them (§9), fuses them (§3), and publishes the
next base model.  Performs no training — only the minimal computation the
ColD constraints allow (§2.3).

Two transports share this logic:

* **in-memory** — the simulation / single-process driver keeps pytrees.
* **on-disk**   — contributions arrive as npz checkpoints in a directory
  (the stand-in for the HF-hub exchange); useful across processes.

Two fuse engines share the contributor-facing API:

* **streaming flat engine** (default for ``average``/``damped``/
  ``task_arithmetic`` when kernels are enabled) — ``upload`` immediately
  folds each contribution into a flat ``[N]`` staging row (the pytree is
  dropped, bounding peak memory to the staging buffer — optionally spilled
  to the npz root) and ``fuse_pending`` performs screen+fuse in a SINGLE
  streaming pass: the Pallas ``cold_fuse`` kernel emits the fused model and
  the per-contributor ``sq_diff`` screening statistic together, the §9 MAD
  screen runs on those norms, and any rejected contributors get weight 0 in
  one cheap second pass over the already-staged buffer.  No contribution is
  ever re-read as a pytree.
* **per-leaf pytree engine** — the seed path (`repro.core.fusion`), kept
  verbatim as the ``REPRO_NO_KERNELS`` oracle and for operators the kernel
  does not cover (``fisher``, ``ties``).

The staging side is **double-buffered** (paper §8, asynchronous updates):
uploads stage into the *front* buffer while ``fuse_pending(wait=False)``
runs the screen+fuse on the *back* buffer — jax's asynchronous dispatch
overlaps the device fuse with the host-side staging work of the next
cohort, no Python threads required.  ``flush()`` (or the next
``fuse_pending``/``download``) finalizes the in-flight fuse: screening,
the optional weight-zeroed re-pass, and the publish.  See
docs/async_repository.md.

``spill=True`` makes the staging buffer **resumable**: every staged row is
written atomically into the npz root together with a small JSON manifest
(``staging_manifest.json``), and ``Repository.open`` recovers
staged-but-unfused rows after a crash — re-staged into the correct buffer
and, under ``mesh=``, the correct per-shard placement (spill files hold
per-shard slices, so the reload never materializes a full ``[N]`` row on
the host).

Passing ``mesh=`` (with optional ``mesh_axes=``) distributes the flat
engine: ``upload`` stages each row directly into its block-cyclic shard
placement (``ShardedFlatSpec``), ``fuse_pending`` runs the screen+fuse
per-shard under ``shard_map`` with exactly ONE all-reduce (the ``sq_diff``
partials), and no device ever materializes the full ``[K, N]`` staging
buffer.  Cohort capacity then scales with the mesh instead of a single
device's HBM.  See docs/sharding.md.

See docs/fusion_engine.md and docs/repository.md for the full contract.
"""
from __future__ import annotations

import functools
import json
import os
import re
import threading
import time
import warnings
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt
from repro.core import fusion
from repro.core.validation import (ScreenReport, norms_from_sq,
                                   screen_contributions, screen_norms)
from repro.kernels import ops
from repro.launch import sharding as SH
from repro.utils import faults, trace
from repro.utils.flat import (SKETCH_BUCKETS, BufferPair, CohortSketch,
                              FlatSpec, ShardedFlatSpec, StagedBuffer,
                              StagingSide, delta_decode, delta_decode_sharded,
                              delta_entries, sketch_apply_delta)

# operators the streaming flat engine covers; everything else (fisher, ties)
# falls back to the per-leaf pytree engine
FLAT_OPS = ("average", "damped", "task_arithmetic")

MANIFEST = "staging_manifest.json"
SKETCH_FILE = "cohort_sketch.json"


class NothingToFuse(RuntimeError):
    """The repository declined a fuse: nothing is staged, or the §9 screen
    rejected every contribution.  The service loop treats it as a per-
    cohort outcome and keeps running; any other error (a device or
    compile failure) propagates."""


# on-disk artifact naming in the npz root (compact() walks these)
_BASE_RE = re.compile(r"^base_iter(\d{4,})\.npz$")
_ROW_RE = re.compile(r"^iter\d{4,}_contrib\d{3,}\.npz$")


@dataclass
class FusionRecord:
    iteration: int
    n_contributions: int
    n_accepted: int
    op: str
    diff_norms: List[float]
    # the cadence: from the fuse's start to its publish, idle cycles included
    wall_time: float
    # host seconds the fuse took: staging and dispatch, then the finalize
    # (its wait on the device included); their sum is ``fuse_latency_s``
    stage_s: float = 0.0
    finalize_s: float = 0.0


@dataclass
class PendingFusion:
    """Handle to an in-flight fuse: dispatched to the device, not yet
    screened or published.  ``Repository.flush()`` (or the next
    ``fuse_pending``/``download``) finalizes it; ``record`` is set once the
    publish happened."""

    # StagedBuffer (dense cohort) or MixedStage (compressed rows present);
    # kept only while a screen re-pass may need it
    stage: Optional[Any]
    fused: jax.Array
    sq: jax.Array
    weights: jax.Array
    k: int
    t0: float
    record: Optional[FusionRecord] = None
    stage_s: float = 0.0
    # per-fusion overrides (fuse_pending(buffer=..., alpha=/screen=/op=));
    # None defers to the repository's configuration
    alpha: Optional[float] = None
    use_screen: Optional[bool] = None
    op: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.record is not None


@dataclass
class MixedStage:
    """Fuse operand for a cohort that mixes dense staged rows with
    delta-compressed submissions (docs/service_loop.md).  ``dense`` holds
    the stacked dense rows (cohort positions ``dense_pos``); the
    compressed rows ride as their stacked codec arrays (``comp_pos``) and
    are decoded *inside* the fuse (``ops.fuse_flat_compressed``) — a dense
    ``[N]`` row per compressed contributor never materializes.  Kept as
    the ``PendingFusion`` stage so the §9 screen's zero-weight re-pass can
    re-fuse with adjusted cohort-order weights, exactly like a dense
    ``StagedBuffer``."""

    dense: Optional[StagedBuffer]
    indices: jax.Array   # [C, nb, kb] int16 ([C, S, nb, kb] sharded)
    values: jax.Array    # [C, nb, kb] int8
    scales: jax.Array    # [C, nb] f32 ([C, S, nb] sharded)
    block: int
    dense_pos: np.ndarray  # cohort positions of the dense rows, in order
    comp_pos: np.ndarray   # cohort positions of the compressed rows

    @property
    def k(self) -> int:
        return len(self.dense_pos) + len(self.comp_pos)


def stage_stack(*rows):
    """K staged rows -> the ``[K, ...]`` fuse operand (``jit_stage_stack``
    in a profiler trace)."""
    return jnp.stack(rows)


@functools.lru_cache(maxsize=32)
def _stack_fn(k: int, sharding):
    """Jitted K-row stack with the staging out-sharding: each device
    concatenates its local shard slices, so stacking never gathers the
    cohort onto one device.  Cached per (K, sharding) to avoid re-tracing
    every fuse."""
    del k  # shapes key the jit cache; K only keys the lru entry
    return jax.jit(stage_stack, out_shardings=sharding)


@functools.lru_cache(maxsize=32)
def _stack_plain_fn(k: int):
    """Jitted single-device K-row stack.  Eager ops on the CPU backend
    execute synchronously; only jitted computations dispatch asynchronously
    — and the stack must dispatch async for the double-buffered fuse to
    overlap uploads (docs/async_repository.md)."""
    del k
    return jax.jit(stage_stack)


def _json_default(o):
    if isinstance(o, (np.ndarray, np.generic, jax.Array)):
        return np.asarray(o).tolist()
    return str(o)


class Repository:
    def __init__(
        self,
        base_params,
        *,
        fusion_op: str = "average",
        fusion_kwargs: Optional[Dict[str, Any]] = None,
        screen: bool = True,
        mad_threshold: float = 5.0,
        root: Optional[str] = None,
        keep_history: bool = False,
        use_flat: Optional[bool] = None,
        spill: bool = False,
        spill_workers: int = 0,
        mesh: Optional[Any] = None,
        mesh_axes: Optional[Any] = None,
    ):
        self._base = base_params
        self.fusion_op = fusion_op
        self.fusion_kwargs = dict(fusion_kwargs or {})
        self.screen = screen
        self.mad_threshold = mad_threshold
        self.iteration = 0
        self.root = root
        self.keep_history = keep_history
        if use_flat is None:
            # the sharded engine is plain XLA under shard_map, so a mesh
            # forces the flat path regardless of the kernel toggle
            use_flat = fusion_op in FLAT_OPS and (
                mesh is not None or ops.kernels_enabled())
        elif use_flat and fusion_op not in FLAT_OPS:
            raise ValueError(f"flat engine does not cover fusion_op={fusion_op!r}")
        if mesh is not None and not use_flat:
            raise ValueError("mesh= requires the flat engine "
                             f"(fusion_op={fusion_op!r}, use_flat={use_flat})")
        self.use_flat = use_flat
        self.mesh = mesh
        if mesh is not None:
            axes = SH.norm_axes(
                mesh.axis_names if mesh_axes is None else mesh_axes)
            missing = [a for a in axes if a not in mesh.axis_names]
            if missing:
                raise ValueError(f"mesh_axes {missing} not in mesh {mesh.axis_names}")
            self.mesh_axes = axes
            self._n_shards = SH.axes_extent(mesh, axes)
        else:
            self.mesh_axes = ()
            self._n_shards = 1
        if spill and not root:
            raise ValueError("spill=True requires an on-disk root")
        if spill and not use_flat:
            raise ValueError("spill=True requires the flat engine "
                             f"(fusion_op={fusion_op!r}, use_flat={use_flat})")
        self.spill = spill
        self.history: List[FusionRecord] = []
        # double-buffered staging: uploads fill the FRONT side; a dispatched
        # fuse owns the BACK side until it publishes (docs/async_repository.md)
        self._buffers = BufferPair()
        self._inflight: Optional[PendingFusion] = None
        self._snapshots: List[Any] = []
        self._spec: Optional[FlatSpec] = None
        self._sspec: Optional[ShardedFlatSpec] = None
        self._base_flat: Optional[jax.Array] = None
        # optional executor draining host-side spill writes off the upload path
        self._spill_pool = (
            ThreadPoolExecutor(max_workers=spill_workers,
                               thread_name_prefix="repo-spill")
            if spill and spill_workers > 0 else None)
        self._spill_futures: List[Future] = []
        self._row_futures: Dict[str, Future] = {}
        self._manifest_lock = threading.Lock()
        self._publish_lock = threading.Lock()
        self._persisted_iteration = -1
        # in-process publish subscribers (the fuse-to-serve hot path,
        # docs/serving.md): notified AFTER the iteration bump with a
        # consistent (iteration, base, flat) snapshot — raw cross-thread
        # polling of (iteration, _base) can pair iteration k with k+1's
        # weights because _publish_flat installs the base first
        self._publish_listeners: List[Any] = []
        # novelty admission state (docs/service_loop.md): None until the
        # service (or a caller) enables it via enable_cohort_sketch
        self.cohort_sketch: Optional[CohortSketch] = None
        # base-family membership (docs/service_loop.md): set by
        # RepositoryFamily; None for a standalone repository.  extra_meta
        # rides along in repository.json verbatim — the family manifest
        # lives there, and a plain open+publish must never drop it.
        self.family_name: Optional[str] = None
        self.extra_meta: Dict[str, Any] = {}
        if root:
            os.makedirs(root, exist_ok=True)
            self._persist_base()

    # -- staging-list views (front buffer) ------------------------------
    # The parallel per-contribution lists keep their historical names; they
    # always alias the FRONT side of the double buffer.
    @property
    def _pending(self) -> List[Any]:
        return self._buffers.front.rows

    @_pending.setter
    def _pending(self, v: List[Any]) -> None:
        self._buffers.front.rows = list(v)

    @property
    def _pending_fishers(self) -> List[Any]:
        return self._buffers.front.fishers

    @_pending_fishers.setter
    def _pending_fishers(self, v: List[Any]) -> None:
        self._buffers.front.fishers = list(v)

    @property
    def _pending_weights(self) -> List[Any]:
        return self._buffers.front.weights

    @_pending_weights.setter
    def _pending_weights(self, v: List[Any]) -> None:
        self._buffers.front.weights = list(v)

    # -- public staging introspection (service loop) --------------------
    @property
    def n_staged(self) -> int:
        """Rows staged in the front buffer (not yet part of any fuse)."""
        return len(self._buffers.front.rows)

    @property
    def inflight(self) -> bool:
        """True while a dispatched fuse awaits finalize/publish."""
        return self._inflight is not None

    def staged_spill_files(self) -> set:
        """Root-relative file names of every manifest-tracked staged row —
        front AND in-flight back cohort.  This is exactly the set a crash
        right now would recover, which is what lets the service loop decide
        'consumed' by set difference (docs/service_loop.md)."""
        with self._manifest_lock:
            return {e["file"] for e in self._buffers.manifest_entries()}

    # -- flat staging ---------------------------------------------------
    def _ensure_flat_base(self):
        if self._spec is None:
            self._spec = FlatSpec.from_tree(self._base)
        if self.mesh is not None and self._sspec is None:
            self._sspec = ShardedFlatSpec.from_spec(self._spec, self._n_shards)
        if self._base_flat is None:
            flat = self._spec.flatten(self._base)
            self._base_flat = self._stage_row(flat) if self.mesh is not None else flat

    def _stage_row(self, row: jax.Array) -> jax.Array:
        """[N] row -> its block-cyclic [S, shard_len] placement: each device
        receives only its own slice, at upload time — the full row never
        needs to exist on a fuse device."""
        return jax.device_put(
            self._sspec.shard(row), SH.flat_row_sharding(self.mesh, self.mesh_axes))

    def _load_staged_row(self, p):
        """A pending entry -> its staged array form.  In-memory rows pass
        through; spilled rows load from disk — per shard for the sharded
        layout (``FlatShardReader`` + ``stage_row_from_shards``: the host
        only ever holds one shard's slice, never the full [N] row), or as a
        portable [N] row for the flat layout (re-sharded by _stack_stage
        under a mesh)."""
        if not isinstance(p, str):
            return p
        fut = self._row_futures.pop(p, None)
        if fut is not None:
            fut.result()  # wait for (and surface errors from) THIS row's write
        # compressed before sharded: a sharded compressed file carries the
        # shard-spec entry too, and FlatShardReader has no buffers to read
        if ckpt.is_flat_compressed(p):
            # generic (non-fuse) access to a compressed submission — e.g.
            # recovery without spill, or a layout-mismatch restage: decode
            # the dense row against the current base.  The fuse itself
            # never takes this path (_stage_mixed keeps payloads sparse).
            payloads, meta = ckpt.load_flat_delta(p)
            row = self._decode_compressed_dense(payloads, meta)
            return self._stage_row(row) if self.mesh is not None else row
        if ckpt.is_flat_sharded(p):
            with ckpt.FlatShardReader(p) as r:
                if self.mesh is not None and r.sspec == self._sspec:
                    return SH.stage_row_from_shards(
                        self.mesh, self.mesh_axes, r.sspec.n_shards,
                        r.sspec.shard_len, r.shard)
                # layout mismatch (repository reopened under a different
                # mesh): fall back to host reassembly + restage
                row = jnp.asarray(r.full_row())
            return self._stage_row(row) if self.mesh is not None else row
        with self._stage_span("repo.spill_read"):
            row, _ = ckpt.load_flat(p, as_jax=False)
        with self._stage_span("repo.h2d"):
            return jnp.asarray(row)  # as load_flat(as_jax=True) puts it

    def _stack_stage(self, rows: List[jax.Array]) -> jax.Array:
        """Stack K staged rows into the fuse operand.  On a mesh the stack
        runs under jit with the staging out-sharding, so each device
        concatenates its local slices — the [K, N] buffer is never
        materialized on one device."""
        with self._stage_span("repo.stack"):
            if self.mesh is None:
                return _stack_plain_fn(len(rows))(*rows)
            rows = [r if r.ndim == 2 else self._stage_row(r)
                    for r in rows]  # [N] rows re-shard
            stack = _stack_fn(
                len(rows), SH.flat_stage_sharding(self.mesh, self.mesh_axes))
            return stack(*rows)

    def _fuse_flat(self, stage, weights, alpha, *, donate: bool):
        if isinstance(stage, MixedStage):
            return self._fuse_mixed(stage, weights, alpha)
        if self.mesh is not None:
            return ops.fuse_flat_sharded(
                self._base_flat, stage, weights, alpha,
                mesh=self.mesh, axes=self.mesh_axes)
        return ops.fuse_flat(self._base_flat, stage, weights, alpha, donate=donate)

    def _fuse_mixed(self, ms: MixedStage, weights, alpha):
        """Screen+fuse a mixed cohort: compressed deltas are decoded and
        accumulated on device in the same pass as the fuse — never into a
        dense ``[N]`` row per contributor — and the sq statistics come
        back scattered to cohort order, so ``_finalize_flat``'s screen and
        zero-weight re-pass see the same ``[K]`` layout as a dense fuse.
        Never donates: the payload stacks must survive a re-pass."""
        w = jnp.asarray(weights, jnp.float32)
        dpos = jnp.asarray(ms.dense_pos, jnp.int32)
        cpos = jnp.asarray(ms.comp_pos, jnp.int32)
        wc = jnp.take(w, cpos)
        dense = ms.dense if len(ms.dense_pos) else None
        wd = jnp.take(w, dpos) if len(ms.dense_pos) else None
        if self.mesh is not None:
            fused, sq_split = ops.fuse_flat_compressed_sharded(
                self._base_flat, ms.indices, ms.values, ms.scales, wc, alpha,
                mesh=self.mesh, axes=self.mesh_axes, block=ms.block,
                dense=dense, dense_weights=wd)
        else:
            fused, sq_split = ops.fuse_flat_compressed(
                self._base_flat, ms.indices, ms.values, ms.scales, wc, alpha,
                block=ms.block, dense=dense, dense_weights=wd)
        # sq_split is (dense..., compressed...); scatter back to cohort order
        perm = jnp.concatenate([dpos, cpos])
        sq = jnp.zeros((ms.k,), jnp.float32).at[perm].set(sq_split)
        return fused, sq

    def _decode_compressed_dense(self, payloads, meta, *, base=None):
        """Slow-path decode of a compressed submission to a dense host
        ``[N]`` row (layout/geometry fallbacks only): Δ scattered dense,
        plus ``base`` (default: the current base)."""
        if base is None:
            base = self.flat_base_host()
        if meta.get("sharded") and meta.get("shard_spec"):
            ss = ShardedFlatSpec.from_json(meta["shard_spec"])
            return jnp.asarray(delta_decode_sharded(payloads, ss, base))
        return jnp.asarray(delta_decode(payloads[0], base))

    def _decode_vs_declared(self, payloads, meta, declared: int):
        """Vintage-mismatch fallback (belt and braces under the service's
        admission pin): decode against the base the rider *declared*,
        loaded from its retained ``base_iterNNNN.npz`` — a compressed row
        is never decoded against a base it was not computed from."""
        path = (os.path.join(self.root, f"base_iter{declared:04d}.npz")
                if self.root else None)
        if path is None or not os.path.exists(path):
            raise ValueError(
                f"compressed row declares base_iteration={declared} but the "
                f"repository is at iteration {self.iteration} and "
                f"base_iter{declared:04d}.npz is not on disk — cannot decode "
                "(compact keep_bases must cover the declared vintage)")
        base = np.asarray(self._spec.flatten(ckpt.load(path)))
        return self._decode_compressed_dense(payloads, meta, base=base)

    def _publish_flat(self, fused: jax.Array):
        """Fused flat buffer -> the new base pytree (+ cached flat form)."""
        with trace.span("repo.publish", iteration=self.iteration):
            row = self._sspec.unshard(fused) if self.mesh is not None else fused
            self._base = self._spec.unflatten(row)
        self._base_flat = fused

    # -- publish subscription (fuse-to-serve hot path) ------------------
    def add_publish_listener(self, fn) -> None:
        """Register ``fn(iteration, base, flat)`` to run after every base
        movement — cohort publish, async contribution, and ``rollback``
        (where ``iteration`` moves *backwards*).  Called on whichever
        thread published, after the iteration bump, with a consistent
        snapshot: ``base`` is the immutable published pytree and ``flat``
        its cached flat form (``None`` when the engine keeps no flat
        cache, e.g. after a rollback restore).  Listeners must be cheap
        and must not raise; a ``ServingWorker`` stores the snapshot and
        does the device transfer on its own thread (docs/serving.md)."""
        self._publish_listeners.append(fn)

    def _notify_publish(self) -> None:
        for fn in list(self._publish_listeners):
            fn(self.iteration, self._base, self._base_flat)

    def _staging_iteration(self) -> int:
        """The iteration newly staged uploads belong to: one ahead of the
        repository while a fuse is in flight (its publish will advance
        ``iteration`` before the staged cohort fuses)."""
        return self.iteration + (1 if self._inflight is not None else 0)

    def _stage_span(self, name: str):
        """A trace span of the staging work, tagged with the iteration it
        stages for (read only while tracing is on)."""
        if not trace.enabled():
            return trace.span(name)
        return trace.span(name, iteration=self._staging_iteration())

    def _contrib_path(self, idx: int) -> str:
        return os.path.join(
            self.root,
            f"iter{self._staging_iteration():04d}_contrib{idx:03d}.npz")

    # -- spill manifest -------------------------------------------------
    def _manifest_path(self) -> str:
        return os.path.join(self.root, MANIFEST)

    def _write_manifest(self) -> None:
        """Persist the staged-but-unfused row list (back + front sides).
        Called with the row file already on disk, so a crash between row
        write and manifest write only loses the newest row — never records
        a row that does not exist."""
        ckpt.save_json_atomic(self._manifest_path(), {
            "version": 1,
            "entries": self._buffers.manifest_entries(),
        })

    def _spill_row(self, row: jax.Array, idx: int, weight) -> str:
        """Write one staged row to the npz root (per-shard slices under a
        mesh, portable [N] otherwise), then append it to the manifest —
        synchronously, or on the spill executor when ``spill_workers>0``."""
        path = self._contrib_path(idx)
        side = self._buffers.front
        spec, sspec, mesh = self._spec, self._sspec, self.mesh
        row_host = np.asarray(row)
        entry = {
            "file": os.path.basename(path),
            "idx": idx,
            # the iteration this row will fuse INTO the publish of: a
            # manifest entry with staged_at < the recorded repository
            # iteration was already consumed (its publish landed before the
            # manifest rewrite did) and recovery must skip it, or a crash
            # in that window would double-apply the cohort
            "staged_at": self._staging_iteration(),
            "weight": None if weight is None else float(weight),
            "dtype": spec.dtype,
            "size": spec.size,
            "sharded": mesh is not None,
        }
        if mesh is not None:
            entry["shard_spec"] = sspec.to_json()

        def write():
            if mesh is not None:
                ckpt.save_flat_shards(
                    path, sspec.shard_slices(row_host), spec, sspec)
            else:
                ckpt.save_flat(path, row_host, spec)
            with self._manifest_lock:
                side.manifest.append(entry)
                self._write_manifest()

        if self._spill_pool is not None:
            fut = self._spill_pool.submit(write)
            self._spill_futures.append(fut)
            # readback waits on exactly THIS row's write, not the whole
            # queue — the fuse's spill loads pipeline against the writer
            self._row_futures[path] = fut
        else:
            write()
        return path

    def _drain_spill(self) -> None:
        """Wait for ALL queued spill/publish writes (no-op when
        synchronous); re-raise the first failure so a lost row cannot be
        silently fused over."""
        futures, self._spill_futures = self._spill_futures, []
        self._row_futures.clear()
        for f in futures:
            f.result()

    # -- contributor-facing API ----------------------------------------
    def download(self):
        """Contributor pulls the current base model (Fig. 1, step 1).
        Finalizes any in-flight fuse first, so the published base is always
        the latest."""
        self._finalize_inflight()
        return self._base

    def upload(self, params, fisher=None, weight: Optional[float] = None) -> int:
        """Contributor pushes a finetuned model (Fig. 1, step 3), optionally
        with its diagonal Fisher (for fusion_op="fisher") and a contribution
        weight (§8 "assigning individual weights to each contributor" — e.g.
        dataset size; used when fusion_op="average"/"damped").  Returns a
        contribution ticket id.

        On the flat engine the pytree is folded into a contiguous staging
        row right here and released — the Repository never holds K live
        pytrees.  Rows stage into the FRONT buffer, so uploads proceed while
        an async fuse runs on the back buffer.  With ``spill=True`` the row
        goes to the npz root instead (atomic write + manifest append: the
        row survives a crash) and only its path stays in memory."""
        side = self._buffers.front
        idx = len(side.rows)
        if self.use_flat:
            self._ensure_flat_base()
            row = self._spec.flatten(params)
            if self.spill:
                side.rows.append(self._spill_row(row, idx, weight))
            else:
                if self.root:
                    # archived contribution stays the portable [N] form
                    ckpt.save_flat(self._contrib_path(idx), row, self._spec)
                side.rows.append(
                    self._stage_row(row) if self.mesh is not None else row)
        else:
            side.rows.append(params)
            if self.root:
                ckpt.save(self._contrib_path(idx), params)
        side.fishers.append(fisher)
        side.weights.append(weight)
        return idx

    def ingest_spilled(self, path: str, *, weight: Optional[float] = None,
                       meta: Optional[Dict[str, Any]] = None) -> int:
        """Queue-ingest entry point (docs/service_loop.md): register an
        already-on-disk flat row — e.g. a contribution-queue submission —
        as a staged contribution **without copying it**.  The file itself
        becomes the spill row: its (root-relative) path is appended to the
        staging manifest atomically, so from this call on the row enjoys
        the same exactly-once crash guarantees as a spilled ``upload``
        (recovered by ``open``, retired only by the publish that consumed
        it).  The row's recorded FlatSpec is validated against the base;
        torn or mismatched files raise without touching the manifest.

        Requires ``spill=True`` — without the manifest there is nothing to
        make the hand-off durable.  ``meta=`` accepts a pre-read
        ``flat_row_meta`` result (the service's admission peek) so the row
        header is not parsed twice.  Returns the contribution ticket id."""
        if not self.spill:
            raise ValueError("ingest_spilled requires spill=True — the "
                             "staging manifest is what makes queue ingest "
                             "crash-safe")
        self._ensure_flat_base()
        path = os.path.abspath(path)
        rel = os.path.relpath(path, os.path.abspath(self.root))
        if rel.startswith(".."):
            raise ValueError(f"ingested rows must live under root= "
                             f"({path} is outside {self.root})")
        if meta is None:
            meta = ckpt.flat_row_meta(path)  # raises on torn / non-flat files
        if meta["dtype"] != self._spec.dtype or int(meta["size"]) != self._spec.size:
            raise ValueError(
                f"row {os.path.basename(path)} has FlatSpec(dtype="
                f"{meta['dtype']}, N={meta['size']}) but the repository base "
                f"is (dtype={self._spec.dtype}, N={self._spec.size}) — "
                "refusing to ingest a mismatched row")
        side = self._buffers.front
        idx = len(side.rows)
        entry = {
            "file": rel.replace(os.sep, "/"),
            "idx": idx,
            "staged_at": self._staging_iteration(),
            "weight": None if weight is None else float(weight),
            "dtype": self._spec.dtype,
            "size": self._spec.size,
            "sharded": bool(meta["sharded"]),
        }
        if meta.get("shard_spec"):
            entry["shard_spec"] = meta["shard_spec"]
        if meta.get("compressed"):
            # by-reference compressed staging: the queue npz holds the
            # DeltaPayload(s), decoded only at dispatch.  The declared
            # vintage rides in the manifest so dispatch and recovery can
            # re-check it (a delta only means anything against the exact
            # base it was computed from — docs/service_loop.md).
            entry["compressed"] = True
            entry["codec"] = meta.get("delta_spec")
            extra = meta.get("extra") or {}
            bi = extra.get("base_iteration")
            if bi is not None:
                entry["base_iteration"] = int(bi)
            # family-vintage backstop: a delta is only decodable against
            # the exact base it was encoded from, and under a base family
            # that base is named.  The service's routed admission rejects
            # cross-family deltas before ingest; this guard makes the
            # invariant unconditional for direct callers too.
            if self.family_name is not None:
                declared = str(extra.get("family") or "main")
                if declared != self.family_name:
                    raise ValueError(
                        f"stale: delta encoded against family "
                        f"{declared!r}, but this member is "
                        f"{self.family_name!r} — refusing to decode "
                        "against the wrong base")
                entry["family"] = declared
        side.rows.append(path)
        side.fishers.append(None)
        side.weights.append(weight)
        with self._manifest_lock:
            side.manifest.append(entry)
            self._write_manifest()
        return idx

    # -- novelty admission sketch (docs/service_loop.md) -----------------
    def _sketch_path(self) -> str:
        return os.path.join(self.root, SKETCH_FILE)

    def enable_cohort_sketch(self, *, window: int = 32,
                             n_buckets: int = SKETCH_BUCKETS) -> CohortSketch:
        """Create (or adopt) the persisted ``CohortSketch`` the novelty
        admission screen queries.  An on-disk ``cohort_sketch.json``
        (recovered by ``open``) is reused when its layout matches —
        ``window`` always follows the caller (the admission policy wins
        over whatever a previous service instance ran with) — otherwise a
        fresh sketch is built.  The current base's sketch is computed and
        the state persisted atomically before returning, so the screen's
        history is durable from the first admission on."""
        if not self.use_flat:
            raise ValueError("cohort sketch requires the flat engine — the "
                             "row sketch is a statistic over flat [N] rows")
        self._ensure_flat_base()
        sk = self.cohort_sketch
        if sk is not None and (sk.size != self._spec.size
                               or sk.n_buckets != n_buckets):
            warnings.warn(
                f"cohort sketch (size={sk.size}, n_buckets={sk.n_buckets}) "
                f"does not match the requested layout (size="
                f"{self._spec.size}, n_buckets={n_buckets}) — rebuilding; "
                "the screen history restarts empty")
            sk = None
        if sk is None:
            sk = CohortSketch(self._spec.size, n_buckets, window)
        else:
            sk.window = int(window)
            del sk.entries[: -sk.window]
        self.cohort_sketch = sk
        self._refresh_base_sketch()
        return sk

    def save_cohort_sketch(self) -> None:
        """Persist the cohort sketch with the manifest's atomic-write
        discipline (no-op for an in-memory repository or before
        ``enable_cohort_sketch``)."""
        if self.cohort_sketch is not None and self.root:
            # compact form: this file is rewritten once per admission, and
            # it is machine state (nobody diffs a sketch by eye)
            ckpt.save_json_atomic(self._sketch_path(),
                                  self.cohort_sketch.to_json(), indent=None)

    def _sketch_of_staged(self, arr) -> np.ndarray:
        """Sketch a staged row — ``[N]`` single-device or ``[S, shard_len]``
        block-cyclic (per-shard partials, one psum) — to host float32."""
        nb = (self.cohort_sketch.n_buckets if self.cohort_sketch is not None
              else SKETCH_BUCKETS)
        if getattr(arr, "ndim", 1) == 2:
            out = ops.row_sketch_sharded(
                arr, mesh=self.mesh, axes=self.mesh_axes,
                block=self._sspec.block, n_buckets=nb)
        else:
            out = ops.row_sketch(arr, nb)
        return np.asarray(jax.device_get(out))

    def _refresh_base_sketch(self) -> None:
        """Recompute the base's sketch (the screen's distance
        normalizer) and persist — called at every publish so a restarted
        daemon screens against the same scale.  The sketch file is
        advisory state: a crash that loses this write only leaves the
        previous base's sketch as the normalizer, never double-fuses.
        No-op on the per-leaf engine (a repository reopened there keeps
        its recovered sketch history untouched for the next flat run)."""
        if self.cohort_sketch is None or not self.use_flat:
            return
        self._ensure_flat_base()  # rebuilt lazily after publish/rollback
        self.cohort_sketch.set_base(self._sketch_of_staged(self._base_flat),
                                    iteration=self.iteration)
        self.save_cohort_sketch()

    def sketch_row_file(self, path: str, *, meta: Optional[Dict[str, Any]] = None
                        ) -> np.ndarray:
        """Content sketch of an on-disk flat row (a queue submission), in
        one read: sharded files matching the mesh layout are sketched
        per shard with a single psum (the full ``[N]`` row never
        materializes on host); everything else reads the portable row.
        Raises on torn/unreadable files — callers quarantine like any
        other unreadable submission.  ``meta=`` reuses a pre-read
        ``flat_row_meta`` peek (skips re-opening the npz header)."""
        self._ensure_flat_base()
        compressed = (ckpt.is_flat_compressed(path) if meta is None
                      else bool(meta.get("compressed")))
        if compressed:
            return self.sketch_delta_file(path)
        sharded = (ckpt.is_flat_sharded(path) if meta is None
                   else bool(meta["sharded"]))
        if not sharded:
            row, _ = ckpt.load_flat(path)
            return self._sketch_of_staged(row)
        return self._sketch_of_staged(self._load_staged_row(path))

    def sketch_delta_file(self, path: str, *,
                          meta: Optional[Dict[str, Any]] = None) -> np.ndarray:
        """Content sketch of a delta-compressed submission without ever
        materializing its dense row: the current base's sketch is
        corrected bucket-wise from the sparse decoded delta
        (``repro.utils.flat.sketch_apply_delta``), reading base values
        only at the delta's own indices.  Matches ``row_sketch_host`` of
        the decoded row up to float rounding, so the novelty screen's
        distances are interchangeable between dense and compressed
        submissions."""
        del meta  # the payload load re-reads the header regardless
        self._ensure_flat_base()
        payloads, dmeta = ckpt.load_flat_delta(path)
        nb = (self.cohort_sketch.n_buckets if self.cohort_sketch is not None
              else SKETCH_BUCKETS)
        if (self.cohort_sketch is not None
                and self.cohort_sketch.base is not None):
            base_sk = np.asarray(self.cohort_sketch.base, np.float64)
        else:
            base_sk = self._sketch_of_staged(self._base_flat).astype(np.float64)
        gis: List[np.ndarray] = []
        dvs: List[np.ndarray] = []
        if bool(dmeta["delta_spec"].get("sharded")):
            ss = ShardedFlatSpec.from_json(dmeta["shard_spec"])
            for s, p in enumerate(payloads):
                li, dv = delta_entries(p)
                gi = ss.global_of(s, li)
                keep = gi < self._spec.size  # drop block-grid padding slots
                gis.append(gi[keep])
                dvs.append(dv[keep])
        else:
            li, dv = delta_entries(payloads[0])
            gis.append(np.asarray(li, np.int64))
            dvs.append(dv)
        gi = np.concatenate(gis) if gis else np.zeros((0,), np.int64)
        dv = np.concatenate(dvs) if dvs else np.zeros((0,), np.float32)
        base_at = self.flat_base_host()[gi]
        sk = sketch_apply_delta(base_sk, gi, dv, base_at, n_buckets=nb)
        return np.asarray(sk, np.float32)

    def contribute_async(self, params, *, alpha: Optional[float] = None) -> FusionRecord:
        """Asynchronous contribution (paper §8: "it would be beneficial if
        the repository was updated asynchronously"): immediately merge ONE
        finetuned model into the base via a damped task-arithmetic update
        θ ← θ + α·(θ_c − θ), without waiting for a cohort (Ilharco et al.
        2022).  α defaults to 1/(1 + iteration) — early contributions move
        the base more, later ones refine it (Polyak-style averaging).

        On the flat engine this is one streaming kernel pass: the same
        launch yields the merged model and the screening norm; if the screen
        rejects, the merged buffer is simply discarded."""
        self.flush()  # quiesce: its publish below must not race queued writes
        a = alpha if alpha is not None else 1.0 / (1.0 + self.iteration)
        t0, t_fin = time.time(), time.perf_counter()
        if self.use_flat:
            self._ensure_flat_base()
            row = self._spec.flatten(params)
            if self.mesh is not None:
                stage = self._stage_row(row)[None]
            else:
                stage = row[None, :]
            fused, sq = self._fuse_flat(stage, jnp.ones((1,), jnp.float32), a,
                                        donate=False)
            if self.screen:
                norm = norms_from_sq(jax.device_get(sq))[0]
                report = screen_norms([norm], mad_threshold=self.mad_threshold)
                if not report.accepted:
                    raise NothingToFuse(f"async contribution rejected: {report.reasons}")
            fused.block_until_ready()
            if self.mesh is not None:
                new_base = self._spec.unflatten(self._sspec.unshard(fused))
            else:
                new_base = self._spec.unflatten(fused)
            new_flat = fused
        else:
            if self.screen:
                report = screen_contributions(
                    self._base, [params], mad_threshold=self.mad_threshold)
                if not report.accepted:
                    raise NothingToFuse(f"async contribution rejected: {report.reasons}")
            new_base = fusion.damped(self._base, [params], alpha=a)
            new_flat = None
        rec = FusionRecord(
            iteration=self.iteration, n_contributions=1, n_accepted=1,
            op=f"async-damped({a:.3f})", diff_norms=[], wall_time=time.time() - t0,
            finalize_s=time.perf_counter() - t_fin,
        )
        self.history.append(rec)
        if self.keep_history:
            self._snapshots.append(self._base)
        self._base = new_base
        self._base_flat = new_flat
        self.iteration += 1
        self._refresh_front_staging()
        if self.root:
            self._persist_base()
            if self.spill or os.path.exists(self._manifest_path()):
                with self._manifest_lock:
                    self._write_manifest()
        self._refresh_base_sketch()  # async publishes move the base too
        self._notify_publish()
        return rec

    # -- repository maintenance ----------------------------------------
    def fuse_pending(
        self,
        buffer: Optional[Union[StagedBuffer, jax.Array]] = None,
        *,
        wait: bool = True,
        alpha: Optional[float] = None,
        screen: Optional[bool] = None,
        op: Optional[str] = None,
    ) -> Union[FusionRecord, PendingFusion]:
        """Screen + fuse a cohort into the new base (Fig. 1, step 4).

        With no arguments: swap the front staging buffer to the back and
        fuse it (finalizing any previously in-flight fuse first).
        ``wait=False`` dispatches the screen+fuse to the device and returns
        a ``PendingFusion`` immediately — uploads of the next cohort then
        overlap the device fuse; ``flush()`` (or the next ``fuse_pending``
        / ``download``) finalizes and publishes.  On the per-leaf engine
        ``wait`` is ignored (the oracle path is synchronous).

        ``buffer=`` fuses an explicit staged operand instead — a
        ``StagedBuffer`` handle (or raw ``[K, N]`` / sharded
        ``[K, S, shard_len]`` array) prepared by the caller; the front
        staging buffer is left untouched.  ``alpha=`` overrides the
        per-op step size, ``screen=`` overrides the §9 screen, and
        ``op=`` relabels the FusionRecord — the family cross-fuse uses
        all three (member bases are not a contributor cohort); they are
        only meaningful with ``buffer=``."""
        self._finalize_inflight()
        if buffer is not None:
            return self._fuse_buffer(buffer, wait=wait, alpha=alpha,
                                     screen=screen, op=op)
        if alpha is not None or screen is not None or op is not None:
            raise ValueError("alpha=/screen=/op= overrides require buffer=")
        if not self._pending:
            raise NothingToFuse("no contributions to fuse")
        t0, t_fin = time.time(), time.perf_counter()
        if not self.use_flat:
            with self._manifest_lock:
                back = self._buffers.swap()
            self._mark_back_fusing()
            try:
                rec = self._fuse_pending_pytree(t0, back)
                rec.finalize_s = time.perf_counter() - t_fin
            except Exception:
                self._restore_back()
                raise
            self._retire_back()
            self._after_publish(rec)
            return rec
        with self._manifest_lock:  # workers read both sides via manifest_entries
            back = self._buffers.swap()
        try:
            pf = self._dispatch_flat(back, t0)
        except Exception:
            self._restore_back()
            raise
        self._inflight = pf
        if wait:
            return self._finalize_inflight()
        return pf

    def flush(self) -> Optional[FusionRecord]:
        """Quiesce the repository: finalize the in-flight fuse, if any,
        and drain every queued spill/publish write.  Returns the finalized
        FusionRecord (None when nothing was in flight)."""
        rec = self._finalize_inflight()
        self._drain_spill()
        return rec

    def _finalize_inflight(self) -> Optional[FusionRecord]:
        """Finalize the in-flight fuse: block on the screening statistic,
        run the weight-zeroed re-pass for rejections, publish the fused
        base, and advance the iteration.  Queued spill writes keep
        draining on the executor — only ``flush()`` waits for them."""
        pf, self._inflight = self._inflight, None
        if pf is None:
            return None
        try:
            rec = self._finalize_flat(pf)
        except Exception:
            # cohort not published: return its rows to the front buffer so
            # they are retried (diluted by new uploads) rather than lost
            self._restore_back()
            raise
        self._retire_back()
        self._after_publish(rec)
        return rec

    def _dispatch_flat(self, back: StagingSide, t0: float) -> PendingFusion:
        """Issue pass 1 (fused + sq_diff in one read of the staged buffer)
        without blocking: jax dispatch is asynchronous, so the device
        crunches while the host stages the next cohort.  The buffer is kept
        alive (no donation) only if a screening re-pass might need it."""
        t_stage = time.perf_counter()
        self._ensure_flat_base()
        K = len(back.rows)
        stage = self._stage_cohort(back)
        w = self._cohort_weights(K, back.weights)
        alpha = self._flat_alpha(K)
        mixed = isinstance(stage, MixedStage)
        fused, sq = self._fuse_flat(stage, w, alpha,
                                    donate=not self.screen and not mixed)
        try:
            # start moving the [K] screening statistic to the host as soon
            # as the fuse produces it, so finalize's device_get is a
            # handshake rather than a transfer
            sq.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # sharded/older arrays may not support it; finalize copies
        # every back row's spill write (and manifest append) has completed
        # by now — _load_staged_row waited on the per-row futures — so the
        # in-flight mark covers the whole cohort
        self._mark_back_fusing()
        return PendingFusion(
            stage=stage if self.screen else None,
            fused=fused, sq=sq, weights=w, k=K, t0=t0,
            stage_s=time.perf_counter() - t_stage)

    def _stage_cohort(self, back: StagingSide):
        """Build the fuse operand for the back cohort.  All-dense cohorts
        take the historical path unchanged (a stacked ``StagedBuffer``,
        donation-eligible); any delta-compressed submission among the rows
        yields a ``MixedStage`` instead."""
        with self._stage_span("repo.stage"):
            for p in back.rows:
                fut = self._row_futures.get(p) if isinstance(p, str) else None
                if fut is not None:
                    fut.result()  # the peek below reads the spilled file
            if any(isinstance(p, str) and ckpt.is_flat_compressed(p)
                   for p in back.rows):
                return self._stage_mixed(back)
            rows = [self._load_staged_row(p) for p in back.rows]
            return StagedBuffer(self._stack_stage(rows))

    def _stage_mixed(self, back: StagingSide):
        """Partition the back cohort into dense rows and compressed payload
        stacks.  Compressed rows ride sparse on the fast path only when
        their declared vintage matches the current iteration (the
        service's admission pin; re-checked here belt-and-braces), their
        layout matches the repository (sharded payloads on a matching
        mesh, whole-row payloads single-device), and their codec geometry
        agrees across the cohort — anything else host-decodes to a dense
        row against the correct base and joins the dense side."""
        entries = {e.get("file"): e for e in back.manifest}
        root = os.path.abspath(self.root) if self.root else None
        dense_rows: List[Any] = []
        dense_pos: List[int] = []
        payload_sets: List[list] = []
        comp_pos: List[int] = []
        geom = None
        for i, p in enumerate(back.rows):
            if not (isinstance(p, str) and ckpt.is_flat_compressed(p)):
                dense_rows.append(self._load_staged_row(p))
                dense_pos.append(i)
                continue
            fut = self._row_futures.pop(p, None)
            if fut is not None:
                fut.result()
            payloads, meta = ckpt.load_flat_delta(p)
            rel = (os.path.relpath(p, root).replace(os.sep, "/")
                   if root else None)
            entry = entries.get(rel, {})
            declared = entry.get(
                "base_iteration",
                (meta.get("extra") or {}).get("base_iteration"))
            if declared is not None and int(declared) != self.iteration:
                dense_rows.append(
                    self._decode_vs_declared(payloads, meta, int(declared)))
                dense_pos.append(i)
                continue
            sharded_payload = bool(meta["delta_spec"].get("sharded"))
            if self.mesh is not None:
                fast = (sharded_payload
                        and meta.get("shard_spec") is not None
                        and ShardedFlatSpec.from_json(meta["shard_spec"])
                        == self._sspec)
            else:
                fast = not sharded_payload
            p0 = payloads[0]
            this = (len(payloads), p0.block, p0.k_per_block, p0.n_blocks)
            if fast and geom is None:
                geom = this
            elif this != geom:
                fast = False
            if fast:
                payload_sets.append(payloads)
                comp_pos.append(i)
            else:
                dense_rows.append(self._decode_compressed_dense(payloads, meta))
                dense_pos.append(i)
        if not comp_pos:
            # every compressed row fell back dense (positions stayed in
            # cohort order, so a plain stacked buffer is exact)
            return StagedBuffer(self._stack_stage(dense_rows))
        if self.mesh is not None:
            idx = np.stack([[q.indices for q in pl] for pl in payload_sets])
            val = np.stack([[q.values for q in pl] for pl in payload_sets])
            scl = np.stack([[q.scales for q in pl] for pl in payload_sets])
        else:
            idx = np.stack([pl[0].indices for pl in payload_sets])
            val = np.stack([pl[0].values for pl in payload_sets])
            scl = np.stack([pl[0].scales for pl in payload_sets])
        dense_stage = (StagedBuffer(self._stack_stage(dense_rows))
                       if dense_rows else None)
        return MixedStage(
            dense=dense_stage,
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            scales=jnp.asarray(scl), block=geom[1],
            dense_pos=np.asarray(dense_pos, np.int32),
            comp_pos=np.asarray(comp_pos, np.int32))

    def _finalize_flat(self, pf: PendingFusion) -> FusionRecord:
        """The host half of the screen+fuse: pull sq_diff (the only device
        sync), apply the §9 decision rule, re-pass with zeroed weights on
        rejections, and publish."""
        t_fin = time.perf_counter()
        with trace.span("repo.finalize", iteration=self.iteration):
            fused = pf.fused
            report: Optional[ScreenReport] = None
            n_accepted = pf.k
            use_screen = self.screen if pf.use_screen is None else pf.use_screen
            if use_screen:
                with trace.span("repo.screen_sync", iteration=self.iteration):
                    sq = jax.device_get(pf.sq)
                norms = norms_from_sq(sq)
                report = screen_norms(norms, mad_threshold=self.mad_threshold)
                n_accepted = len(report.accepted)
                if not report.accepted:
                    raise NothingToFuse(
                        f"all contributions rejected: {report.reasons}")
                if report.rejected:
                    w2 = np.asarray(jax.device_get(pf.weights), np.float32).copy()
                    w2[report.rejected] = 0.0
                    alpha = (self._flat_alpha(n_accepted) if pf.alpha is None
                             else pf.alpha)
                    fused, _ = self._fuse_flat(
                        pf.stage, jnp.asarray(w2), alpha, donate=True)
            fused.block_until_ready()
            rec = FusionRecord(
                iteration=self.iteration,
                n_contributions=pf.k,
                n_accepted=n_accepted,
                op=pf.op or self.fusion_op,
                diff_norms=report.diff_norms if report else [],
                wall_time=time.time() - pf.t0,
                stage_s=pf.stage_s,
            )
            if self.keep_history:
                self._snapshots.append(self._base)
            self._publish_flat(fused)
        rec.finalize_s = time.perf_counter() - t_fin
        pf.record = rec
        return rec

    def _fuse_buffer(self, buffer, *, wait: bool,
                     alpha: Optional[float] = None,
                     screen: Optional[bool] = None,
                     op: Optional[str] = None,
                     ) -> Union[FusionRecord, PendingFusion]:
        """Fuse an explicit staged operand (``fuse_pending(buffer=...)``)."""
        if not self.use_flat:
            raise ValueError("fuse_pending(buffer=...) requires the flat engine")
        self._ensure_flat_base()
        if not isinstance(buffer, StagedBuffer):
            buffer = StagedBuffer(jnp.asarray(buffer))
        if self.mesh is not None:
            want = (self._sspec.n_shards, self._sspec.shard_len)
            if buffer.data.shape[1:] != want:
                raise ValueError(
                    f"staged buffer shape {buffer.data.shape} does not match "
                    f"the sharded layout [K, {want[0]}, {want[1]}]")
        elif buffer.data.shape[1:] != (self._spec.size,):
            raise ValueError(
                f"staged buffer shape {buffer.data.shape} does not match "
                f"the flat layout [K, {self._spec.size}]")
        t0, t_stage = time.time(), time.perf_counter()
        K = buffer.k
        w = self._cohort_weights(K, [])
        use_screen = self.screen if screen is None else bool(screen)
        a = self._flat_alpha(K) if alpha is None else float(alpha)
        # never donate here: the operand belongs to the CALLER (unlike the
        # freshly stacked buffer in _dispatch_flat) and must stay valid
        fused, sq = self._fuse_flat(buffer, w, a, donate=False)
        pf = PendingFusion(
            stage=buffer if use_screen else None,
            fused=fused, sq=sq, weights=w, k=K, t0=t0,
            stage_s=time.perf_counter() - t_stage,
            alpha=None if alpha is None else float(alpha),
            use_screen=None if screen is None else use_screen, op=op)
        if not wait:
            self._inflight = pf
            return pf
        rec = self._finalize_flat(pf)
        self._after_publish(rec)
        return rec

    def _retire_back(self) -> None:
        """Drop the consumed back buffer.  Its manifest entries are NOT
        rewritten here: the manifest may only forget a cohort once the new
        base is durably on disk, so the rewrite is sequenced after the base
        persist in ``_after_publish`` (on the spill executor when one is
        configured)."""
        with self._manifest_lock:  # workers read both sides via manifest_entries
            self._buffers.retire_back()

    def _mark_back_fusing(self) -> None:
        """Stamp the back cohort's manifest entries as in-flight and
        persist the mark.  Recovery may treat an entry as consumed ONLY if
        it carries this mark AND the recorded iteration moved past its
        ``staged_at`` — unconsumed front rows can share the same staged_at
        (e.g. around a ``contribute_async`` publish) and must never be
        skipped."""
        back = self._buffers.back
        if back is None or not back.manifest:
            return
        with self._manifest_lock:
            for e in back.manifest:
                e["fusing"] = True
            if self.root and (self.spill
                              or os.path.exists(self._manifest_path())):
                self._write_manifest()

    def _restore_back(self) -> None:
        """Un-swap after a failed fuse: the back cohort returns to the head
        of the front buffer (in-flight marks dropped), so nothing staged is
        lost."""
        with self._manifest_lock:
            back = self._buffers.back
            if back is None:
                return
            for e in back.manifest:
                e.pop("fusing", None)
            front = self._buffers.front
            back.rows.extend(front.rows)
            back.fishers.extend(front.fishers)
            back.weights.extend(front.weights)
            back.manifest.extend(front.manifest)
            self._buffers.front = back
            self._buffers.back = None

    def _refresh_front_staging(self) -> None:
        """Pending (front) rows survive publishes they did not take part
        in: re-stamp their manifest entries to the next staging iteration,
        so recovery never mistakes them for a consumed cohort.  Callers
        hold no lock; the stamp is a plain dict write raced only by
        ``_write_manifest`` readers, which tolerate either value."""
        for e in self._buffers.front.manifest:
            e["staged_at"] = self._staging_iteration()

    def _after_publish(self, rec: FusionRecord) -> None:
        self.history.append(rec)
        self.iteration += 1
        self._refresh_front_staging()
        if not self.root:
            return
        if self._spill_pool is not None:
            # drain the publish write on the spill executor too: the base
            # npz + repository.json leave the fuse critical path.  State is
            # captured by value (the pytree is immutable), so later host
            # mutations cannot race the write; the manifest rewrite is
            # sequenced AFTER the base persist inside the same task.  A
            # crash before the persist recovers the cohort against the
            # previous base; a crash between persist and rewrite is caught
            # by the staged_at marker (the recorded iteration moved past
            # the entries, so recovery skips them instead of re-applying).
            it, base, meta = self.iteration, self._base, self._render_meta()
            def task():
                with trace.span("repo.persist", iteration=rec.iteration):
                    self._persist_base(it, base, meta)
                faults.crash_point("repo.post_publish_pre_manifest")
                with self._manifest_lock:
                    self._write_manifest()
            self._spill_futures.append(self._spill_pool.submit(task))
        else:
            with trace.span("repo.persist", iteration=rec.iteration):
                self._persist_base()
            faults.crash_point("repo.post_publish_pre_manifest")
            if self.spill or os.path.exists(self._manifest_path()):
                # the second arm: a non-spill reopen that fused recovered
                # rows must still retire them from the manifest, or a later
                # spill=True reopen would re-apply the cohort
                with self._manifest_lock:
                    self._write_manifest()
        # the novelty screen's normalizer tracks the published base
        # (docs/service_loop.md); runs after the durability-critical writes
        # because the sketch is advisory — a crash here costs at most one
        # stale-scale admission decision, never a double fuse
        self._refresh_base_sketch()
        self._notify_publish()

    def _cohort_weights(self, K: int, staged_weights: Sequence[Any]) -> jnp.ndarray:
        """Per-contributor weights for the flat engine (average/damped)."""
        kw = self.fusion_kwargs
        if self.fusion_op in ("average", "damped"):
            if "weights" in kw:
                w = list(kw["weights"])
                if len(w) != K:
                    raise ValueError(f"len(fusion_kwargs['weights'])={len(w)} != K={K}")
                return jnp.asarray(w, jnp.float32)
            if staged_weights and all(w is not None for w in staged_weights):
                return jnp.asarray(list(staged_weights), jnp.float32)
        return jnp.ones((K,), jnp.float32)

    def _flat_alpha(self, n_effective: int) -> float:
        """The kernel's damping coefficient for the configured operator."""
        if self.fusion_op == "damped":
            return float(self.fusion_kwargs.get("alpha", 1.0))
        if self.fusion_op == "task_arithmetic":
            # θ + λ·Σ(θ_c − θ) == θ + (λ·K)·(mean − θ)
            return float(self.fusion_kwargs.get("lam", 1.0)) * n_effective
        return 1.0

    def _fuse_pending_pytree(self, t0: float, back: StagingSide) -> FusionRecord:
        """The seed per-leaf engine (REPRO_NO_KERNELS oracle; also serves
        the operators the kernel does not cover)."""
        models = back.rows
        report: Optional[ScreenReport] = None
        fishers = back.fishers
        weights = back.weights
        if self.screen:
            report = screen_contributions(self._base, models, mad_threshold=self.mad_threshold)
            models = [models[i] for i in report.accepted]
            fishers = [fishers[i] for i in report.accepted]
            weights = [weights[i] for i in report.accepted]
            if not models:
                raise NothingToFuse(f"all contributions rejected: {report.reasons}")
        kw = dict(self.fusion_kwargs)
        if self.fusion_op == "fisher":
            if any(f is None for f in fishers):
                raise RuntimeError("fusion_op='fisher' requires upload(..., fisher=...)")
            kw["fishers"] = fishers
        elif (self.fusion_op in ("average", "damped") and "weights" not in kw
              and all(w is not None for w in weights) and weights):
            kw["weights"] = weights
        new_base = fusion.fuse(self.fusion_op, self._base, models, **kw)
        rec = FusionRecord(
            iteration=self.iteration,
            n_contributions=len(back.rows),
            n_accepted=len(models),
            op=self.fusion_op,
            diff_norms=report.diff_norms if report else [],
            wall_time=time.time() - t0,
        )
        if self.keep_history:
            self._snapshots.append(self._base)
        self._base = new_base
        self._base_flat = None
        return rec

    def rollback(self, to_iteration: int, *, keep_staged: bool = False):
        """Paper §8: "backtracking when a harmful update was done".  Any
        in-flight fuse is finalized first.

        The restore source is the in-memory ``keep_history`` snapshot when
        one exists, else the ``compact``-retained on-disk
        ``base_iterNNNN.npz`` — so a service that keeps no pytree history
        can still back out a harmful publish (the regression gate,
        docs/observability.md).  Missing both raises without touching any
        state.

        ``keep_staged=False`` (the historical behavior) drops the staged
        front cohort with the history; ``keep_staged=True`` preserves it —
        staged-but-unfused rows are re-stamped to the rolled-back staging
        iteration, so a gate-tripped publish never loses the *next*
        cohort's admitted rows.

        Crash safety (on-disk repositories): the restored base's npz
        already exists, so the single commit point is the atomic
        ``repository.json`` rewrite.  A kill -9 before it leaves the old
        (pre-rollback) state for the caller to re-detect and retry — the
        whole sequence is idempotent; a kill -9 after it reopens at the
        rolled-back base.  The ``repo.mid_rollback`` seam sits between
        that commit and the staging-manifest rewrite: entries persisted
        with a pre-rollback ``staged_at`` carry no ``fusing`` mark, so
        recovery re-stages them regardless of the stamp."""
        self.flush()  # quiesce: queued manifest/publish writes must settle
        if not (0 <= to_iteration <= self.iteration):
            raise ValueError(
                f"cannot roll back to iteration {to_iteration} from "
                f"{self.iteration}")
        if self.keep_history and to_iteration < len(self._snapshots):
            base = self._snapshots[to_iteration]
        elif self.root is not None:
            path = os.path.join(self.root, f"base_iter{to_iteration:04d}.npz")
            if not os.path.exists(path):
                raise ValueError(
                    f"no snapshot for iteration {to_iteration}: not in "
                    f"memory (keep_history={self.keep_history}) and "
                    f"{os.path.basename(path)} is not on disk — was it "
                    "compacted away? (compact keep_bases must cover the "
                    "rollback depth)")
            base = ckpt.load(path)
            if self._spec is not None:
                rspec = FlatSpec.from_tree(base)
                if rspec.dtype != self._spec.dtype or rspec.size != self._spec.size:
                    raise ValueError(
                        f"{os.path.basename(path)} loads as FlatSpec(dtype="
                        f"{rspec.dtype}, N={rspec.size}) but the repository "
                        f"base is (dtype={self._spec.dtype}, "
                        f"N={self._spec.size}) — refusing to roll back onto "
                        "a mismatched base")
        elif not self.keep_history:
            raise RuntimeError(
                "rollback requires keep_history=True or an on-disk root")
        else:
            raise ValueError(f"no snapshot for iteration {to_iteration}")
        self._base = base
        self._base_flat = None
        self._snapshots = self._snapshots[:to_iteration]
        self.history = self.history[:to_iteration]
        self.iteration = to_iteration
        # the publish guard must follow the regression or later (smaller-
        # iteration) publishes would be skipped as stale
        self._persisted_iteration = min(self._persisted_iteration, to_iteration)
        if keep_staged:
            # the front cohort survives the rollback; its manifest entries
            # follow the new staging iteration like any other publish
            self._refresh_front_staging()
        else:
            self._buffers = BufferPair()
        if self.root:
            # commit point: repository.json now names the rolled-back
            # iteration (its base npz is already durable — it is the
            # restore source, or the snapshot is re-persisted here)
            self._persist_base()
            faults.crash_point("repo.mid_rollback")
        if self.spill and self.root:
            with self._manifest_lock:
                self._write_manifest()
        self._refresh_base_sketch()  # the screen's normalizer moved too
        self._notify_publish()

    def flat_base_host(self) -> np.ndarray:
        """The current base as a host ``[N]`` float row (the form probe
        suites score).  Requires the flat engine."""
        self._ensure_flat_base()
        return np.asarray(self._spec.flatten(self._base))

    def snapshot(self, iteration: int):
        return self._snapshots[iteration]

    def compact(self, *, keep_bases: int = 2) -> Dict[str, int]:
        """Spill compaction / GC (ROADMAP item): reclaim the npz root.

        Deletes

        * superseded ``base_iterNNNN.npz`` files beyond the newest
          ``keep_bases`` (the persisted-current base is always kept — it is
          what ``open`` loads), and
        * archived contribution rows (``iterNNNN_contribMMM.npz``) not
          referenced by the staging manifest — fused cohorts' archives and
          rows orphaned by a pre-publish crash.

        Only *unreferenced* files are ever deleted, and deletion order is
        irrelevant to recovery, so a crash at ANY point mid-compact leaves
        ``open`` a fully recoverable repository: the current base, the
        manifest, and every manifest-referenced row survive by
        construction.  Queue submissions (``queue/``) belong to the service
        loop's own GC and are never touched.  Quiesces first (in-flight
        fuse finalized, spill writes drained).  Returns deletion counts."""
        if not self.root:
            raise ValueError("compact requires an on-disk root")
        if keep_bases < 1:
            raise ValueError(f"keep_bases must be >= 1, got {keep_bases}")
        self.flush()
        with self._manifest_lock:
            referenced = {os.path.normpath(e["file"])
                          for e in self._buffers.manifest_entries()}
        bases: List[tuple] = []
        rows: List[str] = []
        for name in os.listdir(self.root):
            m = _BASE_RE.match(name)
            if m:
                bases.append((int(m.group(1)), name))
            elif _ROW_RE.match(name) and os.path.normpath(name) not in referenced:
                rows.append(name)
        keep = {it for it, _ in sorted(bases)[-keep_bases:]}
        keep.add(self._persisted_iteration)  # open() loads exactly this one
        n_bases = 0
        for it, name in bases:
            if it not in keep:
                os.remove(os.path.join(self.root, name))
                n_bases += 1
        n_rows = 0
        for name in rows:
            os.remove(os.path.join(self.root, name))
            n_rows += 1
        return {"bases_removed": n_bases, "rows_removed": n_rows}

    # -- persistence -----------------------------------------------------
    def _persist_base(self, iteration: Optional[int] = None,
                      base=None, meta: Optional[Dict[str, Any]] = None):
        """Write the current (or a captured) base + repository.json.  The
        captured form is what the spill executor uses: everything it needs
        is bound at submit time, so the worker never reads mutating state.

        Serialized under the publish lock with a monotonic guard: with
        ``spill_workers>=2`` two publish tasks may run concurrently, and a
        slower, older task must neither interleave its repository.json
        write with the newer one nor land after it and regress the
        recorded iteration."""
        it = self.iteration if iteration is None else iteration
        base = self._base if base is None else base
        meta = self._render_meta() if meta is None else meta
        with self._publish_lock:
            if it < self._persisted_iteration:
                return  # a newer publish already landed
            ckpt.save(os.path.join(self.root, f"base_iter{it:04d}.npz"), base)
            if self.extra_meta:
                # re-merge LIVE extra_meta: a publish task captured before
                # a family spawn must not clobber the manifest entry the
                # spawn just recorded
                meta = {**meta, **self.extra_meta}
            # atomic like every other publish artifact: a crash mid-write
            # must not brick Repository.open with truncated repository.json
            ckpt.save_json_atomic(os.path.join(self.root, "repository.json"),
                                  meta, default=_json_default)
            self._persisted_iteration = it

    def _render_meta(self) -> Dict[str, Any]:
        spec = self._spec if self._spec is not None else FlatSpec.from_tree(self._base)
        meta = {
            "iteration": self.iteration,
            "fusion_op": self.fusion_op,
            "fusion_kwargs": self.fusion_kwargs,
            "screen": self.screen,
            "mad_threshold": self.mad_threshold,
            "spill": self.spill,
            # the flat layout the recorded fusion_kwargs / staged rows are
            # valid against; Repository.open refuses a base that disagrees
            "flat_spec": {"dtype": spec.dtype, "size": spec.size},
            "history": [
                {
                    "iteration": r.iteration,
                    "n_contributions": r.n_contributions,
                    "n_accepted": r.n_accepted,
                    "op": r.op,
                    "diff_norms": [float(n) for n in r.diff_norms],
                    "wall_time": r.wall_time,
                    "stage_s": r.stage_s,
                    "finalize_s": r.finalize_s,
                }
                for r in self.history
            ],
        }
        # opaque rider keys (e.g. the family manifest) survive every
        # publish of this repository verbatim
        meta.update(self.extra_meta)
        return meta

    # -- crash recovery ---------------------------------------------------
    def _recover_staged(self, manifest: Dict[str, Any], spec: FlatSpec) -> int:
        """Re-stage the staged-but-unfused rows a crash left behind
        (docs/async_repository.md).

        * entries marked in-flight (``fusing``) whose ``staged_at``
          iteration is already behind the repository's are skipped — their
          publish landed and only the manifest rewrite was lost to the
          crash; recovering them would apply the cohort twice.  Entries
          without the mark are always recovered: a publish that did not
          consume them (``contribute_async``, an explicit-buffer fuse) may
          have advanced the iteration past their ``staged_at``;
        * entries whose row file is missing or unreadable (a partial write
          never published by ``os.replace``, or a file deleted out from
          under the manifest) are skipped with a warning;
        * a row whose recorded FlatSpec disagrees with the base raises —
          fusing mismatched rows would silently corrupt the model.

        Recovered entries stay manifest-tracked on every engine, so they
        are only retired by the publish of the fuse that consumes them."""
        if self.use_flat:
            self._ensure_flat_base()
        side = self._buffers.front
        recovered = 0
        for e in manifest.get("entries", []):
            if (e.get("fusing")
                    and int(e.get("staged_at", self.iteration)) < self.iteration):
                continue  # consumed by a publish that landed pre-crash
            if (e.get("compressed") and e.get("base_iteration") is not None
                    and int(e["base_iteration"]) != self.iteration):
                # a compressed delta is only decodable against its declared
                # base; the admission pin makes this unreachable in normal
                # flows, but a repository reopened at a different vintage
                # (operator rollback, hand-edited state) must not mis-decode
                warnings.warn(
                    f"spill recovery: skipping compressed row {e['file']} — "
                    f"encoded against base iteration {e['base_iteration']} "
                    f"but the repository reopened at {self.iteration}")
                continue
            path = os.path.join(self.root, e["file"])
            try:
                meta = ckpt.flat_row_meta(path)
            except Exception as err:  # missing / truncated / not-an-npz
                warnings.warn(
                    f"spill recovery: skipping unreadable staged row "
                    f"{e['file']} ({type(err).__name__}: {err})")
                continue
            if meta["dtype"] != spec.dtype or int(meta["size"]) != spec.size:
                raise ValueError(
                    f"staged row {e['file']} was spilled with "
                    f"FlatSpec(dtype={meta['dtype']}, N={meta['size']}) but the "
                    f"repository base is (dtype={spec.dtype}, N={spec.size}) — "
                    "refusing to recover mismatched rows")
            if self.use_flat and self.spill:
                side.rows.append(path)
            elif self.use_flat:
                side.rows.append(self._load_staged_row(path))
            else:
                # per-leaf engine: rebuild the pytree from the flat row
                if meta.get("compressed"):
                    payloads, _ = ckpt.load_flat_delta(path)
                    base_row = np.asarray(spec.flatten(self._base))
                    if meta.get("sharded"):
                        ss = ShardedFlatSpec.from_json(meta["shard_spec"])
                        row = delta_decode_sharded(payloads, ss, base_row)
                    else:
                        row = delta_decode(payloads[0], base_row)
                    row, rspec = jnp.asarray(row), spec
                elif meta.get("sharded"):
                    with ckpt.FlatShardReader(path) as r:
                        row, rspec = jnp.asarray(r.full_row()), r.spec
                else:
                    row, rspec = ckpt.load_flat(path)
                side.rows.append(rspec.unflatten(row))
            fresh = {k: v for k, v in e.items() if k != "fusing"}
            fresh["staged_at"] = self._staging_iteration()
            side.manifest.append(fresh)
            side.fishers.append(None)
            side.weights.append(e.get("weight"))
            recovered += 1
        if self.root:
            with self._manifest_lock:
                self._write_manifest()
        return recovered

    @classmethod
    def open(cls, root: str, **kw) -> "Repository":
        """Re-open an on-disk repository at its latest base model, restoring
        the fusion configuration, screen settings, and history recorded in
        ``repository.json`` (explicit keyword arguments win).

        The loaded base is validated against the recorded flat layout
        (dtype/N) — a swapped or corrupted ``base_iterNNNN.npz`` raises
        instead of silently applying the recorded fusion_kwargs to the
        wrong model.  Staged-but-unfused rows recorded in the spill
        manifest are recovered into the front staging buffer (and their
        shard placement, under ``mesh=``)."""
        with open(os.path.join(root, "repository.json")) as f:
            meta = json.load(f)
        it = meta["iteration"]
        base = ckpt.load(os.path.join(root, f"base_iter{it:04d}.npz"))
        spec = FlatSpec.from_tree(base)
        recorded = meta.get("flat_spec")
        if recorded and (recorded["dtype"] != spec.dtype
                         or int(recorded["size"]) != spec.size):
            raise ValueError(
                f"repository.json records FlatSpec(dtype={recorded['dtype']}, "
                f"N={recorded['size']}) but base_iter{it:04d}.npz loads as "
                f"(dtype={spec.dtype}, N={spec.size}) — the base checkpoint "
                "does not match the recorded configuration; refusing to apply "
                "the stored fusion_kwargs/screen settings to it")
        kw.setdefault("fusion_op", meta.get("fusion_op", "average"))
        if meta.get("fusion_kwargs"):
            kw.setdefault("fusion_kwargs", meta["fusion_kwargs"])
        kw.setdefault("screen", meta.get("screen", True))
        kw.setdefault("mad_threshold", meta.get("mad_threshold", 5.0))
        # constructed with root=None so __init__ does not re-persist (and
        # clobber) base_iter0000; root/spill are restored afterwards
        # (spill is recorded in repository.json; explicit kwargs win)
        spill = bool(kw.pop("spill", meta.get("spill", False)))
        spill_workers = int(kw.pop("spill_workers", 0))
        repo = cls(base, root=None, **kw)
        repo.iteration = it
        repo.root = root
        repo._persisted_iteration = it
        if "families" in meta:
            # the family manifest rides repository.json (RepositoryFamily
            # owns its content); a plain open+publish must carry it forward
            repo.extra_meta["families"] = meta["families"]
        if spill and not repo.use_flat:
            warnings.warn(
                "spill=True requested but the repository reopened on the "
                "per-leaf engine — staged rows will NOT be spilled or "
                "crash-recoverable until reopened on the flat engine")
        repo.spill = spill and repo.use_flat
        if repo.spill and spill_workers > 0:
            repo._spill_pool = ThreadPoolExecutor(
                max_workers=spill_workers, thread_name_prefix="repo-spill")
        repo.history = [
            FusionRecord(
                iteration=r["iteration"],
                n_contributions=r["n_contributions"],
                n_accepted=r["n_accepted"],
                op=r["op"],
                diff_norms=[float(n) for n in r.get("diff_norms", [])],
                wall_time=float(r.get("wall_time", 0.0)),
                stage_s=float(r.get("stage_s", 0.0)),
                finalize_s=float(r.get("finalize_s", 0.0)),
            )
            for r in meta.get("history", [])
        ]
        manifest_path = os.path.join(root, MANIFEST)
        if os.path.exists(manifest_path):
            repo._recover_staged(ckpt.load_json(manifest_path), spec)
        sketch_path = os.path.join(root, SKETCH_FILE)
        if os.path.exists(sketch_path):
            # restore the novelty screen's history so a restarted daemon
            # screens against the same recent cohorts (the file is atomic,
            # but tolerate a hand-damaged one: the screen restarts empty)
            try:
                sk = CohortSketch.from_json(ckpt.load_json(sketch_path))
            except Exception as err:
                warnings.warn(f"cohort sketch unreadable "
                              f"({type(err).__name__}: {err}) — the novelty "
                              "screen history restarts empty")
            else:
                if sk.size == spec.size:
                    repo.cohort_sketch = sk
                else:
                    warnings.warn(
                        f"cohort sketch was built for N={sk.size} rows but "
                        f"the base is N={spec.size} — ignoring it")
        return repo


# ---------------------------------------------------------------------------
# RepositoryFamily — a model zoo of named bases under one root
# ---------------------------------------------------------------------------

FAMILY_DIR = "families"


def family_member_root(root: str, name: str) -> str:
    """Filesystem root of a family member.  ``main`` IS the top-level root
    — a single-base repository and a one-member family share a byte-
    identical layout — and every spawned member owns a complete repository
    layout (queue, spill manifest, sketch, gate state, bases) under
    ``<root>/families/<name>/``."""
    return root if name == "main" else os.path.join(root, FAMILY_DIR, name)


class RepositoryFamily:
    """A named family of Repository members sharing one on-disk root — the
    model-zoo layer of similarity-routed fusion (docs/service_loop.md).

    The **family manifest** is a ``"families"`` key riding the top-level
    ``repository.json`` (the main member's meta): a map of member name →
    ``{root, seeded_from, seed_iteration, created_at}``.  ``open`` on a
    pre-family single-base layout migrates it in place by writing the
    implicit ``{"main": {"root": "."}}`` manifest — no file moves, so
    every existing repository (and ``Repository.open`` caller) keeps
    working; ``Repository.open`` itself carries an existing manifest
    through publishes untouched via ``extra_meta``.

    ``spawn`` creates a new member seeded from an existing member's base
    at a declared vintage.  The member directory is persisted durably
    BEFORE the manifest entry (crash between the two leaves an orphan
    directory that the next same-named spawn adopts idempotently — the
    ``repo.post_family_spawn`` fault seam pins this in the crash matrix).

    ``cross_fuse`` is the inter-cluster merge: every member fuses the
    OTHER members' bases through the ordinary flat fuse path
    (``fuse_pending(buffer=...)``) with step size ``alpha·(M−1)/M``, so
    at ``alpha=1`` each member lands exactly on the simultaneous mean of
    all pre-cross bases (the closed form the routed demo asserts)."""

    def __init__(self, main: Repository, *, member_kw: Optional[Dict[str, Any]] = None):
        if not main.root:
            raise ValueError("RepositoryFamily requires an on-disk root")
        self.root = main.root
        self.member_kw = dict(member_kw or {})
        main.family_name = "main"
        self.members: Dict[str, Repository] = {"main": main}
        self._meta: Dict[str, Dict[str, Any]] = {"main": {"root": "."}}

    @classmethod
    def create(cls, base_params, *, root: str, **kw) -> "RepositoryFamily":
        """Initialize a NEW family: a main member at ``root`` plus the
        manifest.  ``kw`` goes to the Repository constructor and is
        remembered for spawned members."""
        main = Repository(base_params, root=root, **kw)
        fam = cls(main, member_kw=kw)
        fam._write_family_manifest()
        return fam

    @classmethod
    def open(cls, root: str, **kw) -> "RepositoryFamily":
        """Open an on-disk family (or migrate a single-base layout in
        place).  ``kw`` is applied to every member's ``Repository.open``
        and remembered for spawns."""
        main = Repository.open(root, **kw)
        fam = cls(main, member_kw=kw)
        meta = main.extra_meta.get("families")
        if meta is None:
            # single-base layout: migrate by writing the implicit manifest
            fam._write_family_manifest()
            return fam
        fam._meta = {str(n): dict(e) for n, e in meta.items()}
        fam._meta.setdefault("main", {"root": "."})
        for name in sorted(fam._meta):
            if name == "main":
                continue
            mroot = os.path.join(root, fam._meta[name]["root"])
            member = Repository.open(mroot, **kw)
            member.family_name = name
            fam.members[name] = member
        main.extra_meta["families"] = fam._meta
        return fam

    def __len__(self) -> int:
        return len(self.members)

    def member_root(self, name: str) -> str:
        return family_member_root(self.root, name)

    def _write_family_manifest(self) -> None:
        """Persist the manifest into the top-level repository.json under
        the main member's publish lock (publish tasks write the same file;
        ``_persist_base`` re-merges live ``extra_meta``, so a captured
        older publish can never clobber a newer manifest)."""
        main = self.members["main"]
        main.extra_meta["families"] = self._meta
        with main._publish_lock:
            ckpt.save_json_atomic(
                os.path.join(self.root, "repository.json"),
                main._render_meta(), default=_json_default)

    def spawn(self, *, seed_family: str = "main",
              seed_iteration: Optional[int] = None,
              name: Optional[str] = None) -> str:
        """Create (or crash-adopt) a new member seeded from
        ``seed_family``'s base at ``seed_iteration`` (its current base
        when None, or when that vintage's npz is no longer on disk).
        Names are deterministic (``f1``, ``f2``, … smallest free), so a
        spawn replayed after a crash converges on the same member."""
        src = self.members[seed_family]
        if name is None:
            k = 1
            while f"f{k}" in self._meta or f"f{k}" in self.members:
                k += 1
            name = f"f{k}"
        if name in self.members:
            raise ValueError(f"family member {name!r} already exists")
        mroot = self.member_root(name)
        it = src.iteration if seed_iteration is None else int(seed_iteration)
        if os.path.exists(os.path.join(mroot, "repository.json")):
            # a previous spawn persisted the member but crashed before the
            # manifest entry: adopt it as-is
            member = Repository.open(mroot, **self.member_kw)
        else:
            seed_path = os.path.join(src.root, f"base_iter{it:04d}.npz")
            if not os.path.exists(seed_path):
                # declared vintage compacted away (or not yet durable):
                # seed from the source's current base instead
                src.flush()
                it = src.iteration
                src._persist_base()
                seed_path = os.path.join(src.root, f"base_iter{it:04d}.npz")
            seed = ckpt.load(seed_path)
            spawn_kw: Dict[str, Any] = dict(
                fusion_op=src.fusion_op, fusion_kwargs=src.fusion_kwargs,
                screen=src.screen, mad_threshold=src.mad_threshold)
            spawn_kw.update(self.member_kw)
            member = Repository(seed, root=mroot, **spawn_kw)
        member.family_name = name
        self.members[name] = member
        faults.crash_point("repo.post_family_spawn")
        self._meta[name] = {
            "root": f"{FAMILY_DIR}/{name}",
            "seeded_from": seed_family,
            "seed_iteration": it,
            "created_at": time.time(),
        }
        self._write_family_manifest()
        return name

    def cross_fuse(self, *, alpha: float = 1.0) -> Dict[str, FusionRecord]:
        """Inter-cluster merge: fuse every member toward the mean of the
        OTHER members' bases through the ordinary flat fuse path.  All
        pre-cross bases are snapshotted first, so the update is
        simultaneous; with the default ``alpha=1.0`` every member lands
        exactly on the mean of all pre-cross bases, and smaller ``alpha``
        interpolates toward it.  Each member's publish runs the full
        pipeline (history record, iteration bump, persist, listeners) with
        the §9 screen bypassed — member bases are not a contributor
        cohort.  No-op (empty dict) for a family of one."""
        names = sorted(self.members)
        if len(names) < 2:
            return {}
        for n in names:
            m = self.members[n]
            m.flush()
            m._ensure_flat_base()
        bases = {n: self.members[n]._base_flat for n in names}
        ak = float(alpha) * (len(names) - 1) / len(names)
        recs: Dict[str, FusionRecord] = {}
        for n in names:
            m = self.members[n]
            others = [bases[o] for o in names if o != n]
            stage = StagedBuffer(m._stack_stage(others))
            recs[n] = m.fuse_pending(buffer=stage, wait=True, alpha=ak,
                                     screen=False,
                                     op=f"cross_fuse(alpha={alpha:g})")
        return recs
