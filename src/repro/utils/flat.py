"""FlatParams — the contiguous flat-buffer parameter representation.

The Repository hot path (screen + fuse, paper §3/§9) is HBM-bandwidth-bound
streaming arithmetic over whole checkpoints.  Operating per-leaf costs one
device dispatch per (leaf, contributor) pair and forces the Pallas kernel
into one padded launch per leaf.  ``FlatSpec`` fixes the layout once:

* a **static spec** — an ordered tuple of ``(path, shape, dtype, offset)``
  records plus the treedef — hashable, so it can ride through ``jax.jit``
  as a static argument and be serialized next to checkpoints;
* a **1-D buffer** of ``spec.size`` elements in a single storage dtype
  (bf16 if every floating leaf is bf16, else f32), so K contributions stack
  into one ``[K, N]`` operand and the whole model fuses in ONE kernel launch.

Round-trips are views/reshapes inside jit (XLA fuses the slicing into the
consumer); nothing here allocates per-leaf Python-side temporaries beyond
the single concatenated buffer.

``ShardedFlatSpec`` layers a block-cyclic shard layout on top: it maps the
flat ``[N]`` buffer (and the stacked ``[K, N]`` staging buffer) onto a
``[S, shard_len]`` grid whose leading dim lands on a mesh axis, so the
Repository's staging and fuse can be distributed without any device ever
holding the full buffer (see docs/sharding.md).

``StagedBuffer`` and ``BufferPair`` are the staging-side primitives of the
async double-buffered Repository (docs/async_repository.md): a
``StagedBuffer`` is the explicit handle the fuse entry points accept (one
stacked cohort operand, single-device ``[K, N]`` or sharded
``[K, S, shard_len]``), and a ``BufferPair`` is the front/back pair of
staging sides — uploads append to the front while the back is being fused
on device.
"""
from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.pytree import path_str

# minimum 1-D tile granularity on TPU (8 sublanes x 128 lanes); the Pallas
# kernel and the block-cyclic shard layout share this alignment so a shard's
# slice is always a whole number of kernel tiles
LANE = 1024
DEFAULT_SHARD_BLOCK = 64 * 1024

# buckets per row-sketch statistic (kernels/ops.row_sketch): small enough
# that a sketch is a few hundred bytes of JSON, large enough that distinct
# finetunes land distinct bucket profiles
SKETCH_BUCKETS = 32


@dataclass(frozen=True)
class LeafSpec:
    path: str
    shape: Tuple[int, ...]
    dtype: str          # canonical dtype name, e.g. "float32", "bfloat16"
    offset: int         # element offset into the flat buffer
    size: int           # number of elements

    def slice_of(self, buf: jax.Array) -> jax.Array:
        return buf[self.offset : self.offset + self.size].reshape(self.shape)


@dataclass(frozen=True)
class FlatSpec:
    """Static description of a pytree's flat layout.  Hashable/comparable so
    two checkpoints with the same architecture share one spec (and one jit
    cache entry)."""

    leaves: Tuple[LeafSpec, ...]
    treedef: Any                 # jax PyTreeDef (hashable)
    size: int                    # total elements
    dtype: str                   # storage dtype of the flat buffer

    # -- construction ---------------------------------------------------
    @classmethod
    def from_tree(cls, tree) -> "FlatSpec":
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs: List[LeafSpec] = []
        off = 0
        all_bf16 = True
        for path, leaf in flat:
            arr = jnp.asarray(leaf)
            n = int(np.prod(arr.shape)) if arr.shape else 1
            specs.append(LeafSpec(path_str(path), tuple(arr.shape), arr.dtype.name, off, n))
            if arr.dtype != jnp.bfloat16:
                all_bf16 = False
            off += n
        storage = "bfloat16" if (specs and all_bf16) else "float32"
        return cls(tuple(specs), treedef, off, storage)

    # -- round trips ----------------------------------------------------
    def flatten(self, tree) -> jax.Array:
        """Pytree -> contiguous [size] buffer in the storage dtype.

        Concrete leaves on the CPU backend are concatenated through numpy —
        XLA:CPU's many-operand concatenate is ~25x slower than a memcpy
        (measured: 94ms vs 3.9ms for 58 leaves / 4 MB) and this staging
        step IS the Repository upload hot path.  Tracers (or accelerator
        backends, where device->host would be the slow path) go through a
        cached jitted concatenation instead — one dispatch per call, not
        one per leaf."""
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        if len(flat) != len(self.leaves):
            raise ValueError(
                f"tree has {len(flat)} leaves, spec expects {len(self.leaves)}")
        leaves = []
        for spec, (path, leaf) in zip(self.leaves, flat):
            path = path_str(path)
            if path != spec.path:
                raise ValueError(f"leaf path {path!r} != spec path {spec.path!r}")
            shape = tuple(jnp.shape(leaf))
            if shape != spec.shape:
                raise ValueError(
                    f"leaf {spec.path}: shape {shape} != spec {spec.shape}")
            leaves.append(leaf)
        concrete = not any(isinstance(l, jax.core.Tracer) for l in leaves)
        if concrete and jax.default_backend() == "cpu":
            dt = jnp.dtype(self.dtype)
            parts = [np.ravel(np.asarray(l)).astype(dt, copy=False) for l in leaves]
            buf = np.concatenate(parts) if parts else np.zeros((0,), dt)
            return jnp.asarray(buf)
        return _flatten_fn(self)(tuple(leaves))

    def unflatten(self, buf) -> Any:
        """Contiguous [size] buffer -> pytree with original shapes/dtypes."""
        buf = jnp.asarray(buf)
        if buf.shape != (self.size,):
            raise ValueError(f"buffer shape {buf.shape} != ({self.size},)")
        return jax.tree.unflatten(self.treedef, _unflatten_fn(self)(buf))

    # -- serialization (for on-disk spill / flat checkpoints) -----------
    def to_json(self) -> Dict[str, Any]:
        return {
            "dtype": self.dtype,
            "size": self.size,
            "leaves": [
                {"path": s.path, "shape": list(s.shape), "dtype": s.dtype,
                 "offset": s.offset, "size": s.size}
                for s in self.leaves
            ],
        }

    @classmethod
    def from_json(cls, meta: Dict[str, Any]) -> "FlatSpec":
        """Rebuild a spec from its JSON form.  The treedef is reconstructed
        as a nested dict keyed by the path components — the same convention
        the npz checkpoint format uses — so a spec round-tripped through disk
        unflattens to a plain dict tree.

        The leaf tuple is re-derived by flattening that reconstructed dict
        (with each LeafSpec as its own placeholder), NOT taken in JSON file
        order: dicts flatten in sorted-key order, which differs from the
        original flatten order whenever paths do not sort lexicographically
        (e.g. list indices '0','1',...,'10' sort as '0','1','10','2',...).
        The recorded offsets keep every leaf pointing at its original slice
        of the buffer regardless of the new ordering."""
        nested: Dict[str, Any] = {}
        for s in meta["leaves"]:
            node = nested
            parts = s["path"].split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = LeafSpec(
                s["path"], tuple(s["shape"]), s["dtype"], s["offset"], s["size"])
        flat, treedef = jax.tree_util.tree_flatten(
            nested, is_leaf=lambda x: isinstance(x, LeafSpec))
        return cls(tuple(flat), treedef, int(meta["size"]), meta["dtype"])


# the jitted steps are named for the profiler trace's "XLA Modules" line
# (``jit_flat_flatten``, ``jit_flat_unflatten``)
@functools.lru_cache(maxsize=128)
def _flatten_fn(spec: FlatSpec):
    dt = jnp.dtype(spec.dtype)

    @jax.jit
    def flat_flatten(leaves):
        if not leaves:
            return jnp.zeros((0,), dt)
        return jnp.concatenate([jnp.ravel(l).astype(dt) for l in leaves])

    return flat_flatten


@functools.lru_cache(maxsize=128)
def _unflatten_fn(spec: FlatSpec):
    casts = [(s, jnp.dtype(s.dtype)) for s in spec.leaves]

    @jax.jit
    def flat_unflatten(buf):
        return [s.slice_of(buf).astype(dt) for s, dt in casts]

    return flat_unflatten


def flatten_tree(tree) -> Tuple[jax.Array, FlatSpec]:
    """Convenience: build the spec and flatten in one call."""
    spec = FlatSpec.from_tree(tree)
    return spec.flatten(tree), spec


def row_checksum(buf) -> str:
    """CRC32 (hex) over a flat row's raw bytes.

    The contribution queue stamps this into each submission so the service
    can verify, end to end, that the row that fuses is bit-identical to
    the row the contributor wrote — across the atomic npz round trip and,
    for per-shard submissions, across the shard/unshard rearrangement
    (checksummed in portable ``[N]`` form on both sides).  bf16 rows are
    viewed as their uint16 bit pattern, matching the npz storage."""
    arr = np.asarray(buf)
    if arr.dtype == jnp.bfloat16:
        arr = arr.view(np.uint16)
    # crc32 consumes the buffer protocol directly — no tobytes copy of a
    # multi-MB row on the submit path
    return f"{zlib.crc32(np.ascontiguousarray(arr)) & 0xFFFFFFFF:08x}"


# ---------------------------------------------------------------------------
# CohortSketch — the novelty admission screen's recency window
# ---------------------------------------------------------------------------


def row_sketch_host(row, n_buckets: int = SKETCH_BUCKETS) -> np.ndarray:
    """Host (numpy) twin of ``repro.kernels.ref.row_sketch`` — the same
    ``[2, n_buckets]`` tile-bucketed sums/sq-sums statistic, without a
    device round trip.  The submit path uses it to stamp rider sketches
    (the row is already host-resident there; dispatching jax costs ~5x).
    Parity with the kernel/oracle is pinned by tests/test_sketch.py."""
    x = np.asarray(row)
    if x.dtype == jnp.bfloat16:
        x = x.astype(np.float32)
    x = x.astype(np.float32, copy=False)
    t_full = x.shape[0] // LANE
    main = x[: t_full * LANE].reshape(t_full, LANE)
    ts = main.sum(axis=1)
    tq = np.einsum("ij,ij->i", main, main)
    tail = x[t_full * LANE:]
    if tail.size:  # the final partial tile (zero padding adds nothing)
        ts = np.append(ts, tail.sum())
        tq = np.append(tq, np.dot(tail, tail))
    pad = (-ts.shape[0]) % n_buckets
    if pad:
        ts = np.append(ts, np.zeros(pad, np.float32))
        tq = np.append(tq, np.zeros(pad, np.float32))
    # bucket of tile t is t % n_buckets: fold the tile axis over the buckets
    return np.stack([ts.reshape(-1, n_buckets).sum(axis=0),
                     tq.reshape(-1, n_buckets).sum(axis=0)])


class CohortSketch:
    """Recency window of admitted-row content sketches, plus the current
    base's sketch — the host half of the novelty admission screen
    (docs/service_loop.md).

    Each sketch is the ``[2, n_buckets]`` statistic of
    ``repro.kernels.ops.row_sketch``: tile-bucketed sums (projections onto
    bucket indicators) and tile-bucketed squared norms.  Both yield *lower
    bounds* on the true distance between two rows:

    * projections — ``Σ_j (p_a[j] − p_b[j])² / L ≤ ‖a − b‖²`` by
      Cauchy–Schwarz per bucket (``L`` = elements per bucket);
    * blockwise norms — ``Σ_j (√q_a[j] − √q_b[j])² ≤ ‖a − b‖²`` by the
      reverse triangle inequality per bucket.

    The screen compares the larger of the two bounds *relative to each
    row's distance from the base* (same bound, against ``base``): two
    contributions are near-duplicates when their mutual distance is small
    compared with how far either moved from the base — an exact replay
    scores 0 regardless of model scale, while independent finetunes of
    similar magnitude score O(1).  Normalizing by the base distance is what
    keeps the looseness of the bounds out of the decision: numerator and
    denominator lose the same statistical factor.

    ``add`` is idempotent per id (a re-admitted submission replaces its own
    entry — crash recovery must never flag a row as a duplicate of itself)
    and trims to the most recent ``window`` entries.  Each entry records
    the queue ``file`` it was sketched from: the self-match skip demands
    BOTH the id and the file agree, so a replay that forges a previously
    admitted rider id (ids are contributor-supplied) cannot talk its way
    past the screen — only the literal same queue file (the
    post-sketch-persist crash re-screen) is exempt.  ``to_json``/
    ``from_json`` round-trip the whole state; the Repository persists it
    atomically next to the staging manifest (``cohort_sketch.json``).
    """

    EPS = 1e-12

    def __init__(self, size: int, n_buckets: int = SKETCH_BUCKETS,
                 window: int = 32):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.size = int(size)
        self.n_buckets = int(n_buckets)
        self.window = int(window)
        self.base: Optional[np.ndarray] = None
        self.base_iteration: Optional[int] = None
        # recent base sketches by iteration — the router diffs a rider
        # against the base vintage its contributor actually finetuned from,
        # which may already have been superseded by the time the row admits
        self.bases: Dict[int, np.ndarray] = {}
        # (id, originating queue file, sketch, delta projections or None),
        # oldest first
        self.entries: List[Tuple[str, Optional[str], np.ndarray,
                                 Optional[np.ndarray]]] = []

    def __len__(self) -> int:
        return len(self.entries)

    @property
    def seg_elems(self) -> int:
        """Upper bound on elements per bucket (the Cauchy–Schwarz L)."""
        tiles = -(-max(self.size, 1) // LANE)
        return -(-tiles // self.n_buckets) * LANE

    def _check(self, sketch) -> np.ndarray:
        arr = np.asarray(sketch, np.float64)
        if arr.shape != (2, self.n_buckets):
            raise ValueError(
                f"sketch shape {arr.shape} != (2, {self.n_buckets})")
        return arr

    # -- the lower-bound metric -----------------------------------------
    def _lb(self, a: np.ndarray, b: np.ndarray) -> float:
        dp2 = float(np.sum((a[0] - b[0]) ** 2)) / self.seg_elems
        dn2 = float(np.sum((np.sqrt(np.maximum(a[1], 0.0))
                            - np.sqrt(np.maximum(b[1], 0.0))) ** 2))
        return float(np.sqrt(max(dp2, dn2)))

    def distance(self, a, b) -> float:
        """Relative lower-bound distance between two sketches: mutual lb
        distance over the larger base-relative lb distance (row norms when
        no base sketch is set).  0 for exact duplicates; ~O(1) for
        independent contributions of comparable finetune magnitude."""
        a, b = self._check(a), self._check(b)
        d = self._lb(a, b)
        if self.base is not None:
            scale = max(self._lb(a, self.base), self._lb(b, self.base))
        else:
            scale = max(float(np.sqrt(max(np.sum(a[1]), 0.0))),
                        float(np.sqrt(max(np.sum(b[1]), 0.0))))
        if scale <= self.EPS:
            # both rows sit on the base (or are zero): identical for the
            # screen's purposes iff their mutual distance vanishes too
            return 0.0 if d <= self.EPS else float("inf")
        return d / scale

    # -- window maintenance ---------------------------------------------
    BASE_HISTORY = 8

    def set_base(self, sketch, iteration: Optional[int] = None) -> None:
        self.base = self._check(sketch)
        if iteration is not None:
            self.base_iteration = int(iteration)
            self.bases[int(iteration)] = self.base
            for it in sorted(self.bases)[: -self.BASE_HISTORY]:
                del self.bases[it]

    def base_at(self, iteration: Optional[int] = None
                ) -> Optional[np.ndarray]:
        """The base sketch at a given iteration (falling back to the
        current base when that vintage is unknown or unspecified)."""
        if iteration is not None and int(iteration) in self.bases:
            return self.bases[int(iteration)]
        return self.base

    def add(self, sub_id: str, sketch, *, file: Optional[str] = None,
            delta: Optional[Any] = None) -> None:
        arr = self._check(sketch)
        d = None if delta is None else np.asarray(delta, np.float64)
        self.entries = [e for e in self.entries if e[0] != sub_id]
        self.entries.append((str(sub_id), file, arr, d))
        del self.entries[: -self.window]

    def discard(self, sub_id: str) -> None:
        """Drop a submission's entry (admission failed after its sketch
        was recorded — the window must only hold rows that staged)."""
        self.entries = [e for e in self.entries if e[0] != sub_id]

    def nearest(self, sketch, *, skip_id: Optional[str] = None,
                skip_file: Optional[str] = None
                ) -> Optional[Tuple[str, float]]:
        """(id, relative distance) of the closest windowed entry, or None
        when the window is empty.  An entry is excluded only when BOTH its
        id matches ``skip_id`` and its recorded file matches ``skip_file``
        — the submission's own pre-crash entry, never a forged-id replay
        under a different queue file."""
        best: Optional[Tuple[str, float]] = None
        for sub_id, file, s, _d in self.entries:
            if (skip_id is not None and sub_id == skip_id
                    and file is not None and file == skip_file):
                continue
            d = self.distance(sketch, s)
            if best is None or d < best[1]:
                best = (sub_id, d)
        return best

    def match(self, sketch, threshold: float, *,
              skip_id: Optional[str] = None,
              skip_file: Optional[str] = None) -> Optional[Tuple[str, float]]:
        """The admission query: the (id, distance) of a windowed entry
        within ``threshold`` of ``sketch`` — i.e. the near-duplicate to
        reject for — or None when the row is novel."""
        hit = self.nearest(sketch, skip_id=skip_id, skip_file=skip_file)
        if hit is not None and hit[1] <= threshold:
            return hit
        return None

    # -- serialization (cohort_sketch.json) ------------------------------
    def to_json(self) -> Dict[str, Any]:
        return {
            "version": 1,
            "size": self.size,
            "n_buckets": self.n_buckets,
            "window": self.window,
            "base": None if self.base is None else self.base.tolist(),
            "base_iteration": self.base_iteration,
            "bases": {str(it): s.tolist() for it, s in self.bases.items()},
            "entries": [{"id": i, "file": f, "sketch": s.tolist(),
                         "delta": None if d is None else d.tolist()}
                        for i, f, s, d in self.entries],
        }

    @classmethod
    def from_json(cls, meta: Dict[str, Any]) -> "CohortSketch":
        sk = cls(int(meta["size"]), int(meta["n_buckets"]),
                 int(meta["window"]))
        for it, s in meta.get("bases", {}).items():
            sk.bases[int(it)] = sk._check(s)
        if meta.get("base") is not None:
            sk.set_base(meta["base"], iteration=meta.get("base_iteration"))
        for e in meta.get("entries", []):
            sk.add(e["id"], e["sketch"], file=e.get("file"),
                   delta=e.get("delta"))
        return sk


# ---------------------------------------------------------------------------
# FamilyRouter — sketch-distance routing over a family of bases
# ---------------------------------------------------------------------------


@dataclass
class RouteDecision:
    """Outcome of routing one submission against the base family.

    ``family`` is the member to fuse into (None when ``spawn`` — the
    service creates the new member and routes there); ``distance`` is the
    winning relative lower-bound distance (None when the decision was a
    bootstrap fallback); ``scores`` maps every scored member to its
    distance; ``delta`` is the rider's base-relative projection delta, the
    evidence recorded in the routed member's sketch window."""

    family: Optional[str]
    spawn: bool
    distance: Optional[float]
    scores: Dict[str, float]
    delta: Optional[np.ndarray]
    reason: str


class FamilyRouter:
    """Route submissions to their nearest base-family member by sketch
    distance (docs/service_loop.md).

    The unit of comparison is the **delta projection**: bucket projections
    are linear in the row, so ``rider_sketch[0] − base_sketch[0]`` is
    exactly the sketch of the contributor's finetune delta — the task
    direction, with the shared base subtracted out.  Two submissions from
    the same task stream have near-colinear deltas; streams from different
    tasks point elsewhere.  The router scores a rider against member ``m``
    as the minimum over

    * ``lb(rider, base_m) / ‖δ‖``  — how close the full row sits to
      ``m``'s base itself (catches resubmissions of a member's own base),
      using the same two-sided lower bound as the novelty screen; and
    * ``lb_p(δ − δ_e) / max(‖δ‖, ‖δ_e‖)`` over ``m``'s windowed delta
      entries ``δ_e`` — the base-relative distance between finetune
      directions (projection bound only: norms of deltas are not
      recoverable from row sq-norm sketches).

    Colinear same-stream deltas of magnitudes ``m1 ≤ m2`` score
    ``1 − m1/m2`` (small within a cohort window); independent task
    directions score O(1) or above.  Decision rules:

    * no member holds any delta evidence yet → route to the declared
      family (bootstrap: the first stream claims its declared base);
    * a vanishing rider delta (the row IS its declared base) → declared;
    * nearest distance ≤ ``split_threshold`` → route to the argmin
      (ties prefer the declared member);
    * nearest distance > ``split_threshold`` and the family is below
      ``max_bases`` → spawn a new member seeded from the declared base;
      at the cap, route to the argmin anyway (graceful saturation).
    """

    def __init__(self, *, split_threshold: float = 0.8, max_bases: int = 4):
        if split_threshold <= 0:
            raise ValueError(
                f"split_threshold must be > 0, got {split_threshold}")
        self.split_threshold = float(split_threshold)
        self.max_bases = int(max_bases)

    @staticmethod
    def _delta_norm(delta: np.ndarray, seg_elems: int) -> float:
        return float(np.sqrt(np.sum(np.asarray(delta, np.float64) ** 2)
                             / seg_elems))

    def route(self, sketch, sketches: Dict[str, CohortSketch], *,
              declared: str = "main",
              base_iteration: Optional[int] = None) -> RouteDecision:
        """Score ``sketch`` against every family member and decide.

        ``sketches`` maps member name → that member's ``CohortSketch``
        (base sketch + windowed delta evidence); ``declared`` /
        ``base_iteration`` identify the base vintage the rider claims it
        finetuned from, which anchors the delta."""
        if declared not in sketches:
            raise KeyError(f"unknown declared family {declared!r}")
        ref = sketches[declared]
        arr = ref._check(sketch)
        b0 = ref.base_at(base_iteration)
        if b0 is None:
            return RouteDecision(declared, False, None, {}, None,
                                 "declared member holds no base sketch yet")
        delta = arr[0] - np.asarray(b0, np.float64)[0]
        dn = self._delta_norm(delta, ref.seg_elems)
        if dn <= CohortSketch.EPS:
            return RouteDecision(declared, False, 0.0, {}, delta,
                                 "rider sits on its declared base")
        if not any(e[3] is not None for sk in sketches.values()
                   for e in sk.entries):
            return RouteDecision(declared, False, None, {}, delta,
                                 "bootstrap: no routing evidence yet")
        scores: Dict[str, float] = {}
        for name, sk in sketches.items():
            terms: List[float] = []
            if sk.base is not None:
                terms.append(ref._lb(arr, np.asarray(sk.base, np.float64))
                             / dn)
            for e in sk.entries:
                de = e[3]
                if de is None:
                    continue
                den = max(dn, self._delta_norm(de, ref.seg_elems),
                          CohortSketch.EPS)
                terms.append(
                    float(np.sqrt(np.sum((delta - de) ** 2)
                                  / ref.seg_elems)) / den)
            if terms:
                scores[name] = min(terms)
        nearest = min(scores, key=lambda n: (scores[n], n != declared, n))
        best = scores[nearest]
        if best > self.split_threshold and len(sketches) < self.max_bases:
            return RouteDecision(
                None, True, best, scores, delta,
                f"nearest member {nearest} at {best:.3f} > "
                f"split_threshold {self.split_threshold:g}")
        if best > self.split_threshold:
            reason = (f"at max_bases={self.max_bases}: routed to nearest "
                      f"{nearest} despite {best:.3f} > split_threshold")
        else:
            reason = f"nearest member {nearest} at {best:.3f}"
        return RouteDecision(nearest, False, best, scores, delta, reason)


# ---------------------------------------------------------------------------
# ShardedFlatSpec — block-cyclic layout of a flat buffer over a mesh axis
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardedFlatSpec:
    """Block-cyclic layout of a flat ``[N]`` buffer over ``n_shards`` shards.

    The padded buffer is a ``(G, S, B)`` grid of ``G·S`` blocks of ``B``
    elements: block ``j`` lives on shard ``j % S`` at slot ``j // S``
    (classic block-cyclic).  A sharded row is the ``[S, G·B]`` rearrangement
    of that grid, so placing its leading dim on a mesh axis gives every
    device a contiguous ``shard_len``-element slice that is

    * **balanced** — every shard holds exactly ``padded_size / S`` elements
      regardless of the leaf structure underneath, and
    * **tile-aligned** — ``B`` is a multiple of ``LANE`` (8x128), so each
      shard's slice is whole kernel tiles and the per-shard fuse needs no
      re-padding.

    Padding elements are zero; they contribute nothing to either the fused
    output (sliced away on unshard) or the ``sq_diff`` screening statistic
    (0 - 0 = 0), which is what lets the per-shard partials be all-reduced
    without any padding mask.

    The layout is independent of the leaf layout (`FlatSpec`): shard, fuse,
    and unshard all operate on the flat buffer; only the final publish
    re-derives the pytree.
    """

    size: int      # N — unpadded element count
    n_shards: int  # S — mesh-axis extent the layout targets
    block: int     # B — elements per layout block (LANE-aligned)

    # -- construction ---------------------------------------------------
    @classmethod
    def for_size(cls, size: int, n_shards: int,
                 block: Optional[int] = None) -> "ShardedFlatSpec":
        """Pick a layout for an ``[N]`` buffer over ``n_shards`` shards.

        ``block`` defaults to ``DEFAULT_SHARD_BLOCK`` clamped so tiny models
        do not pad to S full kernel blocks: the block shrinks (LANE-aligned)
        until one round of the cycle covers the whole buffer."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if block is None:
            per_shard = -(-max(size, 1) // n_shards)          # ceil
            aligned = -(-per_shard // LANE) * LANE            # lane-align up
            block = min(DEFAULT_SHARD_BLOCK, aligned)
        if block % LANE:
            raise ValueError(f"block {block} is not a multiple of LANE={LANE}")
        return cls(size, n_shards, block)

    @classmethod
    def from_spec(cls, spec: FlatSpec, n_shards: int,
                  block: Optional[int] = None) -> "ShardedFlatSpec":
        return cls.for_size(spec.size, n_shards, block)

    # -- derived geometry ----------------------------------------------
    @property
    def n_super(self) -> int:
        """G — rounds of the block cycle."""
        return -(-max(self.size, 1) // (self.n_shards * self.block))

    @property
    def padded_size(self) -> int:
        return self.n_super * self.n_shards * self.block

    @property
    def shard_len(self) -> int:
        return self.n_super * self.block

    def shard_of(self, i: int) -> Tuple[int, int]:
        """(shard, offset-within-shard) of flat element ``i``."""
        if not (0 <= i < self.size):
            raise ValueError(f"element {i} out of range [0, {self.size})")
        j, r = divmod(i, self.block)
        return j % self.n_shards, (j // self.n_shards) * self.block + r

    def global_of(self, shard: int, offsets) -> np.ndarray:
        """Inverse of ``shard_of``, vectorized: flat global indices of the
        given offsets *within* shard ``shard``.  Offsets that land in the
        block-grid padding map past ``size`` — callers filter those."""
        off = np.asarray(offsets, np.int64)
        slot, r = np.divmod(off, self.block)
        return (slot * self.n_shards + int(shard)) * self.block + r

    # -- rearrangement --------------------------------------------------
    def shard(self, buf) -> jax.Array:
        """``[..., N]`` -> ``[..., S, shard_len]`` block-cyclic rearrangement
        (zero-padded to the block grid)."""
        buf = jnp.asarray(buf)
        if buf.shape[-1] != self.size:
            raise ValueError(f"buffer last dim {buf.shape[-1]} != size {self.size}")
        lead = buf.shape[:-1]
        pad = self.padded_size - self.size
        if pad:
            buf = jnp.concatenate(
                [buf, jnp.zeros(lead + (pad,), buf.dtype)], axis=-1)
        grid = buf.reshape(lead + (self.n_super, self.n_shards, self.block))
        return jnp.swapaxes(grid, -3, -2).reshape(
            lead + (self.n_shards, self.shard_len))

    def unshard(self, arr) -> jax.Array:
        """``[..., S, shard_len]`` -> ``[..., N]`` (padding sliced away)."""
        arr = jnp.asarray(arr)
        want = (self.n_shards, self.shard_len)
        if arr.shape[-2:] != want:
            raise ValueError(f"sharded shape {arr.shape[-2:]} != {want}")
        lead = arr.shape[:-2]
        grid = arr.reshape(lead + (self.n_shards, self.n_super, self.block))
        flat = jnp.swapaxes(grid, -3, -2).reshape(lead + (self.padded_size,))
        return flat[..., : self.size]

    # -- host-side per-shard spill layout -------------------------------
    def shard_slices(self, row) -> List[np.ndarray]:
        """``[N]`` host row -> its S per-shard ``[shard_len]`` slices, in
        numpy (no device round trip) — the spill-per-shard write layout.
        Each slice is exactly what ``shard(row)[s]`` would hold."""
        row = np.asarray(row)
        if row.shape != (self.size,):
            raise ValueError(f"row shape {row.shape} != ({self.size},)")
        pad = self.padded_size - self.size
        if pad:
            row = np.concatenate([row, np.zeros((pad,), row.dtype)])
        grid = row.reshape(self.n_super, self.n_shards, self.block)
        return [np.ascontiguousarray(grid[:, s, :].reshape(self.shard_len))
                for s in range(self.n_shards)]

    def unshard_slices(self, slices: Sequence[np.ndarray]) -> np.ndarray:
        """Per-shard ``[shard_len]`` slices -> the ``[N]`` host row (the
        portability fallback when a spilled layout does not match the mesh
        the repository was reopened under)."""
        if len(slices) != self.n_shards:
            raise ValueError(f"{len(slices)} slices != n_shards {self.n_shards}")
        grid = np.stack([np.asarray(s).reshape(self.n_super, self.block)
                         for s in slices], axis=1)
        return grid.reshape(self.padded_size)[: self.size]

    # -- serialization (spill manifest) ---------------------------------
    def to_json(self) -> Dict[str, Any]:
        return {"size": self.size, "n_shards": self.n_shards, "block": self.block}

    @classmethod
    def from_json(cls, meta: Dict[str, Any]) -> "ShardedFlatSpec":
        return cls(int(meta["size"]), int(meta["n_shards"]), int(meta["block"]))


# ---------------------------------------------------------------------------
# Delta codec — top-k sparse / int8 compressed contributions
# ---------------------------------------------------------------------------

# int16 within-block offsets: a block may not exceed the int16 range
MAX_DELTA_BLOCK = 32768


@dataclass(frozen=True)
class DeltaPayload:
    """One compressed contribution delta: per-block top-k sparse indices,
    int8-quantized values, and per-block f32 scales (docs/service_loop.md
    §Compressed submissions).

    The row of ``size`` elements is partitioned into ``n_blocks`` blocks of
    ``block`` elements (LANE-aligned, so the decode kernel's grid is whole
    tiles); each block keeps exactly ``k_per_block`` entries — the fixed
    shape is what lets K payloads stack into one ``[K, nb, kb]`` kernel
    operand (a global top-k would be ragged).  Unused slots hold
    ``(offset 0, value 0)`` and decode to a harmless ``+0``.

    * ``indices`` — ``[nb, kb]`` int16 offsets *within* each block;
    * ``values``  — ``[nb, kb]`` int8 quantized deltas (±127 clip);
    * ``scales``  — ``[nb]`` f32, ``max|selected delta| / 127`` per block
      (0 for all-zero blocks).

    Reconstruction is ``delta ≈ values·scales`` scattered at the indices:
    kept entries carry ≤ ``scale/2`` quantization error, dropped entries
    err by their own magnitude (bounded by the smallest kept magnitude in
    their block) — the error-bound contract pinned by
    tests/test_delta_codec.py.
    """

    indices: np.ndarray   # [nb, kb] int16
    values: np.ndarray    # [nb, kb] int8
    scales: np.ndarray    # [nb] float32
    size: int             # decoded element count (N, or shard_len)
    block: int            # elements per codec block (LANE-aligned)

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"size must be >= 1, got {self.size}")
        if self.block % LANE or not (0 < self.block <= MAX_DELTA_BLOCK):
            raise ValueError(
                f"block {self.block} must be a LANE multiple in "
                f"(0, {MAX_DELTA_BLOCK}]")
        nb = -(-self.size // self.block)
        idx, val, scl = self.indices, self.values, self.scales
        if idx.dtype != np.int16 or val.dtype != np.int8 \
                or scl.dtype != np.float32:
            raise ValueError(
                f"payload dtypes ({idx.dtype}, {val.dtype}, {scl.dtype}) != "
                "(int16, int8, float32)")
        if idx.ndim != 2 or idx.shape[0] != nb or idx.shape != val.shape \
                or scl.shape != (nb,):
            raise ValueError(
                f"payload shapes idx{idx.shape} val{val.shape} "
                f"scl{scl.shape} inconsistent with size={self.size} "
                f"block={self.block}")
        if idx.shape[1] > self.block:
            raise ValueError(
                f"k_per_block {idx.shape[1]} > block {self.block}")
        if idx.size and (idx.min() < 0 or int(idx.max()) >= self.block):
            raise ValueError("payload indices out of block range")

    @property
    def n_blocks(self) -> int:
        return self.indices.shape[0]

    @property
    def k_per_block(self) -> int:
        return self.indices.shape[1]

    @property
    def nbytes(self) -> int:
        """Encoded payload bytes (the queue-bandwidth figure of merit)."""
        return self.indices.nbytes + self.values.nbytes + self.scales.nbytes


def _as_f32_row(buf, what: str) -> np.ndarray:
    arr = np.asarray(buf)
    if arr.dtype == jnp.bfloat16:
        arr = arr.astype(np.float32)
    arr = np.ascontiguousarray(arr, np.float32)
    if arr.ndim != 1:
        raise ValueError(f"{what} must be 1-D, got shape {arr.shape}")
    return arr


def delta_encode(row, base, *, k_per_block: int,
                 block: int = LANE) -> DeltaPayload:
    """Encode ``row − base`` as a per-block top-k / int8 ``DeltaPayload``.

    Selection is by |delta| per block with a stable order, so the same
    inputs always produce byte-identical payloads (the checksum contract).
    Non-finite deltas are a caller bug and raise — the service treats a
    non-finite *scale* on disk as a malformed rider."""
    row, base = _as_f32_row(row, "row"), _as_f32_row(base, "base")
    if row.shape != base.shape:
        raise ValueError(f"row shape {row.shape} != base shape {base.shape}")
    size = row.shape[0]
    if size < 1:
        raise ValueError("cannot encode an empty row")
    d = row - base
    if not np.isfinite(d).all():
        raise ValueError("delta contains non-finite values")
    nb = -(-size // block)
    kb = int(k_per_block)
    if not (0 <= kb <= block):
        raise ValueError(f"k_per_block {kb} not in [0, {block}]")
    pad = nb * block - size
    if pad:
        d = np.concatenate([d, np.zeros((pad,), np.float32)])
    d = d.reshape(nb, block)
    if kb == 0:
        return DeltaPayload(np.zeros((nb, 0), np.int16),
                            np.zeros((nb, 0), np.int8),
                            np.zeros((nb,), np.float32), size, block)
    # stable top-k by magnitude: deterministic for byte-identical payloads
    order = np.argsort(-np.abs(d), axis=1, kind="stable")[:, :kb]
    sel = np.take_along_axis(d, order, axis=1)            # [nb, kb]
    scales = (np.max(np.abs(sel), axis=1) / 127.0).astype(np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        q = np.where(scales[:, None] > 0.0, sel / scales[:, None], 0.0)
    values = np.clip(np.rint(q), -127, 127).astype(np.int8)
    return DeltaPayload(order.astype(np.int16), values, scales, size, block)


def delta_decode(payload: DeltaPayload, base=None) -> np.ndarray:
    """Decode a payload to its dense f32 delta (or ``base + delta`` when a
    base row is given).  Duplicate indices accumulate — matching the
    decode+accumulate kernel's scatter-add semantics."""
    nb, kb = payload.indices.shape
    dense = np.zeros((nb * payload.block,), np.float32)
    if kb:
        flat_idx = (np.arange(nb, dtype=np.int64)[:, None] * payload.block
                    + payload.indices.astype(np.int64))
        dv = payload.values.astype(np.float32) * payload.scales[:, None]
        np.add.at(dense, flat_idx.reshape(-1), dv.reshape(-1))
    dense = dense[: payload.size]
    if base is None:
        return dense
    base = _as_f32_row(base, "base")
    if base.shape != dense.shape:
        raise ValueError(f"base shape {base.shape} != ({payload.size},)")
    return base + dense


def delta_entries(payload: DeltaPayload
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(flat indices, dequantized delta values) of a payload's non-zero
    entries — padding-slot and zero-quantized entries dropped.  This is the
    sparse view the sketch correction consumes; no dense row materializes."""
    nb, kb = payload.indices.shape
    if kb == 0:
        return (np.zeros((0,), np.int64), np.zeros((0,), np.float32))
    gi = (np.arange(nb, dtype=np.int64)[:, None] * payload.block
          + payload.indices.astype(np.int64)).reshape(-1)
    dv = (payload.values.astype(np.float32)
          * payload.scales[:, None]).reshape(-1)
    keep = (gi < payload.size) & (dv != 0.0)
    return gi[keep], dv[keep]


def delta_encode_sharded(row, base, sspec: ShardedFlatSpec, *,
                         k_per_block: int,
                         block: int = LANE) -> List[DeltaPayload]:
    """Per-shard variant: encode each block-cyclic ``shard_slices`` slice of
    ``row`` against the matching slice of ``base`` — the compressed analog
    of ``save_flat_shards``'s spill layout.  ``sspec.block`` must be a
    multiple of the codec block so codec blocks never straddle shards."""
    if sspec.block % block:
        raise ValueError(
            f"shard block {sspec.block} not a multiple of codec block {block}")
    row_s = sspec.shard_slices(_as_f32_row(row, "row"))
    base_s = sspec.shard_slices(_as_f32_row(base, "base"))
    return [delta_encode(r, b, k_per_block=k_per_block, block=block)
            for r, b in zip(row_s, base_s)]


def delta_decode_sharded(payloads: Sequence[DeltaPayload],
                         sspec: ShardedFlatSpec, base=None) -> np.ndarray:
    """Per-shard payloads -> the dense ``[N]`` delta (or ``base + delta``)
    — the host fallback when a spilled compressed layout does not match the
    mesh the repository runs under."""
    if len(payloads) != sspec.n_shards:
        raise ValueError(
            f"{len(payloads)} payloads != n_shards {sspec.n_shards}")
    delta = sspec.unshard_slices([delta_decode(p) for p in payloads])
    if base is None:
        return delta
    return _as_f32_row(base, "base") + delta


def delta_checksum(payloads) -> str:
    """CRC32 (hex) over the *encoded* payload bytes, in canonical order
    (geometry, then indices/values/scales per payload).  This — not the
    decoded row's CRC — is what ``verify_checksums`` recomputes for a
    compressed submission: the checksum covers the bytes that actually
    cross the queue, so a liar rider stamping the decoded row's CRC is a
    per-file rejection."""
    if isinstance(payloads, DeltaPayload):
        payloads = [payloads]
    crc = 0
    for p in payloads:
        crc = zlib.crc32(f"{p.size}:{p.block}:{p.k_per_block};".encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(p.indices), crc)
        crc = zlib.crc32(np.ascontiguousarray(p.values), crc)
        crc = zlib.crc32(np.ascontiguousarray(p.scales), crc)
    return f"{crc & 0xFFFFFFFF:08x}"


def sketch_apply_delta(base_sketch, indices, dvals, base_at,
                       n_buckets: int = SKETCH_BUCKETS) -> np.ndarray:
    """Sketch of ``base + delta`` from the base's sketch and the sparse
    delta — no dense host row.  Exact in exact arithmetic:

    * bucket of flat element ``i`` is ``(i // LANE) % n_buckets`` (the
      tile-bucket convention of ``row_sketch_host``);
    * sums gain ``Σ dv`` per bucket, squared norms gain
      ``Σ dv·(dv + 2·base[i])`` per bucket (``(b+d)² − b²``).

    ``base_at`` is the base row gathered at ``indices`` — the only base
    values the correction needs."""
    sk = np.array(base_sketch, np.float64, copy=True)
    if sk.shape != (2, n_buckets):
        raise ValueError(f"base sketch shape {sk.shape} != (2, {n_buckets})")
    b = (np.asarray(indices, np.int64) // LANE) % n_buckets
    dv = np.asarray(dvals, np.float64)
    ba = np.asarray(base_at, np.float64)
    sk[0] += np.bincount(b, weights=dv, minlength=n_buckets)
    sk[1] += np.bincount(b, weights=dv * (dv + 2.0 * ba),
                         minlength=n_buckets)
    return sk


# ---------------------------------------------------------------------------
# StagedBuffer / BufferPair — the async double-buffered staging primitives
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StagedBuffer:
    """Explicit handle to one stacked cohort operand.

    The fuse entry points (``ops.fuse_flat``, ``ops.fuse_flat_sharded``,
    ``ops.cohort_fuse_sharded``, ``Repository.fuse_pending``) accept either
    a raw array or this handle; the handle names the layout so callers and
    the Repository can hand a staged cohort around without re-deriving what
    it is:

    * ``data`` is ``[K, N]`` (single device) or ``[K, S, shard_len]``
      (block-cyclic over a mesh, ``sharded`` True);
    * ``k`` is the cohort size (leading dim).
    """

    data: jax.Array

    @property
    def k(self) -> int:
        return self.data.shape[0]

    @property
    def sharded(self) -> bool:
        return self.data.ndim == 3

    @classmethod
    def from_rows(cls, rows: Sequence[jax.Array]) -> "StagedBuffer":
        """Stack K staged ``[N]`` (or ``[S, shard_len]``) rows."""
        if not rows:
            raise ValueError("cannot stage an empty cohort")
        return cls(jnp.stack(list(rows)))


class StagingSide:
    """One side of the double buffer: the parallel per-contribution lists
    the Repository staging keeps (row/path, fisher, weight, and — with
    spill — the manifest entry describing the on-disk row)."""

    __slots__ = ("rows", "fishers", "weights", "manifest")

    def __init__(self):
        self.rows: List[Any] = []
        self.fishers: List[Any] = []
        self.weights: List[Any] = []
        self.manifest: List[Dict[str, Any]] = []

    def __len__(self) -> int:
        return len(self.rows)


class BufferPair:
    """Front/back staging pair (docs/async_repository.md).

    ``upload`` appends to the **front** side; ``swap()`` moves the front
    cohort to the **back** (the fuse operand of the in-flight dispatch) and
    opens a fresh front, so uploads continue while the back is being fused
    on device.  ``retire_back()`` drops the back side once its fuse has
    published.  The pair never holds more than one in-flight cohort: a
    second ``swap()`` before ``retire_back()`` is a caller bug and raises.
    """

    def __init__(self):
        self.front = StagingSide()
        self.back: Optional[StagingSide] = None

    def swap(self) -> StagingSide:
        if self.back is not None:
            raise RuntimeError("back buffer still in flight — finalize the "
                               "pending fuse before swapping again")
        self.back = self.front
        self.front = StagingSide()
        return self.back

    def retire_back(self) -> None:
        self.back = None

    def manifest_entries(self) -> List[Dict[str, Any]]:
        """All staged-but-unfused manifest entries, back (in-flight, not yet
        published) first — exactly the rows a crash right now would need to
        recover.  Reads a local capture of ``back``: spill-executor workers
        call this under the Repository's manifest lock while the main
        thread swaps/retires under the same lock, but the capture keeps a
        concurrent retire from turning the None-check into an attribute
        error even if a future call site forgets the lock."""
        back = self.back
        entries = list(back.manifest) if back is not None else []
        return entries + list(self.front.manifest)
