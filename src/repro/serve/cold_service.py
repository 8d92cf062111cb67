"""The contributor service loop: a queue-driven fusion daemon.

ColD Fusion's core claim (paper Fig. 1, §2.3) is a *synergistic loop*:
many independent contributors continually recycle finetuned models into a
shared base, with only limited communication — no gradients, no lockstep.
This module turns the async double-buffered ``Repository`` into that
always-on service:

* **ContributorClient** — submits finetuned models as atomically-written
  flat rows (whole ``[N]`` or per-shard slices) into a durable on-disk
  **contribution queue** (``<root>/queue/``), and polls the published base
  iteration through a status file.  Contributors never touch the
  Repository object; the queue directory is the only shared surface.
* **ColdService** — a polling daemon that owns the Repository: it batches
  queue arrivals into cohorts under an **admission policy** (size /
  timeout / staleness screening at the queue boundary), drives
  ``fuse_pending(wait=False)`` so device fuses overlap queue drain, and
  publishes a status endpoint (iteration, queue depth, fuse latency).

Exactly-once fusion across crashes
----------------------------------

The hand-off rides the PR 3 spill/manifest machinery instead of inventing
a second durability story.  Admission calls
``Repository.ingest_spilled(path)``: the queue npz *becomes* the spill row
(no copy) and is recorded in the crash-recoverable staging manifest.  The
orderings that make every window safe:

1. a submission exists only once its npz lands via atomic
   ``os.replace`` — a contributor killed mid-enqueue leaves at most an
   ignorable ``.tmp-*`` file, and a retry of the same ``(name, seq)``
   replaces the same file idempotently;
2. **ingest before admit-mark**: the row enters the staging manifest
   (durable) before the queue manifest records it as admitted.  A crash
   between the two is healed on restart: the file is found in
   ``Repository.staged_spill_files()`` and simply re-marked, never
   re-ingested;
3. from staged to published, the Repository's own ``staged_at`` /
   ``fusing`` markers guarantee a killed daemon re-fuses a dispatched
   cohort iff its publish did not land (docs/async_repository.md);
4. **delete file before dropping its queue entry**: a consumed submission
   (admitted, yet absent from the staging manifest) is GC'd file-first, so
   a crash mid-GC leaves an orphan *entry* (harmless, dropped next pass)
   rather than an orphan *file* (which would look like a fresh submission
   and double-fuse).

Every ``faults.crash_point`` below names one of these windows; the
kill-at-checkpoint harness in ``tests/_faults.py`` arms them one at a time
and asserts the restarted daemon converges to the uninterrupted run's
base.  See docs/service_loop.md for the full crash matrix.

The forgetting regression gate
------------------------------

With ``gate=`` (a ``repro.serve.probes.RegressionGate``) armed, every
publish is *probed* before the service builds on it: fuses run
synchronously, the new base is scored by the fixed per-task probe suite,
and the scores are compared against the pre-fuse baseline.  A clean
publish refreshes the durable baseline (``gate_state.json``); a tripped
gate **quarantines** the offending cohort's queue files into
``<root>/quarantine/`` (never deleted, never re-fused) and **rolls the
repository back** to the baseline base on disk.  The gate verdict is
deterministic and the baseline durable, so a kill -9 anywhere in
probe → rollback → quarantine is replayed on restart — the bad publish
can never outlive the daemon that let it through.  Every cycle that
changes state appends one record to the append-only ``metrics.jsonl``
time series (torn tail repaired on restart).  See docs/observability.md.
"""
from __future__ import annotations

import math
import os
import random
import time
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.checkpoint import io as ckpt
from repro.core.repository import (NothingToFuse, Repository,
                                   RepositoryFamily, family_member_root)
from repro.serve.probes import RegressionGate
from repro.utils import faults, trace
from repro.utils.flat import (LANE, FamilyRouter, FlatSpec, ShardedFlatSpec,
                              delta_checksum, delta_encode,
                              delta_encode_sharded, row_checksum,
                              row_sketch_host)

QUEUE_DIR = "queue"
QUEUE_MANIFEST = "queue_manifest.json"
STATUS_FILE = "service_status.json"
QUARANTINE_DIR = "quarantine"
GATE_STATE_FILE = "gate_state.json"
METRICS_FILE = "metrics.jsonl"
# rotation cap for the active metrics file (docs/observability.md): at or
# past this size the daemon renames it to metrics.jsonl.1 before its next
# append.  The daemon is the SINGLE rotator — pool workers only O_APPEND.
METRICS_ROTATE_BYTES = 4 * 1024 * 1024
# owned by the ServingWorker (repro.serve.hot_swap), NOT the daemon: two
# processes atomically rewriting one status file would clobber each other,
# so the worker persists its own file and status() embeds it read-only
SERVING_STATE_FILE = "serving_state.json"


def serving_state_filename(worker_id: Optional[str] = None) -> str:
    """The serving-state file for one worker: the solo ``ServingWorker``
    keeps the historical ``serving_state.json``; pool members namespace
    theirs as ``serving_state-<id>.json`` so N workers under one root
    never clobber each other (``status()`` aggregates the namespace)."""
    if worker_id is None:
        return SERVING_STATE_FILE
    wid = str(worker_id)
    if not wid or any(c in wid for c in "/\\."):
        raise ValueError(f"invalid worker_id for state file: {worker_id!r}")
    return f"serving_state-{wid}.json"
ERROR_RING = 16  # recent_errors entries kept (and persisted) per service
ROUTE_RING = 64  # recent routing decisions surfaced in the status endpoint


def _queue_dir(root: str) -> str:
    return os.path.join(root, QUEUE_DIR)


# ---------------------------------------------------------------------------
# contributor side
# ---------------------------------------------------------------------------


class ContributorClient:
    """A contributor's handle on the service: submit rows, poll the base.

    ``name`` must be unique among concurrently-running contributors — the
    submission file is ``<name>-<seq>.npz``, and that determinism is what
    makes retries idempotent (re-submitting the same ``seq`` atomically
    replaces the same file; it can never enqueue twice).  The default name
    embeds the pid."""

    def __init__(self, root: str, name: Optional[str] = None):
        self.root = root
        self.name = name if name is not None else f"c{os.getpid()}"
        self._seq = 0
        self._spec: Optional[FlatSpec] = None

    # -- submit ---------------------------------------------------------
    def submit(self, params=None, *, row=None, spec: Optional[FlatSpec] = None,
               sspec: Optional[ShardedFlatSpec] = None,
               weight: Optional[float] = None,
               base_iteration: Optional[int] = None,
               seq: Optional[int] = None,
               checksum: bool = False,
               sketch: Optional[bool] = None,
               compress: bool = False,
               base=None,
               family: Optional[str] = None,
               k_per_block: int = 64,
               codec_block: int = LANE) -> str:
        """Enqueue one contribution; returns the submission id once (and
        only once) it is durably in the queue.

        Pass a ``params`` pytree (flattened here), or a pre-flattened
        ``row`` with its ``spec``.  With ``sspec`` the row is written as
        per-shard block-cyclic slices (``ShardedFlatSpec.shard_slices``) —
        the layout a mesh repository stages without host reassembly.
        ``base_iteration`` is the iteration of the base this contribution
        was finetuned from; the service's admission policy screens
        staleness on it.  ``seq`` replays a specific submission (retry);
        by default it auto-increments.

        ``checksum=True`` additionally stamps a CRC of the portable row
        for end-to-end verification under ``verify_checksums`` admission —
        covering the shard/unshard rearrangement, not just the file.
        Torn-file detection needs no checksum: the atomic write hides
        partial files, and the npz zip entry's own CRC is verified on
        read.

        The rider can also carry the row's content **sketch**
        (``repro.kernels.ops.row_sketch`` of the portable row) so the
        service's novelty screen needs no extra row read at admission.
        ``sketch=None`` (default) stamps it iff the service's published
        status says the screen is armed (or no status exists yet);
        True/False force it.  It sits in the same trust class as
        ``weight``/``base_iteration`` (a rider that mis-states it only
        distorts the advisory screen for its own row — no different from
        perturbing the row itself); under ``verify_checksums`` the service
        recomputes it from the file.

        ``compress=True`` enqueues the contribution **delta-compressed**
        (docs/service_loop.md): the difference against ``base`` (the
        pulled base pytree, or its pre-flattened row) is encoded as
        per-block top-``k_per_block`` sparse int8 values with per-block
        float scales (``repro.utils.flat.delta_encode``; per-shard under
        ``sspec``) — typically 5-10x fewer queue bytes than a dense row.
        Requires ``base_iteration``: the service admits a compressed
        delta only against its exact declared base vintage (a delta means
        nothing against any other base).  ``checksum=True`` then stamps a
        CRC of the *encoded payload bytes*, which is what the service
        recomputes under ``verify_checksums``.

        ``family=`` declares which family member's base this contribution
        was finetuned from (docs/service_loop.md routing; default the
        main base).  Under a routing service the declaration anchors the
        rider's delta — the actual fuse target is the router's decision,
        surfaced in the status ``routes`` ring — except for compressed
        submissions, which are *pinned*: routed anywhere but their
        declared member they are rejected, never decoded against the
        wrong base."""
        if row is None:
            if params is None:
                raise ValueError("submit needs params= or row=")
            spec = spec or self._spec or FlatSpec.from_tree(params)
            row = spec.flatten(params)
        elif spec is None:
            raise ValueError("row= requires spec=")
        self._spec = spec
        if seq is None:
            seq = self._seq
        self._seq = max(self._seq, seq) + 1
        sub_id = f"{self.name}-{seq:06d}"
        path = os.path.join(_queue_dir(self.root), sub_id + ".npz")
        os.makedirs(_queue_dir(self.root), exist_ok=True)
        host_row = np.asarray(row)
        payloads = None
        if compress:
            if base is None:
                raise ValueError("compress=True needs base= — the pulled "
                                 "base this contribution was finetuned from")
            if base_iteration is None:
                raise ValueError(
                    "compress=True needs base_iteration= — the service "
                    "admits a compressed delta only against its declared "
                    "base vintage")
            base_row = np.asarray(base if getattr(base, "ndim", None) == 1
                                  else spec.flatten(base))
            if sspec is not None:
                payloads = delta_encode_sharded(
                    host_row, base_row, sspec,
                    k_per_block=k_per_block, block=codec_block)
            else:
                payloads = delta_encode(host_row, base_row,
                                        k_per_block=k_per_block,
                                        block=codec_block)
        extra = {
            "id": sub_id,
            "contributor": self.name,
            "weight": None if weight is None else float(weight),
            "base_iteration": base_iteration,
            "submitted_at": time.time(),
        }
        if family is not None:
            extra["family"] = str(family)
        if compress:
            extra["codec"] = {"k_per_block": int(k_per_block),
                              "block": int(codec_block)}
        if sketch is None:
            st = self.status()
            sketch = (st is None or bool(st.get("novelty_screen"))
                      or bool(st.get("routing")))
        if sketch:
            # the row is already in hand: sketching it here is one cheap
            # host pass over memory, vs a full row re-read at admission
            extra["sketch"] = row_sketch_host(host_row).tolist()
        if checksum:
            # compressed submissions CRC the encoded payload bytes — the
            # artifact actually in the queue — so a rider cannot vouch for
            # a decode it never shipped (the liar-rider seam)
            extra["checksum"] = (delta_checksum(payloads) if compress
                                 else row_checksum(host_row))
        # the armed window: nothing durable has happened yet — a death here
        # (or anywhere inside the atomic write) enqueues nothing, and the
        # caller never receives the id
        faults.crash_point("client.mid_submit")
        if compress:
            ckpt.save_flat_delta(path, payloads, spec, sspec=sspec,
                                 extra=extra)
        elif sspec is not None:
            ckpt.save_flat_shards(path, sspec.shard_slices(host_row), spec,
                                  sspec, extra=extra)
        else:
            ckpt.save_flat(path, host_row, spec, extra=extra)
        return sub_id

    # -- poll -----------------------------------------------------------
    def status(self) -> Optional[Dict[str, Any]]:
        """The service's last published status, or None before the first
        cycle.  Never torn: the file is written atomically."""
        try:
            return ckpt.load_json(os.path.join(self.root, STATUS_FILE))
        except FileNotFoundError:
            return None

    def iteration(self) -> int:
        """The latest published base iteration (0 before any fuse)."""
        st = self.status()
        if st is not None:
            return int(st["iteration"])
        try:
            meta = ckpt.load_json(os.path.join(self.root, "repository.json"))
            return int(meta["iteration"])
        except FileNotFoundError:
            return 0

    def wait_for_iteration(self, target: int, *, timeout: float = 60.0,
                           interval: float = 0.02,
                           max_interval: float = 1.0) -> Dict[str, Any]:
        """Bounded poll until the published iteration reaches ``target``.
        Returns the status observed; raises TimeoutError at the deadline
        (never an unbounded sleep).

        Polling backs off exponentially from ``interval`` with full
        jitter, capped at ``max_interval`` — a fleet of contributors
        waiting on the same status file neither busy-spins the filesystem
        nor thunders in lockstep.  Every sleep is additionally clamped to
        the time remaining, so the total wait stays bounded by
        ``timeout`` regardless of the interval parameters."""
        deadline = time.monotonic() + timeout
        delay = interval
        while True:
            st = self.status()
            if st is not None and int(st["iteration"]) >= target:
                return st
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"iteration {target} not published within {timeout}s "
                    f"(last status: {st})")
            time.sleep(min(remaining, random.uniform(delay / 2, delay)))
            delay = min(delay * 2, max_interval)

    def download_base(self, family: Optional[str] = None):
        """Pull the latest published base pytree (Fig. 1, step 1) — of the
        named family member under a routing service, or the main base by
        default.  The base npz is durable before repository.json names it,
        so the load can never race a publish into a missing file."""
        root = (self.root if family is None
                else family_member_root(self.root, family))
        meta = ckpt.load_json(os.path.join(root, "repository.json"))
        it = int(meta["iteration"])
        return ckpt.load(os.path.join(root, f"base_iter{it:04d}.npz"))

    def family_iteration(self, family: str) -> int:
        """The named family member's published iteration (0 before any
        fuse; also 0 when the member does not exist yet — a member is
        born at iteration 0, so waiters need no existence special-case)."""
        st = self.status()
        fams = (st or {}).get("families") or {}
        if family in fams:
            return int(fams[family]["iteration"])
        try:
            meta = ckpt.load_json(os.path.join(
                family_member_root(self.root, family), "repository.json"))
            return int(meta["iteration"])
        except FileNotFoundError:
            return 0

    def wait_for_family(self, family: str, target: int, *,
                        timeout: float = 60.0, interval: float = 0.02,
                        max_interval: float = 1.0) -> Dict[str, Any]:
        """Bounded poll until the named member's published iteration
        reaches ``target`` — the routed-mode counterpart of
        ``wait_for_iteration``, with the same jittered backoff."""
        deadline = time.monotonic() + timeout
        delay = interval
        while True:
            st = self.status()
            if self.family_iteration(family) >= target:
                return st or {}
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"family {family!r} iteration {target} not published "
                    f"within {timeout}s (last status: {st})")
            time.sleep(min(remaining, random.uniform(delay / 2, delay)))
            delay = min(delay * 2, max_interval)

    def route_of(self, sub_id: str) -> Optional[Dict[str, Any]]:
        """The routing record for one of this contributor's submissions,
        from the status endpoint's recent-routes ring (None when the
        submission has not been routed yet, or has aged out of the
        ring)."""
        st = self.status()
        for rec in (st or {}).get("routes") or []:
            if rec.get("id") == sub_id:
                return rec
        return None


# ---------------------------------------------------------------------------
# service side
# ---------------------------------------------------------------------------


@dataclass
class AdmissionPolicy:
    """Cohort formation + screening at the queue boundary (the "Collaborative
    and Efficient Fine-tuning" framing: cheap per-row admission decisions
    here, the §9 statistical screen inside the fuse).

    * ``min_cohort`` — dispatch a fuse only once this many rows are staged
      (1 = fuse every arrival immediately);
    * ``max_wait_s`` — ...unless the oldest staged row has waited this long
      (0 = size-only batching);
    * ``max_cohort`` — admission stops staging past this many rows per
      cohort; the excess stays queued for the next round;
    * ``max_staleness`` — reject a submission whose recorded
      ``base_iteration`` lags the current base by more than this many
      iterations (None = accept any vintage).  Delta-compressed
      submissions ignore this knob: they are pinned to the *exact*
      current vintage (and deferred while a fuse is in flight), since a
      delta is only decodable against the base it was computed from;
    * ``verify_checksums`` — re-read each row at admission and verify the
      contributor's CRC (costs a full row read; off by default);
    * ``novelty_threshold`` — content-based novelty screen (ROADMAP
      "Similarity/novelty admission"): reject a submission whose row
      sketch sits within this relative distance of any of the last
      ``sketch_window`` admitted rows (``repro.utils.flat.CohortSketch``;
      costs one row read per admission).  0 still rejects exact replays;
      None (default) disables the screen;
    * ``sketch_window`` — how many recent admissions the novelty screen
      remembers (persisted in ``cohort_sketch.json``, so a restarted
      daemon screens against the same history);
    * ``compact_keep_bases`` — run ``Repository.compact`` after each
      publish, keeping this many bases (None = never compact);
    * ``max_bases`` / ``split_threshold`` / ``cross_fuse_every`` — the
      similarity router's knobs, live only when the service wraps a
      ``RepositoryFamily`` (docs/service_loop.md routing): submissions
      whose sketch delta sits further than ``split_threshold`` from every
      member spawn a new base (up to ``max_bases`` members; at the cap
      they route to the nearest anyway), and every ``cross_fuse_every``
      member publishes the whole family cross-fuses toward its mean
      (0 = never cross-fuse).
    """

    min_cohort: int = 1
    max_wait_s: float = 0.0
    max_cohort: int = 64
    max_staleness: Optional[int] = None
    verify_checksums: bool = False
    novelty_threshold: Optional[float] = None
    sketch_window: int = 32
    compact_keep_bases: Optional[int] = None
    max_bases: int = 1
    split_threshold: float = 0.8
    cross_fuse_every: int = 0


@dataclass
class _Lane:
    """Per-family-member service state: the member Repository plus the
    cohort clock and gate baseline that were service-global before
    routing.  A single-base service is exactly one ``main`` lane, so the
    lane machinery IS the old single-repo path, not a parallel one."""

    name: str
    repo: Repository
    queue_dir: str
    gate_path: str
    cohort_since: Optional[float] = None
    failed_cohort_size: Optional[int] = None
    gate_baseline: Optional[Dict[str, float]] = None
    gate_iteration: Optional[int] = None
    last_gate: Optional[Dict[str, Any]] = None


class ColdService:
    """The polling fusion daemon: wraps a spill-enabled Repository behind
    the durable contribution queue.  Single-owner: exactly one service per
    repository root (contributors scale horizontally instead).

    Pass ``family=`` (a ``RepositoryFamily``) instead of ``repo`` to arm
    **similarity routing** (docs/service_loop.md): every fresh submission
    is scored against each member's base sketch and windowed delta
    evidence (``repro.utils.flat.FamilyRouter``), moved into its nearest
    member's queue namespace, and fused there — with new members spawned
    when nothing is near (up to ``policy.max_bases``) and the family
    periodically cross-fused toward its mean."""

    def __init__(self, repo: Optional[Repository] = None, *,
                 family: Optional[RepositoryFamily] = None,
                 policy: Optional[AdmissionPolicy] = None,
                 gate: Optional[RegressionGate] = None):
        if (repo is None) == (family is None):
            raise ValueError(
                "ColdService takes exactly one of repo= (single base) or "
                "family= (similarity-routed RepositoryFamily)")
        if family is not None:
            # spawned members must inherit the queue-ingest spill contract
            family.member_kw.setdefault("spill", True)
            repo = family.members["main"]
        if not repo.root:
            raise ValueError("ColdService requires an on-disk repository")
        self.repo = repo
        self.family = family
        self._routing = family is not None
        self.policy = policy or AdmissionPolicy()
        self.gate = gate
        self.queue_dir = _queue_dir(repo.root)
        self.quarantine_dir = os.path.join(repo.root, QUARANTINE_DIR)
        self._router = FamilyRouter(
            split_threshold=self.policy.split_threshold,
            max_bases=self.policy.max_bases) if self._routing else None
        members = family.members if family is not None else {"main": repo}
        self._lanes: Dict[str, _Lane] = {
            name: self._make_lane(name, member)
            for name, member in members.items()}
        self._main = self._lanes["main"]
        self._qman_path = os.path.join(self.queue_dir, QUEUE_MANIFEST)
        self._status_path = os.path.join(repo.root, STATUS_FILE)
        self._metrics_path = os.path.join(repo.root, METRICS_FILE)
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._rejects: List[Dict[str, str]] = []
        self._fused_ids = 0          # queue submissions retired as fused
        self._rejected = 0
        self._novelty_rejected = 0   # subset of _rejected: near-duplicates
        self._quarantined = 0        # queue submissions banished by the gate
        self._rollbacks = 0          # gate trips that backed out a publish
        self._spawned = 0            # family members minted by the router
        self._cross_fuses = 0        # inter-member merges performed
        self._cross_counter = 0      # member publishes since the last one
        self._routes: List[Dict[str, Any]] = []
        self._last_pub = "main"      # lane of the most recent publish
        self._recent_errors: List[Dict[str, Any]] = []
        self._last_error: Optional[str] = None
        self._last_gate: Optional[Dict[str, Any]] = None
        self._cycle = 0
        self._metrics_mark: Optional[tuple] = None
        self._stop = False
        # a previous daemon killed mid-append leaves a torn final line;
        # truncate it BEFORE the first append or the next record would be
        # welded onto the fragment (mid-file corruption, which readers
        # rightly treat as fatal rather than as a crash artifact)
        torn = ckpt.repair_jsonl_tail(self._metrics_path)
        if torn:
            warnings.warn(f"metrics.jsonl: truncated a torn {torn}-byte "
                          "tail left by a crashed daemon")
        if gate is not None and self.policy.compact_keep_bases is not None \
                and self.policy.compact_keep_bases < 2:
            warnings.warn("regression gate needs the baseline base retained "
                          "on disk — raising compact_keep_bases to 2")
            self.policy.compact_keep_bases = 2
        self._load_queue_manifest()
        if gate is not None:
            # before _recover(): a publish whose gate verdict was lost to a
            # crash must be replayed first, or _recover would GC (as fused)
            # the very cohort the replayed verdict needs to quarantine
            self._init_gate()
        self._recover()
        if self.policy.novelty_threshold is not None or self._routing:
            # adopt (or create) the persisted sketch window before the
            # first admission, so the screen sees pre-crash history; the
            # router needs every member's sketch even with the novelty
            # screen off (base sketches + delta windows ARE its evidence)
            for lane in self._lanes.values():
                lane.repo.enable_cohort_sketch(
                    window=self.policy.sketch_window)
        # publish an initial status so contributors can see the policy
        # (e.g. whether to stamp rider sketches) before the first cycle
        ckpt.save_json_atomic(self._status_path, self.status())
        for lane in self._lanes.values():
            if lane.repo.n_staged:
                # rows recovered from the staging manifest start the cohort
                # clock too — max_wait_s must cover an undersized recovered
                # cohort, not just fresh arrivals
                lane.cohort_since = time.time()

    def _make_lane(self, name: str, member: Repository) -> _Lane:
        if not member.root:
            raise ValueError("ColdService requires an on-disk repository")
        if not member.spill:
            raise ValueError(
                "ColdService requires Repository(spill=True) — queue ingest "
                "rides the crash-recoverable staging manifest "
                f"(family member {name!r})")
        lane = _Lane(name=name, repo=member,
                     queue_dir=os.path.join(member.root, QUEUE_DIR),
                     gate_path=os.path.join(member.root, GATE_STATE_FILE))
        os.makedirs(lane.queue_dir, exist_ok=True)
        return lane

    # -- queue manifest -------------------------------------------------
    def _load_queue_manifest(self) -> None:
        try:
            data = ckpt.load_json(self._qman_path)
        except FileNotFoundError:
            return
        self._entries = {e["id"]: e for e in data.get("entries", [])}
        self._fused_ids = int(data.get("fused_total", 0))
        self._rejected = int(data.get("rejected_total", 0))
        self._novelty_rejected = int(data.get("novelty_rejected_total", 0))
        self._quarantined = int(data.get("quarantined_total", 0))
        self._rollbacks = int(data.get("rollbacks_total", 0))
        self._spawned = int(data.get("families_spawned_total", 0))
        self._cross_fuses = int(data.get("cross_fuses_total", 0))
        self._cross_counter = int(data.get("cross_counter", 0))
        self._recent_errors = list(data.get("recent_errors", []))[-ERROR_RING:]

    def _write_queue_manifest(self) -> None:
        ckpt.save_json_atomic(self._qman_path, {
            "version": 1,
            "fused_total": self._fused_ids,
            "rejected_total": self._rejected,
            "novelty_rejected_total": self._novelty_rejected,
            "quarantined_total": self._quarantined,
            "rollbacks_total": self._rollbacks,
            "families_spawned_total": self._spawned,
            "cross_fuses_total": self._cross_fuses,
            "cross_counter": self._cross_counter,
            "recent_errors": list(self._recent_errors),
            "entries": list(self._entries.values()),
        })

    def _entry_lane(self, e: Dict[str, Any]) -> _Lane:
        """The lane an entry's queue file lives in — ``main`` for entries
        written before routing existed (no ``family`` key)."""
        return self._lanes.get(e.get("family") or "main") or self._main

    def _recover(self) -> None:
        """Reconcile the queue manifest against the reopened repository.
        An *admitted* entry was, by the ingest-before-admit-mark ordering,
        in the staging manifest when it was marked — so if it is absent
        now, its cohort's publish landed (or recovery skipped it as
        consumed): GC it.  Entries still staged will fuse normally."""
        staged = {n: l.repo.staged_spill_files()
                  for n, l in self._lanes.items()}
        changed = False
        for sub_id, e in list(self._entries.items()):
            lane = self._entry_lane(e)
            if f"{QUEUE_DIR}/{e['file']}" in staged[lane.name]:
                continue
            path = os.path.join(lane.queue_dir, e["file"])
            if os.path.exists(path):
                os.remove(path)          # file first; see ordering (4)
            del self._entries[sub_id]
            self._fused_ids += 1
            changed = True
        if changed:
            self._write_queue_manifest()

    # -- the forgetting regression gate ---------------------------------
    def _init_gate(self) -> None:
        """Adopt (or establish) each lane's durable gate baseline,
        replaying any publish whose verdict a crash swallowed.

        Per member, ``gate_state.json`` records the probe scores of its
        last known-good base and iteration.  On start:

        * state matches the member's iteration — adopt it;
        * state lags the member — a publish landed post-baseline whose
          gate never ran (kill -9 between publish and verdict): re-score
          the current base and apply the verdict NOW, exactly as the dead
          daemon would have (probes are deterministic, so the replayed
          verdict is the one that was lost);
        * no state (or implausible state) — baseline = the current base.

        Gate state is strictly per member: a trip on one family member
        quarantines and rolls back that member alone."""
        for lane in list(self._lanes.values()):
            self._init_gate_lane(lane)

    def _init_gate_lane(self, lane: _Lane) -> None:
        state = None
        try:
            state = ckpt.load_json(lane.gate_path)
        except FileNotFoundError:
            pass
        if state is not None:
            try:
                it = int(state["iteration"])
                scores = {k: float(v) for k, v in state["scores"].items()}
            except (KeyError, TypeError, ValueError):
                warnings.warn("gate_state.json unreadable — re-baselining "
                              "on the current base")
                state = None
        if state is not None and it == lane.repo.iteration:
            lane.gate_baseline, lane.gate_iteration = scores, it
            return
        if state is not None and it < lane.repo.iteration:
            lane.gate_baseline, lane.gate_iteration = scores, it
            self._apply_gate_verdict(
                self.gate.check(scores, lane.repo.flat_base_host()), lane)
            return
        if state is not None:
            warnings.warn(
                f"gate_state.json names iteration {it} but the repository "
                f"is at {lane.repo.iteration} — re-baselining")
        self._rebaseline_gate(lane)

    def _rebaseline_gate(self, lane: _Lane) -> None:
        """Score the lane's current base as its new known-good baseline
        and persist it atomically."""
        lane.gate_baseline = self.gate.probes.score(
            lane.repo.flat_base_host())
        lane.gate_iteration = lane.repo.iteration
        ckpt.save_json_atomic(lane.gate_path, {
            "version": 1,
            "iteration": lane.gate_iteration,
            "scores": lane.gate_baseline,
        })

    def _apply_gate_verdict(self, report, lane: _Lane) -> Dict[str, Any]:
        """Act on a probe comparison of the lane's just-published base.

        Clean: the baseline advances to the new base (durably) and the
        service proceeds.  Tripped: the consumed cohort's queue files are
        **quarantined** (moved, counted, never re-fused), then the lane's
        repository **rolls back on disk** to its baseline iteration with
        the staged next cohort preserved — other family members' bases,
        baselines, and in-flight cohorts are untouched.  Quarantine
        strictly precedes rollback: while the bad base is still current,
        the member iteration sits ahead of its ``gate_state.json``, which
        is exactly the signal that makes a restarted daemon replay this
        verdict — roll back first and a crash before quarantine would
        leave the cohort looking ordinarily fused.  Returns the gate
        event for metrics."""
        faults.crash_point("service.post_probe")
        lane.last_gate = self._last_gate = report.to_json()
        if report.ok:
            self._rebaseline_gate(lane)
            return {"event": "probe", "ok": True, "family": lane.name,
                    "iteration": lane.repo.iteration,
                    "probe": self._last_gate}
        bad_iteration = lane.repo.iteration
        moved = self._quarantine_consumed(lane)
        self._emit_metrics({
            "event": "quarantine", "iteration": bad_iteration,
            "family": lane.name,
            "quarantined": moved, "quarantined_total": self._quarantined,
            "regressed": report.regressed, "worst_delta": report.worst,
        })
        faults.crash_point("service.post_quarantine")
        lane.repo.rollback(lane.gate_iteration, keep_staged=True)
        lane.failed_cohort_size = None  # the staged cohort is unrelated
        self._emit_metrics({
            "event": "rollback", "from_iteration": bad_iteration,
            "family": lane.name,
            "to_iteration": lane.gate_iteration,
            "rollbacks_total": self._rollbacks, "probe": self._last_gate,
        })
        return {"event": "rollback", "ok": False, "family": lane.name,
                "from_iteration": bad_iteration,
                "to_iteration": lane.gate_iteration,
                "quarantined": moved, "probe": self._last_gate}

    def _quarantine_consumed(self, lane: _Lane) -> int:
        """Move the lane's consumed cohort's queue files into the shared
        ``<root>/quarantine/`` — file moved (atomic ``os.replace``) before
        its entry is dropped, mirroring GC ordering (4): a crash
        mid-quarantine leaves an orphan *entry* whose file already sits in
        quarantine, finished by the replayed verdict; never an orphan
        queue file that could re-fuse.  Only entries routed to THIS lane
        are candidates — a gate trip never banishes another member's
        cohort.  Counters ride the same queue-manifest write as the entry
        drops, so ``quarantined_total`` (and the rollback count,
        incremented here because a trip quarantines exactly one cohort)
        stay exact across any crash."""
        staged = lane.repo.staged_spill_files()
        moved = 0
        for sub_id, e in list(self._entries.items()):
            if (e.get("family") or "main") != lane.name:
                continue  # another member's cohort: not this verdict's
            if f"{QUEUE_DIR}/{e['file']}" in staged:
                continue  # next cohort, still staged: not this publish's
            src = os.path.join(lane.queue_dir, e["file"])
            if os.path.exists(src):
                os.makedirs(self.quarantine_dir, exist_ok=True)
                os.replace(src, os.path.join(self.quarantine_dir, e["file"]))
            del self._entries[sub_id]
            self._quarantined += 1
            moved += 1
        if moved:
            self._rollbacks += 1
            self._write_queue_manifest()
        return moved

    # -- admission ------------------------------------------------------
    def _scan_new(self) -> List[Tuple[str, Optional[_Lane]]]:
        """Queue files not yet tracked, oldest submission order, as
        ``(filename, lane)`` pairs.  In-flight atomic writes (``*.tmp-*``)
        are invisible by construction.

        Fresh submissions land in the top-level queue and scan with
        ``lane=None`` — unrouted.  Files already sitting in a non-main
        member's queue namespace but absent from the queue manifest are a
        crash artifact of the post-route window (moved, then killed
        before ingest/admit-mark): they scan *forced* to that lane, so
        the restart finishes their admission without re-routing — the
        atomic move IS the durable routing decision."""
        known = {((e.get("family") or "main"), e["file"])
                 for e in self._entries.values()}
        out: List[Tuple[str, Optional[_Lane]]] = [
            (fn, None) for fn in sorted(os.listdir(self.queue_dir))
            if fn.endswith(".npz") and ".tmp-" not in fn
            and ("main", fn) not in known]
        for name, lane in self._lanes.items():
            if name == "main":
                continue
            out.extend(
                (fn, lane) for fn in sorted(os.listdir(lane.queue_dir))
                if fn.endswith(".npz") and ".tmp-" not in fn
                and (name, fn) not in known)
        return out

    def _reject(self, fn: str, reason: str, *, novelty: bool = False,
                lane: Optional[_Lane] = None) -> None:
        self._rejected += 1
        if novelty:
            self._novelty_rejected += 1
        self._rejects = (self._rejects + [{"file": fn, "reason": reason}])[-8:]
        path = os.path.join((lane or self._main).queue_dir, fn)
        if os.path.exists(path):
            os.remove(path)

    @staticmethod
    def _rider_error(extra: Dict[str, Any]) -> Optional[str]:
        """Screen queue-supplied rider metadata before anything consumes
        it: a garbage ``base_iteration``/``weight``/``id`` must be a
        per-file rejection reason, never an exception that aborts the admit
        pass (and stalls every other submission behind it)."""
        sub_id = extra.get("id")
        if sub_id is not None and not isinstance(sub_id, str):
            return f"malformed rider: id={sub_id!r} is not a string"
        base_it = extra.get("base_iteration")
        if base_it is not None:
            try:
                int(base_it)
            except (TypeError, ValueError):
                return (f"malformed rider: base_iteration={base_it!r} "
                        "is not an integer")
        weight = extra.get("weight")
        if weight is not None:
            try:
                w = float(weight)
            except (TypeError, ValueError):
                return f"malformed rider: weight={weight!r} is not a number"
            if not math.isfinite(w):
                # a NaN/inf weight would poison the weight normalization
                # w/Σw and publish a non-finite base — permanently
                return f"malformed rider: weight={weight!r} is not finite"
        return None

    def _checksum_ok(self, path: str, meta: Dict[str, Any],
                     want: str) -> Tuple[bool, Optional[np.ndarray]]:
        """Returns (crc matches, the portable [N] row it read) — callers
        that need the row again (the novelty screen's rider-distrust
        recompute) reuse it instead of paying a second full read.

        Compressed submissions verify against the **encoded payload
        bytes** (``repro.utils.flat.delta_checksum``) — the artifact
        actually enqueued — never against a decode: a liar rider stamping
        the CRC of the row it *claims* to decode to is a per-file
        checksum rejection, not an accepted forgery.  The returned row is
        None (the novelty screen sketches compressed rows from the delta
        instead)."""
        if meta.get("compressed"):
            payloads, _ = ckpt.load_flat_delta(path)
            return delta_checksum(payloads) == want, None
        if meta["sharded"]:
            with ckpt.FlatShardReader(path) as r:
                row = r.full_row()
        else:
            row, _ = ckpt.load_flat(path, as_jax=False)
        return row_checksum(row) == want, row

    def _compressed_screen(self, extra: Dict[str, Any], path: str,
                           lane: Optional[_Lane] = None) -> Optional[str]:
        """Admission screen for a delta-compressed submission.  Returns
        None (admit), ``"defer"`` (leave queued for the next cycle), or a
        per-file rejection reason.

        A delta is only decodable against the exact base it was computed
        from, so the vintage pin is equality — ``base_iteration`` must
        match the current iteration — not the dense rows' lag-tolerant
        ``max_staleness``.  While a fuse is in flight the next publish is
        already moving the base, so a current-vintage delta is *deferred*
        (kept in the queue, neither staged nor rejected) rather than
        admitted into a cohort that would decode it against tomorrow's
        base.  The payload arrays are validated here too: non-finite
        quantization scales would decode to a non-finite delta and poison
        the fuse, so they are malformed-rider rejections at the boundary,
        with the same per-file (never admit-pass-aborting) discipline as
        every other screen."""
        repo = (lane or self._main).repo
        bi = extra.get("base_iteration")
        if bi is None:
            return ("malformed rider: compressed submission without "
                    "base_iteration — a delta is only decodable against "
                    "its declared base")
        bi = int(bi)  # _rider_error already screened non-integers
        if repo.inflight:
            return "defer"
        if bi != repo.iteration:
            return (f"stale: delta encoded against base iteration {bi}, "
                    f"current {repo.iteration} — a compressed "
                    "submission must match the current vintage exactly")
        try:
            payloads, _ = ckpt.load_flat_delta(path)
        except Exception as err:  # torn/garbage payload entries
            return f"unreadable ({type(err).__name__}: {err})"
        for p in payloads:
            if not np.isfinite(p.scales).all():
                return ("malformed rider: non-finite quantization scale "
                        "in delta payload")
        return None

    def _admit(self) -> Dict[str, int]:
        """Stage new queue arrivals into their repository, up to the
        per-member cohort budget.  Unreadable / malformed / mismatched /
        stale / near-duplicate rows are rejected here at the queue
        boundary — they never reach the fuse.  Returns
        ``{"admitted": n, "queue_depth": files left unadmitted}``.

        Under routing, each fresh submission is first scored and moved
        into its member's queue namespace (``_route_admit``); every
        screen after that point — compressed vintage pin, staleness,
        novelty window, ingest — runs against the ROUTED member.  Files
        already sitting in a member namespace (the post-route crash
        window) skip re-scoring entirely.

        Already-staged files (ingested by a pre-crash admit whose
        queue-manifest write was lost) are re-marked UNCONDITIONALLY —
        outside the budget, before anything else.  A budget-starved
        re-mark would let the file fuse and leave the staging manifest
        while still looking brand-new to a later scan, which would
        re-ingest (double-fuse) it.  Re-marks are keyed by *(member,
        file)*: a rider ``id`` that differs from the filename stem must
        reuse the entry already tracking the file, never mint a second
        one."""
        new = self._scan_new()
        if not new:
            return {"admitted": 0, "queue_depth": 0}
        staged = {n: l.repo.staged_spill_files()
                  for n, l in self._lanes.items()}
        threshold = self.policy.novelty_threshold
        admitted = leftover = 0
        rejected0 = self._rejected
        for fn, forced in new:
            lane = forced if forced is not None else self._main
            path = os.path.join(lane.queue_dir, fn)
            sub_id = fn[:-len(".npz")]
            if f"{QUEUE_DIR}/{fn}" in staged[lane.name]:
                # re-mark only; bookkeeping fields best-effort, taken from
                # the entry already tracking this file if there is one
                prev = next(
                    (s for s, e in self._entries.items()
                     if e["file"] == fn
                     and (e.get("family") or "main") == lane.name), None)
                if prev is not None:
                    sub_id = prev
                    extra = {k: self._entries[prev].get(k)
                             for k in ("weight", "contributor")}
                else:
                    extra = {}
                weight = extra.get("weight")
            else:
                if ((forced is not None or not self._routing)
                        and self.policy.max_cohort - lane.repo.n_staged <= 0):
                    # routed-lane budgets are enforced inside _route_admit
                    # (before the move), so only already-placed files are
                    # budget-checked here
                    leftover += 1
                    continue
                try:
                    meta = ckpt.flat_row_meta(path)
                except Exception as err:  # torn/garbage enqueue: quarantine
                    self._reject(fn, f"unreadable ({type(err).__name__}: "
                                     f"{err})", lane=lane)
                    continue
                extra = meta.get("extra") or {}
                rider_err = self._rider_error(extra)
                if rider_err is not None:
                    self._reject(fn, rider_err, lane=lane)
                    continue
                sub_id = extra.get("id") or sub_id
                sketch = delta = None
                if self._routing:
                    if forced is None:
                        routed = self._route_admit(fn, path, meta, extra,
                                                   sub_id)
                        if routed is None:
                            continue
                        if routed == "defer":
                            leftover += 1
                            continue
                        lane, path, sketch, delta = routed
                    else:
                        sketch, bad = self._obtain_sketch(fn, path, meta,
                                                          lane=lane)
                        if bad:
                            continue
                        delta = self._delta_of(sketch, extra)
                if meta.get("compressed"):
                    verdict = self._compressed_screen(extra, path, lane)
                    if verdict == "defer":
                        # current-vintage delta arriving mid-fuse: neither
                        # staged (the in-flight publish is about to move
                        # the base it decodes against) nor rejected — it
                        # stays queued and admits next cycle
                        leftover += 1
                        continue
                    if verdict is not None:
                        self._reject(fn, verdict, lane=lane)
                        continue
                else:
                    stale = self._staleness(extra, lane)
                    if stale is not None:
                        self._reject(fn, stale, lane=lane)
                        continue
                row = None
                if self.policy.verify_checksums and extra.get("checksum"):
                    try:
                        ok, row = self._checksum_ok(path, meta,
                                                    extra["checksum"])
                    except Exception as err:
                        # torn or vanished between the meta peek and the
                        # full-row read: same quarantine as unreadable
                        # metadata, never an aborted admit pass
                        self._reject(fn, f"unreadable ({type(err).__name__}: "
                                         f"{err})", lane=lane)
                        continue
                    if not ok:
                        self._reject(fn, "checksum mismatch", lane=lane)
                        continue
                if threshold is not None or self._routing:
                    # with routing and the novelty screen off the sketch is
                    # still recorded: window deltas are routing evidence
                    dup = self._novelty_check(fn, path, meta, sub_id,
                                              threshold, lane=lane, row=row,
                                              sketch=sketch, delta=delta)
                    if dup:
                        continue
                w = extra.get("weight")
                weight = None if w is None else float(w)
                try:
                    lane.repo.ingest_spilled(path, weight=weight, meta=meta)
                except ValueError as err:  # FlatSpec mismatch etc.
                    if threshold is not None or self._routing:
                        # the pre-ingest sketch of a row that never staged
                        # must not pollute the novelty window
                        lane.repo.cohort_sketch.discard(sub_id)
                        lane.repo.save_cohort_sketch()
                    self._reject(fn, str(err), lane=lane)
                    continue
                # the row is durably staged; the admit-mark below is the
                # recoverable half of the hand-off (ordering (2))
                faults.crash_point("service.post_ingest")
            # dedupe by (member, file): this (re)admission supersedes any
            # entry that tracks the same file under a different id
            for other in [s for s, e in self._entries.items()
                          if e["file"] == fn and s != sub_id
                          and (e.get("family") or "main") == lane.name]:
                del self._entries[other]
            entry = {
                "id": sub_id, "file": fn, "state": "admitted",
                "weight": weight,
                "contributor": extra.get("contributor"),
                "admitted_at": time.time(),
                "staged_iteration": lane.repo.iteration,
            }
            if self._routing:
                entry["family"] = lane.name
            self._entries[sub_id] = entry
            admitted += 1
            lane.failed_cohort_size = None  # new blood: retry a stuck cohort
            if lane.cohort_since is None:
                lane.cohort_since = time.time()
        if admitted or self._rejected != rejected0:
            # rejections persist their counters too: a restarted daemon's
            # totals must agree with what the status endpoint reported
            self._write_queue_manifest()
        return {"admitted": admitted, "queue_depth": leftover}

    # -- routing --------------------------------------------------------
    def _route_admit(self, fn: str, path: str, meta: Dict[str, Any],
                     extra: Dict[str, Any], sub_id: str):
        """Route one fresh top-queue submission against the family
        (docs/service_loop.md).  Returns ``None`` (rejected, counted),
        ``"defer"`` (left queued for the next cycle), or
        ``(lane, path, sketch, delta)`` with ``path`` pointing at the
        file's post-move location in the routed member's queue namespace.

        The atomic ``move_atomic`` into the member namespace IS the
        durable routing decision: a crash anywhere after it (the
        ``service.post_route`` seam) is healed by ``_scan_new``'s
        forced-lane pass, which finishes admission in the routed member
        without re-scoring."""
        declared = str(extra.get("family") or "main")
        dl = self._lanes.get(declared)
        if dl is None:
            self._reject(fn, f"malformed rider: unknown family {declared!r}")
            return None
        bi = extra.get("base_iteration")
        bi = None if bi is None else int(bi)
        sketch, bad = self._obtain_sketch(fn, path, meta, lane=dl,
                                          at=self._main)
        if bad:
            return None
        decision = self._router.route(
            sketch, {n: l.repo.cohort_sketch for n, l in self._lanes.items()},
            declared=declared, base_iteration=bi)
        spawned = False
        if decision.spawn:
            if meta.get("compressed"):
                # the vintage pin below would reject it anyway — never
                # mint a member for a submission that cannot fuse there
                self._reject(fn, self._family_pin_reason(declared, None))
                return None
            lane = self._unclaimed_lane()
            if lane is None:
                lane = self._spawn_lane(declared, bi)
                spawned = True
        else:
            lane = self._lanes[decision.family]
            if meta.get("compressed") and lane.name != declared:
                self._reject(fn, self._family_pin_reason(declared, lane.name))
                return None
        if self.policy.max_cohort - lane.repo.n_staged <= 0:
            return "defer"
        if lane.name != "main":
            dst = os.path.join(lane.queue_dir, fn)
            ckpt.move_atomic(path, dst)
            path = dst
        faults.crash_point("service.post_route")
        self._routes = (self._routes + [{
            "id": sub_id, "family": lane.name,
            "distance": decision.distance, "spawned": spawned,
            "reason": decision.reason}])[-ROUTE_RING:]
        return lane, path, sketch, decision.delta

    @staticmethod
    def _family_pin_reason(declared: str, routed: Optional[str]) -> str:
        dst = ("a new family member" if routed is None
               else f"member {routed!r}")
        return (f"stale: delta encoded against family {declared!r} but "
                f"routed to {dst} — a compressed submission is pinned to "
                "its declared member's base")

    def _unclaimed_lane(self) -> Optional[_Lane]:
        """A spawned-but-evidence-free member: its spawning submission
        crashed away (or failed ingest) before leaving any trace, so the
        next spawn-worthy submission claims it instead of minting another
        — a durable spawn whose rider was lost must not grow the family
        twice."""
        for name in sorted(self._lanes):
            lane = self._lanes[name]
            if (name != "main" and not lane.repo.history
                    and not lane.repo.n_staged and not lane.repo.inflight
                    and lane.repo.cohort_sketch is not None
                    and not lane.repo.cohort_sketch.entries):
                return lane
        return None

    def _spawn_lane(self, declared: str,
                    seed_iteration: Optional[int]) -> _Lane:
        """Mint a new family member seeded from the declared member's base
        vintage, wire up its lane (sketch window, gate baseline), and
        persist the spawn counters."""
        name = self.family.spawn(seed_family=declared,
                                 seed_iteration=seed_iteration)
        member = self.family.members[name]
        lane = self._make_lane(name, member)
        self._lanes[name] = lane
        member.enable_cohort_sketch(window=self.policy.sketch_window)
        if self.gate is not None:
            self._rebaseline_gate(lane)
        self._spawned += 1
        self._write_queue_manifest()
        self._emit_metrics({
            "event": "family_spawn", "family": name,
            "seeded_from": declared, "families": len(self._lanes),
            "families_spawned_total": self._spawned,
        })
        return lane

    def _delta_of(self, sketch, extra: Dict[str, Any]
                  ) -> Optional[np.ndarray]:
        """Recompute a forced-lane file's routing delta (its projection
        sketch minus its declared base vintage's) for the routed member's
        evidence window — the post-route crash path skips the router,
        which would otherwise have supplied it."""
        declared = str(extra.get("family") or "main")
        dl = self._lanes.get(declared)
        if dl is None or dl.repo.cohort_sketch is None:
            return None
        bi = extra.get("base_iteration")
        b0 = dl.repo.cohort_sketch.base_at(None if bi is None else int(bi))
        if b0 is None:
            return None
        return (np.asarray(sketch, np.float64)[0]
                - np.asarray(b0, np.float64)[0])

    def _obtain_sketch(self, fn: str, path: str, meta: Dict[str, Any], *,
                       lane: _Lane, at: Optional[_Lane] = None,
                       row: Optional[np.ndarray] = None
                       ) -> Tuple[Optional[np.ndarray], bool]:
        """The submission's content sketch, as ``(sketch, rejected)``.

        The rider's pre-computed sketch is used when present (no row read
        at all); rows without one — or any rider sketch when
        ``verify_checksums`` distrusts riders — are sketched from ``row``
        (the checksum pass already read it) or from the file in one read
        (``Repository.sketch_row_file``, against ``lane``'s base for
        compressed deltas).  An unreadable file is rejected here (from
        ``at``'s queue namespace — the lane whose directory currently
        holds it) and reported as ``(None, True)``."""
        sk = lane.repo.cohort_sketch
        sketch = None
        rider = (meta.get("extra") or {}).get("sketch")
        if rider is not None and not self.policy.verify_checksums:
            try:
                arr = np.asarray(rider, np.float64)
                if arr.shape == (2, sk.n_buckets) and np.isfinite(arr).all():
                    sketch = arr
            except (TypeError, ValueError):
                sketch = None  # malformed rider sketch: compute from file
        if sketch is None and row is not None:
            sketch = row_sketch_host(row, sk.n_buckets)
        if sketch is None:
            try:
                sketch = lane.repo.sketch_row_file(path, meta=meta)
            except Exception as err:  # torn/vanished since the meta peek
                self._reject(fn, f"unreadable ({type(err).__name__}: {err})",
                             lane=at or lane)
                return None, True
        return sketch, False

    def _novelty_check(self, fn: str, path: str, meta: Dict[str, Any],
                       sub_id: str, threshold: Optional[float], *,
                       lane: Optional[_Lane] = None,
                       row: Optional[np.ndarray] = None,
                       sketch: Optional[np.ndarray] = None,
                       delta: Optional[np.ndarray] = None) -> bool:
        """The content-based novelty screen (docs/service_loop.md): obtain
        the row's sketch, reject the file if it sits within ``threshold``
        of any of the lane's windowed recent admissions, otherwise make
        the sketch (and its routing ``delta`` evidence) durable *before*
        the row stages.  Returns True when the file was rejected (caller
        skips it).  ``threshold=None`` (routing with the novelty screen
        off) skips the match but still records the evidence."""
        lane = lane or self._main
        sk = lane.repo.cohort_sketch
        if sketch is None:
            sketch, rejected = self._obtain_sketch(fn, path, meta, lane=lane,
                                                   row=row)
            if rejected:
                return True
        if threshold is not None:
            # the self-match exemption is keyed by id AND file: only the
            # same queue file's own pre-crash entry is skipped — a replay
            # forging a previously admitted rider id under a new file is
            # still screened
            hit = sk.match(sketch, threshold, skip_id=sub_id, skip_file=fn)
            if hit is not None:
                self._reject(
                    fn, f"near-duplicate of {hit[0]} (sketch distance "
                        f"{hit[1]:.4f} <= novelty_threshold {threshold:g})",
                    novelty=True, lane=lane)
                return True
        sk.add(sub_id, sketch, file=fn, delta=delta)
        lane.repo.save_cohort_sketch()
        # the sketch history is durable before the row stages: a crash in
        # this window re-screens the row against its own entry on restart,
        # which the id+file skip turns into a no-op, not a self-rejection
        faults.crash_point("service.post_sketch")
        return False

    def _staleness(self, extra: Dict[str, Any],
                   lane: Optional[_Lane] = None) -> Optional[str]:
        repo = (lane or self._main).repo
        lim = self.policy.max_staleness
        base_it = extra.get("base_iteration")
        if lim is None or base_it is None:
            return None
        try:
            base_it = int(base_it)
        except (TypeError, ValueError):  # _rider_error screens this first;
            # stay a per-file reason even if a caller skips that screen
            return (f"malformed rider: base_iteration={base_it!r} "
                    "is not an integer")
        lag = repo.iteration - base_it
        if lag > lim:
            return (f"stale: finetuned from iteration {base_it}, "
                    f"current {repo.iteration} (max_staleness={lim})")
        return None

    # -- fuse policy ----------------------------------------------------
    def _should_fuse(self, lane: _Lane) -> bool:
        n = lane.repo.n_staged
        if n == 0:
            return False
        if lane.failed_cohort_size == n:
            return False  # same cohort just failed; wait for arrivals
        if n >= self.policy.min_cohort:
            return True
        return (self.policy.max_wait_s > 0
                and lane.cohort_since is not None
                and time.time() - lane.cohort_since >= self.policy.max_wait_s)

    def _gc_consumed(self) -> None:
        """Drop queue entries whose rows left their member's staging
        manifest — i.e. whose cohort's publish is durable.  File deleted
        before the entry (ordering (4))."""
        staged = {n: l.repo.staged_spill_files()
                  for n, l in self._lanes.items()}
        changed = False
        for sub_id, e in list(self._entries.items()):
            lane = self._entry_lane(e)
            if f"{QUEUE_DIR}/{e['file']}" in staged[lane.name]:
                continue
            path = os.path.join(lane.queue_dir, e["file"])
            if os.path.exists(path):
                os.remove(path)
            faults.crash_point("service.mid_gc")
            del self._entries[sub_id]
            self._fused_ids += 1
            changed = True
        if changed:
            self._write_queue_manifest()

    def _note_error(self, err: Exception, lane: Optional[_Lane] = None
                    ) -> None:
        lane = lane or self._main
        self._last_error = f"{type(err).__name__}: {err}"
        lane.failed_cohort_size = lane.repo.n_staged
        # the ring (unlike last_error) survives the next clean cycle AND a
        # restart: an error observed once is an error an operator can still
        # see.  Persisted via the queue manifest — errors are rare, so the
        # extra atomic write is off every hot path.
        self._recent_errors = (self._recent_errors + [
            {"t": time.time(), "error": self._last_error}])[-ERROR_RING:]
        self._write_queue_manifest()

    # -- the poll cycle -------------------------------------------------
    def run_once(self) -> Dict[str, Any]:
        """One cycle of the service loop: admit (and route) arrivals,
        dispatch (or finalize) per the cohort policy in every lane, gate
        each publish when armed, GC consumed submissions, cross-fuse the
        family on schedule, publish status, append metrics.  Returns the
        status dict it published."""
        self._cycle += 1
        # the base every span of this cycle is tagged with
        it0 = self.repo.iteration
        with trace.span("service.admit", iteration=it0):
            adm = self._admit()
        gate_event = None
        published = []
        for lane in list(self._lanes.values()):
            it_before = lane.repo.iteration
            if self._should_fuse(lane):
                try:
                    if self.gate is not None:
                        # gated: fuse synchronously.  The wait=False
                        # overlap would let a second cohort dispatch
                        # against a base the gate is about to roll back —
                        # its rows would be consumed by a publish that
                        # never survives.  The gate trades that overlap
                        # for the probe (the service_loop/regression_gate
                        # bench bounds the cost).
                        lane.repo.fuse_pending(wait=True)
                    else:
                        # finalizes any in-flight fuse, then dispatches
                        # the staged cohort with wait=False: the device
                        # crunches while the next cycles keep draining
                        # the queue
                        lane.repo.fuse_pending(wait=False)
                    lane.cohort_since = None
                    self._last_error = None
                    faults.crash_point("service.post_dispatch")
                except NothingToFuse as err:  # e.g. all rows rejected
                    self._note_error(err, lane)
            elif lane.repo.inflight:
                # queue drained: publish the in-flight fuse instead of
                # sitting on it until the next arrival
                try:
                    lane.repo.flush()
                    self._last_error = None
                except NothingToFuse as err:
                    self._note_error(err, lane)
            if lane.repo.iteration != it_before:
                published.append(lane)
                self._last_pub = lane.name
                self._cross_counter += 1
                faults.crash_point("service.post_publish")
                if self.gate is not None:
                    gate_event = self._apply_gate_verdict(self.gate.check(
                        lane.gate_baseline, lane.repo.flat_base_host()),
                        lane)
        if published:
            with trace.span("service.bookkeep", iteration=it0):
                self._gc_consumed()
                for lane in published:
                    if (self.policy.compact_keep_bases is not None
                            and not lane.repo.inflight):
                        # compact only while quiescent: its flush() would
                        # otherwise synchronously finalize the fuse
                        # dispatched above and kill the wait=False overlap.
                        # Deferred compaction runs on the drain cycle that
                        # publishes without redispatching.
                        lane.repo.compact(
                            keep_bases=self.policy.compact_keep_bases)
        if (self._routing and self.policy.cross_fuse_every > 0
                and self._cross_counter >= self.policy.cross_fuse_every
                and len(self._lanes) >= 2
                and not any(l.repo.inflight or l.repo.n_staged
                            for l in self._lanes.values())):
            # quiescent on schedule: inter-cluster merge (the counter is
            # persisted, so a crashed daemon neither skips nor repeats
            # the round it already took credit for)
            self._cross_fuse()
        with trace.span("service.bookkeep", iteration=it0):
            st = self.status(admitted=adm["admitted"],
                             queue_depth=adm["queue_depth"])
            ckpt.save_json_atomic(self._status_path, st)
            self._emit_cycle_metrics(st, gate_event)
        return st

    def _cross_fuse(self) -> None:
        """One inter-cluster merge round (``RepositoryFamily.cross_fuse``)
        plus its service bookkeeping: counters persist, every lane's gate
        re-baselines on its moved base (the merge is an operator-level
        blend of gated bases, not a contributor cohort to gate), and the
        event lands in the metrics series."""
        self.family.cross_fuse()
        self._cross_fuses += 1
        self._cross_counter = 0
        self._write_queue_manifest()
        if self.gate is not None:
            for lane in self._lanes.values():
                self._rebaseline_gate(lane)
        self._emit_metrics({
            "event": "cross_fuse",
            "families": {n: l.repo.iteration
                         for n, l in self._lanes.items()},
            "cross_fuses_total": self._cross_fuses,
        })

    # -- metrics time series --------------------------------------------
    def _emit_metrics(self, record: Dict[str, Any]) -> None:
        """One record onto the append-only ``metrics.jsonl`` time series
        (docs/observability.md).  Advisory state: appends happen after the
        durability-critical writes of their cycle, so a crash can lose a
        record but the series never disagrees with the repository.  The
        daemon is the series' single rotator: once the active file
        reaches ``METRICS_ROTATE_BYTES`` it rolls to ``metrics.jsonl.1``
        (concurrent worker appends are rename-safe; see
        ``repro.checkpoint.io.rotate_jsonl``)."""
        ckpt.append_jsonl(self._metrics_path,
                          {"t": time.time(), **record},
                          rotate_bytes=METRICS_ROTATE_BYTES)

    def _emit_cycle_metrics(self, st: Dict[str, Any],
                            gate_event: Optional[Dict[str, Any]]) -> None:
        """Append the per-cycle record — for every cycle that *changed*
        anything (publish, admission, rejection, error, gate event) plus
        the first cycle.  Idle polls repeat the previous mark and are
        skipped, so a long-lived daemon's series grows with events, not
        wall time — and under sustained serve load the daemon (as the
        single rotator) caps the active file via ``METRICS_ROTATE_BYTES``
        in ``_emit_metrics``."""
        mark = (st["iteration"], st["staged"], st["admitted"],
                st["fused_queue_submissions"], st["rejected_total"],
                st["quarantined_total"], st["rollbacks_total"],
                st["last_error"])
        if mark == self._metrics_mark and gate_event is None:
            return
        self._metrics_mark = mark
        last = st["last_fuse"]
        self._emit_metrics({
            "event": "cycle",
            "cycle": self._cycle,
            "iteration": st["iteration"],
            "queue_depth": st["queue_depth"],
            "staged": st["staged"],
            "inflight": st["inflight"],
            "admitted_this_cycle": st["admitted_this_cycle"],
            "cohort": None if last is None else last["n_contributions"],
            "fuse_latency_s": st["fuse_latency_s"],
            "fused_queue_submissions": st["fused_queue_submissions"],
            "rejected_total": st["rejected_total"],
            "novelty_rejected_total": st["novelty_rejected_total"],
            "quarantined_total": st["quarantined_total"],
            "rollbacks_total": st["rollbacks_total"],
            "probe": None if gate_event is None else gate_event.get("probe"),
            "last_error": st["last_error"],
        })

    def serve_forever(self, *, poll_interval: float = 0.02,
                      max_iterations: Optional[int] = None,
                      idle_timeout: Optional[float] = None,
                      max_poll_interval: Optional[float] = None
                      ) -> Dict[str, Any]:
        """Run poll cycles until stopped: by ``request_stop()`` (signal
        handlers), by the published iteration reaching ``max_iterations``
        (once quiescent), or by ``idle_timeout`` seconds without progress
        — no admission and no publish, queue empty.  An undersized cohort
        held below ``min_cohort`` counts as idle time (its rows are
        durable in the staging manifest and survive the exit).  Returns
        the final status.

        No-progress sleeps back off exponentially (with jitter) from
        ``poll_interval`` up to ``max_poll_interval`` (default: the larger
        of ``poll_interval`` and 0.25s) — the same cap discipline as
        ``ContributorClient.wait_for_iteration`` — and reset on any
        progress.  An in-flight fuse pins the sleep at ``poll_interval``
        so its finalize is never backed off."""
        cap = (max(poll_interval, 0.25) if max_poll_interval is None
               else max(poll_interval, max_poll_interval))
        delay = poll_interval
        last_progress = time.monotonic()
        last_its = {n: l.repo.iteration for n, l in self._lanes.items()}
        while not self._stop:
            st = self.run_once()
            its = {n: l.repo.iteration for n, l in self._lanes.items()}
            progress = st["admitted_this_cycle"] or its != last_its
            last_its = its
            if progress:
                last_progress = time.monotonic()
                delay = poll_interval
            idle = (st["queue_depth"] == 0 and st["staged"] == 0
                    and not st["inflight"])
            if (max_iterations is not None and idle
                    and min(its.values()) >= max_iterations):
                # under routing EVERY member must reach the target — main
                # hitting it first must not strand another member's queue
                break
            if (idle_timeout is not None and st["queue_depth"] == 0
                    and not st["inflight"]
                    and time.monotonic() - last_progress >= idle_timeout):
                break
            if not progress:
                # nothing moved this cycle (empty queue, undersized or
                # screen-stuck cohort): sleep instead of busy-spinning the
                # scan/status write. An in-flight fuse finalizes next cycle.
                if st["inflight"]:
                    time.sleep(poll_interval)
                else:
                    time.sleep(random.uniform(delay / 2, delay))
                    delay = min(delay * 2, cap)
        return self.close()

    def request_stop(self) -> None:
        self._stop = True

    def close(self) -> Dict[str, Any]:
        """Quiesce: finalize any in-flight fuse, GC, publish a final
        status with ``running=False``.  Staged-but-unfused rows stay in
        the (durable) manifest for the next service instance."""
        self._stop = True
        for lane in list(self._lanes.values()):
            try:
                lane.repo.flush()
            except NothingToFuse as err:
                self._note_error(err, lane)
        self._gc_consumed()
        st = self.status()
        st["running"] = False
        ckpt.save_json_atomic(self._status_path, st)
        return st

    # -- status endpoint ------------------------------------------------
    def status(self, *, admitted: int = 0,
               queue_depth: Optional[int] = None) -> Dict[str, Any]:
        """The fields contributors (and operators) poll; persisted
        atomically to ``<root>/service_status.json`` every cycle.  See
        docs/service_loop.md for the field reference.  ``queue_depth=``
        reuses the admit pass's scan (one directory listing per cycle, not
        two); standalone calls re-scan.

        Aggregate fields (``staged``, ``inflight``, ``fuses``,
        ``fused_contributions``) sum/any over the whole family;
        ``iteration`` stays the main base's.  Under routing a
        ``families`` map carries each member's own iteration/staging/gate
        view, plus the recent ``routes`` ring and the spawn/cross-fuse
        totals."""
        lanes = self._lanes.values()
        lh = (self._lanes.get(self._last_pub) or self._main).repo.history
        last = lh[-1] if lh else None
        st = {
            "iteration": self.repo.iteration,
            "queue_depth": (len(self._scan_new()) if queue_depth is None
                            else queue_depth),
            "staged": sum(l.repo.n_staged for l in lanes),
            "inflight": any(l.repo.inflight for l in lanes),
            "admitted": len(self._entries),
            "admitted_this_cycle": admitted,
            "fuses": sum(len(l.repo.history) for l in lanes),
            "fused_contributions": sum(r.n_contributions for l in lanes
                                       for r in l.repo.history),
            "fused_queue_submissions": self._fused_ids,
            "rejected_total": self._rejected,
            "novelty_rejected_total": self._novelty_rejected,
            "novelty_screen": self.policy.novelty_threshold is not None,
            "sketch_entries": (None if self.repo.cohort_sketch is None
                               else len(self.repo.cohort_sketch)),
            "recent_rejects": list(self._rejects),
            "gate": self.gate is not None,
            "quarantined_total": self._quarantined,
            "rollbacks_total": self._rollbacks,
            "last_gate": self._last_gate,
            "routing": self._routing,
            "fuse_latency_s": (last.stage_s + last.finalize_s) if last
                              else None,
            "last_fuse": None if last is None else {
                "iteration": last.iteration,
                "n_contributions": last.n_contributions,
                "n_accepted": last.n_accepted,
                "op": last.op,
                "wall_time": last.wall_time,
            },
            "last_error": self._last_error,
            "recent_errors": list(self._recent_errors),
            "serving": self._serving_state(),
            "pid": os.getpid(),
            "running": not self._stop,
            "updated_at": time.time(),
        }
        if self._routing:
            st["families"] = {
                name: {
                    "iteration": lane.repo.iteration,
                    "staged": lane.repo.n_staged,
                    "inflight": lane.repo.inflight,
                    "fuses": len(lane.repo.history),
                    "fused_contributions": sum(
                        r.n_contributions for r in lane.repo.history),
                    "gate_iteration": lane.gate_iteration,
                    "last_gate": lane.last_gate,
                } for name, lane in self._lanes.items()}
            st["routes"] = list(self._routes)
            st["families_spawned_total"] = self._spawned
            st["cross_fuses_total"] = self._cross_fuses
        return st

    def _serving_state(self) -> Optional[Dict[str, Any]]:
        """The hot-swap worker-state namespace, embedded read-only (None
        when no worker ever served this root).

        A solo worker's ``serving_state.json`` passes through unchanged
        (the historical status shape).  When namespaced pool files
        (``serving_state-<id>.json``) exist, the block becomes an
        aggregate: the per-worker map plus rollups — summed request/swap
        counters, summed inflight, and ``iteration`` set only when every
        worker agrees (mid-swap divergence surfaces as ``None`` rather
        than a misleading single number)."""
        root = self.repo.root
        workers: Dict[str, Dict[str, Any]] = {}
        try:
            names = sorted(os.listdir(root))
        except FileNotFoundError:
            names = []
        for fn in names:
            if (fn.startswith("serving_state-") and fn.endswith(".json")):
                try:
                    workers[fn[len("serving_state-"):-len(".json")]] = \
                        ckpt.load_json(os.path.join(root, fn))
                except (FileNotFoundError, ValueError):
                    continue  # mid-replace or torn: skip, not fatal
        solo = None
        try:
            solo = ckpt.load_json(os.path.join(root, SERVING_STATE_FILE))
        except (FileNotFoundError, ValueError):
            pass
        if not workers:
            return solo   # legacy single-worker shape (or None)
        if solo is not None:
            workers.setdefault(solo.get("worker", "solo"), solo)
        iters = {w.get("iteration") for w in workers.values()}
        agg: Dict[str, Any] = {
            "workers": workers,
            "n_workers": len(workers),
            "iteration": iters.pop() if len(iters) == 1 else None,
            "swapping": any(w.get("swapping") for w in workers.values()),
        }
        for key in ("swaps_total", "live_swaps", "requests_total",
                    "requests_pinned_across_swaps", "requests_batched",
                    "inflight"):
            agg[key] = sum(int(w.get(key) or 0) for w in workers.values())
        agg["versions_served"] = sorted(
            {v for w in workers.values()
             for v in (w.get("versions_served") or [])})
        return agg
