"""Pytree checkpointing (npz-based; no orbax offline).

Flattens a pytree of arrays into an ``.npz`` keyed by the path string; the
treedef is reconstructed from the keys on load, so files are self-contained
and diff-able.  Used by the host-level Repository (contributors exchange
checkpoints, Fig. 1) and by the training driver.

All writes are atomic: the npz is written to a ``.tmp-<pid>`` sibling and
``os.replace``d into place, so a contributor crashing mid-upload can never
leave a truncated checkpoint in the repository root.

Three formats share the atomic writer:

* **tree** (``save``/``load``) — one npz entry per leaf, human-diffable;
* **flat** (``save_flat``/``load_flat``) — a single contiguous buffer plus
  its ``FlatSpec`` layout (JSON), the Repository's staging/spill format —
  one sequential read brings a contribution back as a fusable ``[N]`` row;
* **flat-sharded** (``save_flat_shards``/``FlatShardReader``) — the same
  row split into its S block-cyclic per-shard slices, one npz entry each,
  so a mesh repository's spilled rows reload shard by shard and the full
  ``[N]`` row never materializes on the host (docs/async_repository.md).

``save_json_atomic`` extends the same crash discipline to the Repository's
spill manifest: a reader can never observe a half-written JSON file.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import trace
from repro.utils.flat import DeltaPayload, FlatSpec, ShardedFlatSpec
from repro.utils.pytree import path_str

_SEP = "::"
_BF16 = "__bf16__"  # npz has no bfloat16: stored as uint16 bit pattern
_FLAT_BUF = "__flat_buffer__"
_FLAT_SPEC = "__flat_spec__"
_FLAT_SSPEC = "__flat_shard_spec__"
_FLAT_EXTRA = "__flat_extra__"  # free-form JSON rider (queue submissions)
_SHARD_FMT = "__flat_shard_{:04d}__"
_DELTA_SPEC = "__delta_spec__"      # codec geometry (compressed submissions)
_DELTA_IDX = "__delta_indices__"    # int16 [nb, kb] (or [S, nb, kb])
_DELTA_VAL = "__delta_values__"     # int8  [nb, kb] (or [S, nb, kb])
_DELTA_SCL = "__delta_scales__"     # f32   [nb]     (or [S, nb])


def _count_file(counter: str, path: str) -> None:
    """Add ``path``'s size to the trace counter (only while tracing)."""
    if trace.enabled():
        trace.count(counter, os.path.getsize(path))


def _flatten(tree) -> Dict[str, np.ndarray]:
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        key = path_str(path).replace("/", _SEP)
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            key += _BF16
            arr = arr.view(np.uint16)
        out[key] = arr
    return out


def _unflatten(d: Dict[str, np.ndarray]) -> Any:
    tree: Dict[str, Any] = {}
    for key, val in d.items():
        if key.endswith(_BF16):
            key = key[: -len(_BF16)]
            val = val.view(jnp.bfloat16)
        parts = key.split(_SEP)
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return tree


def _atomic_savez(path: str, arrays: Dict[str, np.ndarray]) -> None:
    path = os.path.abspath(path)
    # preserve np.savez semantics: a suffix-less target gets ".npz" appended
    if not path.endswith(".npz"):
        path += ".npz"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        np.savez(tmp, **arrays)
        # np.savez itself appends .npz when the target lacks the suffix
        if not tmp.endswith(".npz") and os.path.exists(tmp + ".npz"):
            tmp += ".npz"
        _count_file("io.write_bytes", tmp)
        os.replace(tmp, path)
    except BaseException:
        for cand in (tmp, tmp + ".npz"):
            if os.path.exists(cand):
                os.remove(cand)
        raise


def save(path: str, tree) -> None:
    _atomic_savez(path, _flatten(tree))


def load(path: str, *, as_jax: bool = True):
    with np.load(path) as data:
        tree = _unflatten({k: data[k] for k in data.files})
    if as_jax:
        tree = jax.tree.map(jnp.asarray, tree)
    return tree


# -- flat-buffer format (Repository staging / spill) ------------------------


def _extra_entry(extra: Dict[str, Any]) -> np.ndarray:
    return np.frombuffer(json.dumps(extra).encode(), dtype=np.uint8)


def save_flat(path: str, buf, spec: FlatSpec, *,
              extra: Optional[Dict[str, Any]] = None) -> None:
    """Persist a flat parameter buffer + its layout spec in one npz.

    ``extra`` rides along as a free-form JSON entry (surfaced by
    ``flat_row_meta``) — the contribution queue uses it for submission
    metadata (contributor, weight, base iteration, checksum) without
    changing the row format."""
    arr = np.asarray(buf)
    if arr.dtype == jnp.bfloat16:
        arr = arr.view(np.uint16)
    arrays = {
        _FLAT_BUF: arr,
        _FLAT_SPEC: np.frombuffer(
            json.dumps(spec.to_json()).encode(), dtype=np.uint8),
    }
    if extra is not None:
        arrays[_FLAT_EXTRA] = _extra_entry(extra)
    _atomic_savez(path, arrays)


def load_flat(path: str, *, as_jax: bool = True) -> Tuple[Any, FlatSpec]:
    """Load (buffer, spec) written by ``save_flat``."""
    with np.load(path) as data:
        if _FLAT_BUF not in data.files:
            raise ValueError(f"{path} is not a flat checkpoint")
        meta = json.loads(bytes(data[_FLAT_SPEC]).decode())
        spec = FlatSpec.from_json(meta)
        buf = data[_FLAT_BUF]
    _count_file("io.read_bytes", path)
    if spec.dtype == "bfloat16":
        buf = buf.view(jnp.bfloat16)
    if as_jax:
        buf = jnp.asarray(buf)
    return buf, spec


def is_flat(path: str) -> bool:
    with np.load(path) as data:
        return _FLAT_BUF in data.files


# -- atomic JSON (Repository spill manifest) --------------------------------


def save_json_atomic(path: str, obj: Any, *, default=None,
                     indent: Optional[int] = 2) -> None:
    """Write JSON with the same tmp + ``os.replace`` discipline as the npz
    writer: a crash mid-write can never leave a truncated manifest (or
    repository.json).  ``indent=None`` writes compact single-line JSON —
    for machine-only state rewritten on hot paths (the cohort sketch)."""
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # pid AND thread id: spill-executor threads of one process must not
    # truncate each other's in-progress tmp file
    tmp = f"{path}.tmp-{os.getpid()}-{threading.get_ident()}"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=indent, default=default)
        _count_file("io.write_bytes", tmp)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def move_atomic(src: str, dst: str) -> None:
    """Move a file with ``os.replace`` semantics, creating the destination
    directory first.  Same-filesystem renames are atomic: an observer sees
    the file at exactly one of the two paths, never torn or at both — the
    discipline the routed admission path relies on when it re-homes a
    queue file into a family member's queue."""
    dst = os.path.abspath(dst)
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    os.replace(src, dst)


# -- append-only JSONL (service metrics time series) ------------------------
#
# The atomic tmp+replace discipline above is wrong for a *time series*: a
# metrics log is appended hundreds of times per run and must never be
# rewritten whole.  Instead the file is strictly append-only — one JSON
# object per line — and readers tolerate exactly the damage a kill -9 can
# inflict on an O_APPEND writer: a torn FINAL line (no interior line can
# tear, because every earlier append completed before the next began).


def append_jsonl(path: str, obj: Any, *, default=None,
                 rotate_bytes: int | None = None) -> None:
    """Append one record to a JSONL file as a single ``\\n``-terminated
    line.  The line is built before the file is touched, so a serialization
    error appends nothing; a crash mid-``write`` leaves at most a torn
    final line, which ``read_jsonl``/``repair_jsonl_tail`` skip.

    ``rotate_bytes`` caps the active file: when it already holds at least
    that many bytes, it is rotated to ``<path>.1`` (replacing any previous
    rotation) before the append, so the active file never grows unboundedly
    under sustained load.  Rotation must have a SINGLE rotator — concurrent
    appenders are safe (O_APPEND), concurrent rotators are not; in the
    serving stack only the daemon rotates, pool workers plain-append."""
    line = json.dumps(obj, default=default)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    if rotate_bytes is not None:
        rotate_jsonl(path, rotate_bytes)
    with open(path, "a") as f:
        f.write(line + "\n")


def rotate_jsonl(path: str, max_bytes: int) -> bool:
    """Rotate ``path`` to ``path.1`` if it holds >= ``max_bytes`` bytes
    (single rotation slot: a previous ``path.1`` is replaced).  The rename
    is atomic, so a concurrent O_APPEND writer loses no records — a write
    racing the rename lands whole in exactly one of the two files; the
    next append recreates the active file.  Torn-tail repair and the
    read-side skip still apply to the ACTIVE file only: rotation moves a
    complete-records prefix (the torn tail, if any, is always the newest
    write, which postdates the size check).  Returns True if rotated."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return False
    if size < max_bytes:
        return False
    os.replace(path, path + ".1")
    return True


def read_jsonl(path: str, *, warn: bool = True,
               include_rotated: bool = False) -> list:
    """Parse a JSONL file, returning the records in order.  A torn tail —
    an unterminated or unparseable FINAL line, the only damage an
    append-only writer's death can cause — is skipped (with a warning by
    default), never raised: a monitoring reader must not stall the daemon
    or the operator.  A malformed line anywhere *else* raises ``ValueError``
    — that is corruption, not a crash artifact.  A missing file is an
    empty series, not an error (the reader may start before the first
    append).  ``include_rotated=True`` prepends the records of the
    rotation slot ``<path>.1`` (see ``rotate_jsonl``), yielding the full
    retained series in time order."""
    if include_rotated:
        return (read_jsonl(path + ".1", warn=warn)
                + read_jsonl(path, warn=warn))
    out = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except FileNotFoundError:
        return out
    for i, line in enumerate(lines):
        stripped = line.strip()
        if not stripped:
            continue
        try:
            rec = json.loads(stripped)
        except json.JSONDecodeError as err:
            if i == len(lines) - 1:
                if warn:
                    import warnings
                    warnings.warn(f"{path}: skipping torn final line "
                                  f"({len(stripped)} bytes): {err}")
                break
            raise ValueError(
                f"{path}: malformed record at line {i + 1} (not the torn "
                f"tail a crash can leave): {err}") from err
        out.append(rec)
    return out


def repair_jsonl_tail(path: str) -> int:
    """Truncate a torn final line off a JSONL file so future appends start
    on a record boundary (appending after a torn tail would corrupt a
    MID-file line, which ``read_jsonl`` treats as fatal).  Complete records
    are never modified — the file stays append-only in the only sense that
    matters.  Returns the number of bytes truncated (0 when intact); a
    missing file is a no-op."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        return 0
    keep = len(data)
    while keep > 0:
        if data[:keep].endswith(b"\n"):
            # the final terminated line must itself parse, or it is torn
            # too (a partial line that happened to flush its newline)
            last = data[:keep].rstrip(b"\n").rsplit(b"\n", 1)[-1]
            try:
                if last.strip():
                    json.loads(last.decode())
                break
            except (json.JSONDecodeError, UnicodeDecodeError):
                keep = len(data[:keep].rstrip(b"\n").rsplit(b"\n", 1)[0])
                if keep:
                    keep += 1  # keep the preceding line's newline
                continue
        keep -= 1
    torn = len(data) - keep
    if torn:
        with open(path, "r+b") as f:
            f.truncate(keep)
    return torn


# -- per-shard flat format (sharded spill) ----------------------------------


def _spec_entry(spec: FlatSpec) -> np.ndarray:
    return np.frombuffer(json.dumps(spec.to_json()).encode(), dtype=np.uint8)


def save_flat_shards(path: str, slices: Sequence[np.ndarray],
                     spec: FlatSpec, sspec: ShardedFlatSpec, *,
                     extra: Optional[Dict[str, Any]] = None) -> None:
    """Persist one flat row as its S block-cyclic per-shard slices
    (``ShardedFlatSpec.shard_slices``), one npz entry per shard, plus both
    layout specs.  Written atomically like every checkpoint.  ``extra`` is
    the same free-form JSON rider ``save_flat`` accepts."""
    if len(slices) != sspec.n_shards:
        raise ValueError(f"{len(slices)} slices != n_shards {sspec.n_shards}")
    arrays: Dict[str, np.ndarray] = {
        _FLAT_SPEC: _spec_entry(spec),
        _FLAT_SSPEC: np.frombuffer(
            json.dumps(sspec.to_json()).encode(), dtype=np.uint8),
    }
    if extra is not None:
        arrays[_FLAT_EXTRA] = _extra_entry(extra)
    for i, s in enumerate(slices):
        arr = np.asarray(s)
        if arr.dtype == jnp.bfloat16:
            arr = arr.view(np.uint16)
        arrays[_SHARD_FMT.format(i)] = arr
    _atomic_savez(path, arrays)


def is_flat_sharded(path: str) -> bool:
    with np.load(path) as data:
        return _FLAT_SSPEC in data.files


class FlatShardReader:
    """Lazy per-shard reader over a ``save_flat_shards`` npz.

    ``np.load`` decompresses entries on access, so ``shard(i)`` brings only
    that shard's ``[shard_len]`` slice onto the host — the reload path of
    the sharded spill never holds the full ``[N]`` row.  Use as a context
    manager (the underlying zip file stays open between reads).
    """

    def __init__(self, path: str):
        self.path = path
        self._data = np.load(path)
        if _FLAT_SSPEC not in self._data.files:
            self._data.close()
            raise ValueError(f"{path} is not a sharded flat checkpoint")
        self.spec = FlatSpec.from_json(
            json.loads(bytes(self._data[_FLAT_SPEC]).decode()))
        self.sspec = ShardedFlatSpec.from_json(
            json.loads(bytes(self._data[_FLAT_SSPEC]).decode()))

    def shard(self, i: int) -> np.ndarray:
        """One ``[shard_len]`` slice, host-side."""
        buf = self._data[_SHARD_FMT.format(i)]
        if self.spec.dtype == "bfloat16":
            buf = buf.view(jnp.bfloat16)
        return buf

    def full_row(self) -> np.ndarray:
        """Reassemble the portable ``[N]`` row (the fallback when the spill
        layout does not match the mesh the repository reopened under — this
        path DOES materialize the row on host, by design)."""
        return self.sspec.unshard_slices(
            [self.shard(i) for i in range(self.sspec.n_shards)])

    def close(self) -> None:
        self._data.close()

    def __enter__(self) -> "FlatShardReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def flat_row_meta(path: str) -> Dict[str, Any]:
    """Peek a spilled row's layout without touching its buffer entries:
    returns the ``FlatSpec`` JSON dict plus ``{"sharded": bool}`` (and the
    ``ShardedFlatSpec`` JSON under ``"shard_spec"`` when sharded).  A
    delta-compressed row (``save_flat_delta``) additionally carries
    ``{"compressed": True, "delta_spec": {...}}``.  Used by crash recovery
    to validate manifest entries cheaply."""
    with np.load(path) as data:
        if _FLAT_SPEC not in data.files:
            raise ValueError(f"{path} is not a flat checkpoint")
        meta = json.loads(bytes(data[_FLAT_SPEC]).decode())
        meta["sharded"] = _FLAT_SSPEC in data.files
        if meta["sharded"]:
            meta["shard_spec"] = json.loads(bytes(data[_FLAT_SSPEC]).decode())
        meta["compressed"] = _DELTA_SPEC in data.files
        if meta["compressed"]:
            meta["delta_spec"] = json.loads(bytes(data[_DELTA_SPEC]).decode())
        if _FLAT_EXTRA in data.files:
            meta["extra"] = json.loads(bytes(data[_FLAT_EXTRA]).decode())
    return meta


# -- delta-compressed flat format (compressed queue submissions) ------------
#
# A compressed submission never carries the dense [N] row: it persists the
# DeltaPayload arrays (per-block top-k int16 offsets, int8 values, f32
# scales — repro.utils.flat.delta_encode) plus the SAME FlatSpec/
# ShardedFlatSpec layout entries the dense formats write, so
# ``flat_row_meta`` validation and by-reference ingest work unchanged.  The
# sharded variant stacks the S per-shard payloads along a leading axis
# (every shard has identical codec geometry: shard_len is uniform by
# construction), one npz entry per array — not per shard — keeping the
# file layout O(1) in S.


def save_flat_delta(path: str, payloads, spec: FlatSpec, *,
                    sspec: Optional[ShardedFlatSpec] = None,
                    extra: Optional[Dict[str, Any]] = None) -> None:
    """Persist a compressed contribution: one ``DeltaPayload`` (whole-row)
    or a list of S per-shard payloads with their ``sspec`` (the compressed
    analog of ``save_flat``/``save_flat_shards``).  Written atomically;
    ``extra`` is the same free-form JSON rider."""
    if isinstance(payloads, DeltaPayload):
        if sspec is not None:
            raise ValueError("whole-row payload with a shard spec")
        plist = [payloads]
    else:
        plist = list(payloads)
        if sspec is None:
            raise ValueError("a payload list requires its ShardedFlatSpec")
        if len(plist) != sspec.n_shards:
            raise ValueError(
                f"{len(plist)} payloads != n_shards {sspec.n_shards}")
    p0 = plist[0]
    for p in plist:
        if (p.size, p.block, p.indices.shape) != \
                (p0.size, p0.block, p0.indices.shape):
            raise ValueError("per-shard payload geometries differ")
    dspec = {
        "version": 1,
        "size": p0.size,
        "block": p0.block,
        "k_per_block": p0.k_per_block,
        "sharded": sspec is not None,
    }
    arrays: Dict[str, np.ndarray] = {
        _FLAT_SPEC: _spec_entry(spec),
        _DELTA_SPEC: np.frombuffer(
            json.dumps(dspec).encode(), dtype=np.uint8),
        _DELTA_IDX: np.stack([p.indices for p in plist]),
        _DELTA_VAL: np.stack([p.values for p in plist]),
        _DELTA_SCL: np.stack([p.scales for p in plist]),
    }
    if sspec is None:
        for k in (_DELTA_IDX, _DELTA_VAL, _DELTA_SCL):
            arrays[k] = arrays[k][0]
    else:
        arrays[_FLAT_SSPEC] = np.frombuffer(
            json.dumps(sspec.to_json()).encode(), dtype=np.uint8)
    if extra is not None:
        arrays[_FLAT_EXTRA] = _extra_entry(extra)
    _atomic_savez(path, arrays)


def load_flat_delta(path: str) -> Tuple[list, Dict[str, Any]]:
    """Load a ``save_flat_delta`` file: returns (payloads, meta) where
    ``payloads`` is the list of ``DeltaPayload`` (length 1 whole-row, S
    sharded) and ``meta`` is the ``flat_row_meta`` dict.  Every geometry
    mismatch — wrong dtypes, inconsistent shapes, out-of-range offsets —
    raises (``DeltaPayload`` validates on construction), as does any zip-
    or entry-level truncation: a torn compressed file is a rejection,
    never a stall or a silent mis-decode."""
    meta = flat_row_meta(path)
    if not meta.get("compressed"):
        raise ValueError(f"{path} is not a compressed flat checkpoint")
    dspec = meta["delta_spec"]
    size, block = int(dspec["size"]), int(dspec["block"])
    kb = int(dspec["k_per_block"])
    sharded = bool(dspec["sharded"])
    with np.load(path) as data:
        for k in (_DELTA_IDX, _DELTA_VAL, _DELTA_SCL):
            if k not in data.files:
                raise ValueError(f"{path}: missing delta entry {k}")
        idx, val, scl = data[_DELTA_IDX], data[_DELTA_VAL], data[_DELTA_SCL]
    _count_file("io.read_bytes", path)
    if not sharded:
        idx, val, scl = idx[None], val[None], scl[None]
    n = idx.shape[0]
    if sharded:
        ss = ShardedFlatSpec.from_json(meta["shard_spec"])
        if n != ss.n_shards:
            raise ValueError(
                f"{path}: {n} payloads != n_shards {ss.n_shards}")
        if size != ss.shard_len:
            raise ValueError(
                f"{path}: payload size {size} != shard_len {ss.shard_len}")
    if val.shape[0] != n or scl.shape[0] != n:
        raise ValueError(f"{path}: delta entry leading dims disagree")
    payloads = []
    for i in range(n):
        p = DeltaPayload(idx[i], val[i], scl[i], size, block)
        if p.k_per_block != kb:
            raise ValueError(
                f"{path}: k_per_block {p.k_per_block} != declared {kb}")
        payloads.append(p)
    return payloads, meta


def is_flat_compressed(path: str) -> bool:
    with np.load(path) as data:
        return _DELTA_SPEC in data.files
