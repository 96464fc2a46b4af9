"""Crash-consistent sharded checkpointing: async double-buffered saves,
per-leaf integrity checksums, two-phase cross-rank commit.

Layout (one directory per step):
    <dir>/step_000000100/
        shard_00000.npz             # rank 0's pieces of every leaf
        shard_00000.SHARD_COMMITTED # written (and fsync'd) after its npz
        shard_00001.npz             # rank 1's pieces ...
        shard_00001.SHARD_COMMITTED
        ...
        manifest.json               # format 2: paths, shapes, dtypes,
                                    #   per-shard index + CRC32, data step
        COMMITTED                   # global marker — written only when
                                    #   every shard landed

Sharded saves: a leaf that is a non-fully-replicated ``jax.Array`` (the
ZeRO-2 stacked momentum / rule slots sharded on the bucket ``L`` axis,
the device-axis int8 EF residual under ``P("data")``) is split into its
per-rank device shards (``addressable_shards``, ``replica_id == 0``,
sorted by index) and each rank's piece lands in that rank's shard file —
so every rank's state survives the checkpoint, not just rank 0's
replica.  Replicated / host leaves go to rank 0's file.  On a real
multi-host cluster each host would write only its addressable pieces;
here single-host writes all ranks.

Commit protocol (two-phase):
  1. per rank: write + fsync ``shard_r.npz``, then write + fsync
     ``shard_r.SHARD_COMMITTED``;
  2. write + fsync ``manifest.json`` (which records a CRC32 per leaf
     piece), then the global ``COMMITTED``;
  3. atomically rename the tmp dir into place.
A crash anywhere before (3) leaves only an invisible ``.tmp_step_*``
dir; a ``COMMITTED`` checkpoint missing any ``SHARD_COMMITTED`` is
detected as corruption (torn multi-rank commit), never restored.

Integrity: every piece's CRC32 is recorded in the manifest and verified
on restore.  Bit-rot, a truncated shard, a missing rank shard or a torn
manifest each raise :class:`CheckpointCorruptionError` naming the leaf
path and shard rank; ``restore_latest`` logs the name and falls back to
the previous committed checkpoint.

Async double-buffered writer: ``save()`` copies device state into one of
two preallocated (pinned) host buffers at the step boundary, then a
background writer thread serializes, checksums and fsyncs from the
buffer — the step loop stalls only for the device->host copy.
Backpressure: never more than one write in flight; a second ``save()``
blocks until the first completes.  ``snapshot()`` fills a buffer without
writing (the watchdog-armed step loop calls it each step) and
``emergency_save()`` persists the last snapshot synchronously — reusing
the same buffer instead of taking a blocking device snapshot from a
possibly-hung step.

Fault-tolerance contract:
  * saves are atomic (tmp dir + rename + two-phase markers);
  * ``restore_latest`` skips uncommitted / partial / corrupt steps with
    a named warning;
  * the data-stream step is stored in the manifest so restart resumes
    the exact batch sequence;
  * ``keep`` bounds disk usage — retention never prunes the newest
    last-known-good step, a step that is mid-restore, or anything while
    another write could race it (all writes are serialized through the
    single writer handshake).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
import warnings
import zipfile
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.types import tree_paths


class CheckpointCorruptionError(RuntimeError):
    """A committed checkpoint failed integrity verification on restore
    (checksum mismatch, truncated or missing shard, torn multi-rank
    commit).  The message names the checkpoint, the leaf path and the
    shard rank so the fault-injection proofs can assert detection *by
    name*."""


def _fsync(path: Path) -> None:
    with open(path, "rb") as f:
        os.fsync(f.fileno())


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _leaf_pieces(v: Any) -> List[Tuple[int, List[List[int]], Any]]:
    """Split one leaf into per-rank pieces: ``(rank, index, array-like)``
    where ``index`` is the piece's ``[[start, stop], ...]`` window in the
    global array.  Non-fully-replicated jax.Arrays split into their
    device shards (one rank per distinct shard, sorted by offset);
    everything else is rank 0's single full piece."""
    if isinstance(v, jax.Array) and not v.sharding.is_fully_replicated:
        shards = [s for s in v.addressable_shards if s.replica_id == 0]
        shards.sort(key=lambda s: tuple(sl.start or 0 for sl in s.index))
        out = []
        for rank, s in enumerate(shards):
            idx = [[int(sl.start or 0),
                    int(sl.stop) if sl.stop is not None else int(dim)]
                   for sl, dim in zip(s.index, v.shape)]
            out.append((rank, idx, s.data))
        return out
    return [(0, [[0, int(d)] for d in np.shape(v)], v)]


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.async_save = async_save
        # writer handshake: _cv guards everything below; _inflight is True
        # from the moment a job is submitted (or a blocking write starts)
        # until its _write returns — backpressure keeps it to one at a time
        self._cv = threading.Condition()
        self._inflight = False
        self._pending: Optional[dict] = None
        self._writer: Optional[threading.Thread] = None
        # double buffer: two host-side slots; the slot referenced by the
        # submitted/in-flight job is pinned, fills go to the other one
        self._slots: List[Optional[dict]] = [None, None]
        self._busy_slot: Optional[int] = None
        self._last_slot: Optional[int] = None
        self._last_snapshot: Optional[dict] = None
        # steps currently being restored — retention must not delete them
        self._reading: Dict[int, int] = {}
        self._read_lock = threading.Lock()
        # parsed-manifest / directory-scan caches (invalidated on
        # save / prune / mark_good and keyed on file stats, so
        # restore_latest & good_steps stop re-parsing every manifest)
        self._cache_lock = threading.Lock()
        self._scan_cache: Optional[Tuple[int, List[int]]] = None
        self._manifest_cache: Dict[str, Tuple[int, int, dict]] = {}

    # ------------------------------------------------------------------
    def _step_dir(self, step: int) -> Path:
        return self.dir / f"step_{step:09d}"

    # ------------------------------------------------------------------
    # host snapshot buffers
    # ------------------------------------------------------------------
    def _pick_slot(self) -> int:
        for s in (0, 1):
            if s != self._busy_slot and s != self._last_slot:
                return s
        return next(s for s in (0, 1) if s != self._busy_slot)

    def _fill(self, slot_idx: int, state: Any) -> None:
        """Device->host copy of ``state`` into buffer ``slot_idx``,
        reusing the preallocated arrays when the structure matches."""
        flat = tree_paths(state)
        entries = []
        sig = []
        for path, v in flat:
            pieces = _leaf_pieces(v)
            dt = getattr(v, "dtype", None)
            dtype = str(np.dtype(dt) if dt is not None
                        else np.asarray(v).dtype)
            shape = [int(d) for d in np.shape(v)]
            sig.append((path, dtype, tuple(shape),
                        tuple((r, tuple(map(tuple, ix)),
                               tuple(np.shape(p))) for r, ix, p in pieces)))
            entries.append({"path": path, "shape": shape, "dtype": dtype,
                            "pieces": pieces})
        slot = self._slots[slot_idx]
        sig = tuple(sig)
        if slot is not None and slot["sig"] == sig:
            for leaf, src in zip(slot["leaves"], entries):
                for (_, _, buf), (_, _, piece) in zip(leaf["pieces"],
                                                      src["pieces"]):
                    np.copyto(buf, np.asarray(piece))
        else:
            for e in entries:
                e["pieces"] = [(r, ix, np.array(np.asarray(p), copy=True))
                               for r, ix, p in e["pieces"]]
            self._slots[slot_idx] = {"sig": sig, "leaves": entries}
        self._last_slot = slot_idx

    def _make_job(self, step: int, slot_idx: int,
                  data_step: Optional[int], layout: Optional[dict]) -> dict:
        return {"step": int(step),
                "data_step": int(data_step if data_step is not None
                                 else step),
                "time": time.time(), "layout": layout, "slot": slot_idx}

    # ------------------------------------------------------------------
    # save / snapshot / emergency save
    # ------------------------------------------------------------------
    def save(self, step: int, state: Any, data_step: Optional[int] = None,
             block: bool = False, layout: Optional[dict] = None):
        """state: arbitrary pytree of arrays.  ``layout`` (JSON-serializable,
        see ``repro.distributed.elastic.state_layout``) records what mesh /
        shard size the state is laid out for, so restore can detect a mesh
        mismatch and reshard instead of feeding garbage into the sharded
        update.  Async (the default): the caller stalls only for the
        device->host buffer copy; serialization, checksumming and fsync
        run on the background writer thread.  ``block=True`` writes on the
        calling thread."""
        with self._cv:
            while self._inflight or self._pending is not None:
                self._cv.wait()
            slot = self._pick_slot()
            self._fill(slot, state)
            job = self._make_job(step, slot, data_step, layout)
            self._last_snapshot = job
            self._inflight = True
            self._busy_slot = slot
            if self.async_save and not block:
                self._pending = job
                self._ensure_writer()
                self._cv.notify_all()
                return
        # blocking path: write on the caller thread (exceptions propagate)
        try:
            self._write(job)
        finally:
            with self._cv:
                self._inflight = False
                self._busy_slot = None
                self._cv.notify_all()

    def snapshot(self, step: int, state: Any,
                 data_step: Optional[int] = None,
                 layout: Optional[dict] = None) -> None:
        """Fill a host buffer from ``state`` without writing anything —
        the watchdog-armed step loop calls this at every step boundary so
        :meth:`emergency_save` can persist the latest state without
        taking a device snapshot from a possibly-hung step.  Never blocks
        on an in-flight write: the double buffer guarantees a free slot."""
        with self._cv:
            slot = self._pick_slot()
            self._fill(slot, state)
            self._last_snapshot = self._make_job(step, slot, data_step,
                                                 layout)

    def emergency_save(self) -> Optional[int]:
        """Synchronously persist the most recent :meth:`snapshot` /
        :meth:`save` buffer, if it is newer than the newest committed
        checkpoint.  Returns the step written, or None if there was
        nothing newer to save.  Called from the watchdog timer thread —
        it drains any in-flight write first, then writes from the pinned
        buffer (no device access, safe while the step loop is hung)."""
        with self._cv:
            while self._inflight or self._pending is not None:
                self._cv.wait()
            job = self._last_snapshot
            if job is None:
                return None
            latest = self.latest_step()
            if latest is not None and job["step"] <= latest:
                return None
            self._inflight = True
            self._busy_slot = job["slot"]
        try:
            self._write(job)
        finally:
            with self._cv:
                self._inflight = False
                self._busy_slot = None
                self._cv.notify_all()
        return job["step"]

    def _ensure_writer(self) -> None:
        if self._writer is None or not self._writer.is_alive():
            self._writer = threading.Thread(target=self._writer_loop,
                                            daemon=True)
            self._writer.start()

    def _writer_loop(self) -> None:
        while True:
            with self._cv:
                while self._pending is None:
                    self._cv.wait()
                job = self._pending
                self._pending = None
            try:
                self._write(job)
            except BaseException as e:  # noqa: BLE001 — keep the loop alive
                warnings.warn(f"async checkpoint write for step "
                              f"{job['step']} failed: {e!r}",
                              RuntimeWarning, stacklevel=1)
            finally:
                with self._cv:
                    self._inflight = False
                    self._busy_slot = None
                    self._cv.notify_all()

    def wait(self):
        """Drain: block until no write is pending or in flight."""
        with self._cv:
            while self._inflight or self._pending is not None:
                self._cv.wait()

    # ------------------------------------------------------------------
    # the writer (runs on the writer thread, or the caller when blocking)
    # ------------------------------------------------------------------
    def _write(self, job: dict) -> None:
        slot = self._slots[job["slot"]]
        step = job["step"]
        tmp = self.dir / f".tmp_step_{step:09d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        n_shards = 1 + max((r for leaf in slot["leaves"]
                            for r, _, _ in leaf["pieces"]), default=0)
        per_rank: List[Dict[str, np.ndarray]] = [{} for _ in range(n_shards)]
        leaves_manifest = []
        for i, leaf in enumerate(slot["leaves"]):
            shards = []
            for rank, index, arr in leaf["pieces"]:
                per_rank[rank][f"leaf_{i}"] = arr
                shards.append({"rank": rank, "index": index,
                               "shape": [int(d) for d in arr.shape],
                               "crc32": _crc(arr)})
            leaves_manifest.append({"path": leaf["path"],
                                    "shape": leaf["shape"],
                                    "dtype": leaf["dtype"],
                                    "shards": shards})
        # phase 1: every rank's shard file + its SHARD_COMMITTED marker
        for rank in range(n_shards):
            spath = tmp / f"shard_{rank:05d}.npz"
            np.savez(spath, **per_rank[rank])
            _fsync(spath)
            marker = tmp / f"shard_{rank:05d}.SHARD_COMMITTED"
            marker.write_text("ok")
            _fsync(marker)
        # phase 2: manifest (with per-piece CRCs), then the global marker
        manifest = {"format": 2, "step": step, "data_step": job["data_step"],
                    "time": job["time"], "n_shards": n_shards,
                    "leaves": leaves_manifest}
        if job["layout"] is not None:
            manifest["layout"] = job["layout"]
        mpath = tmp / "manifest.json"
        mpath.write_text(json.dumps(manifest))
        _fsync(mpath)
        cpath = tmp / "COMMITTED"
        cpath.write_text("ok")
        _fsync(cpath)
        final = self._step_dir(step)
        if final.exists():
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._invalidate()
        self._prune()

    # ------------------------------------------------------------------
    # directory scan (cached) + retention
    # ------------------------------------------------------------------
    def _invalidate(self) -> None:
        with self._cache_lock:
            self._scan_cache = None
            self._manifest_cache.clear()

    def _read_manifest(self, d: Path) -> dict:
        """Parse ``d/manifest.json`` with a stat-keyed cache: a manifest
        rewritten in place (torn at the filesystem level) re-parses, an
        unchanged one is returned from cache."""
        mpath = d / "manifest.json"
        st = mpath.stat()
        key = d.name
        with self._cache_lock:
            hit = self._manifest_cache.get(key)
            if hit is not None and hit[0] == st.st_mtime_ns \
                    and hit[1] == st.st_size:
                return hit[2]
        manifest = json.loads(mpath.read_text())
        with self._cache_lock:
            self._manifest_cache[key] = (st.st_mtime_ns, st.st_size, manifest)
        return manifest

    def _committed_steps(self):
        """Steps with a COMMITTED marker *and* a parseable manifest.  A
        torn / unparseable manifest.json is treated exactly like a missing
        commit marker (warn by name, skip the step) — the atomic-rename
        commit makes it unlikely, but a disk-full truncation or an fsck
        salvage can still produce one, and a restore that dies mid-ladder
        on it would defeat the fallback this ordering exists for.

        Caching: the directory *listing* is cached keyed on the directory
        mtime (a commit, prune or externally created step dir bumps it),
        and each manifest parse is cached keyed on the file's stat
        (``_read_manifest``) — so repeated ``restore_latest`` /
        ``good_steps`` calls stop re-globbing and re-parsing JSON, while
        in-place damage to a manifest (which does NOT bump the parent
        directory mtime) still re-parses and re-fires its warning on
        every call until the step is pruned or repaired."""
        try:
            mt = self.dir.stat().st_mtime_ns
        except OSError:
            mt = None
        with self._cache_lock:
            cached = (list(self._scan_cache[1])
                      if mt is not None and self._scan_cache is not None
                      and self._scan_cache[0] == mt else None)
        names = cached if cached is not None else sorted(
            p.name for p in self.dir.glob("step_*"))
        if cached is None and mt is not None:
            with self._cache_lock:
                self._scan_cache = (mt, list(names))
        out = []
        for name in names:
            p = self.dir / name
            if not (p / "COMMITTED").exists():
                continue
            try:
                self._read_manifest(p)
            except (OSError, ValueError) as e:
                warnings.warn(
                    f"checkpoint {p.name}: torn/unparseable manifest.json "
                    f"({e}) — treating like a missing commit marker",
                    RuntimeWarning, stacklevel=2)
                continue
            out.append(int(name.split("_")[1]))
        return out

    def _prune(self):
        steps = self._committed_steps()
        if not self.keep:
            return
        # the newest last-known-good step is never pruned: it is the rewind
        # ladder's restore target, and three newer-but-poisoned checkpoints
        # must not be able to push it out of the retention window.  A step
        # currently being restored is likewise pinned — deleting a
        # checkpoint mid-read would tear the very restore it serves.  (All
        # writes are serialized through the writer handshake, so prune —
        # which only ever runs at the tail of _write — cannot race one.)
        with self._read_lock:
            reading = set(self._reading)
        keepers = (set(steps[-self.keep:]) | set(self.good_steps()[-1:])
                   | reading)
        pruned = False
        for s in steps:
            if s not in keepers:
                shutil.rmtree(self._step_dir(s), ignore_errors=True)
                pruned = True
        if pruned:
            self._invalidate()

    def latest_step(self) -> Optional[int]:
        steps = self._committed_steps()
        return steps[-1] if steps else None

    # ------------------------------------------------------------------
    # last-known-good: a committed checkpoint is *promoted* to "good" only
    # after the launcher has watched a health window of anomaly-free steps
    # go by (checkpoint/manager.py stores the marker; the promotion policy
    # lives in launch/train.py).  The rewind ladder restores the newest
    # good step, never merely the newest step — the newest step is usually
    # the one written just before the anomaly surfaced.
    def mark_good(self, step: int) -> None:
        """Promote a committed step to last-known-good (idempotent)."""
        self.wait()
        d = self._step_dir(step)
        if not (d / "COMMITTED").exists():
            raise ValueError(
                f"cannot mark step {step} good: no committed checkpoint "
                f"at {d}")
        (d / "GOOD").write_text("ok")
        self._invalidate()

    def good_steps(self):
        return [s for s in self._committed_steps()
                if (self._step_dir(s) / "GOOD").exists()]

    def latest_good_step(self) -> Optional[int]:
        good = self.good_steps()
        return good[-1] if good else None

    def read_layout(self, step: int) -> Optional[dict]:
        """The state-layout manifest entry written at save time (mesh size,
        shard size, rule, bucket plan — see
        ``repro.distributed.elastic.state_layout``); None for checkpoints
        that predate it."""
        return self._read_manifest(self._step_dir(step)).get("layout")

    # ------------------------------------------------------------------
    # restore
    # ------------------------------------------------------------------
    def _validate(self, step: int, manifest: dict, like: Any) -> None:
        """Template-vs-manifest validation: restoring into a template whose
        tree, shapes or dtypes disagree with what was saved must fail
        naming the offending leaf and both sides — not die in an opaque
        reshape, and never silently coerce (a shape mismatch on a bucketed
        state usually means a mesh-size mismatch, which has a dedicated
        fix)."""
        flat = tree_paths(like)
        man = manifest["leaves"]
        if len(flat) != len(man):
            raise ValueError(
                f"checkpoint step {step} holds {len(man)} leaves but the "
                f"restore template has {len(flat)} — different state "
                f"structure (model / optimizer / compression mismatch?)")
        for (path, leaf), m in zip(flat, man, strict=False):
            if m["path"] != path:
                raise ValueError(
                    f"checkpoint step {step}: tree mismatch — checkpoint "
                    f"leaf {m['path']!r} where the template has {path!r}")
            shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
            if tuple(m["shape"]) != shape:
                raise ValueError(
                    f"checkpoint step {step}: leaf {path!r} was saved with "
                    f"shape {tuple(m['shape'])} but the template expects "
                    f"{shape} — a bucketed-state mismatch like this usually "
                    f"means the checkpoint was written for a different mesh "
                    f"size (see read_layout / "
                    f"repro.distributed.elastic.reshard_bucketed_state)")
            dtype = getattr(leaf, "dtype", None)
            if dtype is not None and m["dtype"] != str(np.dtype(dtype)):
                raise ValueError(
                    f"checkpoint step {step}: leaf {path!r} was saved as "
                    f"{m['dtype']} but the template expects "
                    f"{np.dtype(dtype)} — refusing to cast optimizer state "
                    f"silently")

    def _load_arrays(self, d: Path, manifest: dict) -> List[np.ndarray]:
        """Reassemble every leaf from the per-rank shard files, verifying
        the two-phase commit markers and every piece's CRC32.  Raises
        :class:`CheckpointCorruptionError` naming the checkpoint, leaf
        path and shard rank on any integrity failure."""
        if int(manifest.get("format", 1)) < 2:
            # legacy single-file layout (pre-sharded checkpoints)
            with np.load(d / "shard_00000.npz") as z:
                return [z[f"leaf_{i}"]
                        for i in range(len(manifest["leaves"]))]
        n_shards = int(manifest.get("n_shards", 1))
        for r in range(n_shards):
            if not (d / f"shard_{r:05d}.SHARD_COMMITTED").exists():
                raise CheckpointCorruptionError(
                    f"checkpoint {d.name}: shard rank {r} is missing its "
                    f"SHARD_COMMITTED marker under a global COMMITTED — "
                    f"torn multi-rank commit")
        zs: Dict[int, Any] = {}
        try:
            for r in range(n_shards):
                spath = d / f"shard_{r:05d}.npz"
                if not spath.exists():
                    raise CheckpointCorruptionError(
                        f"checkpoint {d.name}: missing shard file "
                        f"shard_{r:05d}.npz (rank {r})")
                try:
                    zs[r] = np.load(spath)
                except (OSError, ValueError, zipfile.BadZipFile) as e:
                    raise CheckpointCorruptionError(
                        f"checkpoint {d.name}: shard rank {r} is "
                        f"truncated/unreadable ({e})") from e
            arrays = []
            for i, leaf in enumerate(manifest["leaves"]):
                out = np.empty(tuple(leaf["shape"]),
                               np.dtype(leaf["dtype"]))
                for sh in leaf["shards"]:
                    rank = int(sh["rank"])
                    try:
                        piece = zs[rank][f"leaf_{i}"]
                    except KeyError as e:
                        raise CheckpointCorruptionError(
                            f"checkpoint {d.name}: leaf {leaf['path']!r} "
                            f"is missing from shard rank {rank}") from e
                    except (OSError, ValueError,
                            zipfile.BadZipFile, zlib.error) as e:
                        raise CheckpointCorruptionError(
                            f"checkpoint {d.name}: leaf {leaf['path']!r} "
                            f"shard rank {rank} is truncated/unreadable "
                            f"({e})") from e
                    if list(piece.shape) != list(sh["shape"]):
                        raise CheckpointCorruptionError(
                            f"checkpoint {d.name}: leaf {leaf['path']!r} "
                            f"shard rank {rank} has shape "
                            f"{tuple(piece.shape)} but the manifest "
                            f"records {tuple(sh['shape'])} — truncated "
                            f"shard")
                    crc = _crc(piece)
                    if crc != int(sh["crc32"]):
                        raise CheckpointCorruptionError(
                            f"checkpoint {d.name}: checksum mismatch on "
                            f"leaf {leaf['path']!r} shard rank {rank} "
                            f"(stored {int(sh['crc32']):#010x}, recomputed "
                            f"{crc:#010x}) — bit-rot or torn write")
                    if piece.dtype != out.dtype and piece.dtype.kind == "V":
                        # npz keeps a dtype numpy does not know (bfloat16)
                        # as raw bytes: reinterpret, never convert
                        piece = piece.view(out.dtype)
                    idx = tuple(slice(a, b) for a, b in sh["index"])
                    out[idx] = piece
                arrays.append(out)
            return arrays
        finally:
            for z in zs.values():
                z.close()

    def restore(self, step: int, like: Any) -> Tuple[Any, int]:
        """Restore into the structure of ``like``; returns (state, data_step).
        ``like``'s leaves only need shapes/dtypes (``jax.eval_shape``
        templates work); they are validated against the manifest first,
        then every shard piece's CRC32 is verified before assembly.  The
        step is registered as mid-restore for the duration so retention
        cannot delete it underneath the read."""
        d = self._step_dir(step)
        with self._read_lock:
            self._reading[step] = self._reading.get(step, 0) + 1
        try:
            manifest = self._read_manifest(d)
            self._validate(step, manifest, like)
            arrays = self._load_arrays(d, manifest)
        finally:
            with self._read_lock:
                self._reading[step] -= 1
                if not self._reading[step]:
                    del self._reading[step]
        leaves, treedef = jax.tree_util.tree_flatten(like)
        restored = [np.asarray(a).astype(leaf.dtype).reshape(leaf.shape)
                    for a, leaf in zip(arrays, leaves, strict=False)]
        return (jax.tree_util.tree_unflatten(treedef, restored),
                int(manifest["data_step"]))

    def restore_latest(self, like: Any) -> Optional[Tuple[Any, int, int]]:
        """Restore the newest committed step, falling back to the previous
        committed step (with a named warning) when a checkpoint turns out
        unreadable or corrupt mid-restore — a torn npz, a checksum
        mismatch, a missing rank shard or a manifest that goes bad
        between listing and reading is a damaged artifact, not a caller
        bug.  Genuine template mismatches (``_validate``'s ValueError)
        still propagate: restoring older state into the wrong structure
        would not fix those."""
        for step in reversed(self._committed_steps()):
            try:
                state, data_step = self.restore(step, like)
            except (OSError, json.JSONDecodeError, zipfile.BadZipFile,
                    CheckpointCorruptionError) as e:
                warnings.warn(
                    f"checkpoint step_{step:09d} is unreadable ({e}) — "
                    f"falling back to the previous committed step",
                    RuntimeWarning, stacklevel=2)
                continue
            return state, step, data_step
        return None
