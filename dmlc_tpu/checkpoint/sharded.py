"""Sharded pytree checkpoints: per-shard blobs + a JSON manifest.

Layout under a checkpoint directory URI:
    manifest.json                       tree/shape/dtype/sharding metadata
    <leaf-key>.<shard-id>               raw little-endian shard bytes

Shard identity is the global index (slice extents) the shard covers, so
restore works on any mesh with the same axis names/sizes via
jax.make_array_from_callback; replicated shards are written once
(replica_id == 0).  All IO goes through Stream.create — local paths and
gs:// behave identically (GCS writes use the resumable-upload stream).
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional

import numpy as np

from ..base import DMLCError, check
from ..io.stream import Stream


class CorruptCheckpoint(DMLCError):
    """A checkpoint shard failed its CRC32C digest — the on-disk bytes
    differ from what ``save_pytree`` recorded in the manifest."""


class MissingLeaf(DMLCError):
    """The restore template asks for a leaf the checkpoint's manifest
    does not carry (e.g. a pre-PR checkpoint without the persisted
    stream-position leaf).  Typed so callers can probe for optional
    leaves without matching on message text."""

MANIFEST = "manifest.json"


def _local_path(uri: str) -> Optional[str]:
    """Filesystem path for local URIs, None for object stores."""
    if uri.startswith("file://"):
        return uri[len("file://"):]
    return None if "://" in uri else uri


def _commit_manifest(uri: str, data: bytes) -> None:
    """Write the manifest LAST and ATOMICALLY — the commit record of a
    checkpoint.  Shards without a committed manifest are invisible to
    restore, so a preemption at ANY point mid-save leaves the previous
    committed step as the restore target instead of a torn one.

    Local paths go through write-to-temp + fsync + rename (atomic on
    POSIX); object stores get a plain PUT, which is already all-or-
    nothing at the object level."""
    from ..resilience import fault_point

    fault_point("checkpoint.commit", uri=uri)
    target = _join(uri, MANIFEST)
    path = _local_path(target)
    if path is None:
        with Stream.create(target, "w") as s:
            s.write(data)
        return
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _leaf_key(path) -> str:
    import jax

    key = jax.tree_util.keystr(path)
    safe = "".join(c if (c.isalnum() or c in "._-") else "_" for c in key)
    return safe.strip("_") or "leaf"


def _index_key(index, shape) -> str:
    """Stable string for a global shard index (tuple of slices)."""
    parts = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        parts.append(f"{start}-{stop}")
    return "_".join(parts) if parts else "scalar"


def _spec_to_json(arr) -> Optional[list]:
    sharding = getattr(arr, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            out.append(list(entry))
        else:
            out.append(entry)
    return out


def _spec_from_json(raw):
    from jax.sharding import PartitionSpec as P

    if raw is None:
        return P()
    return P(*[tuple(e) if isinstance(e, list) else e for e in raw])


def _join(base: str, name: str) -> str:
    return base.rstrip("/") + "/" + name


def _read_all(s: Stream, chunk: int = 8 << 20) -> bytes:
    parts = []
    while True:
        d = s.read(chunk)
        if not d:
            return b"".join(parts)
        parts.append(d)


def _ensure_dir(uri: str) -> None:
    """Create the directory for local checkpoint paths (object stores
    have no directories to create)."""
    if "://" in uri and not uri.startswith("file://"):
        return
    import os

    os.makedirs(uri[len("file://"):] if uri.startswith("file://") else uri,
                exist_ok=True)


def save_pytree(uri: str, tree: Any, *, process_index: int = 0) -> None:
    """Write a pytree of jax.Arrays / numpy arrays under ``uri``.

    Multi-host: every process writes its addressable shards; only
    process 0 writes the manifest (call with process_index=jax.process_index()).
    """
    import jax

    from .. import telemetry

    with telemetry.span("checkpoint.save", stage="checkpoint",
                        args={"uri": uri}):
        _ensure_dir(uri)
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        manifest: Dict[str, Any] = {"format": 1, "leaves": {}}
        nbytes = 0
        for path, leaf in leaves:
            key = _leaf_key(path)
            check(key not in manifest["leaves"], f"duplicate leaf key {key}")
            arr = leaf
            entry: Dict[str, Any] = {
                "path": jax.tree_util.keystr(path),
                "shape": list(np.shape(arr)),
                "dtype": str(arr.dtype) if hasattr(arr, "dtype")
                else str(np.asarray(arr).dtype),
                "spec": _spec_to_json(arr),
                "shards": {},
                # per-shard CRC32C digest, recorded at save time and
                # verified on restore: a flipped shard fails restore
                # LOUDLY instead of poisoning the optimizer state, and
                # restore_latest falls back to the previous committed
                # step (additive manifest field: pre-digest checkpoints
                # restore unverified)
                "crc32c": {},
            }
            from ..io.integrity import crc32c

            if hasattr(arr, "addressable_shards"):
                for shard in arr.addressable_shards:
                    if shard.replica_id != 0:
                        continue
                    ikey = _index_key(shard.index, arr.shape)
                    fname = f"{key}.{ikey}"
                    entry["shards"][ikey] = fname
                    raw = np.ascontiguousarray(shard.data).tobytes()
                    entry["crc32c"][ikey] = crc32c(raw)
                    nbytes += len(raw)
                    with Stream.create(_join(uri, fname), "w") as s:
                        s.write(raw)
            else:
                npa = np.asarray(arr)
                ikey = _index_key(tuple(slice(0, d) for d in npa.shape),
                                  npa.shape)
                entry["shards"][ikey] = f"{key}.{ikey}"
                raw = np.ascontiguousarray(npa).tobytes()
                entry["crc32c"][ikey] = crc32c(raw)
                nbytes += len(raw)
                with Stream.create(_join(uri, f"{key}.{ikey}"), "w") as s:
                    s.write(raw)
            manifest["leaves"][key] = entry
        telemetry.inc("checkpoint", "bytes_written", nbytes)
        telemetry.inc("checkpoint", "saves")
        if process_index == 0:
            # shards first, manifest last: the atomic manifest commit is
            # what makes the checkpoint exist at all (crash consistency)
            _commit_manifest(uri,
                             json.dumps(manifest, indent=1).encode())


def _parse_index(ikey: str, shape) -> tuple:
    if ikey == "scalar":
        return ()
    return tuple(
        slice(int(a), int(b))
        for a, b in (p.split("-") for p in ikey.split("_"))
    )


def _try_extents(ikey: str, shape) -> Optional[tuple]:
    """((start, stop), ...) if ikey is a well-formed in-bounds shard key
    for ``shape``, else None (e.g. a suffix captured from another leaf)."""
    if ikey == "scalar":
        return () if shape == () else None
    parts = ikey.split("_")
    if len(parts) != len(shape):
        return None
    out = []
    for p, dim in zip(parts, shape):
        m = p.split("-")
        if len(m) != 2 or not (m[0].isdigit() and m[1].isdigit()):
            return None
        a, b = int(m[0]), int(m[1])
        if not (0 <= a < b <= dim):
            return None
        out.append((a, b))
    return tuple(out)


def _exact_cover(ikeys, shape) -> bool:
    """True iff the shard boxes tile the array exactly: pairwise disjoint
    and total volume == array size (O(#shards) memory, no bool mask)."""
    boxes = [_try_extents(k, shape) for k in ikeys]
    if any(b is None for b in boxes):
        return False
    total = 1
    for d in shape:
        total *= d
    vol = 0
    for b in boxes:
        v = 1
        for a, c in b:
            v *= c - a
        vol += v
    if vol != total:
        return False
    for i in range(len(boxes)):
        for j in range(i + 1, len(boxes)):
            if all(a1 < b2 and a2 < b1
                   for (a1, b1), (a2, b2) in zip(boxes[i], boxes[j])):
                return False  # overlap (scalar duplicates hit vol != total)
    return True


def restore_pytree(uri: str, template: Any, *, mesh=None) -> Any:
    """Restore a pytree saved by save_pytree.

    ``template`` supplies the tree structure (values ignored).  With
    ``mesh``, leaves come back as sharded jax.Arrays per the recorded
    PartitionSpec; without, as host numpy arrays.
    """
    from .. import telemetry

    with telemetry.span("checkpoint.restore", stage="checkpoint",
                        args={"uri": uri}):
        out = _restore_pytree(uri, template, mesh=mesh)
    telemetry.inc("checkpoint", "restores")
    return out


def _restore_pytree(uri: str, template: Any, *, mesh=None) -> Any:
    import jax

    with Stream.create(_join(uri, MANIFEST), "r") as s:
        raw_manifest = _read_all(s)
    # the manifest is the digest root of trust, so it carries no digest
    # of its own — but a rotted manifest must still cost one checkpoint
    # interval, not the job: parse/shape failures are CorruptCheckpoint
    # (restore_latest falls back), while read errors stay transient
    try:
        manifest = json.loads(raw_manifest)
        if not isinstance(manifest, dict):
            raise ValueError("manifest is not a JSON object")
    except ValueError as e:
        raise CorruptCheckpoint(
            f"checkpoint manifest at {uri} is unparseable ({e}) — "
            f"the checkpoint is corrupt")
    if manifest.get("format") != 1:
        raise CorruptCheckpoint(
            f"checkpoint manifest at {uri} has unknown format "
            f"{manifest.get('format')!r} — the checkpoint is corrupt")
    leaves_meta = manifest.get("leaves")
    if leaves_meta is None:
        raise CorruptCheckpoint(
            f"checkpoint manifest at {uri} lacks its leaves table — "
            f"the checkpoint is corrupt")

    paths, treedef = jax.tree_util.tree_flatten_with_path(template)

    def load_shard_bytes(key: str, ikey: str, dtype, shape,
                         want_crc=None) -> np.ndarray:
        # shard filenames are derived deterministically (f"{key}.{ikey}"),
        # NOT looked up in the manifest: in a multi-host save every process
        # writes its own addressable shards but only process 0 writes the
        # manifest, so the manifest's shards dict covers one process only
        with Stream.create(_join(uri, f"{key}.{ikey}"), "r") as s:
            raw = _read_all(s)
        from .. import telemetry

        telemetry.inc("checkpoint", "bytes_read", len(raw))
        if want_crc is not None:
            from ..io.integrity import crc32c

            got = crc32c(raw)
            if got != int(want_crc):
                telemetry.inc("integrity", "checksum_failures")
                telemetry.record_event("checkpoint_shard_corrupt",
                                       uri=uri, shard=f"{key}.{ikey}")
                raise CorruptCheckpoint(
                    f"checkpoint shard {key}.{ikey} failed its CRC32C "
                    f"digest (manifest {int(want_crc):#010x}, file "
                    f"{got:#010x}) — the checkpoint at {uri} is "
                    f"corrupt")
        return np.frombuffer(raw, dtype=dtype).reshape(shape)

    listing_cache: list = []

    def dir_listing() -> list:
        """Checkpoint-dir file names, listed once per restore (lazy)."""
        if not listing_cache:
            from ..io.filesys import FileSystem
            from ..io.uri import URI

            base = URI(uri if "://" in uri else "file://" + uri)
            fs = FileSystem.get_instance(base)
            listing_cache.append(
                [f.path.name.rsplit("/", 1)[-1]
                 for f in fs.list_directory(base)])
        return listing_cache[0]

    def shard_keys_for(key: str, meta, shape) -> list:
        """Shard ikeys covering the leaf.  The manifest is the fast path;
        when it does not cover the array (multi-host save: each process
        writes its shards but only process 0 writes the manifest), the
        directory listing supplies the rest.  Suffixes are validated as
        ikeys for this shape, so a leaf key that dot-prefixes another
        leaf's key never captures the other leaf's files."""
        ikeys = [k for k in meta["shards"]
                 if _try_extents(k, shape) is not None]
        if _exact_cover(ikeys, shape):
            return ikeys
        prefix = key + "."
        extra = {n[len(prefix):] for n in dir_listing()
                 if n.startswith(prefix)}
        ikeys = sorted(set(ikeys)
                       | {k for k in extra if _try_extents(k, shape)})
        check(_exact_cover(ikeys, shape),
              f"checkpoint leaf {key}: shard files {ikeys} do not tile the "
              f"array exactly (incomplete multi-host save, or stale shards "
              f"from a save with a different sharding layout — clean the "
              f"checkpoint directory)")
        return ikeys

    out_leaves = []
    for path, _ in paths:
        key = _leaf_key(path)
        meta = leaves_meta.get(key)
        if meta is None:
            raise MissingLeaf(f"checkpoint missing leaf {key}")
        shape = tuple(meta["shape"])
        dtype = np.dtype(meta["dtype"])
        crcs = meta.get("crc32c") or {}
        if mesh is not None:
            spec = _spec_from_json(meta["spec"])
            sharding = jax.sharding.NamedSharding(mesh, spec)

            def cb(index, key=key, shape=shape, dtype=dtype, crcs=crcs):
                ikey = _index_key(index, shape)
                extent = tuple(
                    (0 if sl.start is None else sl.start,
                     dim if sl.stop is None else sl.stop)
                    for sl, dim in zip(index, shape))
                sub_shape = tuple(b - a for a, b in extent)
                # digests cover the shards THIS manifest writer saved;
                # other hosts' shards (and resharded reads) verify only
                # when the shard layout matches — absent digest = no
                # verification, never a false failure
                return load_shard_bytes(key, ikey, dtype, sub_shape,
                                        want_crc=crcs.get(ikey))

            out_leaves.append(
                jax.make_array_from_callback(shape, sharding, cb))
        else:
            full = np.zeros(shape, dtype)
            for ikey in shard_keys_for(key, meta, shape):
                idx = _parse_index(ikey, shape)
                sub_shape = tuple(sl.stop - sl.start for sl in idx)
                full[idx] = load_shard_bytes(key, ikey, dtype, sub_shape,
                                             want_crc=crcs.get(ikey))
            out_leaves.append(full)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


class CheckpointManager:
    """Step-numbered checkpoints with crash-consistent restore and
    retention.

    The policy layer the reference leaves to users (SURVEY.md §5),
    matching common trainer needs: save(step, tree), restore latest,
    keep the newest ``max_to_keep`` (local paths only for deletion).

    Crash consistency: a checkpoint EXISTS only once its manifest is
    committed (written last, atomically — see ``_commit_manifest``).
    ``latest_step``/``restore_latest`` scan the step directories and
    skip any without a committed manifest, so a preemption mid-save can
    never be restored from; the ``LATEST`` file is written as a
    human/ops hint but is never trusted as the restore pointer."""

    def __init__(self, base_uri: str, *, max_to_keep: int = 3):
        check(max_to_keep >= 1,
              f"max_to_keep must be >= 1, got {max_to_keep} (0 would "
              f"delete every checkpoint including the one just saved)")
        self.base = base_uri.rstrip("/")
        self.max_to_keep = max_to_keep

    def _step_dir(self, step: int) -> str:
        return f"{self.base}/step_{step:08d}"

    def save(self, step: int, tree: Any, *, process_index: int = 0) -> None:
        save_pytree(self._step_dir(step), tree, process_index=process_index)
        if process_index == 0:
            with Stream.create(_join(self.base, "LATEST"), "w") as s:
                s.write(str(step).encode())
            self._retain()

    def _has_manifest(self, step: int) -> bool:
        s = Stream.create(_join(self._step_dir(step), MANIFEST), "r",
                          allow_null=True)
        if s is None:
            return False
        s.close()
        return True

    def _step_dirs(self) -> Optional[List[int]]:
        """Step numbers with a step_* directory under base (committed
        or not); None when the base cannot be listed (no checkpoint
        yet, or an exotic store)."""
        from ..io.filesys import FileSystem
        from ..io.uri import URI

        base = URI(self.base if "://" in self.base
                   else "file://" + self.base)
        try:
            fs = FileSystem.get_instance(base)
            entries = fs.list_directory(base)
        except OSError:
            return None
        steps = []
        for f in entries:
            name = f.path.name.rstrip("/").rsplit("/", 1)[-1]
            m = re.match(r"^step_(\d+)$", name)
            if m:
                steps.append(int(m.group(1)))
        return steps

    def _committed_steps(self) -> List[int]:
        """Committed step numbers, newest first (empty when the base
        cannot be listed — the LATEST-hint fallback covers that)."""
        steps = self._step_dirs()
        if steps is None:
            return []
        return [s for s in sorted(steps, reverse=True)
                if self._has_manifest(s)]

    def latest_step(self) -> Optional[int]:
        """Newest step with a COMMITTED manifest.  Directory scan, not
        the LATEST pointer: after a preemption mid-save the newest step
        dir is torn (shards, no manifest) and must be skipped."""
        steps = self._step_dirs()
        if steps is None:
            # unlistable store: fall back to the LATEST hint, but still
            # require its manifest to be committed
            s = Stream.create(_join(self.base, "LATEST"), "r",
                              allow_null=True)
            if s is None:
                return None
            with s:
                raw = s.read(64).strip()
            if not raw:
                return None
            step = int(raw)
            return step if self._has_manifest(step) else None
        for step in sorted(steps, reverse=True):
            if self._has_manifest(step):
                return step
        return None

    def restore_latest(self, template: Any, *, mesh=None):
        """Restore the newest committed checkpoint, falling back a step
        when a restore fails its shard digests (a silently flipped shard
        must cost ONE checkpoint interval, not the job): each committed
        step is tried newest-first; a corrupt one is logged and the next
        older committed step restores instead.  Raises only when every
        committed checkpoint is corrupt.  Only :class:`CorruptCheckpoint`
        triggers the fallback — transient read errors and template
        mismatches propagate rather than silently discarding the newest
        committed step."""
        candidates = self._committed_steps()
        if not candidates:
            step = self.latest_step()  # unlistable store: LATEST hint
            if step is None:
                return None, None
            candidates = [step]
        last_err: Optional[DMLCError] = None
        for step in candidates:
            try:
                return step, restore_pytree(self._step_dir(step),
                                            template, mesh=mesh)
            except CorruptCheckpoint as e:
                from ..logging import warning

                last_err = e
                warning(f"checkpoint step {step} failed to restore "
                        f"({e}); falling back to the previous "
                        f"committed step")
        raise DMLCError(
            f"no committed checkpoint under {self.base} restored "
            f"cleanly (last error: {last_err})")

    def _retain(self) -> None:
        import shutil

        if not os.path.isdir(self.base):
            return  # retention is local-only; object stores keep all
        committed, torn = [], []
        for name in os.listdir(self.base):
            m = re.match(r"^step_(\d+)$", name)
            if m:
                step = int(m.group(1))
                (committed if self._has_manifest(step)
                 else torn).append(step)
        # keep the newest max_to_keep COMMITTED checkpoints: a torn dir
        # (preempted save) must never push a restorable step out of the
        # retention window
        for old in sorted(committed)[: -self.max_to_keep or None]:
            shutil.rmtree(self._step_dir(old), ignore_errors=True)
        # torn dirs older than the newest committed step are dead
        # litter (their save will never be completed); newer ones may
        # be another process's save in flight — leave those alone
        if committed:
            for step in torn:
                if step < max(committed):
                    shutil.rmtree(self._step_dir(step),
                                  ignore_errors=True)
