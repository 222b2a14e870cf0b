"""Filesystem snapshot repository: incremental, content-addressed blobs.

The analog of the reference's BlobStoreRepository + fs repository type
(repositories/blobstore/BlobStoreRepository.java:157,
repositories/fs/FsRepository.java): segment data persists once per
content digest under blobs/ and is shared by every snapshot referencing
it (the reference's incremental-by-file behavior keyed on Lucene file
identity; here the identity is a digest over the segment's doc ids +
seqnos + versions, which uniquely name its content within an index
incarnation). Snapshot manifests and per-segment live masks are written
per snapshot; deletes garbage-collect unreferenced blobs.

Layout under the repository location:
    blobs/<digest>/seg-1.{npz,meta.json,src.jsonl}   immutable, shared
    snapshots/<name>.json                            manifest
    snapshots/<name>/<index>-s<shard>-<j>.live.npy   live masks
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import numpy as np

from ..index import store


class RepositoryError(Exception):
    def __init__(self, status: int, err_type: str, reason: str):
        super().__init__(reason)
        self.status = status
        self.err_type = err_type
        self.reason = reason


_NAME_RE = re.compile(r"^[a-z0-9][a-z0-9_\-.]*$")


def _segment_digest(index_uuid: str, segment) -> str:
    """Content identity of a segment within an index incarnation: doc ids
    + per-doc seqnos/versions uniquely determine what the engine wrote.
    Ids are length-prefixed so the encoding is injective (a NUL inside an
    id cannot alias another id list)."""
    h = hashlib.sha1()
    h.update(index_uuid.encode())
    for doc_id in segment.ids:
        raw = doc_id.encode()
        h.update(f"{len(raw)}:".encode())
        h.update(raw)
    if segment.seqnos is not None:
        h.update(segment.seqnos.tobytes())
    if segment.versions is not None:
        h.update(segment.versions.tobytes())
    h.update(str(segment.num_docs).encode())
    return h.hexdigest()


class FsRepository:
    def __init__(self, name: str, location: str):
        self.name = name
        self.location = location
        # Serializes create/delete/restore against each other: blob dedup
        # (exists-check then write) and GC (manifest scan then delete)
        # race destructively without it.
        self._lock = threading.Lock()
        os.makedirs(os.path.join(location, "blobs"), exist_ok=True)
        os.makedirs(os.path.join(location, "snapshots"), exist_ok=True)

    # ------------------------------------------------------------ snapshot

    def _manifest_path(self, snapshot: str) -> str:
        return os.path.join(self.location, "snapshots", f"{snapshot}.json")

    def snapshot_names(self) -> list[str]:
        out = []
        for f in sorted(os.listdir(os.path.join(self.location, "snapshots"))):
            if f.endswith(".json"):
                out.append(f[: -len(".json")])
        return out

    def create(self, snapshot: str, node, indices: list[str] | None) -> dict:
        """Snapshot the refreshed state of the selected indices."""
        with self._lock:
            return self._create(snapshot, node, indices)

    def _create(self, snapshot: str, node, indices: list[str] | None) -> dict:
        if not _NAME_RE.match(snapshot):
            raise RepositoryError(
                400, "invalid_snapshot_name_exception",
                f"invalid snapshot name [{snapshot}]",
            )
        if os.path.exists(self._manifest_path(snapshot)):
            raise RepositoryError(
                400,
                "invalid_snapshot_name_exception",
                f"snapshot with the same name [{snapshot}] already exists",
            )
        selected = sorted(indices or node.indices.keys())
        for name in selected:
            if name not in node.indices:
                raise RepositoryError(
                    404, "index_not_found_exception", f"no such index [{name}]"
                )
        snap_dir = os.path.join(self.location, "snapshots", snapshot)
        os.makedirs(snap_dir, exist_ok=True)
        manifest: dict[str, Any] = {
            "snapshot": snapshot,
            "state": "SUCCESS",
            # staticcheck: ignore[wallclock-duration] user-facing ES API epoch timestamp (snapshot start time), not a duration
            "start_time_in_millis": int(time.time() * 1000),
            "indices": {},
        }
        for name in selected:
            svc = node.indices[name]
            shards = []
            for shard_idx, engine in enumerate(svc.engines):
                with engine.lock:
                    engine.refresh()
                    handles = [
                        (h, h.live_host.copy())
                        for h in engine.segments
                        if h.segment.num_docs > 0
                    ]
                    max_seqno = engine.max_seqno
                    # Delete tombstones: their seqnos/versions exist only
                    # in the op maps, not in any surviving doc row — the
                    # restored shard needs them for seqno uniqueness and
                    # version-line continuity (same data flush() commits).
                    # export converts monotonic ages to wall clock.
                    tombstones = engine.export_tombstones()
                segs = []
                for j, (handle, live) in enumerate(handles):
                    digest = _segment_digest(svc.uuid, handle.segment)
                    blob_dir = os.path.join(self.location, "blobs", digest)
                    if not os.path.isdir(blob_dir):
                        tmp = f"{blob_dir}.tmp-{os.getpid()}-{threading.get_ident()}"
                        shutil.rmtree(tmp, ignore_errors=True)
                        os.makedirs(tmp)
                        store.persist_segment(tmp, 1, handle.segment)
                        os.replace(tmp, blob_dir)
                    live_file = f"{name}-s{shard_idx}-{j}.live.npy"
                    np.save(
                        os.path.join(snap_dir, live_file),
                        live,
                        allow_pickle=False,
                    )
                    segs.append({"blob": digest, "live": live_file})
                shards.append(
                    {
                        "segments": segs,
                        "max_seqno": max_seqno,
                        "tombstones": tombstones,
                    }
                )
            manifest["indices"][name] = {
                "uuid": svc.uuid,
                "settings": svc.settings,
                "mappings": svc.mappings.to_json(),
                "shards": shards,
            }
        # staticcheck: ignore[wallclock-duration] user-facing ES API epoch timestamp (snapshot end time), not a duration
        manifest["end_time_in_millis"] = int(time.time() * 1000)
        tmp = self._manifest_path(snapshot) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._manifest_path(snapshot))
        return manifest

    def get(self, snapshot: str | None = None) -> list[dict]:
        names = (
            self.snapshot_names()
            if snapshot in (None, "_all")
            else [snapshot]
        )
        out = []
        for name in names:
            path = self._manifest_path(name)
            if not os.path.exists(path):
                raise RepositoryError(
                    404,
                    "snapshot_missing_exception",
                    f"[{self.name}:{name}] is missing",
                )
            with open(path) as f:
                out.append(json.load(f))
        return out

    def delete(self, snapshot: str) -> None:
        with self._lock:
            path = self._manifest_path(snapshot)
            if not os.path.exists(path):
                raise RepositoryError(
                    404,
                    "snapshot_missing_exception",
                    f"[{self.name}:{snapshot}] is missing",
                )
            os.remove(path)
            shutil.rmtree(
                os.path.join(self.location, "snapshots", snapshot),
                ignore_errors=True,
            )
            self._gc_blobs()

    def _gc_blobs(self) -> None:
        """Remove blobs no remaining snapshot references (the reference's
        cleanup after delete)."""
        referenced: set[str] = set()
        for name in self.snapshot_names():
            for idx in self.get(name)[0]["indices"].values():
                for shard in idx["shards"]:
                    referenced.update(s["blob"] for s in shard["segments"])
        blob_root = os.path.join(self.location, "blobs")
        for digest in os.listdir(blob_root):
            if digest not in referenced:
                shutil.rmtree(
                    os.path.join(blob_root, digest), ignore_errors=True
                )

    # ------------------------------------------------------------- restore

    def restore(
        self,
        snapshot: str,
        node,
        indices: list[str] | None = None,
        rename_pattern: str | None = None,
        rename_replacement: str | None = None,
    ) -> dict:
        """Rebuild indices from a snapshot: exact segment restore (packed
        straight back to the device), preserving versions/seqnos and the
        shard seqno high-water mark / delete tombstones. Every target is
        validated BEFORE any index is created — a failing request restores
        nothing (the reference's RestoreService validates up front)."""
        with self._lock:
            manifest = self.get(snapshot)[0]
            selected = sorted(indices or manifest["indices"].keys())
            snap_dir = os.path.join(self.location, "snapshots", snapshot)
            plan: list[tuple[str, str, dict]] = []
            seen_targets: set[str] = set()
            for name in selected:
                meta = manifest["indices"].get(name)
                if meta is None:
                    raise RepositoryError(
                        404,
                        "index_not_found_exception",
                        f"index [{name}] not found in snapshot [{snapshot}]",
                    )
                target = name
                if rename_pattern and rename_replacement is not None:
                    try:
                        target = re.sub(
                            rename_pattern, rename_replacement, name
                        )
                    except re.error as e:
                        raise RepositoryError(
                            400,
                            "snapshot_restore_exception",
                            f"invalid rename_pattern: {e}",
                        ) from None
                if not _NAME_RE.match(target):
                    raise RepositoryError(
                        400,
                        "snapshot_restore_exception",
                        f"invalid renamed index name [{target}]",
                    )
                if target in node.indices or target in seen_targets:
                    raise RepositoryError(
                        400,
                        "snapshot_restore_exception",
                        f"cannot restore index [{target}] because an open "
                        f"index with same name already exists in the cluster",
                    )
                seen_targets.add(target)
                plan.append((name, target, meta))
            restored = []
            for name, target, meta in plan:
                node.create_index(
                    target,
                    {
                        "settings": meta["settings"],
                        "mappings": meta["mappings"],
                    },
                )
                svc = node.indices[target]

                def restore_shard(engine, shard_meta):
                    batch = []
                    for seg_meta in shard_meta["segments"]:
                        blob_dir = os.path.join(
                            self.location, "blobs", seg_meta["blob"]
                        )
                        segment, _ = store.load_segment(blob_dir, 1)
                        live = np.load(
                            os.path.join(snap_dir, seg_meta["live"]),
                            allow_pickle=False,
                        )
                        batch.append((segment, live))
                    engine.restore_segments(batch)
                    engine.restore_shard_state(
                        shard_meta.get("max_seqno", -1),
                        shard_meta.get("tombstones", {}),
                    )
                    if engine.data_path is not None:
                        engine.flush()

                shards = list(zip(svc.engines, meta["shards"]))
                if len(shards) == 1:
                    restore_shard(*shards[0])
                else:
                    # Shards restore side by side: each engine reads its
                    # own blobs and packs onto its own device under its
                    # own lock (the breaker and ledger lock their
                    # shared counts). The first failure re-raises once
                    # every shard has finished.
                    with ThreadPoolExecutor(len(shards)) as pool:
                        list(pool.map(lambda a: restore_shard(*a), shards))
                restored.append(target)
        return {
            "snapshot": {
                "snapshot": snapshot,
                "indices": restored,
                "shards": {
                    "total": sum(
                        len(manifest["indices"][n]["shards"])
                        for n in selected
                    ),
                    "failed": 0,
                    "successful": sum(
                        len(manifest["indices"][n]["shards"])
                        for n in selected
                    ),
                },
            }
        }
