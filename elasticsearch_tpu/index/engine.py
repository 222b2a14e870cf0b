"""Shard engine: the write path and searchable-snapshot lifecycle.

The analog of the reference's InternalEngine (server/src/main/java/org/
elasticsearch/index/engine/InternalEngine.java:851): documents land in an
in-memory indexing buffer (SegmentBuilder ≈ the IndexWriter RAM buffer),
`refresh()` freezes the buffer into an immutable Segment and uploads it to
the device (≈ opening a new DirectoryReader over a flushed Lucene segment,
FsDirectoryFactory mmap path), and deletes/updates flip live-doc masks on
already-refreshed segments (≈ Lucene liveDocs,
ContextIndexSearcher.java:181-195).

Key semantic carried over from Lucene: BM25 term statistics (df, docCount,
sumTotalTermFreq) are *shard-level* — aggregated across every searchable
segment at search time (Lucene computes them from the top-level IndexReader,
not per leaf). `field_stats()` provides that aggregate; the query compiler
consumes it per segment so multi-segment scoring matches a single-segment
index bit-for-bit.

Sequence numbers: every index/delete op gets a monotonically increasing
seqno (InternalEngine.java:829 generateSeqNoForOperation); the translog
(index/translog.py) persists ops by seqno for restart recovery.

Durability (when constructed with a data_path): ops append to the translog
(fsynced per request via `sync_translog`), `flush()` persists segments +
live masks and writes a commit point, recovery at construction loads the
last commit and replays translog ops above its seqno — the
Translog/commitIndexWriter/recoverFromTranslog cycle of the reference
(InternalEngine.java:851, translog/Translog.java:71-107).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from dataclasses import dataclass, field as dc_field
from typing import Any

import numpy as np

from ..common.breaker import BreakerError
from ..ops.bm25 import BM25Params
from ..query.compile import Compiler, FieldStats, aggregate_field_stats
from . import store
from .mapping import Mappings
from .merge import merged_live_segment
from .segment import Segment, SegmentBuilder
from .tiles import (
    DeviceSegment,
    device_nbytes,
    estimate_segment_device_bytes,
    pack_segment,
    repack_tn,
)
from .translog import Translog


def _mono_to_wall_ts(mono_ts: float) -> float:
    """Monotonic instant -> wall-clock epoch seconds, at a persistence
    boundary. In-memory tombstone ages use time.monotonic() (NTP-step
    immune); only the persisted form may (and must) be wall clock, since
    monotonic readings are meaningless across processes."""
    # staticcheck: ignore[wallclock-duration] persistence boundary: monotonic readings do not survive a restart, epoch does
    return mono_ts - time.monotonic() + time.time()


def _wall_to_mono_ts(wall_ts: float) -> float:
    """Wall-clock epoch seconds (from a commit/snapshot) -> this
    process's monotonic clock, preserving the recorded age."""
    # staticcheck: ignore[wallclock-duration] persistence boundary: converting a persisted epoch age back onto the monotonic clock
    return wall_ts - time.time() + time.monotonic()


# Process-unique ids for engines and segment handles. The filter cache
# (index/filter_cache.py) keys mask planes on these instead of id(obj):
# CPython reuses addresses after GC, so an id()-keyed entry could silently
# alias a NEW segment with an old segment's mask — a monotonic counter
# cannot collide within a process.
_ENGINE_UIDS = itertools.count(1)
_HANDLE_UIDS = itertools.count(1)


class InvalidCasError(ValueError):
    """Malformed CAS request (one-sided if_seq_no/if_primary_term) — 400."""


class VersionConflictError(Exception):
    """Seqno/term CAS failure — maps to HTTP 409 version_conflict_engine_exception.

    The engine-level contract of the reference's if_seq_no/if_primary_term
    compare-and-set (action/index/IndexRequest.java:109, enforced in
    InternalEngine.planIndexingAsPrimary's version-map check).
    """

    def __init__(self, doc_id: str, reason: str):
        super().__init__(f"[{doc_id}]: version conflict, {reason}")
        self.doc_id = doc_id


@dataclass
class SegmentHandle:
    """One searchable segment plus its mutable deletion state."""

    segment: Segment
    device: DeviceSegment
    base: int  # global doc id base for this segment
    live_host: np.ndarray  # bool[N] host copy of the live mask
    live_dirty: bool = False
    seg_id: int | None = None  # on-disk id once persisted by flush()
    nbytes: int = 0  # device bytes held (HBM breaker accounting)
    # Process-unique handle id: the filter cache's segment key component.
    # dataclasses.replace (merge re-basing, scroll freezing) copies it —
    # correct, since those clones share the SAME immutable postings and
    # doc-values planes, so cached masks stay valid for them.
    uid: int = dc_field(default_factory=lambda: next(_HANDLE_UIDS))
    # Monotonic epoch of the DEVICE-visible live mask: bumps on every
    # sync_live upload. (uid, live_epoch) identifies the searchable
    # content of this handle exactly — the mesh view keys its per-handle
    # compaction pieces and per-shard filter-cache rows on it, so a
    # refresh that only touches OTHER handles leaves them warm.
    live_epoch: int = 0
    _id_index: dict[str, int] | None = None  # lazy _id -> local (ids query)

    @property
    def id_index(self) -> dict[str, int]:
        if self._id_index is None:
            self._id_index = {d: i for i, d in enumerate(self.segment.ids)}
        return self._id_index

    def soft_delete(self, local_doc: int) -> None:
        if self.live_host[local_doc]:
            self.live_host[local_doc] = False
            self.live_dirty = True

    def sync_live(self) -> None:
        """Re-upload the live mask if deletions happened since last sync."""
        if self.live_dirty:
            if self.device is None:
                # Demoted to host: the re-pack (Engine.ensure_device)
                # re-derives the device mask from live_host and clears
                # the dirty flag then.
                return
            import jax

            self.device.live = jax.device_put(
                self.live_host.copy(), self.device.live.sharding
            )
            self.live_dirty = False
            self.live_epoch += 1

    @property
    def live_count(self) -> int:
        return int(np.count_nonzero(self.live_host))


class Engine:
    """Indexing buffer + refreshed device segments for one shard."""

    def __init__(
        self,
        mappings: Mappings | None = None,
        params: BM25Params = BM25Params(),
        device=None,
        data_path: str | None = None,
        durability: str = "request",
        max_segments: int = 10,
        merge_factor: int = 8,
        breaker=None,  # common.breaker.CircuitBreaker (HBM accounting)
        metrics=None,  # obs.metrics.MetricsRegistry (refresh/merge counters)
    ):
        self.mappings = mappings or Mappings()
        self.params = params
        self.device = device
        # Merge policy (the reference's EsTieredMergePolicy, simplified to
        # a segment-count budget): when a refresh pushes the searchable
        # segment count past `max_segments`, the smallest `merge_factor`
        # segments compact into one — bounding kernel launches per query.
        self.max_segments = max(1, int(max_segments))
        self.merge_factor = max(2, int(merge_factor))
        self.breaker = breaker
        self.metrics = metrics
        # Refresh/merge accounting (the reference's RefreshStats /
        # MergeStats): plain ints read by `_stats`/`_nodes/stats`, mirrored
        # onto the node registry (estpu_refresh_* / estpu_merge_*) when one
        # is wired.
        self.refresh_total = 0
        self.refresh_ms_total = 0.0
        self.merges_total = 0
        self.merge_docs_total = 0
        self.merge_ms_total = 0.0
        # Process-unique engine id: filter-cache key component + the
        # per-index clear handle (`POST /{index}/_cache/clear`).
        self.uid = next(_ENGINE_UIDS)
        self.segments: list[SegmentHandle] = []
        # Serializes the whole write path (index/delete/refresh/flush and
        # the version map) — the REST layer dispatches concurrent requests
        # from ThreadingHTTPServer, and seqno assignment, buffer mutation,
        # and the flush/roll window must be atomic with respect to each
        # other (the reference guards the same invariants with
        # InternalEngine's versionMap + readLock/writeLock).
        self.lock = threading.RLock()
        self._buffer = SegmentBuilder(self.mappings)
        self._buffer_ids: dict[str, int] = {}  # _id -> local doc in buffer
        self._buffer_deleted: set[int] = set()  # buffer locals dropped pre-refresh
        self._live_ids: dict[str, tuple[int, int]] = {}  # _id -> (seg idx, local)
        self._seqno = -1
        self._auto_id = 0
        self.primary_term = 1
        # Version map: _id -> latest op version, kept across deletes
        # (tombstones) so re-creating a deleted doc continues its version
        # line, like the reference's LiveVersionMap delete tombstones.
        # Tombstones persist in the commit point and are pruned after
        # gc_deletes (ES index.gc_deletes, default 60s) — after that a
        # re-create legitimately restarts at version 1, exactly like the
        # reference after tombstone GC.
        self._versions: dict[str, int] = {}
        self._doc_seqnos: dict[str, int] = {}  # _id -> seqno of last op
        # _id -> MONOTONIC delete time: gc_deletes measures an age, and a
        # wall clock stepped by NTP would prune tombstones early (version
        # lines break) or never. Persistence boundaries (commit/snapshot)
        # convert to wall time so values stay comparable across restarts
        # — see _mono_to_wall_ts/_wall_to_mono_ts.
        self._tombstone_ts: dict[str, float] = {}
        self.gc_deletes_s = 60.0
        self._stats_cache: dict[str, FieldStats] | None = None
        # Replication state (index/seqno.py): the local checkpoint is the
        # highest contiguous processed seqno (replicas apply out of order);
        # the ops history retains recent ops for peer-recovery catch-up —
        # the analog of the reference's translog retention / soft-delete
        # ops history (index/seqno/RetentionLeases, RecoverySourceHandler).
        from .seqno import LocalCheckpointTracker

        self.checkpoint = LocalCheckpointTracker()
        self._ops_history: list[dict] = []
        self._ops_floor = -1  # seqnos <= floor no longer individually held
        self.history_retention = 10_000
        # Highest primary term any applied op carried: a copy whose ops
        # line predates the current term may hold diverged (never-acked)
        # ops and must full-resync rather than ops-catch-up.
        self.max_op_term = 0
        # Monotonic refresh generation: bumps whenever the searchable view
        # changes (new segment, live-mask sync, recovery). Cache keys built
        # from this are safe where id()-of-handle keys are not (CPython
        # reuses addresses after GC).
        self.generation = 0
        self.data_path = data_path
        self.translog: Translog | None = None
        self._next_seg_id = 1
        self._recovering = False
        # Cold-tier demotion (cluster/remediation.py lifecycle loop):
        # device planes dropped to free HBM, host segments stay — the
        # next search (or an explicit promotion) re-packs on demand.
        self._demoted = False
        if data_path is not None:
            os.makedirs(data_path, exist_ok=True)
            # Recovery must load durably-acked data regardless of the HBM
            # budget (the breaker rejects NEW allocations, not committed
            # state): _pack_accounted accounts without enforcing while set.
            self._recovering = True
            try:
                self._recover()
                self.translog = Translog(
                    os.path.join(data_path, "translog"), durability
                )
                self._replay_translog()
            finally:
                self._recovering = False
        # Everything recovered is contiguous by construction; ops below the
        # recovered point are not individually available for catch-up.
        self.checkpoint.advance_to(self._seqno)
        self._ops_floor = self._seqno

    # ------------------------------------------------------------- write path

    def next_seqno(self) -> int:
        self._seqno += 1
        return self._seqno

    @property
    def max_seqno(self) -> int:
        return self._seqno

    def _exists(self, doc_id: str) -> bool:
        """Doc currently live (buffered or refreshed)."""
        return doc_id in self._buffer_ids or doc_id in self._live_ids

    def _check_cas(
        self, doc_id: str, if_seq_no: int | None, if_primary_term: int | None
    ) -> None:
        """Enforce the if_seq_no/if_primary_term compare-and-set contract."""
        if if_seq_no is None and if_primary_term is None:
            return
        if if_seq_no is None or if_primary_term is None:
            # The reference rejects one-sided CAS up front with 400
            # (IndexRequest.validate: "ifSeqNo is unassigned, but primary
            # term is [x]").
            raise InvalidCasError(
                "if_seq_no and if_primary_term must be provided together"
            )
        if not self._exists(doc_id):
            raise VersionConflictError(
                doc_id,
                f"required seqNo [{if_seq_no}], but no document was found",
            )
        cur_seq = self._doc_seqnos.get(doc_id, -1)
        if cur_seq != if_seq_no:
            raise VersionConflictError(
                doc_id,
                f"required seqNo [{if_seq_no}], current document has "
                f"seqNo [{cur_seq}]",
            )
        if if_primary_term != self.primary_term:
            raise VersionConflictError(
                doc_id,
                f"required primaryTerm [{if_primary_term}], current "
                f"primaryTerm [{self.primary_term}]",
            )

    def index(
        self,
        source: dict[str, Any],
        doc_id: str | None = None,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
        op_type: str = "index",
    ) -> dict:
        """Index (create or overwrite) one document. Returns op metadata.

        op_type="create" enforces put-if-absent inside the engine lock (the
        reference's IndexRequest.opType CREATE → version conflict when the
        doc exists), closing the get-then-index race window.
        """
        with self.lock:
            if doc_id is None:
                doc_id = f"_auto_{self._auto_id}"
                self._auto_id += 1
            self._check_cas(doc_id, if_seq_no, if_primary_term)
            exists = self._exists(doc_id)
            if op_type == "create" and exists:
                raise VersionConflictError(
                    doc_id, "document already exists"
                )
            version = self._versions.get(doc_id, 0) + 1
            seqno = self.next_seqno()
            try:
                # SegmentBuilder.add is atomic (stage-then-commit), so a
                # mapper failure here leaves no partial doc; the seqno is
                # handed back and no prior copy has been tombstoned yet.
                local = self._buffer.add(
                    source, doc_id, version=version, seqno=seqno
                )
            except ValueError:
                self._seqno -= 1
                raise
            created = not exists
            self._delete_existing(doc_id)
            self._buffer_ids[doc_id] = local
            self._versions[doc_id] = version
            self._doc_seqnos[doc_id] = seqno
            self._tombstone_ts.pop(doc_id, None)
            op = {
                "seqno": seqno,
                "op": "index",
                "id": doc_id,
                "version": version,
                "source": source,
                "term": self.primary_term,
            }
            if self.translog is not None:
                self.translog.add(op)
            self._record_op(op)
            return {
                "_id": doc_id,
                "result": "created" if created else "updated",
                "_seq_no": seqno,
                "_version": version,
                "_primary_term": self.primary_term,
            }

    def delete(
        self,
        doc_id: str,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
    ) -> dict:
        with self.lock:
            self._check_cas(doc_id, if_seq_no, if_primary_term)
            found = self._delete_existing(doc_id) > 0
            version = self._versions.get(doc_id, 0) + (1 if found else 0)
            seqno = self.next_seqno() if found else self._seqno
            if found:
                self._versions[doc_id] = version
                self._doc_seqnos[doc_id] = seqno
                self._tombstone_ts[doc_id] = time.monotonic()
                op = {
                    "seqno": seqno,
                    "op": "delete",
                    "id": doc_id,
                    "version": version,
                    "term": self.primary_term,
                }
                if self.translog is not None:
                    self.translog.add(op)
                self._record_op(op)
            return {
                "_id": doc_id,
                "result": "deleted" if found else "not_found",
                "_seq_no": seqno,
                "_version": version if found else 1,
                "_primary_term": self.primary_term,
            }

    # ------------------------------------------------------- replication

    def _record_op(self, op: dict) -> None:
        """Retain the op for peer-recovery catch-up and advance the local
        checkpoint. Caller holds the engine lock."""
        self.checkpoint.mark(int(op["seqno"]))
        self.max_op_term = max(self.max_op_term, int(op.get("term", 0)))
        self._ops_history.append(op)
        if len(self._ops_history) > self.history_retention:
            drop = len(self._ops_history) - self.history_retention
            self._ops_floor = max(
                self._ops_floor,
                max(int(o["seqno"]) for o in self._ops_history[:drop]),
            )
            del self._ops_history[:drop]

    @property
    def local_checkpoint(self) -> int:
        return self.checkpoint.checkpoint

    def _apply_external_op(self, op: dict, write_translog: bool) -> None:
        """Apply an op that already carries its seqno/version (replica
        fan-out or translog replay). Per-doc conflicts resolve newest-
        seqno-wins; stale ops are no-ops but still count as processed.
        Caller holds the engine lock."""
        doc_id = op["id"]
        seqno = int(op["seqno"])
        version = int(op.get("version", self._versions.get(doc_id, 0) + 1))
        if seqno > self._doc_seqnos.get(doc_id, -1):
            if op["op"] == "index":
                self._delete_existing(doc_id)
                local = self._buffer.add(
                    op["source"], doc_id, version=version, seqno=seqno
                )
                self._buffer_ids[doc_id] = local
                self._versions[doc_id] = version
                self._doc_seqnos[doc_id] = seqno
                self._tombstone_ts.pop(doc_id, None)
                self._bump_auto_id(doc_id)
            else:
                self._delete_existing(doc_id)
                self._versions[doc_id] = version
                self._doc_seqnos[doc_id] = seqno
                self._tombstone_ts[doc_id] = time.monotonic()
        self._seqno = max(self._seqno, seqno)
        if write_translog and self.translog is not None:
            self.translog.add(op)
        self._record_op(op)

    def apply_replica(self, op: dict) -> dict:
        """Apply a primary-replicated op with its assigned seqno/version.

        Replica-side semantics of the reference's TransportShardBulkAction
        replica phase: ops may arrive out of order, so per-doc conflicts
        resolve newest-seqno-wins (index/engine/InternalEngine
        planIndexingAsNonPrimary), stale ops are no-ops (still marked
        processed), and the local checkpoint advances through the tracker.
        """
        with self.lock:
            self._apply_external_op(op, write_translog=True)
            return {"local_checkpoint": self.local_checkpoint}

    def ops_since(self, seqno: int) -> list[dict] | None:
        """Retained ops with seqno > `seqno` in seqno order, or None when
        the history no longer reaches back that far (caller must fall back
        to a full resync — the reference's file-based recovery path)."""
        with self.lock:
            if seqno < self._ops_floor:
                return None
            return sorted(
                (o for o in self._ops_history if int(o["seqno"]) > seqno),
                key=lambda o: int(o["seqno"]),
            )

    def resync_payload(self) -> dict:
        """Full-copy payload: every live doc (with version/seqno) plus the
        tombstone version lines — the ops-history-exhausted recovery path.
        """
        with self.lock:
            docs = []
            for doc_id, local in self._buffer_ids.items():
                if local not in self._buffer_deleted:
                    docs.append(
                        {
                            "id": doc_id,
                            "source": self._buffer._sources[local],
                            "version": self._versions.get(doc_id, 1),
                            "seqno": self._doc_seqnos.get(doc_id, -1),
                        }
                    )
            for handle in self.segments:
                seg = handle.segment
                for local in np.flatnonzero(handle.live_host):
                    local = int(local)
                    doc_id = seg.ids[local]
                    if doc_id in self._buffer_ids:
                        continue
                    docs.append(
                        {
                            "id": doc_id,
                            "source": seg.sources[local],
                            "version": seg.doc_version(local),
                            "seqno": seg.doc_seqno(local),
                        }
                    )
            return {
                "docs": docs,
                "tombstones": {
                    doc_id: [
                        self._versions.get(doc_id, 1),
                        self._doc_seqnos.get(doc_id, -1),
                    ]
                    for doc_id in self._tombstone_ts
                },
                "max_seqno": self._seqno,
            }

    def apply_resync(self, payload: dict) -> None:
        """Install a full-copy payload on an empty/stale replica."""
        with self.lock:
            for doc in payload["docs"]:
                self.apply_replica(
                    {
                        "op": "index",
                        "id": doc["id"],
                        "source": doc["source"],
                        "version": doc["version"],
                        "seqno": doc["seqno"],
                    }
                )
            for doc_id, (version, seqno) in payload["tombstones"].items():
                self.apply_replica(
                    {
                        "op": "delete",
                        "id": doc_id,
                        "version": version,
                        "seqno": seqno,
                    }
                )
            # Seqnos in a full copy are sparse (merged-away ops are gone):
            # everything at or below the primary's max is processed here.
            self._seqno = max(self._seqno, int(payload["max_seqno"]))
            self.checkpoint.advance_to(self._seqno)
            self._ops_floor = max(self._ops_floor, self._seqno)

    def sync_translog(self) -> None:
        """fsync the translog — the per-request durability point the write
        path acks through (TransportWriteAction's waitForSync analog).
        Under index.translog.durability=async the request-time fsync is
        skipped; flush() still syncs via Translog.roll."""
        if self.translog is not None and self.translog.durability == "request":
            self.translog.sync()

    def _delete_existing(self, doc_id: str) -> int:
        """Tombstone any live copy of doc_id; returns number removed (0/1)."""
        removed = 0
        buf_local = self._buffer_ids.pop(doc_id, None)
        if buf_local is not None:
            # Buffered doc not yet refreshed: mark for drop at refresh time.
            self._buffer_deleted.add(buf_local)
            removed = 1
        loc = self._live_ids.pop(doc_id, None)
        if loc is not None:
            seg_idx, local = loc
            self.segments[seg_idx].soft_delete(local)
            removed = 1
        return removed

    def get(self, doc_id: str) -> dict[str, Any] | None:
        """Realtime GET: buffer first (like the reference's getFromTranslog,
        InternalEngine.java:639), then refreshed segments."""
        with self.lock:
            local = self._buffer_ids.get(doc_id)
            if local is not None:
                return self._buffer._sources[local]
            loc = self._live_ids.get(doc_id)
            if loc is not None:
                seg_idx, local = loc
                return self.segments[seg_idx].segment.sources[local]
            return None

    def get_with_meta(self, doc_id: str) -> dict[str, Any] | None:
        """Realtime GET returning {_source, _version, _seq_no, _primary_term}."""
        with self.lock:
            source = self.get(doc_id)
            if source is None:
                return None
            return {
                "_source": source,
                "_version": self._versions.get(doc_id, 1),
                "_seq_no": self._doc_seqnos.get(doc_id, -1),
                "_primary_term": self.primary_term,
            }

    # ----------------------------------------------------------- refresh/read

    def refresh(self) -> bool:
        """Make buffered docs searchable; returns True if anything changed.

        Buffered docs that were deleted/overwritten before the refresh are
        dropped rather than indexed-then-masked (the reference achieves the
        same via the version map + Lucene delete-by-term on flush).
        """
        t0 = time.monotonic()
        # Completed refreshes only (the reference RefreshStats contract):
        # a refresh that raises (e.g. the HBM breaker rejecting the pack)
        # must not inflate the totals the bench p50s are built on.
        out = self._refresh_locked()
        elapsed_ms = (time.monotonic() - t0) * 1e3
        self.refresh_total += 1
        self.refresh_ms_total += elapsed_ms
        if self.metrics is not None:
            self.metrics.counter(
                "estpu_refresh_total",
                "Engine refreshes (buffer freeze + live-mask syncs)",
            ).inc()
            self.metrics.counter(
                "estpu_refresh_ms_total",
                "Wall-clock ms spent in engine refreshes",
            ).inc(elapsed_ms)
        return out

    def _refresh_locked(self) -> bool:
        with self.lock:
            changed = False
            for handle in self.segments:
                if handle.live_dirty:
                    handle.sync_live()
                    changed = True
            if changed:
                self.generation += 1
            if self._buffer.num_docs == 0:
                return changed
            deleted = self._buffer_deleted
            if deleted:
                # Rebuild the buffer without dropped docs.
                keep = [
                    i for i in range(self._buffer.num_docs) if i not in deleted
                ]
                rebuilt = SegmentBuilder(self.mappings)
                id_map = {}
                for i in keep:
                    new_local = rebuilt.add(
                        self._buffer._sources[i],
                        self._buffer._ids[i],
                        version=self._buffer._versions[i],
                        seqno=self._buffer._seqnos[i],
                    )
                    id_map[i] = new_local
                self._buffer = rebuilt
                self._buffer_ids = {
                    d: id_map[l]
                    for d, l in self._buffer_ids.items()
                    if l in id_map
                }
                deleted.clear()
                if self._buffer.num_docs == 0:
                    return changed
            segment = self._buffer.build()
            base = sum(h.segment.num_docs for h in self.segments)
            device, nbytes = self._pack_accounted(segment)
            handle = SegmentHandle(
                segment=segment,
                device=device,
                base=base,
                live_host=np.ones(segment.num_docs, dtype=bool),
                nbytes=nbytes,
            )
            seg_idx = len(self.segments)
            self.segments.append(handle)
            for doc_id, local in self._buffer_ids.items():
                self._live_ids[doc_id] = (seg_idx, local)
            self._buffer = SegmentBuilder(self.mappings)
            self._buffer_ids = {}
            self._stats_cache = None
            self.generation += 1
            self._maybe_merge()
            self._sync_impacts()
            return True

    def _pack_accounted(
        self, segment, deleted=None, enforce: bool = True
    ) -> tuple[DeviceSegment, int]:
        """Pack a segment with HBM breaker accounting: reserve the estimate
        first (reject BEFORE touching the device when over budget), settle
        to actual bytes after. enforce=False accounts without rejecting —
        recovery must load committed data regardless."""
        est = estimate_segment_device_bytes(segment)
        if self.breaker is not None:
            if enforce and not self._recovering:
                self.breaker.add(est, label="segment", scope=self.uid)
            else:
                self.breaker.add_unchecked(
                    est, label="segment", scope=self.uid
                )
        try:
            device = pack_segment(
                segment,
                self.device,
                deleted=deleted,
                k1=self.params.k1,
                b=self.params.b,
            )
        except Exception:
            if self.breaker is not None:
                self.breaker.release(est, label="segment", scope=self.uid)
            raise
        actual = device_nbytes(device)
        if self.breaker is not None:
            # Settle the reservation to the packed truth; mirrored into
            # the HBM ledger through the breaker, so ledger "segment"
            # bytes track sum(handle.nbytes) exactly (the consistency
            # law's segment leg).
            if actual > est:
                self.breaker.add_unchecked(
                    actual - est, label="segment", scope=self.uid
                )
            else:
                self.breaker.release(
                    est - actual, label="segment", scope=self.uid
                )
        return device, actual

    @property
    def device_bytes(self) -> int:
        """HBM held by this engine's packed segments."""
        return sum(h.nbytes for h in self.segments)

    # -------------------------------------------------- cold-tier demotion

    @property
    def demoted(self) -> bool:
        """True while device planes are dropped (host segments remain)."""
        return self._demoted

    def demote_device(self) -> int:
        """Drop every packed device plane to free HBM, keeping the host
        segments (postings, doc values, live masks) intact — the cold
        tier of the remediation lifecycle loop. Searches re-pack on
        demand through `ensure_device`, bit-identically: the device
        planes are a pure function of the host segments. Returns the
        HBM bytes released from the breaker."""
        with self.lock:
            if self._demoted or not self.segments:
                return 0
            freed = 0
            for handle in self.segments:
                if handle.nbytes and self.breaker is not None:
                    self.breaker.release(
                        handle.nbytes, label="segment", scope=self.uid
                    )
                freed += handle.nbytes
                handle.device = None
                handle.nbytes = 0
            self._demoted = True
            return freed

    def ensure_device(self) -> bool:
        """Re-pack any dropped device planes (promotion / on-demand
        re-pack at search time). Same `_pack_accounted` path as refresh,
        so the HBM breaker + ledger account the return trip; handle uids
        and the engine generation are unchanged — the planes hold the
        SAME searchable content, so filter/ANN cache entries stay warm
        and hits stay bit-identical through the demote/re-pack cycle.
        Returns True when a re-pack happened."""
        if not self._demoted:
            return False
        with self.lock:
            if not self._demoted:
                return False
            for handle in self.segments:
                if handle.device is not None:
                    continue
                device, nbytes = self._pack_accounted(handle.segment)
                handle.device = device
                handle.nbytes = nbytes
                if handle.live_dirty:
                    # Deletions landed while demoted: the device mask
                    # must advance past the pack-time all-live default,
                    # and the epoch must bump so mask caches re-key.
                    import jax

                    # staticcheck: ignore[lock-blocking-call] deliberate: the re-packed plane and its live mask must install atomically against concurrent refresh/delete; promotion is a rare background action, not a request path
                    handle.device.live = jax.device_put(
                        handle.live_host.copy(), handle.device.live.sharding
                    )
                    handle.live_dirty = False
                    handle.live_epoch += 1
                elif not bool(handle.live_host.all()):
                    import jax

                    # staticcheck: ignore[lock-blocking-call] deliberate: same atomic plane+mask install as the dirty branch (epoch unchanged — the mask content equals what caches already keyed)
                    handle.device.live = jax.device_put(
                        handle.live_host.copy(), handle.device.live.sharding
                    )
            self._demoted = False
            return True

    # ------------------------------------------------------------- merging

    def _maybe_merge(self) -> None:
        """Compact the smallest segments when the count exceeds the budget
        (called under the engine lock from refresh)."""
        if len(self.segments) <= self.max_segments:
            return
        over = len(self.segments) - self.max_segments
        n_merge = min(len(self.segments), max(2, over + 1, self.merge_factor))
        by_size = sorted(
            range(len(self.segments)),
            key=lambda i: self.segments[i].segment.num_docs,
        )
        try:
            self._merge_segments(sorted(by_size[:n_merge]))
        except BreakerError:
            # A merge transiently doubles the merged bytes; under memory
            # pressure skip the compaction rather than failing the refresh
            # (the reference's merges back off the same way under throttle).
            pass

    def force_merge(self, max_num_segments: int = 1) -> dict:
        """Merge down to at most `max_num_segments` searchable segments
        (the reference's POST /_forcemerge → ForceMergeRequest)."""
        with self.lock:
            self.refresh()
            target = max(1, int(max_num_segments))
            if len(self.segments) > target:
                # One merge of the (count - target + 1) smallest segments
                # reaches the target exactly.
                n_merge = len(self.segments) - target + 1
                by_size = sorted(
                    range(len(self.segments)),
                    key=lambda i: self.segments[i].segment.num_docs,
                )
                self._merge_segments(sorted(by_size[:n_merge]))
                self._sync_impacts()
            return {"num_segments": len(self.segments)}

    def _merge_segments(self, indices: list[int]) -> None:
        """Rewrite the given segments (by position) into one live-docs-only
        segment, placed at the first merged position.

        Like a Lucene merge, deleted docs are purged — their postings leave
        the term statistics — and doc ids are renumbered. The merge is pure
        posting concatenation (index/merge.py): term dictionaries union,
        doc ids renumber via cumulative live-doc offsets, stats fold
        arithmetically — NO document is re-analyzed (hook-counted via
        estpu_analysis_calls_total), so merge cost is array I/O like a
        Lucene SegmentMerger pass, not a tokenizer pass over the shard.
        Callers hold the engine lock. Scroll snapshots are unaffected:
        they hold frozen handle clones and this replaces the engine's
        segment LIST."""
        if len(indices) < 2:
            return
        t0 = time.monotonic()
        merge_set = set(indices)
        merged_segment = merged_live_segment(
            [self.segments[idx].segment for idx in indices],
            [self.segments[idx].live_host for idx in indices],
        )
        merged_device, merged_nbytes = self._pack_accounted(merged_segment)
        if self.breaker is not None:
            # The merged-away segments' device arrays become garbage once
            # the handle list swaps (snapshots may pin them briefly).
            self.breaker.release(
                sum(self.segments[i].nbytes for i in indices),
                label="segment",
                scope=self.uid,
            )
        merged_handle = SegmentHandle(
            segment=merged_segment,
            device=merged_device,
            base=0,  # bases renumber below
            live_host=np.ones(merged_segment.num_docs, dtype=bool),
            nbytes=merged_nbytes,
        )
        new_segments: list[SegmentHandle] = []
        for idx, handle in enumerate(self.segments):
            if idx == indices[0]:
                new_segments.append(merged_handle)
            elif idx not in merge_set:
                new_segments.append(handle)
        # Renumber bases copy-on-write: in-flight searches pin
        # `list(engine.segments)` without the lock, so mutating a shared
        # handle's base would corrupt their (base + local) doc ordering
        # mid-request. A re-based survivor is a fresh handle object; the
        # pinned snapshot keeps the old one with its old base.
        from dataclasses import replace as dc_replace

        base = 0
        rebased: list[SegmentHandle] = []
        self._live_ids = {}
        for seg_idx, handle in enumerate(new_segments):
            if handle.base != base:
                handle = dc_replace(handle, base=base)
            rebased.append(handle)
            base += handle.segment.num_docs
            live = handle.live_host
            for local, doc_id in enumerate(handle.segment.ids):
                if live[local]:
                    self._live_ids[doc_id] = (seg_idx, local)
        self.segments = rebased
        self._stats_cache = None
        self.generation += 1
        elapsed_ms = (time.monotonic() - t0) * 1e3
        self.merges_total += 1
        self.merge_docs_total += merged_segment.num_docs
        self.merge_ms_total += elapsed_ms
        if self.metrics is not None:
            self.metrics.counter(
                "estpu_merge_total",
                "Segment merges (posting-concatenation compactions)",
            ).inc()
            self.metrics.counter(
                "estpu_merge_docs_moved_total",
                "Live docs moved into merged segments",
            ).inc(merged_segment.num_docs)
            self.metrics.counter(
                "estpu_merge_ms_total",
                "Wall-clock ms spent in segment merges",
            ).inc(elapsed_ms)

    def flush(self) -> dict:
        """Refresh, persist segments + live masks, commit, trim the translog.

        The reference's InternalEngine.flush: Lucene commit embedding the
        translog generation, then trimUnreferencedReaders. After a flush,
        everything up to max_seqno survives a crash without replay.
        """
        with self.lock:
            self.refresh()
            self._gc_tombstones()
            if self.data_path is None:
                return {"committed": False}
            for handle in self.segments:
                if handle.seg_id is None:
                    handle.seg_id = self._next_seg_id
                    self._next_seg_id += 1
                    store.persist_segment(
                        self.data_path, handle.seg_id, handle.segment
                    )
                store.persist_live(
                    self.data_path, handle.seg_id, handle.live_host
                )
            store.write_commit(
                self.data_path,
                {
                    "segments": [h.seg_id for h in self.segments],
                    "max_seqno": self._seqno,
                    "next_seg_id": self._next_seg_id,
                    # Delete tombstones ride in the commit so the version
                    # line survives restart (until gc_deletes prunes them).
                    "tombstones": self.export_tombstones(),
                },
            )
            if self.translog is not None:
                # Holding the engine lock across refresh→commit→roll keeps
                # the persisted_seqno honest: no op can take a seqno between
                # the refresh snapshot and the generation retirement.
                self.translog.roll(self._seqno)
            store.gc_segments(
                self.data_path, {h.seg_id for h in self.segments}
            )
            return {"committed": True, "max_seqno": self._seqno}

    def close(self) -> None:
        if self.breaker is not None:
            self.breaker.release(
                self.device_bytes, label="segment", scope=self.uid
            )
        if self.translog is not None:
            self.translog.close()

    def export_tombstones(self) -> dict[str, list]:
        """{_id: [version, seqno, wall_ts]} for persistence (commit point
        and snapshot manifests): in-memory tombstone times are monotonic
        (see __init__), so the persisted form converts to wall clock —
        the only representation comparable across process restarts."""
        return {
            doc_id: [
                self._versions.get(doc_id, 1),
                self._doc_seqnos.get(doc_id, -1),
                _mono_to_wall_ts(ts),
            ]
            for doc_id, ts in self._tombstone_ts.items()
        }

    def _gc_tombstones(self) -> None:
        """Prune delete tombstones older than gc_deletes (ES gc_deletes)."""
        cutoff = time.monotonic() - self.gc_deletes_s
        expired = [
            doc_id for doc_id, ts in self._tombstone_ts.items() if ts < cutoff
        ]
        for doc_id in expired:
            del self._tombstone_ts[doc_id]
            self._versions.pop(doc_id, None)
            self._doc_seqnos.pop(doc_id, None)

    def _recover(self) -> None:
        """Load the last commit's segments (recovery-from-disk at boot,
        the engine-local slice of GatewayMetaState + store recovery)."""
        commit = store.read_commit(self.data_path)
        if commit is None:
            return
        self._seqno = commit["max_seqno"]
        self._next_seg_id = commit.get("next_seg_id", 1)
        for doc_id, (version, seqno, ts) in commit.get(
            "tombstones", {}
        ).items():
            self._versions[doc_id] = int(version)
            self._doc_seqnos[doc_id] = int(seqno)
            self._tombstone_ts[doc_id] = _wall_to_mono_ts(float(ts))
        for seg_id in commit["segments"]:
            segment, live = store.load_segment(self.data_path, seg_id)
            # _recovering makes the breaker account without rejecting:
            # committed data must load.
            self._install_segment(segment, live, seg_id=seg_id)
        self.generation += 1
        self._sync_impacts()

    def _install_segment(
        self, segment, live: np.ndarray, seg_id: int | None = None
    ) -> None:
        """Install one already-built segment: pack + handle + id/version/
        seqno map rebuild. The single implementation behind boot recovery
        and snapshot restore (they must never diverge). Caller holds the
        lock and bumps generation/impacts once after the batch."""
        deleted = np.flatnonzero(~live)
        device, nbytes = self._pack_accounted(segment, deleted=deleted)
        base = sum(h.segment.num_docs for h in self.segments)
        handle = SegmentHandle(
            segment=segment,
            device=device,
            base=base,
            live_host=live.copy(),
            seg_id=seg_id,
            nbytes=nbytes,
        )
        seg_idx = len(self.segments)
        self.segments.append(handle)
        for local, doc_id in enumerate(segment.ids):
            if live[local]:
                self._live_ids[doc_id] = (seg_idx, local)
                self._versions[doc_id] = segment.doc_version(local)
                self._doc_seqnos[doc_id] = segment.doc_seqno(local)
            self._bump_auto_id(doc_id)
        if segment.seqnos is not None and len(segment.seqnos):
            self._seqno = max(self._seqno, int(segment.seqnos.max()))
        self._stats_cache = None

    def restore_segments(
        self, segments_with_live: list[tuple[Any, np.ndarray]]
    ) -> None:
        """Append snapshot segments (restore path): install the whole
        batch, then sync impacts/generation ONCE — per-segment syncing
        would recompute device impacts O(k²) as avgdl moves. The HBM
        breaker enforces here — a restore is a NEW allocation, unlike
        recovery."""
        with self.lock:
            for segment, live in segments_with_live:
                self._install_segment(segment, live)
            self.generation += 1
            self._sync_impacts()

    def restore_shard_state(
        self, max_seqno: int, tombstones: dict[str, Any]
    ) -> None:
        """Restore shard-level op state a snapshot carries beyond segment
        rows: the seqno high-water mark (delete ops' seqnos live only in
        the translog, not in any surviving doc row) and delete tombstones
        so restored version lines continue, exactly like flush/recover."""
        with self.lock:
            self._seqno = max(self._seqno, int(max_seqno))
            for doc_id, (version, seqno, ts) in tombstones.items():
                if doc_id in self._live_ids or doc_id in self._buffer_ids:
                    continue
                self._versions[doc_id] = int(version)
                self._doc_seqnos[doc_id] = int(seqno)
                self._tombstone_ts[doc_id] = _wall_to_mono_ts(float(ts))

    def _replay_translog(self) -> None:
        """Re-apply ops above the commit's seqno (recoverFromTranslog).

        Shares the replica apply path (the ops already carry seqnos);
        write_translog=False — these ops are already IN the translog."""
        assert self.translog is not None
        replayed = False
        for op in self.translog.replay(above_seqno=self._seqno):
            replayed = True
            self._apply_external_op(op, write_translog=False)
        if replayed:
            self.refresh()

    def _bump_auto_id(self, doc_id: str) -> None:
        """Keep the auto-id counter ahead of every recovered auto id."""
        if doc_id.startswith("_auto_"):
            try:
                self._auto_id = max(self._auto_id, int(doc_id[6:]) + 1)
            except ValueError:
                pass

    def _sync_impacts(self) -> None:
        """Align every segment's precomputed impacts with shard-level stats.

        Shard-level avgdl moves as segments accumulate; impacts baked with a
        stale avgdl would silently push queries onto the slow gather path
        (or produce non-reader-level scores). Mirrors Lucene's reader-level
        CollectionStatistics being recomputed per searcher.
        """
        stats = self.field_stats()
        for handle in self.segments:
            for name, fld in handle.segment.fields.items():
                dfield = handle.device.fields[name]
                target = stats[name].avgdl if name in stats else fld.avgdl
                if (
                    dfield.tn_avgdl != float(target)
                    or dfield.tn_k1 != self.params.k1
                    or dfield.tn_b != self.params.b
                ):
                    repack_tn(dfield, fld, target, self.params.k1, self.params.b)

    @property
    def num_docs(self) -> int:
        """Live (searchable) docs, excluding the unrefreshed buffer."""
        return sum(h.live_count for h in self.segments)

    @property
    def buffered_docs(self) -> int:
        return self._buffer.num_docs

    def field_stats(self) -> dict[str, FieldStats]:
        """Shard-level BM25 statistics aggregated across segments.

        Matches Lucene's IndexReader-level TermStatistics/CollectionStatistics
        (what the reference's ContextIndexSearcher.termStatistics returns when
        no AggregatedDfs override is installed). Statistics only change on
        refresh (new segments), so the aggregate is cached per refresh.
        """
        if self._stats_cache is None:
            self._stats_cache = aggregate_field_stats(
                [h.segment for h in self.segments]
            )
        return self._stats_cache

    def compiler_for(
        self,
        handle: SegmentHandle,
        stats: dict[str, FieldStats] | None = None,
        nt_floor: int = 1,
    ) -> Compiler:
        return Compiler(
            fields=handle.device.fields,
            doc_values=handle.device.doc_values,
            mappings=self.mappings,
            params=self.params,
            stats=stats if stats is not None else self.field_stats(),
            id_index=lambda: handle.id_index,  # built only if an ids query compiles
            nested=handle.device.nested,
            percolator=handle.segment.percolator,
            nt_floor=nt_floor,
        )
