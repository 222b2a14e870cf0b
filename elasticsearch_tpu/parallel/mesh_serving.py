"""SPMD mesh serving: REST `_search` through one shard_map program.

This wires `parallel/sharded.py` into the PRODUCTION search path. A
multi-shard index whose shard count fits the device mesh serves its query
phase as a single SPMD program — per-shard scoring + local top-k, then the
coordinator reduce as `all_gather`/`psum` collectives over ICI — instead of
the host-side per-shard loop in `ShardedSearchCoordinator._scatter_merge`.
The reference's analogous path is the transport-level scatter/gather
(action/search/AbstractSearchAsyncAction.java:280) plus coordinator reduce
(action/search/SearchPhaseController.java:398); here both collapse into
collectives (SURVEY §2.3 rows 1/3).

Design:

- `MeshView` maintains a device-resident "searchable snapshot" of the
  index: one merged segment per shard (its engine's live docs, in
  host-path global-doc order), packed onto that shard's mesh device as
  [1, ...] rows that the stacked arrays are assembled from (one copy).
  The engines themselves pack on the same devices (`mesh_devices` places
  the index before its engines are built), so a chip holds only its own
  shard.
- **Delta-scaled refresh** (ROADMAP item 4): per-shard buffers are keyed
  by the engine's monotonic refresh `generation`; a search only re-merges
  shards whose generation moved. Within a changed shard, the merge is
  TOKENIZATION-FREE posting concatenation (index/merge.py): per-handle
  live-compacted pieces are cached by (handle uid, live epoch) — the
  PR-9 cache-key scheme — so only NEW or merged handles compact, and the
  concatenation itself is pure array ops (zero analysis calls,
  hook-counted via estpu_analysis_calls_total). On the device side,
  `pack_segment_delta` compares the merged host arrays against the
  previous snapshot's and re-uploads only the planes the delta actually
  touched (an append-only one-doc refresh re-uploads the written fields'
  postings + the live mask; untouched fields' tile planes are shared
  with the previous snapshot) — counted as
  estpu_mesh_field_planes_{packed,reused}_total. The global stacked
  arrays are re-assembled zero-copy from the per-shard device buffers
  with `jax.make_array_from_single_device_arrays`. Padded doc/tile
  shapes grow in pow-2 steps, so unchanged shards' buffers stay valid
  across growth-free refreshes; any shape growth or schema change
  rebuilds every shard (geometric, so amortized-incremental). KNOWN
  COST: a changed shard's merged postings still re-CONCATENATE in full
  (array I/O, not analysis) because the stacked planes interleave
  handles term-major; per-handle device subplanes would need multi-span
  term worklists in the compiler.
- **Filter-cache rows survive refresh**: mesh-path mask planes are
  cached per SHARD ROW, keyed by the shard's (handle uid, live epoch)
  signature instead of the old generation sum (which killed every
  stacked plane on any refresh). A one-shard refresh rebuilds only that
  shard's row (one single-shard mask launch); unchanged shards' rows
  keep hitting, and the [S, N] stacked plane is re-assembled zero-copy
  from the cached rows (see MeshIndex._apply_filter_cache).
- **Statistics parity**: plans compile with statistics aggregated from the
  ENGINE segments (tombstones included — Lucene keeps deleted docs in
  term stats until merge), exactly what `ShardedSearchCoordinator.
  global_stats` feeds the host path, so mesh scores are bit-identical to
  host-loop scores. Because those stats drift between shard uploads, the
  packed precomputed-impact (tn) planes may go stale; `MeshIndex.
  _tn_avgdl` reports each field's PACK-TIME scope so the query compiler
  falls back to the norm-cache gather kernel (`terms_gather`) whenever
  they don't match — the same staleness contract the engine's own
  `_sync_impacts` path uses.
- The fetch phase (source/highlight/docvalue_fields/fields) stays on the
  host against the snapshot's merged segments, mirroring the reference's
  query-then-fetch split.

One launch serves the full production request shape: sorted searches
(single numeric doc-values key, asc/desc, missing first/last, optional
trailing `_doc` tiebreak — ranked by an encoded (sort key, shard, doc)
composite and merged by in-program collectives), `search_after` cursors
(a key-range mask applied before the local top-k), aggregations in the
mesh-eligible family (metric/percentile family, fixed-edge histogram/
range with psum'd integer count planes, keyword/numeric terms,
cardinality, and the filter/global/missing nesting family), and `size:0`
agg-only / count-only requests. Requests outside the supported shape
(rescore, profile, multi-key field sorts, array-bucket aggs with metric
subs, top_hits/composite/matrix_stats/significant_terms) fall back to
the host-loop coordinator — counted by reason in
`estpu_mesh_fallback_total`, never silently. Result parity between the
two paths is asserted bit-exactly by tests/test_mesh_serving.py and the
tests/test_mesh_sorted_aggs.py fuzz suite.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field as dc_field
from typing import Any

import numpy as np

from ..index.filter_cache import mesh_cache_scope
from ..index.merge import compact_segment, concat_segments
from ..index.segment import Segment
from ..index.tiles import TILE, device_nbytes, pack_segment_delta
from ..obs.metrics import timed_launch
from ..obs.tracing import TRACER
from ..ops.bm25_device import segment_tree, supports_maxscore
from ..query.compile import (
    CompiledQuery,
    Compiler,
    FieldStats,
    SpecUnifyError,
    aggregate_field_stats,
    equalize_compiled,
)
from .sharded import (
    ShardedIndex,
    fill_union_schema,
    sharded_execute,
    sharded_execute_maxscore,
    sharded_execute_request,
    pack_plan,
    stack_maxscore,
    stack_plans,
    union_schema,
)


def _pow2(n: int, floor: int = 1) -> int:
    return 1 << max(0, max(n, floor) - 1).bit_length()


def _wait(timed, out):
    """The host blocked on one SPMD launch's outputs (`mesh.wait`): the
    launch timer's own wait when instrumented, else block here, so the
    span means the same either way."""
    import jax

    with TRACER.span("mesh.wait"):
        return jax.block_until_ready(timed.dispatched(out))


# Error classification for the serving breaker. Sticky failures are wrong-
# answer or will-never-work conditions (plan/compile bugs, parity breaks):
# retrying them risks serving bad results or paying a doomed compile per
# request forever. Transient failures are capacity/runtime conditions
# (device OOM holding the mesh copy, executor hiccups) that clear when
# pressure does.
_STICKY_ERROR_TYPES = (TypeError, ValueError, NotImplementedError, AssertionError)
_STICKY_ERROR_TOKENS = ("INVALID_ARGUMENT", "parity", "mismatch")
_TRANSIENT_ERROR_TOKENS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
    "OOM",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
)


def classify_mesh_error(e: BaseException) -> str:
    """'sticky' | 'transient' for an execute-stage mesh failure."""
    text = str(e)
    if isinstance(e, MemoryError) or any(
        tok in text for tok in _TRANSIENT_ERROR_TOKENS
    ):
        return "transient"
    if isinstance(e, _STICKY_ERROR_TYPES) or any(
        tok.lower() in text.lower() for tok in _STICKY_ERROR_TOKENS
    ):
        return "sticky"
    # Unknown runtime failures are treated as transient: a cooldown'd
    # retry is recoverable, a permanent disable is not.
    return "transient"


class MeshServingBreaker:
    """Circuit breaker for the SPMD serving path.

    closed → (threshold transient failures) → open → [cooldown] →
    half-open → closed on the first success / back to open on failure.
    Sticky failures (see classify_mesh_error) latch the breaker open for
    the life of the process — those need a code fix, not a retry. Disable
    and re-enable transitions are counted for `_nodes/stats`.
    """

    def __init__(
        self,
        failure_threshold: int | None = None,
        cooldown_s: float | None = None,
    ):
        if failure_threshold is None:
            failure_threshold = int(
                os.environ.get("ESTPU_MESH_BREAKER_FAILURES", 3)
            )
        if cooldown_s is None:
            cooldown_s = float(
                os.environ.get("ESTPU_MESH_BREAKER_COOLDOWN_S", 30.0)
            )
        self.failure_threshold = max(1, failure_threshold)
        self.cooldown_s = cooldown_s
        self.state = "closed"  # closed | open | half_open
        self.sticky = False
        self.failures = 0  # consecutive transient failures while closed
        self.opened_at = 0.0
        self.disable_events = 0
        self.reenable_events = 0
        self.last_error = ""
        self._lock = threading.Lock()

    def allow(self) -> bool:
        """May the next request try the mesh? Flips open → half-open once
        the cooldown has elapsed (that request is the trial)."""
        with self._lock:
            if self.sticky:
                return False
            if self.state == "open":
                if time.monotonic() - self.opened_at >= self.cooldown_s:
                    self.state = "half_open"
                    return True
                return False
            return True

    def is_open(self) -> bool:
        """Side-effect-free probe: is the mesh path currently not served?
        (Unlike allow(), never performs the open → half-open transition.)"""
        with self._lock:
            if self.sticky:
                return True
            return (
                self.state == "open"
                and time.monotonic() - self.opened_at < self.cooldown_s
            )

    def record_failure(self, e: BaseException) -> None:
        with self._lock:
            self.last_error = f"{type(e).__name__}: {e}"
            if classify_mesh_error(e) == "sticky":
                self.sticky = True
                if self.state != "open":
                    self.disable_events += 1
                self.state = "open"
                self.opened_at = time.monotonic()
                return
            self.failures += 1
            if self.state == "half_open" or self.failures >= self.failure_threshold:
                if self.state != "open":
                    self.disable_events += 1
                self.state = "open"
                self.opened_at = time.monotonic()
                self.failures = 0

    def record_success(self) -> None:
        with self._lock:
            self.failures = 0
            if self.state == "half_open":
                self.state = "closed"
                self.reenable_events += 1

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {
                "state": "disabled" if self.sticky else self.state,
                "sticky": self.sticky,
                "failure_threshold": self.failure_threshold,
                "cooldown_seconds": self.cooldown_s,
                "disable_events": self.disable_events,
                "reenable_events": self.reenable_events,
                "last_error": self.last_error,
            }


@dataclass
class _MeshHandle:
    """Host-side handle for a snapshot's merged shard segment (duck-typed
    for SearchService._fetch_source/_fetch_highlight/_fetch_fields, which
    only read handle.segment; the agg compile additionally reads
    handle.device, and the mesh agg merge reads handle.spans)."""

    segment: Segment
    # The packed DeviceSegment behind the stacked pytree row (same device
    # buffers, host-side field/column views for the agg planner).
    device: Any = None
    # Engine-handle boundaries inside the merged doc space: [lo, hi) per
    # original segment, in handle order. The f64-exact metric folds walk
    # these spans so their partial sums group exactly like the host loop's
    # per-segment folds (bit-identical results).
    spans: list = dc_field(default_factory=list)


@dataclass
class MeshIndex(ShardedIndex):
    """A ShardedIndex whose statistics scope is injected (engine-derived)
    and whose tn validity tracks per-shard pack time."""

    serving_stats: dict[str, FieldStats] | None = None
    pack_avgdls: list[dict[str, float]] | None = None
    # Per-shard content signatures — tuple of (handle uid, live epoch)
    # per shard — and per-shard device seg trees (each leaf the [1, ...]
    # row the stacked array is assembled from): the row-granular
    # filter-cache machinery below keys mask-plane rows on the former and
    # rebuilds a single missing row on the latter.
    shard_sigs: tuple = ()
    shard_trees: list = dc_field(default_factory=list)

    def field_stats(self) -> dict[str, FieldStats]:
        if self.serving_stats is not None:
            return self.serving_stats
        return super().field_stats()

    def _apply_filter_cache(
        self, query, compiled, record: bool = True, entries: list | None = None
    ):
        """Row-granular mesh filter cache: mask planes are cached per
        SHARD ROW, keyed on the shard's (handle uid, live epoch)
        signature — the same uid scheme the solo filter/ANN caches use —
        so a refresh of one shard invalidates ONLY that shard's row.
        The [S, N] stacked plane the kernel consumes is re-assembled
        zero-copy from the cached rows (each row already lives on its
        shard's mesh device); a missing row is rebuilt with a
        single-shard `compute_filter_mask_row` launch against that
        shard's own row of the stacked planes. Bit-exactness holds because the stacked builder
        was itself a vmap of the same per-shard mask program
        (ops/bm25_device.compute_filter_mask_stacked), gated by the
        tests/test_mesh_refresh.py fuzz. The assembled [S, N] view is
        deliberately NOT cached: it shares the rows' device buffers
        zero-copy, so a cached view would pin HBM past the rows' own
        eviction — re-assembly is a metadata-only operation paid per
        request (S row gets + one make_array call)."""
        cache = self.filter_cache
        if cache is None or not self.shard_sigs:
            return super()._apply_filter_cache(query, compiled, record, entries)
        from ..index.filter_cache import (
            apply_cached_masks,
            record_filter_usage,
        )
        from ..ops.bm25_device import compute_filter_mask_row

        if entries is None:
            entries = record_filter_usage(cache, query, record=record)
        if not entries:
            return compiled, {}
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P(self.axis))
        n_shards = self.n_shards
        npad = self.docs_per_shard
        scope = self.cache_scope

        def build(child_spec, child_arrays, norm):
            rows = []
            hit_rows = 0
            for s in range(n_shards):
                rkey = (
                    scope,
                    ("row", s, self.shard_sigs[s], npad),
                    0,
                    norm,
                )
                row = cache.get(rkey)
                if row is None:
                    arrays_s = jax.tree.map(
                        lambda x: x[s], child_arrays
                    )
                    row = compute_filter_mask_row(
                        self.shard_trees[s], child_spec, arrays_s
                    )
                    cache.put(rkey, row, int(row.nbytes))
                else:
                    hit_rows += 1
                rows.append(row)
            if hit_rows:
                cache.note_reuse(hit_rows)
            shape = (n_shards, npad)
            index_map = sharding.addressable_devices_indices_map(shape)
            ordered = [
                rows[idx[0].start if idx[0].start is not None else 0]
                for _, idx in index_map.items()
            ]
            plane = jax.make_array_from_single_device_arrays(
                shape, sharding, ordered
            )
            return plane, 0

        compiled, masks, _reused = apply_cached_masks(
            cache, (scope, 0, 0), query, compiled, build,
            const_fill=lambda: {
                "boost": np.zeros(self.n_shards, dtype=np.float32)
            },
            entries=entries,
            store_planes=False,
        )
        return compiled, masks

    def _tn_avgdl(self, shard: int, field: str, fstats) -> float:
        # The compiled spec KIND must stay shard-uniform (one shard_map
        # program): only report a valid tn scope when every shard packed
        # the field with the same avgdl; any divergence forces the gather
        # kernel everywhere.
        if not self.pack_avgdls:
            return -1.0
        vals = {d.get(field) for d in self.pack_avgdls}
        if len(vals) == 1:
            v = vals.pop()
            if v is not None:
                return float(v)
        return -1.0


@dataclass
class _Snapshot:
    """One immutable generation-consistent serving view."""

    gens: tuple
    index: MeshIndex
    handles: list[_MeshHandle]
    # The pinned engine segment handles the serving statistics came from
    # (flat, shard order): the agg planner's histogram-range scope, so
    # plan-time behavior (bucket windows, TooManyBuckets) matches the
    # host-loop coordinator exactly — tombstoned values included.
    engine_handles: list = dc_field(default_factory=list)


class MeshView:
    """Generation-consistent device mesh view of one index's shards."""

    def __init__(self, engines, mappings, params, mesh, axis: str = "shard",
                 filter_cache=None):
        self.engines = engines
        self.mappings = mappings
        self.params = params
        self.mesh = mesh
        self.axis = axis
        # index.filter_cache.FilterCache (the node's, when wired by
        # create_index): the plain-scoring serve path substitutes cached
        # [S, N] mask planes for repeated filter clauses. Planes are
        # cached per SHARD ROW keyed on (handle uid, live epoch)
        # signatures (MeshIndex._apply_filter_cache), so a refresh of one
        # shard invalidates only that shard's row; rows of unchanged
        # shards keep hitting. Stale rows/views are purged eagerly on
        # snapshot change (purge_scope).
        self.filter_cache = filter_cache
        self._lock = threading.Lock()
        self._snap: _Snapshot | None = None
        # Per-shard cache reused across refreshes.
        n = len(engines)
        self._host_segs: list[Segment | None] = [None] * n
        # Per-handle live-compacted pieces, keyed (handle uid, live
        # epoch): a refresh re-compacts only handles whose key is new
        # (fresh segment, merge output, or a live-mask sync); unchanged
        # handles reuse their piece — the host-side half of delta
        # scaling. Pruned to the engines' live handle set every refresh.
        self._pieces: dict[tuple[int, int], Segment] = {}
        # Per-shard content signature: tuple of (uid, live_epoch) in
        # handle order — the filter-cache row key component and the
        # skip-repack check (a generation bump that leaves a shard's
        # signature unchanged needs no re-merge).
        self._shard_sig: list[tuple | None] = [None] * n
        # Union-schema-filled copies actually packed (what snapshots see).
        self._filled_segs: list[Segment | None] = [None] * n
        # Packed DeviceSegments, every device array a [1, ...] shard row,
        # and their jit-input pytrees: the stacked arrays are assembled
        # from these very buffers, so the mesh holds one copy of a shard.
        self._trees: list[Any] = [None] * n
        self._devs: list[Any] = [None] * n
        self._spans: list[list] = [[] for _ in range(n)]  # handle spans
        self._pack_avgdl: list[dict[str, float]] = [{} for _ in range(n)]
        self._shapes: dict[str, Any] | None = None  # current padded shapes
        # Test/observability hooks.
        self.served = 0  # searches answered by the SPMD program
        self.packs = 0  # shard pack+upload operations performed
        self.seg_reuses = 0  # shard buffers reused across refreshes
        self.rebuilds = 0  # full (all-shard) rebuilds
        # Fallback accounting: every serve() decline is counted by reason
        # (never silent) — mirrored on the metrics registry as
        # estpu_mesh_fallback_total{reason} and surfaced in `_nodes/stats`
        # under mesh_serving; the coordinator tags the request span with
        # last_fallback_reason.
        self.fallbacks: dict[str, int] = {}
        self.last_fallback_reason: str | None = None
        # obs.MetricsRegistry (the node's, when wired by create_index);
        # standalone views get a private registry.
        from ..obs.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()
        # Resilience: execute-stage failures route requests back to the
        # host-loop path through a circuit breaker — transient failures
        # (device OOM under the mesh copy) half-open after a cooldown and
        # re-enable on the first success; sticky failures (compile/parity
        # bugs) stay off for the life of the process.
        self.exec_failures = 0  # lifetime count, for _nodes/stats
        self.breaker = MeshServingBreaker()
        # exec.ExecPlanner (set by the node): SPMD servings are recorded
        # as "mesh_spmd" decisions with their observed latency, so the
        # node-wide cost model and `_nodes/stats` counters see this
        # backend's traffic alongside device/blockmax/oracle.
        self.planner = None
        # obs.DeviceInstruments + obs.device.HbmLedger (set by the node):
        # per-launch timing for the one-launch SPMD program, and ledger
        # registration of the mesh snapshot's device bytes under label
        # "mesh_plane" scoped to this index's mesh cache scope.
        self.device = None
        self.ledger = None
        self.plane_bytes = 0  # current snapshot's registered device bytes
        self._shard_plane_bytes = [0] * n  # the same, by shard (its chip)

    @property
    def disabled(self) -> bool:
        """Back-compat view of the breaker: True while the SPMD path is
        not being tried (sticky-latched or cooling down)."""
        return self.breaker.is_open()

    # ------------------------------------------------------------- refresh

    def _merged_segment(self, handles: list) -> tuple[Segment, list]:
        """One segment of the shard's device-visible live docs, in host-path
        order (segment handles in order, local ids ascending) so equal-score
        tie-breaks match the coordinator merge exactly. Also returns the
        [lo, hi) span each engine handle occupies in the merged doc space
        (the f64-exact agg folds group by these).

        Tokenization-free: each handle contributes a live-compacted PIECE
        (index/merge.compact_segment — a flatnonzero gather, cached by
        (uid, live epoch) so only new/changed handles compact) and the
        pieces concatenate as pure array ops (concat_segments). No
        document is re-analyzed — the SegmentBuilder re-add loop this
        replaces re-tokenized the whole shard on every one-doc refresh."""
        pieces: list[Segment] = []
        spans: list[tuple[int, int]] = []
        base = 0
        for handle in handles:
            key = (handle.uid, handle.live_epoch)
            piece = self._pieces.get(key)
            if piece is None:
                # The mask the device kernels currently serve — NOT
                # live_host, which may carry deletes that only become
                # searchable at the next refresh (generation bump) on the
                # host path too.
                live = np.asarray(handle.device.live)[
                    : handle.segment.num_docs
                ]
                piece = compact_segment(handle.segment, live)
                self._pieces[key] = piece
            pieces.append(piece)
            spans.append((base, base + piece.num_docs))
            base += piece.num_docs
        return concat_segments(pieces), spans

    def _schema(self, segs: list[Segment]) -> dict[str, Any]:
        """Union schema + pow-2 padded shapes covering every shard."""
        fields, dv, vec = union_schema(segs)
        docs = 1
        tiles: dict[str, int] = {}
        pos_tiles: dict[str, int] = {}
        for seg in segs:
            docs = max(docs, seg.num_docs)
        for seg in segs:
            for name, has_norms in fields.items():
                f = seg.fields.get(name)
                postings = len(f.doc_ids) if f is not None else 0
                tiles[name] = max(
                    tiles.get(name, 0), _pow2(postings // TILE + 2)
                )
                if has_norms:
                    npos = (
                        len(f.positions)
                        if f is not None and f.positions is not None
                        else 0
                    )
                    pos_tiles[name] = max(
                        pos_tiles.get(name, 0), _pow2(npos // TILE + 2)
                    )
        return {
            "fields": fields,
            "dv": dv,
            "vec": vec,
            "docs": _pow2(docs),
            "tiles": tiles,
            "pos_tiles": pos_tiles,
        }

    @staticmethod
    def _shapes_fit(old: dict[str, Any] | None, new: dict[str, Any]) -> bool:
        """True when buffers packed under `old` remain stackable with
        shards packed under shapes covering `new` (schema identical, no
        padded dimension grew)."""
        if old is None:
            return False
        if (
            old["fields"] != new["fields"]
            or old["dv"] != new["dv"]
            or old["vec"] != new["vec"]
        ):
            return False
        if new["docs"] > old["docs"]:
            return False
        for name, t in new["tiles"].items():
            if t > old["tiles"].get(name, 0):
                return False
        for name, t in new["pos_tiles"].items():
            if t > old["pos_tiles"].get(name, 0):
                return False
        return True

    def _shard_device(self, shard: int):
        return self.mesh.devices.reshape(-1)[shard]

    def _pack_shard(self, shard: int, seg: Segment, shapes: dict[str, Any],
                    stats: dict[str, FieldStats],
                    delta_ok: bool = False):
        """Pack one shard's merged segment onto its mesh device, every
        plane uploaded as a [1, ...] row for the global-array assembly (no
        second, stacked copy). Returns
        (tree, filled segment, pack avgdls) — the caller commits them into
        the per-shard caches only once EVERY shard packed, so a mid-rebuild
        failure can't leave mixed-shape buffers behind.

        `delta_ok` (padded shapes unchanged) enables plane-level upload
        skipping: pack_segment_delta compares the merged host arrays
        against the previous snapshot's filled segment and reuses every
        device plane the delta didn't touch — the device half of the
        delta-scaled refresh, counted as
        estpu_mesh_field_planes_{packed,reused}_total.

        The union-schema fill COPIES the segment (fill_union_schema):
        `seg` stays pristine in the per-shard cache, and segments held by a
        previous, still-serving snapshot are never mutated under a
        concurrent compile."""
        device = self._shard_device(shard)
        seg = fill_union_schema(
            seg, shapes["fields"], shapes["dv"], shapes["vec"]
        )
        avgdl = {
            name: (stats[name].avgdl if name in stats else 1.0)
            for name in shapes["fields"]
        }
        prev_seg = self._filled_segs[shard] if delta_ok else None
        prev_dev = self._devs[shard] if delta_ok else None
        dev, reused, packed = pack_segment_delta(
            seg,
            prev_seg,
            prev_dev,
            device=device,
            pad_docs_to=shapes["docs"],
            field_min_tiles=shapes["tiles"],
            field_avgdl=avgdl,
            k1=self.params.k1,
            b=self.params.b,
            field_pos_min_tiles=shapes["pos_tiles"],
            stack_axis=True,
        )
        if reused or packed:
            self.metrics.counter(
                "estpu_mesh_field_planes_reused_total",
                "Mesh refresh device planes shared with the previous "
                "snapshot (upload skipped: host arrays byte-identical)",
            ).inc(reused)
            self.metrics.counter(
                "estpu_mesh_field_planes_packed_total",
                "Mesh refresh device planes re-packed and re-uploaded",
            ).inc(packed)
        # agg_segment_tree = segment_tree + keyword ordinal planes: the
        # one stacked pytree serves both the scoring kernels and the
        # in-program aggregation planes.
        from ..ops.aggs_device import agg_segment_tree

        return agg_segment_tree(dev), seg, avgdl, dev

    def _assemble(self) -> Any:
        """Zero-copy global stacked pytree from the per-shard buffers."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        flats = []
        treedef = None
        for tree in self._trees:
            leaves, treedef = jax.tree.flatten(tree)
            flats.append(leaves)
        sharding = NamedSharding(self.mesh, P(self.axis))
        n = len(flats)
        out_leaves = []
        for li in range(len(flats[0])):
            per_shard = [flats[s][li] for s in range(n)]
            shape = (n,) + tuple(per_shard[0].shape[1:])
            index_map = sharding.addressable_devices_indices_map(shape)
            arrays = [
                per_shard[idx[0].start if idx[0].start is not None else 0]
                for _, idx in index_map.items()
            ]
            out_leaves.append(
                jax.make_array_from_single_device_arrays(
                    shape, sharding, arrays
                )
            )
        return jax.tree.unflatten(treedef, out_leaves)

    def _pin_engines(self) -> tuple[tuple, list[list]]:
        """(generations, per-engine segment-handle lists) read atomically
        per engine under its lock, so generation and handle list can never
        disagree — merged device data, recorded generations, and serving
        statistics all derive from this one pinned view."""
        gens = []
        pinned = []
        for e in self.engines:
            with e.lock:
                gens.append(e.generation)
                pinned.append(list(e.segments))
        return tuple(gens), pinned

    def _ensure(self) -> _Snapshot:
        """Refresh the mesh view to the engines' current generations."""
        snap = self._snap
        if snap is not None and snap.gens == tuple(
            e.generation for e in self.engines
        ):
            return snap
        with self._lock:
            gens, pinned = self._pin_engines()
            snap = self._snap
            if snap is not None and snap.gens == gens:
                return snap
            n = len(self.engines)
            # Content signatures: a generation bump whose shard signature
            # is unchanged (e.g. another shard's write) needs no re-merge.
            sigs = [
                tuple((h.uid, h.live_epoch) for h in pinned[i])
                for i in range(n)
            ]
            changed = [
                i for i in range(n)
                if self._shard_sig[i] != sigs[i]
                or self._host_segs[i] is None
            ]
            merged = {
                i: s for i, s in enumerate(self._host_segs) if s is not None
            }
            spans = {i: self._spans[i] for i in merged}
            for i in changed:
                merged[i], spans[i] = self._merged_segment(pinned[i])
            # Prune compaction pieces of handles no longer serving
            # (merged away, dropped): keyed (uid, live_epoch) like the
            # filter/ANN cache entries they mirror.
            live_keys = {
                (h.uid, h.live_epoch)
                for handles in pinned
                for h in handles
            }
            self._pieces = {
                k: v for k, v in self._pieces.items() if k in live_keys
            }
            new_shapes = self._schema([merged[i] for i in sorted(merged)])
            # Serving statistics: the ENGINE view (tombstones included),
            # computed from the same pinned handle lists the merges came
            # from — identical to ShardedSearchCoordinator.global_stats at
            # these generations, and device data and statistics can never
            # mix generations.
            stats = aggregate_field_stats(
                [h.segment for handles in pinned for h in handles]
            )
            if self._shapes_fit(self._shapes, new_shapes):
                shapes = self._shapes
                to_pack = changed
                delta_ok = True
            else:
                shapes = new_shapes
                to_pack = list(range(n))
                delta_ok = False
            # Stage every pack, then commit atomically: a failure here
            # leaves all per-shard caches untouched (old snapshot keeps
            # serving; the gen mismatch retries the refresh next search).
            # Shards pack side by side, each onto its own device: the
            # host work is numpy that releases the interpreter lock.
            with ThreadPoolExecutor(max(1, len(to_pack))) as pool:
                packed = dict(zip(to_pack, pool.map(
                    lambda i: self._pack_shard(
                        i, merged[i], shapes, stats, delta_ok=delta_ok
                    ),
                    to_pack,
                )))
            if shapes is not self._shapes:
                self._shapes = shapes
                self.rebuilds += 1
            for i in changed:
                self._host_segs[i] = merged[i]
                self._spans[i] = spans[i]
            for i, (tree, filled, avgdl, dev) in packed.items():
                self._trees[i] = tree
                self._filled_segs[i] = filled
                self._pack_avgdl[i] = avgdl
                self._devs[i] = dev
                self.packs += 1
            self.seg_reuses += n - len(to_pack)
            self.metrics.counter(
                "estpu_mesh_segments_packed_total",
                "Mesh refresh shard segments re-merged and re-packed",
            ).inc(len(to_pack))
            self.metrics.counter(
                "estpu_mesh_segments_reused_total",
                "Mesh refresh shard segments served from unchanged "
                "buffers (no re-merge, no re-upload)",
            ).inc(n - len(to_pack))
            self._shard_sig = list(sigs)
            scope = mesh_cache_scope(self.engines)
            docs_pad = self._shapes["docs"]
            if self.filter_cache is not None:
                # Eager purge of mask rows no refresh can serve again —
                # dead signatures free their HBM now instead of waiting
                # for LRU. Live rows (unchanged shards) survive: that is
                # the delta-scaled cache-survival contract.
                keep = {
                    ("row", s, sigs[s], docs_pad) for s in range(n)
                }
                self.filter_cache.purge_scope(scope, keep)
            # HBM ledger: this snapshot's resident device bytes on each
            # shard's chip (shared delta-reused planes count once —
            # device_nbytes walks the CURRENT views). The registration
            # swaps atomically with the snapshot commit; the
            # consistency-law twin is plane_bytes.
            by_shard = [
                device_nbytes(d) if d is not None else 0 for d in self._devs
            ]
            if self.ledger is not None:
                # Register BEFORE releasing the previous snapshot's
                # bytes: both snapshots coexist across the swap (delta
                # reuse aside), and the high watermark must see it.
                for i, nb in enumerate(by_shard):
                    self.ledger.register(
                        "mesh_plane", scope, nb, device=self._shard_device(i)
                    )
                self._release_plane_bytes(scope)
            self._shard_plane_bytes = by_shard
            self.plane_bytes = sum(by_shard)
            segments = [s for s in self._filled_segs]
            index = MeshIndex(
                mesh=self.mesh,
                axis=self.axis,
                mappings=self.mappings,
                segments=segments,
                seg_stacked=self._assemble(),
                docs_per_shard=docs_pad,
                params=self.params,
                serving_stats=stats,
                pack_avgdls=list(self._pack_avgdl),
                filter_cache=self.filter_cache,
                cache_scope=scope,
                cache_generation=sum(gens),
                shard_sigs=tuple(sigs),
                shard_trees=list(self._trees),
            )
            self._snap = _Snapshot(
                gens=gens,
                index=index,
                handles=[
                    _MeshHandle(s, device=self._devs[i], spans=self._spans[i])
                    for i, s in enumerate(segments)
                ],
                engine_handles=[h for handles in pinned for h in handles],
            )
            return self._snap

    def _release_plane_bytes(self, scope) -> None:
        for i, nb in enumerate(self._shard_plane_bytes):
            self.ledger.release(
                "mesh_plane", scope, nb, device=self._shard_device(i)
            )

    def release_ledger(self) -> None:
        """Release this view's mesh-plane ledger registration (index
        deletion: the snapshot's device arrays die with the view)."""
        if self.ledger is not None and self.plane_bytes:
            self._release_plane_bytes(mesh_cache_scope(self.engines))
        self._shard_plane_bytes = [0] * len(self.engines)
        self.plane_bytes = 0

    # -------------------------------------------------------------- serve

    def _fallback(self, reason: str):
        """Count (never silently drop) one serve() decline and return the
        None the coordinator interprets as host-loop fallback. The reason
        is attached to the ENCLOSING mesh.serve span as an event from this
        thread's own trace context (race-free under concurrent searches);
        last_fallback_reason is a single-threaded test/diagnostic hook."""
        self.last_fallback_reason = reason
        with self._lock:
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1
        self.metrics.counter(
            "estpu_mesh_fallback_total",
            "SPMD mesh fallbacks to the host-loop coordinator by reason",
            reason=reason,
        ).inc()
        TRACER.event("mesh.fallback", reason=reason)
        return None

    @staticmethod
    def ineligible_reason(request) -> str | None:
        """Shape-level reason this request cannot serve on the SPMD path
        (None = eligible). Context-free — mapping/plan-level declines
        (unsortable field, non-uniform compile) surface inside serve()."""
        from ..search.aggs import mesh_agg_ineligible_reason
        from ..search.service import normalized_sort

        if request.rescore or request.profile:
            return "ineligible_shape"
        if getattr(request, "knn", None) is not None:
            # kNN serves through the host loop's ANN/exact kernels; the
            # stacked-shard SPMD program has no vector planes yet.
            return "knn"
        if request.after_doc >= 0:
            # Engine-global doc cursors (scroll internals) address the
            # host path's doc space, not the mesh's.
            return "ineligible_shape"
        if request.sort is not None:
            keys = normalized_sort(request)
            if len(keys) != 1:
                # Multi-key field sorts lexsort on the host path.
                return "sort_shape"
            fname, desc, _mf = keys[0]
            if fname == "_score" and not desc:
                return "sort_shape"  # bottom-k: host execute_score_asc
        if request.aggs is not None:
            reason = mesh_agg_ineligible_reason(request.aggs)
            if reason is not None:
                return reason
        return None

    @classmethod
    def eligible(cls, request) -> bool:
        """Request shapes the SPMD query phase covers; everything else
        falls back to the host-loop coordinator. Sorted searches
        (single numeric key, asc/desc, missing first/last, optional _doc
        tiebreak), aggregations in the mesh-eligible family, search_after
        cursors and size:0 agg-only/count requests are all served."""
        return cls.ineligible_reason(request) is None

    def _sort_plan(self, request):
        """(sort_field, desc, missing_first, want_sort_values) for the
        kernel, or an ineligibility reason string. sort_field None =
        score-ordered."""
        from ..search.service import normalized_sort

        if request.sort is None:
            return (None, False, False, False)
        ((fname, desc, mfirst),) = normalized_sort(request)
        if fname == "_score":
            return (None, False, False, True)
        fm = self.mappings.get(fname)
        if fm is None or not fm.is_numeric:
            return "sort_shape"  # host path raises the 400 verbatim
        return (fname, desc, mfirst, True)

    def _compile_aggs(self, coordinator, snap, request):
        """(Aggregator, specs tuple, stacked arrays) for the request's agg
        tree, compiled shard-uniform across the mesh. Raises ValueError
        when per-shard lowering diverges (non-uniform filter plans)."""
        from ..search.aggs import Aggregator, _pow2 as agg_pow2

        idx = snap.index
        term_fields: set[str] = set()

        def collect(nodes):
            for n in nodes:
                if n.kind in ("terms", "rare_terms", "cardinality"):
                    f = n.params.get("field")
                    if f:
                        term_fields.add(f)
                collect(n.subs)

        collect(request.aggs)
        term_pads: dict[str, int] = {}
        for f in term_fields:
            widths = [
                h.device.fields[f].num_terms
                for h in snap.handles
                if h.device is not None and f in h.device.fields
            ]
            if widths:
                term_pads[f] = agg_pow2(max(widths))
        agg = Aggregator(
            self.engines[0],
            request.aggs,
            handles=snap.handles,
            index_name=coordinator.index_name,
            term_pads=term_pads,
            range_handles=snap.engine_handles,
        )
        # Keep EVERY shard row (the stacked program is mesh-wide); the
        # default constructor filter drops empty merged shards.
        agg.handles = list(snap.handles)
        import jax

        per_shard = [
            agg.compile_for(snap.handles[s], idx.shard_compiler(s))
            for s in range(len(snap.handles))
        ]
        specs = {s for s, _ in per_shard}
        if len(specs) != 1:
            raise ValueError(
                "aggregation plans did not lower shard-uniform"
            )
        arrays = jax.tree.map(
            lambda *xs: np.stack(xs), *[a for _, a in per_shard]
        )
        return agg, per_shard[0][0], arrays

    def _shard_compiler(self, snap: _Snapshot, shard: int) -> Compiler:
        """The planner of one shard's packed planes under the snapshot's
        pushed-down statistics, as an engine plans its own segment
        (Engine.compiler_for): MaxScore reads the host-side per-tile
        impact maxima and span extents the pack keeps."""
        dev = snap.handles[shard].device
        return Compiler(
            fields=dev.fields,
            doc_values=dev.doc_values,
            mappings=self.mappings,
            params=self.params,
            stats=snap.index.field_stats(),
        )

    def _compile_maxscore(self, snap: _Snapshot, query, threshold: int):
        """The stacked plan of MaxScore traffic (query/compile.Compiler.
        compile_maxscore on every shard, `stack_maxscore`), or None for any
        other query, whose plan `ShardedIndex.compile` makes as before.
        Where any shard's split declines, every shard takes the exhaustive
        plan, so the launch stays one program."""
        n = len(self.engines)
        plans = [
            self._shard_compiler(snap, i).compile_maxscore(query, threshold)
            for i in range(n)
        ]
        if any(p is None for p in plans):
            return None
        if not all(supports_maxscore(p.spec) for p in plans):
            if self.device is not None:
                self.device.maxscore("bypass")
            return self._compile_exhaustive(snap, query)
        return stack_maxscore(plans)

    def _compile_exhaustive(self, snap: _Snapshot, query):
        """The stacked exhaustive plan of MaxScore traffic (Compiler.
        compile_exhaustive on every shard): for a declined split and for
        a MaxScore launch whose check failed. Its few shapes are shared
        by all such queries."""
        plans = [
            self._shard_compiler(snap, i).compile_exhaustive(query)
            for i in range(len(self.engines))
        ]
        try:
            plans = equalize_compiled(plans)
        except SpecUnifyError:
            return snap.index.compile(query)
        return CompiledQuery(
            spec=plans[0].spec, arrays=stack_plans([p.arrays for p in plans])
        )

    def _serve_maxscore(self, idx, compiled, k: int):
        """(scores, global ids, total) of one MaxScore launch, as host
        arrays, or None when its threshold check failed and the exhaustive
        plan must answer. Outcomes count on estpu_sparse_maxscore_total."""
        import jax

        with timed_launch(
            self.device, "mesh_spmd", (compiled.spec, k, None, False, "plain"),
            "mesh_spmd",
        ) as tl:
            with TRACER.span("mesh.dispatch"):
                buf, layout = pack_plan(compiled.arrays)
                out = sharded_execute_maxscore(
                    idx.mesh, idx.axis, idx.seg_stacked, buf, compiled.spec, k,
                    idx.docs_per_shard, layout,
                )
            out = _wait(tl, out)
        with TRACER.span("mesh.fetch"):
            scores, gids, total, ok = jax.device_get(out)
        ok = bool(ok)
        if self.device is not None:
            self.device.maxscore("served" if ok else "fallback")
        return (scores, gids, total) if ok else None

    def _serve_plain(self, idx, request, compiled, k: int, fc_entries):
        """(scores, global ids, total) of the plain-score launch."""
        # The hot plain-score path keeps the candidate-centric sparse
        # kernel (no dense planes, no agg planes). Filter cache: repeated
        # filter clauses swap in their cached [S, N] mask planes
        # (bit-identical by construction — gated by
        # tests/test_filter_cache.py's mesh fuzz); the sorted/agg
        # one-launch program still recomputes filters (honest residue,
        # ROADMAP item 3).
        seg = idx.seg_stacked
        if idx.filter_cache is not None:
            # record=False: the coordinator already counted this
            # request's sighting; recording here too would double-count
            # whenever execution fails and the request falls back to the
            # host loop. Its collected entries ride along so the AST
            # isn't re-walked.
            with TRACER.span("mesh.compile"):
                compiled, fc_masks = idx._apply_filter_cache(
                    request.query, compiled, record=False, entries=fc_entries,
                )
            if fc_masks:
                seg = {**idx.seg_stacked, "masks": fc_masks}
        with timed_launch(
            self.device, "mesh_spmd", (compiled.spec, k, None, False, "plain"),
            "mesh_spmd",
        ) as tl:
            with TRACER.span("mesh.dispatch"):
                out = sharded_execute(
                    idx.mesh, idx.axis, seg, compiled.arrays, compiled.spec, k,
                    idx.docs_per_shard,
                )
            return _wait(tl, out)

    def serve(self, coordinator, request, task=None, fc_entries=None):
        """Answer a SearchRequest via ONE SPMD program — scoring, sorted or
        score-ordered top-k with search_after masking, psum'd totals, and
        the aggregation planes all inside a single shard_map launch — or
        None (with the fallback counted by reason) to make the coordinator
        fall back to the host-loop path."""
        from ..search.aggs import new_merge_state
        from ..search.service import SearchHit, SearchResponse, clamp_total

        reason = self.ineligible_reason(request)
        if reason is not None:
            return self._fallback(reason)
        if not self.breaker.allow():
            return self._fallback("breaker")
        if any(
            h.segment.nested for e in self.engines for h in e.segments
        ):
            # Nested blocks are not mesh-stackable yet; without this guard
            # the mesh compiler (which has no nested context) would lower
            # nested queries to match_none and serve wrong results.
            return self._fallback("nested")
        sort_plan = self._sort_plan(request)
        if isinstance(sort_plan, str):
            return self._fallback(sort_plan)
        sort_field, sort_desc, missing_first, want_sort_values = sort_plan
        k = max(0, request.from_) + max(0, request.size)
        # A plain score-sorted top-k with an integer track_total_hits may
        # take the MaxScore plan (`_compile_maxscore`).
        maxscore_threshold = None
        if (
            sort_field is None
            and request.search_after is None
            and request.aggs is None
            and not want_sort_values
            and k > 0
            and not isinstance(request.track_total_hits, bool)
        ):
            maxscore_threshold = int(request.track_total_hits)
        start = time.monotonic()
        with TRACER.span("mesh.refresh"):
            snap = self._ensure()
        idx = snap.index
        agg = None
        aggs_spec = None
        aggs_arrays = ()
        with TRACER.span("mesh.compile"):
            try:
                compiled = None
                if maxscore_threshold is not None:
                    compiled = self._compile_maxscore(
                        snap, request.query, maxscore_threshold
                    )
                if compiled is None:
                    compiled = idx.compile(request.query)
                if request.aggs is not None:
                    agg, aggs_spec, aggs_arrays = self._compile_aggs(
                        coordinator, snap, request
                    )
            # staticcheck: ignore[broad-except] compile fallback: non-shard-uniform plans route to the host loop, which re-raises user-facing query and agg validation errors identically
            except Exception:
                # Plans (query or aggregations) the mesh can't make
                # shard-uniform fall back; user-facing validation errors
                # re-raise identically from the host path.
                return self._fallback("non_uniform_plan")
        if sort_field is not None and k > 0 and sort_field not in (
            idx.segments[0].doc_values if idx.segments else {}
        ):
            # Mapped numeric field no document carries: the host path's
            # missing-column branch owns that shape.
            return self._fallback("sort_shape")
        # search_after cursor, in the kernel's transformed ascending key
        # space; public cursors are key-only, so the global doc tiebreak
        # is pushed past every shard (ties never qualify).
        has_after = request.search_after is not None
        after_key = np.float32(0.0)
        after_doc = len(self.engines) * idx.docs_per_shard
        if has_after:
            raw = request.search_after[0]
            fmax = np.float32(np.finfo(np.float32).max)
            if sort_field is None:
                if raw is None or not isinstance(raw, (int, float)):
                    return self._fallback("ineligible_shape")
                after_key = np.float32(raw)
            elif raw is None:
                after_key = -fmax if missing_first else fmax
            else:
                after_key = np.float32(raw)
                if sort_desc:
                    after_key = np.float32(-after_key)
        if task is not None:
            task.raise_if_cancelled()
        plain = (
            sort_field is None
            and not has_after
            and aggs_spec is None
            and not want_sort_values
            and k > 0
        )
        fetch = contextlib.ExitStack()
        try:
            if plain:
                # MaxScore traffic launches its MaxScore plan first; a
                # failed check re-plans exhaustively, still on the mesh.
                answer = None
                if supports_maxscore(compiled.spec):
                    answer = self._serve_maxscore(idx, compiled, k)
                    if answer is None:
                        with TRACER.span("mesh.compile"):
                            compiled = self._compile_exhaustive(
                                snap, request.query
                            )
                if answer is None:
                    answer = self._serve_plain(
                        idx, request, compiled, k, fc_entries
                    )
                scores, gids, total = answer
                keys = vals = None
                n_after = total
                agg_out = ()
            else:
                with timed_launch(
                    self.device,
                    "mesh_spmd",
                    (
                        compiled.spec, k, sort_field, sort_desc,
                        missing_first, has_after, aggs_spec,
                    ),
                    "mesh_spmd",
                ) as tl:
                    with TRACER.span("mesh.dispatch"):
                        out = sharded_execute_request(
                            idx.mesh,
                            idx.axis,
                            idx.seg_stacked,
                            compiled.arrays,
                            compiled.spec,
                            k,
                            idx.docs_per_shard,
                            sort_field=sort_field,
                            sort_desc=sort_desc,
                            missing_first=missing_first,
                            has_after=has_after,
                            after_key=after_key,
                            after_doc=after_doc,
                            aggs_spec=aggs_spec,
                            aggs_arrays_stacked=aggs_arrays,
                        )
                    keys, vals, gids, total, n_after, agg_out = _wait(tl, out)
                scores = vals
            import jax

            # From here to the response: what the host does with the
            # program's outputs (device_get, hits, the response).
            fetch.enter_context(TRACER.span("mesh.fetch"))
            # One batched transfer of every output.
            scores, gids, total, n_after, agg_np = jax.device_get(
                (scores, gids, total, n_after, agg_out)
            )
            total = int(total)
            n_after = int(n_after)
        # staticcheck: ignore[broad-except] execute failures (incl. injected ones) must feed the mesh circuit breaker and fall back — the breaker's error classification is the tested behavior
        except Exception as e:
            # Execute-stage failure (XLA lowering, device OOM holding the
            # mesh copy): fall back to the host loop and feed the breaker —
            # transient failures re-enable after a cooldown'd success,
            # sticky (compile/parity) failures latch off for good.
            fetch.close()
            self.exec_failures += 1
            self.breaker.record_failure(e)
            return self._fallback("execute_error")
        with fetch:
            self.breaker.record_success()
            self.served += 1
            shape = "plain" if sort_field is None else "sorted"
            if aggs_spec is not None:
                shape = shape + "_aggs" if k > 0 else "aggs_only"
            elif k == 0:
                shape = "count_only"
            self.metrics.counter(
                "estpu_mesh_served_total",
                "Searches served by the one-launch SPMD program, by shape",
                shape=shape,
            ).inc()
            if self.planner is not None:
                self.planner.record(
                    ("mesh", compiled.spec, k, sort_field, aggs_spec is not None),
                    "mesh_spmd",
                    time.monotonic() - start,
                )
            aggregations = None
            if agg is not None:
                from ..search.aggs import merge_mesh_result

                states = [new_merge_state(n) for n in request.aggs]
                for node, state, res in zip(request.aggs, states, agg_np):
                    merge_mesh_result(node, state, res, snap.handles)
                aggregations = agg.render_states(states)
            timed_out = bool(task is not None and task.check_deadline())
            limit = n_after if has_after else total
            n = min(k, limit, len(gids))
            max_score = None
            if request.sort is None and scores is not None and n > 0:
                max_score = float(scores[0])
            hits = []
            svc = coordinator.services[0]
            for rank in range(max(0, request.from_), n):
                shard, local = idx.locate(int(gids[rank]))
                handle = snap.handles[shard]
                score = None
                sort_out = None
                if sort_field is not None:
                    raw = float(scores[rank])
                    sort_out = [None if np.isnan(scores[rank]) else raw]
                else:
                    score = float(scores[rank])
                    if want_sort_values:
                        sort_out = [score]
                hits.append(
                    SearchHit(
                        doc_id=handle.segment.ids[local],
                        score=score,
                        source=svc._fetch_source(handle, local, request),
                        sort=sort_out,
                        global_doc=-1,
                        handle=handle,
                        local=local,
                    )
                )
            coordinator._apply_fetch_subphases(request, hits)
            total_out, relation = clamp_total(total, request.track_total_hits)
            return SearchResponse(
                took_ms=int((time.monotonic() - start) * 1000),
                total=total_out,
                total_relation=relation,
                max_score=max_score,
                hits=hits,
                aggregations=aggregations,
                shards=len(self.engines),
                timed_out=timed_out,
            )


def mesh_devices(n_shards: int) -> list | None:
    """The devices an index of `n_shards` is served on, shard i on the
    i-th, when SPMD serving can work here: >1 shard, enough local devices
    for one shard per device, and not disabled via ESTPU_MESH_SERVING=0.
    None means the host loop over engines on the default device. Decided
    before the engines are built, so each packs its planes on its own
    shard's device."""
    if n_shards < 2:
        return None
    if os.environ.get("ESTPU_MESH_SERVING", "1") == "0":
        return None
    try:
        import jax

        devices = jax.devices()
    # staticcheck: ignore[broad-except] device-probe guard: no usable mesh means host-loop serving, not an error
    except Exception:
        return None
    if len(devices) < n_shards:
        return None
    return list(devices[:n_shards])


def maybe_mesh_view(
    engines, mappings, params, devices, filter_cache=None
) -> MeshView | None:
    """A MeshView of the engines over `devices` (`mesh_devices`, shard i
    on the i-th), or None when there are none: the host loop serves."""
    if devices is None:
        return None
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices), ("shard",))
    return MeshView(engines, mappings, params, mesh, filter_cache=filter_cache)
