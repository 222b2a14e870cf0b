"""Sharded search over a device mesh: the cluster, in one XLA program.

The reference scales search by scattering per-shard QUERY requests over TCP
and reducing on a coordinator (AbstractSearchAsyncAction.java:280 fan-out;
SearchPhaseController.java:398 reduce; QueryPhaseResultConsumer incremental
merge). Here the entire scatter-gather collapses into a single SPMD program:

- every shard's tiled postings live on its own device (leading `shard` mesh
  axis, `jax.sharding.NamedSharding`);
- one `shard_map` program scores all shards simultaneously, takes each
  shard's local top-k, and merges via `jax.lax.all_gather` over the ICI —
  the coordinator reduce becomes a collective;
- total-hit counts reduce with `psum`.

Global term statistics: per-shard IDF would make scores depend on routing
(the reference has the same artifact and fixes it with the DFS phase,
search/dfs/DfsPhase.java:31). `ShardedIndex.field_stats` aggregates
statistics across shards at plan time — the DFS phase equivalent, free on
the host because the coordinator owns all term dictionaries here.

Tie-breaking: the merged flat top-k favors lower (shard, local-rank) on
equal scores, which is exactly (shard index, doc id) order — the same
contract as the reference's mergeTopDocs shard-order tie-break.

Doc addressing: global doc = shard * padded_size + local, reversible on the
host for the fetch phase (`locate`).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field as dc_field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.mapping import Mappings
from ..index.segment import FieldIndex, Segment, SegmentBuilder
from ..index.tiles import TILE, pack_segment, tile_doc_bounds
from ..obs.metrics import timed_launch
from ..ops.bm25 import BM25Params
from ..ops.bm25_device import (
    NEG_INF,
    _eval_node,
    _maxscore_inner,
    _sparse_inner,
    segment_tree,
    supports_sparse,
)
from ..query.compile import (
    CompiledQuery,
    Compiler,
    FieldStats,
    SpecUnifyError,
    aggregate_field_stats,
    equalize_compiled,
    pad_arrays_to_spec,
    unify_specs,
)
from ..query.dsl import Query
from .routing import shard_for_id

# `jax.named_scope` of the cross-shard merge (all-gather, top-k, psum): a
# profiler trace carries it in each device op's name metadata.
SCOPE_REDUCE = "mesh.reduce"


def _shard_map(body, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the reduce mixes
    per-shard and replicated values)."""
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


def _empty_field(name: str, num_docs: int, has_norms: bool) -> FieldIndex:
    return FieldIndex(
        name=name,
        terms={},
        df=np.zeros(0, dtype=np.int32),
        offsets=np.zeros(1, dtype=np.int64),
        doc_ids=np.zeros(0, dtype=np.int32),
        tfs=np.zeros(0, dtype=np.float32),
        norm_bytes=np.zeros(num_docs, dtype=np.uint8),
        doc_count=0,
        sum_total_tf=0,
        has_norms=has_norms,
        present=np.zeros(num_docs, dtype=bool),
        # Text fields carry (empty) position planes so every shard's pytree
        # has the same structure for the mesh stack.
        pos_offsets=np.zeros(1, dtype=np.int64) if has_norms else None,
        positions=np.zeros(0, dtype=np.int32) if has_norms else None,
    )


def union_schema(
    segments: list[Segment],
) -> tuple[dict[str, bool], set[str], dict[str, int]]:
    """Cross-shard union of (field -> has_norms, doc-value names,
    vector field -> dim) — the single definition of the uniform-schema
    invariant every stacked mesh pytree relies on."""
    fields: dict[str, bool] = {}
    dv: set[str] = set()
    vec: dict[str, int] = {}
    for seg in segments:
        for name, fld in seg.fields.items():
            fields[name] = fld.has_norms
        dv.update(seg.doc_values)
        for name, mat in seg.vectors.items():
            vec[name] = mat.shape[1]
    return fields, dv, vec


def fill_union_schema(
    seg: Segment,
    fields: dict[str, bool],
    dv: set[str],
    vec: dict[str, int],
) -> Segment:
    """Shallow-copied segment carrying the cross-shard union schema
    (missing fields empty, doc-value columns NaN, vector columns zero) so
    every shard's packed pytree has identical structure.

    Returns a COPY with fresh dicts — never mutates `seg`, which callers
    (the mesh serving view, mesh_snapshot) may share with still-serving
    snapshots on other threads.
    """
    from dataclasses import replace as dc_replace

    new_fields = dict(seg.fields)
    for name, has_norms in fields.items():
        if name not in new_fields:
            new_fields[name] = _empty_field(name, seg.num_docs, has_norms)
    new_dv = dict(seg.doc_values)
    for name in dv:
        if name not in new_dv:
            new_dv[name] = np.full(seg.num_docs, np.nan)
    new_vec = dict(seg.vectors)
    for name, dim in vec.items():
        if name not in new_vec:
            new_vec[name] = np.zeros((seg.num_docs, dim), dtype=np.float32)
    return dc_replace(
        seg, fields=new_fields, doc_values=new_dv, vectors=new_vec
    )


_SHARDED_UIDS = itertools.count(1)


@dataclass
class ShardedIndex:
    """N shards stacked on a leading mesh axis, searchable as one program."""

    mesh: Mesh
    axis: str
    mappings: Mappings
    segments: list[Segment]  # host-side, for stats + fetch phase
    seg_stacked: Any  # pytree: every leaf [n_shards, ...], device-sharded
    docs_per_shard: int  # padded per-shard doc capacity (global id stride)
    params: BM25Params
    # index.filter_cache.FilterCache: when set, `search` substitutes
    # cacheable filter-context clauses with [S, N] stacked mask planes
    # (computed once via compute_filter_mask_stacked, keyed on this
    # index's process-unique uid — shards are immutable, so planes never
    # go stale; the cache's LRU/HBM budget still bounds residency).
    filter_cache: Any = None
    # Cache-key scope + generation override (mesh_serving.MeshView): a
    # refresh-tracking view sets scope to its engines' uid tuple and
    # generation to their monotonic sum, so snapshot rebuilds invalidate
    # planes via the ordinary stale-generation purge and the per-index
    # `_cache/clear` can address them. None = the immutable default
    # (this instance's process-unique uid, generation pinned 0).
    cache_scope: Any = None
    cache_generation: int = 0
    # obs.metrics.DeviceInstruments: per-launch timing (queue/execute
    # split + retrace-census attribution) for direct mesh searches.
    # None = uninstrumented (the MeshView serving path wraps its own
    # launches in MeshView.serve instead).
    instruments: Any = None
    _stats_cache: dict[str, FieldStats] | None = None
    _id_indexes: list[dict[str, int] | None] | None = None
    # Memoized per-(shard, field) tile doc-id bounds for plan-time
    # conjunction range pruning (computed once; shards are immutable).
    _tile_bounds: dict | None = None
    _cache_uid: int = dc_field(
        default_factory=lambda: next(_SHARDED_UIDS)
    )

    def _field_tile_bounds(self, shard: int, name: str):
        if self._tile_bounds is None:
            self._tile_bounds = {}
        key = (shard, name)
        if key not in self._tile_bounds:
            fld = self.segments[shard].fields.get(name)
            if fld is None or not len(fld.doc_ids):
                self._tile_bounds[key] = (None, None)
            else:
                self._tile_bounds[key] = tile_doc_bounds(
                    fld.doc_ids, self.segments[shard].num_docs
                )
        return self._tile_bounds[key]

    def _id_index(self, shard: int) -> dict[str, int]:
        """Memoized _id -> local map per shard (the index is an immutable
        snapshot, so building it once per shard suffices)."""
        if self._id_indexes is None:
            self._id_indexes = [None] * len(self.segments)
        if self._id_indexes[shard] is None:
            self._id_indexes[shard] = {
                d: i for i, d in enumerate(self.segments[shard].ids)
            }
        return self._id_indexes[shard]

    @classmethod
    def from_docs(
        cls,
        docs: list[tuple[str, dict]],
        mappings: Mappings,
        mesh: Mesh,
        axis: str = "shard",
        params: BM25Params = BM25Params(),
    ) -> "ShardedIndex":
        """Route (id, source) docs to shards and build the stacked index."""
        n_shards = mesh.shape[axis]
        builders = [SegmentBuilder(mappings) for _ in range(n_shards)]
        for doc_id, source in docs:
            builders[shard_for_id(doc_id, n_shards)].add(source, doc_id)
        return cls.from_segments(
            [b.build() for b in builders], mappings, mesh, axis, params
        )

    @classmethod
    def from_segments(
        cls,
        segments: list[Segment],
        mappings: Mappings,
        mesh: Mesh,
        axis: str = "shard",
        params: BM25Params = BM25Params(),
    ) -> "ShardedIndex":
        n_shards = mesh.shape[axis]
        if len(segments) != n_shards:
            raise ValueError(
                f"{len(segments)} segments for a {n_shards}-shard mesh axis"
            )
        if any(s.nested for s in segments):
            raise ValueError(
                "nested blocks are not mesh-stackable yet; serve nested "
                "indices through the host-loop coordinator"
            )
        # Uniform schema: every shard carries the union of fields/columns.
        all_fields, all_dv, all_vec = union_schema(segments)
        n_pad = max((s.num_docs for s in segments), default=0)
        n_pad = max(n_pad, 1)
        min_tiles: dict[str, int] = {}
        pos_min_tiles: dict[str, int] = {}
        for seg in segments:
            for name in all_fields:
                fld = seg.fields.get(name)
                postings = len(fld.doc_ids) if fld is not None else 0
                tiles = postings // TILE + 2  # data tiles + sentinel tile
                min_tiles[name] = max(min_tiles.get(name, 0), tiles)
                npos = (
                    len(fld.positions)
                    if fld is not None and fld.positions is not None
                    else 0
                )
                if all_fields[name]:  # text field: position planes stack too
                    pos_min_tiles[name] = max(
                        pos_min_tiles.get(name, 0), npos // TILE + 2
                    )
        # Global (cross-shard) avgdl so precomputed impacts match the DFS
        # statistics scope the compiler will score with.
        global_stats = aggregate_field_stats(segments)
        global_avgdl = {name: s.avgdl for name, s in global_stats.items()}
        trees = []
        segments = [
            fill_union_schema(seg, all_fields, all_dv, all_vec)
            for seg in segments
        ]
        for seg in segments:
            dev = pack_segment(
                seg,
                pad_docs_to=n_pad,
                field_min_tiles=min_tiles,
                field_avgdl=global_avgdl,
                k1=params.k1,
                b=params.b,
                field_pos_min_tiles=pos_min_tiles,
            )
            trees.append(segment_tree(dev))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        sharding = NamedSharding(mesh, P(axis))
        stacked = jax.tree.map(
            lambda x: jax.device_put(x, sharding), stacked
        )
        return cls(
            mesh=mesh,
            axis=axis,
            mappings=mappings,
            segments=segments,
            seg_stacked=stacked,
            docs_per_shard=n_pad,
            params=params,
        )

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    def field_stats(self) -> dict[str, FieldStats]:
        """Cross-shard statistics: the DFS phase, computed at plan time.

        Cached — shards are immutable once the index is built."""
        if self._stats_cache is None:
            self._stats_cache = aggregate_field_stats(self.segments)
        return self._stats_cache

    def _tn_avgdl(self, shard: int, field: str, fstats) -> float:
        """Statistics scope the packed tn (impact) planes are valid for.

        The base class packs at build time with the same aggregated stats
        `compile` scores with, so the fast precomputed-impact kernel always
        applies. `MeshIndex` (parallel/mesh_serving.py) overrides this with
        the per-shard PACK-TIME avgdl so the compiler falls back to the
        norm-cache gather kernel whenever statistics have drifted since the
        shard was last uploaded — stale tn planes are then simply unused.
        """
        return float(fstats.avgdl) if fstats else 1.0

    def shard_compiler(self, shard: int, nt_floor: int = 1) -> Compiler:
        """Host-side planning view for one shard over the same offsets the
        device sees — the per-shard Compiler behind `compile`, also used
        by the mesh serving path to lower aggregation plans (filter-agg
        sub-queries) into shard-uniform specs."""
        stats = self.field_stats()
        seg = self.segments[shard]
        fields = {}
        for name, fld in seg.fields.items():
            postings = len(fld.doc_ids)
            nt = postings // TILE + 2
            fstats = stats.get(name)
            b_lo, b_hi = self._field_tile_bounds(shard, name)
            fields[name] = _PlanField(
                tile_doc_lo=b_lo,
                tile_doc_hi=b_hi,
                name=name,
                terms=fld.terms,
                df=fld.df,
                offsets=fld.offsets,
                doc_count=fld.doc_count,
                sum_total_tf=fld.sum_total_tf,
                has_norms=fld.has_norms,
                num_tiles_=max(nt, 0),
                # Impacts validity scope: see _tn_avgdl. When it matches
                # the stats avgdl the fast (precomputed-impact) kernel
                # applies; otherwise the gather kernel recomputes
                # impacts from tf + norm bytes with the current stats.
                tn_avgdl=self._tn_avgdl(shard, name, fstats),
                tn_k1=self.params.k1,
                tn_b=self.params.b,
                pos_offsets=fld.pos_offsets,
                pos_num_tiles_=(
                    len(fld.positions) // TILE + 2
                    if fld.positions is not None
                    else 0
                ),
            )
        return Compiler(
            fields=fields,
            doc_values={name: None for name in seg.doc_values},
            mappings=self.mappings,
            params=self.params,
            stats=stats,
            nt_floor=nt_floor,
            id_index=lambda s=shard: self._id_index(s),
        )

    def compile(self, query: Query, nt_floor: int = 1) -> CompiledQuery:
        """Compile per shard with uniform buckets; stack arrays on axis 0."""
        first = [
            self.shard_compiler(i, nt_floor).compile(query)
            for i in range(len(self.segments))
        ]
        specs_match = len({c.spec for c in first}) == 1
        if not specs_match:
            # Per-node-position equalization: each clause's bucket rises
            # only to ITS max across shards (array padding, no recompile).
            # The old single group-wide nt_floor let one fat clause (a
            # high-df filter term) inflate every other clause's worklist
            # — the BENCH_r05 cfg3 sort blow-up.
            try:
                first = equalize_compiled(first)
            except SpecUnifyError:
                nt_max = max(_max_nt(c.spec) for c in first)
                first = [
                    self.shard_compiler(i, nt_max).compile(query)
                    for i in range(len(self.segments))
                ]
            if len({c.spec for c in first}) != 1:
                raise AssertionError(
                    "sharded compile produced divergent specs even with a "
                    "common worklist floor"
                )
        spec = first[0].spec
        return CompiledQuery(spec=spec, arrays=stack_plans([c.arrays for c in first]))

    def compile_batch(self, queries: list[Query]) -> CompiledQuery:
        """Compile a batch of same-shape queries; arrays get a leading Q axis.

        All queries must lower to the same operator-tree structure; shape
        buckets (term count, tile count) are equalized automatically by
        recompiling with the batch-max floors — the batched executor then
        runs one program for the whole batch.
        """
        compiled = [self.compile(q) for q in queries]
        specs = {c.spec for c in compiled}
        if len(specs) != 1:
            try:
                compiled = equalize_compiled(compiled)
            except SpecUnifyError:
                pass
            specs = {c.spec for c in compiled}
        if len(specs) != 1:
            raise ValueError(
                "batched queries must share one compiled operator tree; got "
                f"{len(specs)} distinct specs after bucket equalization"
            )
        arrays = jax.tree.map(
            lambda *xs: np.stack(xs), *[c.arrays for c in compiled]
        )
        return CompiledQuery(spec=compiled[0].spec, arrays=arrays)

    def compile_batch_buckets(
        self, queries: list[Query]
    ) -> list[tuple[CompiledQuery, list[int]]]:
        """Adaptive worklist bucketing for a query batch: instead of ONE
        launch padded to the batch-wide maximum (whose padding made cfg3's
        batched execution slower than sequential, BENCH_r05), queries
        group into pow-2 sub-buckets — each query padded only to its own
        bucket, one launch per bucket. A smaller group is merged into a
        larger bucket only when the padding it would pay costs less than
        the launch it saves (exec/cost.coalesce_wins). Returns
        [(batched CompiledQuery, query positions)] covering all queries.
        """
        from ..exec.batcher import plan_spec_buckets

        compiled = [self.compile(q) for q in queries]
        by_spec: dict[tuple, list[int]] = {}
        for pos, c in enumerate(compiled):
            by_spec.setdefault(c.spec, []).append(pos)
        buckets = plan_spec_buckets(
            list(by_spec.items()), n_shards=self.n_shards
        )
        out: list[tuple[CompiledQuery, list[int]]] = []
        for bucket_specs in buckets:
            positions = [p for s in bucket_specs for p in by_spec[s]]
            target = unify_specs(list(bucket_specs))
            arrays = jax.tree.map(
                lambda *xs: np.stack(xs),
                *[
                    pad_arrays_to_spec(
                        compiled[p].spec, target, compiled[p].arrays
                    )
                    for p in positions
                ],
            )
            out.append((CompiledQuery(spec=target, arrays=arrays), positions))
        return out

    def search_batch(self, queries: list[Query], k: int, batch_axis: str):
        """Batched sharded search over a 2D (batch × shard) mesh."""
        compiled = self.compile_batch(queries)
        return sharded_execute_batch(
            self.mesh,
            self.axis,
            batch_axis,
            self.seg_stacked,
            compiled.arrays,
            compiled.spec,
            k,
            self.docs_per_shard,
        )

    def locate(self, global_doc: int) -> tuple[int, int]:
        """global doc id -> (shard, local doc id) for the fetch phase."""
        return divmod(int(global_doc), self.docs_per_shard)

    def _apply_filter_cache(
        self, query: Query, compiled: CompiledQuery, record: bool = True,
        entries: list | None = None,
    ):
        """Mesh-path filter cache: substitute [S, N] stacked mask planes
        for cacheable top-level filter clauses. The planes ride the seg
        pytree (P(axis)-sharded like every other plane), so the shard_map
        body reads its own shard's row — bit-identical to recomputing the
        clause in-program. `record=False` skips the admission sighting
        (MeshView.serve passes it: the coordinator already recorded the
        request, and an execute-failure fallback to the host loop must
        not leave a second sighting behind)."""
        from ..index.filter_cache import (
            apply_cached_masks,
            record_filter_usage,
        )
        from ..ops.bm25_device import compute_filter_mask_stacked

        cache = self.filter_cache
        if entries is None:
            entries = record_filter_usage(cache, query, record=record)
        if not entries:
            return compiled, {}

        def build(child_spec, child_arrays, _norm):
            plane = compute_filter_mask_stacked(
                self.seg_stacked, child_spec, child_arrays
            )
            plane = jax.device_put(
                plane, NamedSharding(self.mesh, P(self.axis))
            )
            return plane, int(plane.nbytes)

        scope = (
            self.cache_scope
            if self.cache_scope is not None
            else ("sharded", self._cache_uid)
        )
        prefix = (scope, int(self.cache_generation), 0)
        compiled, masks, _reused = apply_cached_masks(
            cache, prefix, query, compiled, build,
            const_fill=lambda: {
                "boost": np.zeros(self.n_shards, dtype=np.float32)
            },
            entries=entries,
        )
        return compiled, masks

    def search(self, query: Query, k: int = 10):
        """One-call sharded search: (scores f32[k'], global_ids, total)."""
        compiled = self.compile(query)
        seg = self.seg_stacked
        if self.filter_cache is not None:
            compiled, masks = self._apply_filter_cache(query, compiled)
            if masks:
                seg = {**self.seg_stacked, "masks": masks}
        with timed_launch(
            self.instruments,
            "mesh_spmd",
            (compiled.spec, k, "sharded_direct"),
            "mesh_spmd",
        ) as tl:
            scores, ids, total = tl.dispatched(
                sharded_execute(
                    self.mesh,
                    self.axis,
                    seg,
                    compiled.arrays,
                    compiled.spec,
                    k,
                    self.docs_per_shard,
                )
            )
        scores, ids = np.asarray(scores), np.asarray(ids)
        n = min(k, int(total))
        return scores[:n], ids[:n], int(total)


@dataclass
class _PlanField:
    """Host-only planning stand-in for DeviceField (term dict + spans)."""

    name: str
    terms: dict
    df: Any
    offsets: Any
    doc_count: int
    sum_total_tf: int
    has_norms: bool
    num_tiles_: int
    tn_avgdl: float = -1.0
    tn_k1: float = 1.2
    tn_b: float = 0.75
    pos_offsets: Any = None  # int64[P+1] host copy (phrase planning)
    pos_num_tiles_: int = 0
    # Per-tile doc-id extrema (tiles.tile_doc_bounds), for plan-time
    # conjunction range pruning; None disables it.
    tile_doc_lo: Any = None
    tile_doc_hi: Any = None

    @property
    def avgdl(self) -> float:
        if self.doc_count == 0:
            return 1.0
        return self.sum_total_tf / self.doc_count

    @property
    def pad_tile(self) -> int:
        return self.num_tiles_ - 1

    @property
    def pos_pad_tile(self) -> int:
        return self.pos_num_tiles_ - 1

    def term_span(self, term: str) -> tuple[int, int]:
        tid = self.terms.get(term)
        if tid is None:
            return (0, 0)
        return int(self.offsets[tid]), int(self.offsets[tid + 1])

    def term_pos_span(self, term: str) -> tuple[int, int]:
        tid = self.terms.get(term)
        if tid is None or self.pos_offsets is None:
            return (0, 0)
        return (
            int(self.pos_offsets[self.offsets[tid]]),
            int(self.pos_offsets[self.offsets[tid + 1]]),
        )

    def term_df(self, term: str) -> int:
        tid = self.terms.get(term)
        if tid is None:
            return 0
        return int(self.df[tid])


def stack_plans(per_shard: list) -> Any:
    """Per-shard plan arrays of one spec, stacked on a leading shard axis."""
    return jax.tree.map(lambda *xs: np.stack(xs), *per_shard)


def pack_plan(arrays) -> tuple[np.ndarray, tuple]:
    """Stacked [S, ...] plan arrays of 4-byte dtypes as one int32 buffer
    [S, L], bit for bit, and the static layout `_unpack_plan` reads it
    back with. Each argument leaf of a mesh launch is a host-to-device
    transfer to every shard's device: on a four-chip v5e host the eleven
    leaves of a MaxScore plan took 6.7 ms a launch, one buffer 1.3 ms."""
    leaves, treedef = jax.tree.flatten(arrays)
    n = leaves[0].shape[0]
    parts, layout, offset = [], [], 0
    for leaf in leaves:
        flat = np.ascontiguousarray(leaf).reshape(n, -1)
        if flat.dtype.itemsize != 4:
            raise TypeError(f"plan leaf of dtype {flat.dtype} is not 4 bytes wide")
        layout.append((tuple(leaf.shape[1:]), flat.dtype.str, offset, flat.shape[1]))
        flat = flat.view(np.int32)
        offset += flat.shape[1]
        parts.append(flat)
    return np.concatenate(parts, axis=1), (treedef, tuple(layout))


def _unpack_plan(row, layout):
    """One shard's plan arrays from its row of a `pack_plan` buffer."""
    treedef, entries = layout
    leaves = [
        jax.lax.bitcast_convert_type(row[off : off + size], jnp.dtype(dtype)).reshape(shape)
        for shape, dtype, off, size in entries
    ]
    return jax.tree.unflatten(treedef, leaves)


def stack_maxscore(plans: list[CompiledQuery]) -> CompiledQuery:
    """Per-shard MaxScore plans (Compiler.compile_maxscore) as one stacked
    plan for `sharded_execute_maxscore`. Shards split their terms on
    their own statistics, so their plans may differ in worklist bucket
    and fence rows, both raised to the shards' largest, and in which
    terms are non-essential, whose arrays have a fixed size."""
    nt = max(p.spec[2] for p in plans)
    rows = max(p.spec[4] for p in plans)
    spec = (*plans[0].spec[:2], nt, plans[0].spec[3], rows)
    padded = []
    for p in plans:
        arrays = dict(p.arrays)
        for key in ("tile_ids", "starts", "ends", "weights", "term_of"):
            # Empty spans: the kernel's [start, end) mask drops them.
            pad = np.zeros(nt - p.spec[2], dtype=arrays[key].dtype)
            arrays[key] = np.concatenate([arrays[key], pad])
        padded.append(arrays)
    return CompiledQuery(spec=spec, arrays=stack_plans(padded))


def _max_nt(spec: tuple) -> int:
    """Largest worklist bucket anywhere in a compiled spec."""
    kind = spec[0]
    if kind in ("terms", "terms_const", "terms_gather", "phrase",
                "span_near", "span_not"):
        return spec[2]
    if kind == "doc_set":
        return spec[1]
    if kind in ("const", "script"):
        return _max_nt(spec[1])
    if kind == "nested":
        return _max_nt(spec[2])
    if kind == "boosting":
        return max(_max_nt(spec[1]), _max_nt(spec[2]))
    if kind == "terms_set":
        return max(
            _max_nt(spec[1]),
            max((_max_nt(c) for c in spec[2]), default=1),
        )
    if kind == "function_score":
        out = _max_nt(spec[1])
        for fil in spec[3]:
            if fil is not None:
                out = max(out, _max_nt(fil))
        return out
    if kind == "dismax":
        return max((_max_nt(c) for c in spec[1]), default=1)
    if kind == "bool":
        out = 1
        for group in spec[1:5]:
            for child in group:
                out = max(out, _max_nt(child))
        return out
    return 1


@partial(
    jax.jit, static_argnames=("mesh", "axis", "spec", "k", "docs_per_shard")
)
def sharded_execute(
    mesh: Mesh, axis: str, seg_stacked, arrays_stacked, spec, k: int, docs_per_shard: int
):
    """SPMD query: per-shard score + top-k, all-gather merge, psum totals.

    Replaces the reference's transport-level scatter/gather + coordinator
    reduce with in-program collectives over ICI (SURVEY §2.3 row 3).
    Returns replicated (scores f32[k], global ids i32[k], total i32[]).
    """

    def body(seg, arrays):
        seg = jax.tree.map(lambda x: x[0], seg)
        arrays = jax.tree.map(lambda x: x[0], arrays)
        live = seg["live"]
        n = live.shape[0]
        kk = min(k, n)
        if supports_sparse(spec):
            # Candidate-centric kernel: no [N] score plane, no dense
            # top-k — the same fast path single-chip serving uses.
            local_s, local_i, count = _sparse_inner(seg, spec, arrays, kk)
        else:
            scores, matched = _eval_node(spec, arrays, seg, n)
            eligible = matched & live
            masked = jnp.where(eligible, scores, jnp.float32(NEG_INF))
            local_s, local_i = jax.lax.top_k(masked, kk)
            count = jnp.sum(eligible, dtype=jnp.int32)
        return _merge_top_k(local_s, local_i, count, axis, k, docs_per_shard)

    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(), P(), P()),
    )(seg_stacked, arrays_stacked)


def _merge_top_k(local_s, local_i, count, axis: str, k: int, docs_per_shard: int):
    """Inside a shard_map body: each shard's local top-k and match count
    merged over the mesh into the replicated global top-k (scores, global
    ids) and the psum'd total."""
    shard_id = jax.lax.axis_index(axis)
    global_i = shard_id.astype(jnp.int32) * docs_per_shard + local_i.astype(
        jnp.int32
    )
    with jax.named_scope(SCOPE_REDUCE):
        all_s = jax.lax.all_gather(local_s, axis)  # [S, kk]
        all_i = jax.lax.all_gather(global_i, axis)
        flat_s = all_s.reshape(-1)
        flat_i = all_i.reshape(-1)
        # Merge to min(k, S*kk), not kk: when k exceeds docs_per_shard
        # the union across shards can still fill k hits (ES returns
        # min(size, total) hits; the host trims by the psum'd total).
        top_s, idx = jax.lax.top_k(flat_s, min(k, flat_s.shape[0]))
        top_i = flat_i[idx]
        total = jax.lax.psum(count, axis)
    return top_s, top_i, total


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "spec", "k", "docs_per_shard", "layout"),
)
def sharded_execute_maxscore(
    mesh: Mesh, axis: str, seg_stacked, plan_buf, spec, k: int, docs_per_shard: int,
    layout: tuple,
):
    """SPMD MaxScore disjunction (ops/bm25_device.execute_maxscore on
    every shard), merged like `sharded_execute`; the stacked plan arrives
    as one `pack_plan` buffer and its `layout`.

    Each shard scores its essential postings' candidates exactly; a doc
    it skipped matches only that shard's non-essential terms and scores
    at most the shard's `ub_n`. The merged answer is therefore exact when
    the GLOBAL k-th score beats the largest `ub_n` of any shard (with the
    kernel's fp32 margin), a weaker condition than every shard's own
    check. The total is the psum of the shards' lower bounds, each past
    the request's track_total_hits threshold. Returns replicated
    (scores f32[k], global ids i32[k], total i32[], ok bool[]); `ok`
    false means the answer must come from the exhaustive plan."""

    def body(seg, buf):
        seg = jax.tree.map(lambda x: x[0], seg)
        arrays = _unpack_plan(buf[0], layout)
        kk = min(k, seg["live"].shape[0])
        local_s, local_i, count, _ = _maxscore_inner(seg, spec, arrays, kk)
        top_s, top_i, total = _merge_top_k(
            local_s, local_i, count, axis, k, docs_per_shard
        )
        with jax.named_scope(SCOPE_REDUCE):
            ub_n = jax.lax.pmax(arrays["ub_n"], axis)
        margin = ub_n * jnp.float32(1 + 1e-6) + jnp.float32(1e-6)
        if top_s.shape[0] < k:
            ok = jnp.bool_(False)
        else:
            ok = top_s[k - 1] > margin
        return top_s, top_i, total, ok

    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(), P(), P(), P()),
    )(seg_stacked, plan_buf)


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "spec", "k", "docs_per_shard", "sort_field",
        "sort_desc", "missing_first", "has_after", "aggs_spec",
    ),
)
def sharded_execute_request(
    mesh: Mesh,
    axis: str,
    seg_stacked,
    arrays_stacked,
    spec,
    k: int,
    docs_per_shard: int,
    sort_field: str | None = None,
    sort_desc: bool = False,
    missing_first: bool = False,
    has_after: bool = False,
    after_key=0.0,
    after_doc=0,
    aggs_spec: tuple | None = None,
    aggs_arrays_stacked=(),
):
    """One shard_map launch serving a full query phase: scoring, sorted or
    score-ordered top-k with search_after cursor masking, psum'd totals,
    AND the aggregation planes — the whole coordinator reduce as in-program
    collectives (SearchPhaseController.java:477 / FieldSortBuilder merged
    into the XLA program).

    - Field sorts rank by the transformed ascending (sort key, shard, doc)
      composite: keys via ops.bm25_device.sort_key_plane (desc negation,
      missing pinned first/last), the (shard, doc) tiebreak implicit in
      jax.lax.top_k's stable lower-flat-index-first ordering over the
      all-gathered [shard, k] key planes — bit-identical hit order to the
      host-loop FieldSortBuilder-style merge.
    - search_after applies as a key-range mask BEFORE the local top-k (the
      next page may lie beyond a shard's uncursored top-k). `after_doc` is
      mesh-global (shard * docs_per_shard + local); key-only public
      cursors pass n_shards * docs_per_shard so key ties never qualify.
    - Aggregations evaluate off the shared eligibility mask exactly like
      the single-segment program (ops/aggs_device.execute_aggs); integer
      count planes (histogram/range buckets, filter-family doc_counts) are
      psum-combined IN PROGRAM (exact — int addition is grouping-free),
      while per-shard planes (masks for the f64-exact metric finish,
      keyword ordinal counts) come back stacked [S, ...] from the same
      launch for the host fold.

    Returns (merge keys f32[k'] ascending, sort values f32[k'] (raw column
    values / scores), global ids i32[k'], total i32[], n_after i32[],
    agg results pytree with leading shard axis).
    """
    from ..ops.aggs_device import _eval_agg, mesh_combine

    def body(seg, arrays, agg_arrays, a_key, a_doc):
        seg = jax.tree.map(lambda x: x[0], seg)
        arrays = jax.tree.map(lambda x: x[0], arrays)
        agg_arrays = jax.tree.map(lambda x: x[0], agg_arrays)
        live = seg["live"]
        n = live.shape[0]
        scores, matched = _eval_node(spec, arrays, seg, n)
        eligible = matched & live
        count = jnp.sum(eligible, dtype=jnp.int32)
        total = jax.lax.psum(count, axis)
        shard_id = jax.lax.axis_index(axis).astype(jnp.int32)
        if k > 0:
            from ..ops.bm25_device import sort_key_plane

            kk = min(k, n)
            iota = jnp.arange(n, dtype=jnp.int32)
            local_after = a_doc - shard_id * docs_per_shard
            if sort_field is not None:
                col, key = sort_key_plane(
                    seg, sort_field, sort_desc, missing_first
                )
                keep = eligible
                if has_after:
                    keep = keep & (
                        (key > a_key)
                        | ((key == a_key) & (iota > local_after))
                    )
                masked = jnp.where(keep, key, jnp.float32(jnp.inf))
                neg, ids = jax.lax.top_k(-masked, kk)
                local_key = -neg  # ascending merge-key space
                local_val = col[ids]  # raw values (NaN = missing)
            else:
                keep = eligible
                if has_after:
                    keep = keep & (
                        (scores < a_key)
                        | ((scores == a_key) & (iota > local_after))
                    )
                masked = jnp.where(keep, scores, jnp.float32(NEG_INF))
                top_s, ids = jax.lax.top_k(masked, kk)
                local_key = -top_s  # score desc == key asc
                local_val = top_s
            n_after = jnp.sum(keep, dtype=jnp.int32)
            gids = shard_id * docs_per_shard + ids.astype(jnp.int32)
            with jax.named_scope(SCOPE_REDUCE):
                all_key = jax.lax.all_gather(local_key, axis).reshape(-1)
                all_val = jax.lax.all_gather(local_val, axis).reshape(-1)
                all_gid = jax.lax.all_gather(gids, axis).reshape(-1)
                m = min(k, all_key.shape[0])
                # Stable top-k over -key: equal keys favor the lower flat
                # index = (shard, per-shard rank) — the host merge
                # tiebreak.
                neg_top, idxm = jax.lax.top_k(-all_key, m)
                out_key = -neg_top
                out_val = all_val[idxm]
                out_gid = all_gid[idxm]
                n_after_total = jax.lax.psum(n_after, axis)
        else:  # agg-only / count-only request: no hits merge at all
            out_key = jnp.zeros(0, dtype=jnp.float32)
            out_val = jnp.zeros(0, dtype=jnp.float32)
            out_gid = jnp.zeros(0, dtype=jnp.int32)
            n_after_total = jnp.zeros((), dtype=jnp.int32)
        if aggs_spec is not None:
            results = tuple(
                _eval_agg(s, a, seg, eligible, scores, n)
                for s, a in zip(aggs_spec, agg_arrays)
            )
            results = mesh_combine(aggs_spec, results, axis)
            # Leading [1, ...] axis so P(axis) out-specs stack per-shard
            # planes to [S, ...]; psum'd (replicated) leaves stack to
            # identical rows — the host reads row 0 for those.
            agg_out = jax.tree.map(lambda x: x[None], results)
        else:
            agg_out = ()
        return out_key, out_val, out_gid, total, n_after_total, agg_out

    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(axis)),
    )(
        seg_stacked,
        arrays_stacked,
        aggs_arrays_stacked,
        jnp.float32(after_key),
        jnp.int32(after_doc),
    )


@partial(
    jax.jit,
    static_argnames=("mesh", "shard_axis", "batch_axis", "spec", "k", "docs_per_shard"),
)
def sharded_execute_batch(
    mesh: Mesh,
    shard_axis: str,
    batch_axis: str,
    seg_stacked,
    arrays_batched,  # leaves [Q, S, ...]
    spec,
    k: int,
    docs_per_shard: int,
):
    """Query-batch × shard SPMD search over a 2D mesh.

    The replica/data-parallel analog (SURVEY §2.3 row 2): the index is
    replicated over `batch_axis` and sharded over `shard_axis`; a batch of
    same-shape compiled queries is sharded over `batch_axis`. Each device
    scores its query sub-batch against its shard; the shard reduce is an
    `all_gather` over ICI exactly as in `sharded_execute`.

    Returns (scores f32[Q, k], global ids i32[Q, k], totals i32[Q]), sharded
    over `batch_axis`.
    """

    def body(seg, arrays):
        seg = jax.tree.map(lambda x: x[0], seg)  # strip shard axis
        arrays = jax.tree.map(lambda x: x[:, 0], arrays)  # [Qb, ...]
        live = seg["live"]
        n = live.shape[0]
        kk = min(k, n)

        def one(one_arrays):
            if supports_sparse(spec):
                return _sparse_inner(seg, spec, one_arrays, kk)
            scores, matched = _eval_node(spec, one_arrays, seg, n)
            eligible = matched & live
            masked = jnp.where(eligible, scores, jnp.float32(NEG_INF))
            local_s, local_i = jax.lax.top_k(masked, kk)
            return local_s, local_i, jnp.sum(eligible, dtype=jnp.int32)

        local_s, local_i, counts = jax.vmap(one)(arrays)  # [Qb, kk]
        shard_id = jax.lax.axis_index(shard_axis).astype(jnp.int32)
        global_i = shard_id * docs_per_shard + local_i.astype(jnp.int32)
        with jax.named_scope(SCOPE_REDUCE):
            all_s = jax.lax.all_gather(local_s, shard_axis)  # [S, Qb, kk]
            all_i = jax.lax.all_gather(global_i, shard_axis)
            qb = all_s.shape[1]
            flat_s = all_s.transpose(1, 0, 2).reshape(qb, -1)  # [Qb, S*kk]
            flat_i = all_i.transpose(1, 0, 2).reshape(qb, -1)
            top_s, idx = jax.lax.top_k(flat_s, min(k, flat_s.shape[-1]))
            top_i = jnp.take_along_axis(flat_i, idx, axis=1)
            totals = jax.lax.psum(counts, shard_axis)
        return top_s, top_i, totals

    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(shard_axis), P(batch_axis, shard_axis)),
        out_specs=(P(batch_axis), P(batch_axis), P(batch_axis)),
    )(seg_stacked, arrays_batched)
