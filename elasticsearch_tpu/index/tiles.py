"""Device-resident tiled index layout.

This is the HBM-resident replacement for Lucene's mmap'd segment files
(reference: server/src/main/java/org/elasticsearch/index/store/
FsDirectoryFactory.java:36 — immutable scoring files memory-mapped with
optional preload). Instead of pointer-chased posting blocks, a field's
postings live on device as flat CSR arrays padded to a tile multiple:

    doc_ids : int32[P_pad]   local doc ids (sentinel = num_docs for padding)
    tfs     : float32[P_pad] term frequencies (0 for padding)

A query term is a contiguous [start, end) slice of these arrays. Because XLA
needs static shapes, the per-query access pattern is expressed as *tile
gathers*: the flat arrays are viewed as [P_pad // TILE, TILE] and a term's
postings are covered by the tile ids it spans (host-computed at plan time,
padded to a per-query bucket). The kernel in ops/bm25_device.py gathers those
tiles, masks positions outside [start, end), and scatter-adds BM25
contributions into a dense score vector.

norm bytes (uint8, Lucene SmallFloat field lengths) ride along with one extra
sentinel slot so padded doc ids gather norm 0 harmlessly; numeric doc-values
columns and dense vectors are uploaded densely.

Design notes (TPU-first):
- Tile gathers keep HBM reads contiguous and aligned to the 128-lane layout.
- The padding sentinel doc id == num_docs scatters into an extra slot that is
  sliced off, so no masking is needed on the scatter itself.
- All arrays are device-put once at refresh; per-query host→device traffic is
  only the plan's small integer/float arrays (tile ids, weights, norm cache).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field as dc_field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .segment import FieldIndex, Segment

TILE = 256  # postings per tile; multiple of the 128-lane TPU layout


def _putter(device, stack_axis: bool):
    """The upload of one host plane: as it is, or with a leading axis of
    one (the shard row a mesh assembles its stacked arrays from)."""
    if stack_axis:
        return lambda x: jax.device_put(x[None], device)
    return lambda x: jax.device_put(x, device)


def _pad_to_tile(arr: np.ndarray, pad_value, tile: int = TILE) -> np.ndarray:
    """Pad to a tile multiple PLUS one extra all-padding sentinel tile.

    The sentinel tile (always the last) is the target of padding slots in
    per-query tile-id arrays: its global positions are >= every real posting
    position, so the kernel's [start, end) mask can never select it.
    """
    p = len(arr)
    p_pad = ((p + tile - 1) // tile) * tile + tile
    out = np.full(p_pad, pad_value, dtype=arr.dtype)
    out[:p] = arr
    return out


@dataclass
class DeviceField:
    """One field's postings resident on device (plus host-side term dict)."""

    name: str
    # Host-side planning data (term dictionary stays on host, like the
    # reference's terms dict staying on-heap while postings are mmap'd):
    terms: dict[str, int]
    df: np.ndarray  # int32[T] host copy, for IDF at plan time
    offsets: np.ndarray  # int64[T+1] host copy, for tile id computation
    doc_count: int
    sum_total_tf: int
    has_norms: bool
    # Device arrays:
    doc_ids: jax.Array  # int32[NT, TILE]  (sentinel num_docs in padding)
    tfs: jax.Array  # float32[NT, TILE]
    norm_bytes: jax.Array  # uint8[N + 1]   (sentinel slot at N)
    present: jax.Array  # bool[N] doc has a value for this field (exists query)
    # Precomputed per-posting BM25 impact factor tn = tf * normInverse, f32,
    # same [NT, TILE] layout as tfs. Scoring is then the pure elementwise
    # `w - w / (1 + tn)` — Lucene's exact fp32 expression order — with NO
    # random gather in the hot loop (gathers, not FLOPs, dominate on TPU).
    # Valid for (tn_avgdl, tn_k1, tn_b); other statistics/params fall back
    # to the gather kernel. The reference computes the same quantity lazily
    # per (field, query) via its norm cache (BM25Similarity scorer).
    tn: jax.Array  # float32[NT, TILE]
    tn_avgdl: float
    tn_k1: float
    tn_b: float
    # Host-side per-tile max impact (block-max analog): tile_max[j] =
    # max(tn[j, :]) — the plan-time upper-bound source for tile pruning
    # (reference behavior: Lucene block-max WAND skipping enabled by
    # search/query/TopDocsCollectorContext.java:68).
    tile_max: np.ndarray | None = None
    # Host-side per-tile doc-id extrema (padding sentinels == num_docs
    # only widen the max, keeping bounds conservative): the plan-time
    # bounds for conjunction doc-range pruning — a must tile whose
    # [lo, hi] cannot intersect the doc range a single-span filter bounds
    # is dropped at compile time, exactly (query/compile._terms_arrays).
    # The analog of Lucene's per-block min/max docID skip data.
    tile_doc_lo: np.ndarray | None = None
    tile_doc_hi: np.ndarray | None = None
    device: Any = None  # placement used at pack time (repacks must match)
    # Global ordinals plane for keyword fields (terms aggregations): term id
    # owning each posting position, same [NT, TILE] layout, sentinel = T for
    # padding. The analog of the reference's fielddata global ordinals
    # (index/fielddata/; terms agg collects ordinals then resolves strings
    # at reduce time). Only packed for norms-disabled (keyword) fields.
    ord_terms: jax.Array | None = None  # int32[NT, TILE]
    # Proximity planes (text fields; Lucene .pos analog): flat position
    # entries in CSR term→doc→occurrence order, tiled like postings. A
    # phrase term's entries are the contiguous slice
    # [pos_offsets[offsets[tid]], pos_offsets[offsets[tid+1]]) — the host
    # plans tile worklists over this space exactly like postings tiles.
    pos_doc: jax.Array | None = None  # int32[PT, TILE] owning doc (sentinel N)
    pos_val: jax.Array | None = None  # int32[PT, TILE] position (sentinel -1)
    pos_offsets: np.ndarray | None = None  # int64[P+1] host copy (planning)

    def term_pos_span(self, term: str) -> tuple[int, int]:
        """[start, end) position-entry span for a term; (0, 0) if absent."""
        tid = self.terms.get(term)
        if tid is None or self.pos_offsets is None:
            return (0, 0)
        return (
            int(self.pos_offsets[self.offsets[tid]]),
            int(self.pos_offsets[self.offsets[tid + 1]]),
        )

    @property
    def pos_pad_tile(self) -> int:
        """Tile id of the all-sentinel padding tile of the position planes."""
        return self.pos_doc.shape[-2] - 1

    @property
    def num_terms(self) -> int:
        return len(self.df)

    @property
    def num_tiles(self) -> int:
        return self.doc_ids.shape[-2]

    @property
    def pad_tile(self) -> int:
        """Tile id of the all-sentinel padding tile (always the last)."""
        return self.doc_ids.shape[-2] - 1

    @property
    def avgdl(self) -> float:
        if self.doc_count == 0:
            return 1.0
        return self.sum_total_tf / self.doc_count

    def term_span(self, term: str) -> tuple[int, int]:
        """[start, end) posting positions for a term; (0, 0) if absent."""
        tid = self.terms.get(term)
        if tid is None:
            return (0, 0)
        return int(self.offsets[tid]), int(self.offsets[tid + 1])

    def term_df(self, term: str) -> int:
        tid = self.terms.get(term)
        if tid is None:
            return 0
        return int(self.df[tid])

    @functools.cached_property
    def longest_span(self) -> int:
        """Postings of the field's longest term span (host planning)."""
        return int(np.diff(self.offsets).max()) if self.num_terms else 0


@dataclass
class DeviceSegment:
    """A Segment uploaded to device memory (the 'refreshed' searchable form).

    The analog of the reference's opened DirectoryReader over a committed
    Lucene segment (index/engine/InternalEngine.java refresh →
    ContextIndexSearcher over segment leaves). `live` is the liveDocs deletion
    mask (ContextIndexSearcher.java:181-195): True = visible.
    """

    num_docs: int
    fields: dict[str, DeviceField]
    doc_values: dict[str, jax.Array]  # float64 is TPU-hostile: stored f32
    vectors: dict[str, jax.Array]  # float32[N, D]
    live: jax.Array  # bool[N]
    # Host-side fetch-phase data:
    sources: list[dict[str, Any]]
    ids: list[str]
    # Nested blocks: path -> (inner DeviceSegment over the nested-doc
    # space, parent_of i32[NN] device map). The block-join planes.
    nested: dict[str, tuple["DeviceSegment", jax.Array]] = dc_field(
        default_factory=dict
    )

    def field(self, name: str) -> DeviceField:
        try:
            return self.fields[name]
        except KeyError:
            raise KeyError(
                f"no inverted field [{name}] in segment; have {sorted(self.fields)}"
            ) from None


def compute_tn(
    field: FieldIndex, avgdl: float, k1: float, b: float
) -> np.ndarray:
    """Per-posting impact tn = tf * normInverse(normByte) in fp32.

    Matches the oracle's (and Lucene's) op order exactly: the fp32 product
    `freq * normInv` that BM25Similarity's scorer feeds into
    `weight - weight / (1 + freq * normInv)`.
    """
    from ..ops.bm25 import BM25Params, norm_inverse_cache

    cache = norm_inverse_cache(avgdl, BM25Params(k1=k1, b=b))
    if not field.has_norms:
        cache = np.full(256, cache[1], dtype=np.float32)
    ninv = cache[field.norm_bytes[field.doc_ids]]
    return (field.tfs.astype(np.float32) * ninv).astype(np.float32)


def pack_field(
    field: FieldIndex,
    num_docs: int,
    device=None,
    min_tiles: int = 0,
    avgdl: float | None = None,
    k1: float = 1.2,
    b: float = 0.75,
    min_pos_tiles: int = 0,
    stack_axis: bool = False,
) -> DeviceField:
    """Pack one FieldIndex into tiled device arrays.

    `num_docs` may exceed the segment's own doc count (sharded stacking pads
    every shard to a common size); the scatter sentinel is always `num_docs`.
    `min_tiles` pads the tile axis so shards stack to equal shapes.
    `avgdl` is the statistics scope used for the precomputed impacts —
    shard-level (cross-segment) or global (cross-shard); defaults to this
    segment's own. `stack_axis` gives every device array a leading axis of
    one, so a mesh stacks the arrays of its shards without a copy.
    """
    if avgdl is None:
        avgdl = field.avgdl
    doc_ids = _pad_to_tile(field.doc_ids.astype(np.int32), np.int32(num_docs))
    tfs = _pad_to_tile(field.tfs.astype(np.float32), np.float32(0.0))
    tn = _pad_to_tile(compute_tn(field, avgdl, k1, b), np.float32(0.0))
    if min_tiles and len(doc_ids) < min_tiles * TILE:
        extra = min_tiles * TILE - len(doc_ids)
        doc_ids = np.concatenate(
            [doc_ids, np.full(extra, num_docs, dtype=np.int32)]
        )
        tfs = np.concatenate([tfs, np.zeros(extra, dtype=np.float32)])
        tn = np.concatenate([tn, np.zeros(extra, dtype=np.float32)])
    norm_ext = np.zeros(num_docs + 1, dtype=np.uint8)
    norm_ext[: len(field.norm_bytes)] = field.norm_bytes
    tile_max = tn.reshape(-1, TILE).max(axis=1)
    doc_tiles_host = doc_ids.reshape(-1, TILE)
    tile_doc_lo = doc_tiles_host.min(axis=1)
    tile_doc_hi = doc_tiles_host.max(axis=1)
    put = _putter(device, stack_axis)
    pos_doc = pos_val = None
    pos_offsets_host = None
    if field.positions is not None:
        # Expand the owning doc per position entry (CSR expansion over
        # per-posting counts), then tile both planes like postings.
        counts = np.diff(field.pos_offsets).astype(np.int64)
        owners = np.repeat(field.doc_ids.astype(np.int32), counts)
        pd = _pad_to_tile(owners, np.int32(num_docs))
        pv = _pad_to_tile(field.positions.astype(np.int32), np.int32(-1))
        if min_pos_tiles and len(pd) < min_pos_tiles * TILE:
            extra = min_pos_tiles * TILE - len(pd)
            pd = np.concatenate([pd, np.full(extra, num_docs, dtype=np.int32)])
            pv = np.concatenate([pv, np.full(extra, -1, dtype=np.int32)])
        pos_doc = put(pd.reshape(-1, TILE))
        pos_val = put(pv.reshape(-1, TILE))
        pos_offsets_host = field.pos_offsets
    ord_terms = None
    if not field.has_norms:
        # keyword field: per-posting owning term id (CSR expansion),
        # padded with sentinel T so padding scatters into a discard slot.
        # Built even for an EMPTY vocabulary: the SPMD mesh path stacks
        # one agg program over every shard, so a shard where the field is
        # union-schema-filled empty must still present the same ordinal
        # plane structure (all padding, sentinel 0 → the discard slot).
        t_count = len(field.df)
        ords = np.repeat(
            np.arange(t_count, dtype=np.int32),
            np.diff(field.offsets).astype(np.int64),
        )
        ords_pad = np.full(len(doc_ids), t_count, dtype=np.int32)
        ords_pad[: len(ords)] = ords
        ord_terms = put(ords_pad.reshape(-1, TILE))
    return DeviceField(
        name=field.name,
        terms=field.terms,
        df=field.df,
        offsets=field.offsets,
        doc_count=field.doc_count,
        sum_total_tf=field.sum_total_tf,
        has_norms=field.has_norms,
        doc_ids=put(doc_ids.reshape(-1, TILE)),
        tfs=put(tfs.reshape(-1, TILE)),
        norm_bytes=put(norm_ext),
        present=put(_fit_bool(field.present, field.norm_bytes, num_docs)),
        tn=put(tn.reshape(-1, TILE)),
        tn_avgdl=float(avgdl),
        tn_k1=k1,
        tn_b=b,
        tile_max=tile_max,
        tile_doc_lo=tile_doc_lo,
        tile_doc_hi=tile_doc_hi,
        device=device,
        ord_terms=ord_terms,
        pos_doc=pos_doc,
        pos_val=pos_val,
        pos_offsets=pos_offsets_host,
    )


def _padded_equal(a: np.ndarray, b: np.ndarray, fill) -> bool:
    """Equal once both are padded with `fill` to a common length (the
    pack always pads per-doc planes to the shared doc capacity, so two
    host arrays produce identical DEVICE planes iff they agree where
    they overlap and the longer one's tail is all `fill`). `fill` of
    NaN compares tails with isnan; 2-D arrays compare per row."""
    if len(a) == len(b):
        if a.dtype.kind == "f" or b.dtype.kind == "f":
            return np.array_equal(a, b, equal_nan=True)
        return np.array_equal(a, b)
    short, longer = (a, b) if len(a) < len(b) else (b, a)
    head, tail = longer[: len(short)], longer[len(short) :]
    if a.dtype.kind == "f" or b.dtype.kind == "f":
        if not np.array_equal(head, short, equal_nan=True):
            return False
    elif not np.array_equal(head, short):
        return False
    if isinstance(fill, float) and np.isnan(fill):
        return bool(np.all(np.isnan(tail)))
    return bool(np.all(tail == fill))


def _field_plane_reusable(
    fld: FieldIndex,
    prev_fld: FieldIndex | None,
    prev_dev: DeviceField | None,
    avgdl: float,
    k1: float,
    b: float,
) -> bool:
    """May `prev_dev`'s device planes serve `fld` unchanged?

    True only when every host array that feeds the pack produces
    byte-identical device planes AND the precomputed-impact scope
    (avgdl, k1, b) matches. Postings/positions must match exactly;
    per-doc planes (norms, presence) may differ by an all-empty tail —
    the pack zero-pads them to the shared doc capacity anyway, so a
    freshly appended doc that does NOT carry this field leaves the
    packed planes bit-identical. Device arrays are immutable, so sharing
    them with a previous snapshot is safe (the same contract as
    dataclasses.replace handle clones)."""
    if prev_fld is None or prev_dev is None:
        return False
    if (
        prev_dev.tn_avgdl != float(avgdl)
        or prev_dev.tn_k1 != k1
        or prev_dev.tn_b != b
        or fld.has_norms != prev_fld.has_norms
    ):
        return False
    if fld.terms != prev_fld.terms:
        return False
    for attr in ("df", "offsets", "doc_ids", "tfs"):
        if not np.array_equal(getattr(fld, attr), getattr(prev_fld, attr)):
            return False
    if not _padded_equal(fld.norm_bytes, prev_fld.norm_bytes, 0):
        return False
    from .merge import _field_present

    if not _padded_equal(
        _field_present(fld), _field_present(prev_fld), False
    ):
        return False
    if (fld.positions is None) != (prev_fld.positions is None):
        return False
    if fld.positions is not None and not (
        np.array_equal(fld.pos_offsets, prev_fld.pos_offsets)
        and np.array_equal(fld.positions, prev_fld.positions)
    ):
        return False
    return True


def pack_segment_delta(
    segment: Segment,
    prev_segment: Segment | None,
    prev_device: DeviceSegment | None,
    device=None,
    pad_docs_to: int = 0,
    field_min_tiles: dict[str, int] | None = None,
    field_avgdl: dict[str, float] | None = None,
    k1: float = 1.2,
    b: float = 0.75,
    field_pos_min_tiles: dict[str, int] | None = None,
    stack_axis: bool = False,
) -> tuple[DeviceSegment, int, int]:
    """pack_segment with per-plane upload skipping against a previous pack.

    The delta-scaled refresh's device half (mesh_serving.MeshView): after
    an append-only refresh, most fields' merged postings are byte-identical
    to the previous snapshot's, so their device planes (doc_ids/tfs/tn/
    norms/ordinals/positions) are REUSED rather than re-uploaded — only
    fields the delta actually touched repack, plus the per-segment live
    mask (always fresh: deletions move it). Callers must pass prev_*
    packed under the SAME padded shapes (pad_docs_to / min-tile maps);
    shape growth forces a full pack upstream. Returns
    (device segment, planes reused, planes packed). Nested blocks never
    take this path (the mesh excludes them)."""
    if prev_segment is None or prev_device is None or segment.nested:
        dev = pack_segment(
            segment,
            device,
            pad_docs_to=pad_docs_to,
            field_min_tiles=field_min_tiles,
            field_avgdl=field_avgdl,
            k1=k1,
            b=b,
            field_pos_min_tiles=field_pos_min_tiles,
            stack_axis=stack_axis,
        )
        return dev, 0, len(dev.fields) + len(dev.doc_values) + len(dev.vectors)
    n = max(segment.num_docs, pad_docs_to)
    if prev_device.num_docs != n:
        dev = pack_segment(
            segment,
            device,
            pad_docs_to=pad_docs_to,
            field_min_tiles=field_min_tiles,
            field_avgdl=field_avgdl,
            k1=k1,
            b=b,
            field_pos_min_tiles=field_pos_min_tiles,
            stack_axis=stack_axis,
        )
        return dev, 0, len(dev.fields) + len(dev.doc_values) + len(dev.vectors)
    put = _putter(device, stack_axis)
    min_tiles = field_min_tiles or {}
    avgdls = field_avgdl or {}
    pos_min_tiles = field_pos_min_tiles or {}
    reused = 0
    packed = 0
    fields: dict[str, DeviceField] = {}
    for name, f in segment.fields.items():
        avgdl = avgdls.get(name)
        if avgdl is None:
            avgdl = f.avgdl
        prev_dev = prev_device.fields.get(name)
        if _field_plane_reusable(
            f, prev_segment.fields.get(name), prev_dev, avgdl, k1, b
        ):
            fields[name] = prev_dev
            reused += 1
        else:
            fields[name] = pack_field(
                f,
                n,
                device,
                min_tiles.get(name, 0),
                avgdl,
                k1,
                b,
                pos_min_tiles.get(name, 0),
                stack_axis,
            )
            packed += 1
    doc_values: dict[str, jax.Array] = {}
    for name, col in segment.doc_values.items():
        prev_col = prev_segment.doc_values.get(name)
        if prev_col is not None and _padded_equal(col, prev_col, np.nan):
            doc_values[name] = prev_device.doc_values[name]
            reused += 1
        else:
            padded = np.full(n, np.nan, dtype=np.float32)
            padded[: len(col)] = col.astype(np.float32)
            doc_values[name] = put(padded)
            packed += 1
    vectors: dict[str, jax.Array] = {}
    for name, mat in segment.vectors.items():
        prev_mat = prev_segment.vectors.get(name)
        if (
            prev_mat is not None
            and mat.shape[1] == prev_mat.shape[1]
            and _padded_equal(mat, prev_mat, 0.0)
        ):
            vectors[name] = prev_device.vectors[name]
            reused += 1
        else:
            padded = np.zeros((n, mat.shape[1]), dtype=np.float32)
            padded[: len(mat)] = mat
            vectors[name] = put(padded)
            packed += 1
    live = np.zeros(n, dtype=bool)
    live[: segment.num_docs] = True
    return (
        DeviceSegment(
            num_docs=n,
            fields=fields,
            doc_values=doc_values,
            vectors=vectors,
            live=put(live),
            sources=segment.sources,
            ids=segment.ids,
            nested={},
        ),
        reused,
        packed,
    )


def repack_tn(
    dfield: DeviceField, field: FieldIndex, avgdl: float, k1: float, b: float
) -> None:
    """Recompute a DeviceField's per-posting impacts for new statistics.

    Used when shard-level avgdl drifts as segments accumulate (the engine
    keeps impacts aligned with reader-level statistics, like Lucene
    recomputing its norm cache per searcher). Preserves the existing device
    shape (including sharded min-tile padding).
    """
    total = dfield.doc_ids.shape[0] * TILE
    tn = np.zeros(total, dtype=np.float32)
    raw = compute_tn(field, avgdl, k1, b)
    tn[: len(raw)] = raw
    tiled = tn.reshape(-1, TILE)
    dfield.tn = jax.device_put(tiled, dfield.device)
    dfield.tile_max = tiled.max(axis=1)
    dfield.tn_avgdl = float(avgdl)
    dfield.tn_k1 = k1
    dfield.tn_b = b


# ---------------------------------------------------------------------------
# Multi-tenant packed planes.
#
# The north-star workload is millions of SMALL tenants: per-launch dispatch
# (~1-2 ms) dwarfs the scoring work of a 5k-doc index, so one launch per
# (tenant, query) loses to a CPU oracle by an order of magnitude (BENCH_r05
# cfg1: 0.08x). The packed layout concatenates many small DeviceSegments
# into ONE shared set of tile planes — a tenant/index-id dimension expressed
# as contiguous doc-id and tile ranges — so a single batched XLA launch
# scores many tenants' queries at once, amortizing dispatch the same way
# the reference amortizes per-segment work inside one Lucene IndexSearcher
# pass rather than paying a JVM entry per segment.
#
# Layout invariants:
# - tenant t owns GLOBAL doc ids [doc_base[t], doc_base[t] + num_docs[t]);
#   every member doc id is rewritten local + doc_base at pack time, and the
#   member's padding sentinels (== its local num_docs) are rewritten to the
#   GLOBAL sentinel (plane num_docs) so padding can never alias the next
#   tenant's first doc;
# - tenant t's postings for a field occupy GLOBAL tiles
#   [tile_base[t], tile_base[t] + member tiles) — each member plane already
#   ends in its own all-sentinel padding tile, which becomes the member's
#   in-plane pad target;
# - per-member compile `views` are ordinary DeviceFields sharing the packed
#   device arrays with the member's own host metadata (terms dict, df,
#   statistics) and posting offsets shifted by tile_base * TILE, so the
#   standard Compiler emits plans directly in packed coordinates — per-
#   tenant IDF/avgdl (and therefore fp32 scores) are untouched by packing.
#
# Cross-tenant isolation is structural (a query's worklist tiles all lie in
# its own tenant's tile range) AND enforced: the packed kernel masks
# eligibility to the tenant's [doc lo, doc hi) bounds
# (ops/bm25_device.execute_batch_packed), so a host-side plan bug cannot
# leak another tenant's docs into a top-k.
# ---------------------------------------------------------------------------


@dataclass
class PackedField:
    """One field's postings for ALL members, concatenated on device."""

    name: str
    doc_ids: jax.Array  # i32[NT_total, TILE], GLOBAL ids, sentinel = N_total
    tfs: jax.Array  # f32[NT_total, TILE]
    tn: jax.Array  # f32[NT_total, TILE] per-posting impacts (per-member stats)
    norm_bytes: jax.Array  # u8[N_total + 1]
    present: jax.Array  # bool[N_total]
    tile_base: dict[int, int]  # member index -> first global tile
    views: dict[int, DeviceField]  # member index -> compile view


@dataclass
class PackedPlane:
    """Several small DeviceSegments concatenated into shared tile planes."""

    num_docs: int  # total packed doc space (sum of member doc spaces)
    doc_base: list[int]  # member index -> global doc-id base
    doc_count: list[int]  # member index -> member doc-space size
    fields: dict[str, PackedField]
    live: jax.Array  # bool[N_total], concat of member live masks

    @property
    def n_members(self) -> int:
        return len(self.doc_base)

    def member_bounds(self, member: int) -> tuple[int, int]:
        """GLOBAL [lo, hi) doc-id bounds of one member — the per-tenant
        mask the packed kernel applies so no cross-tenant doc can appear
        in this member's results."""
        lo = self.doc_base[member]
        return lo, lo + self.doc_count[member]

    def member_fields(self, member: int) -> dict[str, DeviceField]:
        """Compile views for one member: a dict shaped exactly like
        DeviceSegment.fields, sharing the packed device arrays."""
        return {
            name: pf.views[member]
            for name, pf in self.fields.items()
            if member in pf.views
        }


def pack_field_packed(
    name: str,
    members: list[tuple[DeviceField | None, int, int]],
    n_total: int,
) -> PackedField | None:
    """Concatenate one field's member planes into a packed field.

    `members`: (DeviceField or None when the member lacks the field,
    member doc base, member doc-space size) per member, in member order.
    Returns None when no member has the field.

    Doc ids are rewritten to global ids with the member's padding sentinel
    (its local num_docs) mapped to the GLOBAL sentinel n_total; norm bytes
    and presence land at the member's doc range (absent members contribute
    zeros so a stray gather reads norm 0 / not-present, never another
    tenant's bytes).
    """
    if not any(df is not None for df, _b, _n in members):
        return None
    id_parts, tf_parts, tn_parts = [], [], []
    norm_parts, present_parts = [], []
    tile_base: dict[int, int] = {}
    shifted: list[tuple[int, DeviceField, int, int, int]] = []
    tiles = 0
    for m, (dfield, base, n_member) in enumerate(members):
        if dfield is None:
            norm_parts.append(np.zeros(n_member, dtype=np.uint8))
            present_parts.append(np.zeros(n_member, dtype=bool))
            continue
        ids = dfield.doc_ids
        # Sentinel rewrite BEFORE the base shift: a member pad slot must
        # scatter into the plane's own discard slot, not into the doc
        # range of whichever tenant happens to follow.
        id_parts.append(
            jnp.where(
                ids == jnp.int32(n_member),
                jnp.int32(n_total),
                ids + jnp.int32(base),
            )
        )
        tf_parts.append(dfield.tfs)
        tn_parts.append(dfield.tn)
        norm_parts.append(np.asarray(dfield.norm_bytes)[:n_member])
        present_parts.append(np.asarray(dfield.present)[:n_member])
        tile_base[m] = tiles
        shifted.append((m, dfield, base, n_member, tiles))
        tiles += dfield.num_tiles
    doc_ids = jnp.concatenate(id_parts, axis=0)
    tfs = jnp.concatenate(tf_parts, axis=0)
    tn = jnp.concatenate(tn_parts, axis=0)
    norm_bytes = jax.device_put(
        np.concatenate(norm_parts + [np.zeros(1, dtype=np.uint8)])
    )
    present = jax.device_put(np.concatenate(present_parts))
    views: dict[int, DeviceField] = {}
    for m, dfield, base, n_member, tbase in shifted:
        lo, hi = dfield.tile_doc_lo, dfield.tile_doc_hi
        if lo is not None:
            # Global per-tile doc bounds (the tile_doc_bounds machinery's
            # packed form): real ids shift by the member base; a bound that
            # IS the member sentinel stays the (global) sentinel so range
            # pruning remains conservative at partially-padded tiles.
            lo = np.where(lo == n_member, n_total, lo + base).astype(np.int64)
            hi = np.where(hi == n_member, n_total, hi + base).astype(np.int64)
        views[m] = DeviceField(
            name=name,
            terms=dfield.terms,
            df=dfield.df,
            # Posting positions shift with the member's tile range, so the
            # unmodified Compiler plans straight into packed coordinates.
            offsets=dfield.offsets + np.int64(tbase * TILE),
            doc_count=dfield.doc_count,
            sum_total_tf=dfield.sum_total_tf,
            has_norms=dfield.has_norms,
            doc_ids=doc_ids,
            tfs=tfs,
            norm_bytes=norm_bytes,
            present=present,
            tn=tn,
            tn_avgdl=dfield.tn_avgdl,
            tn_k1=dfield.tn_k1,
            tn_b=dfield.tn_b,
            tile_max=(
                None
                if dfield.tile_max is None
                else _shifted_tile_plane(dfield.tile_max, tbase, tiles)
            ),
            tile_doc_lo=_shifted_tile_plane(lo, tbase, tiles, fill=n_total),
            tile_doc_hi=_shifted_tile_plane(hi, tbase, tiles, fill=n_total),
            device=dfield.device,
        )
    return PackedField(
        name=name,
        doc_ids=doc_ids,
        tfs=tfs,
        tn=tn,
        norm_bytes=norm_bytes,
        present=present,
        tile_base=tile_base,
        views=views,
    )


def _shifted_tile_plane(
    local: np.ndarray | None, tile_base: int, total_tiles: int, fill=0.0
):
    """Host per-tile metadata (tile_max / doc bounds) placed at the
    member's global tile range; other members' tiles carry `fill` (their
    entries are only ever indexed through THIS member's tile ids, which
    stay in range by construction — fill is belt-and-braces)."""
    if local is None:
        return None
    out = np.full(total_tiles, fill, dtype=np.asarray(local).dtype)
    out[tile_base : tile_base + len(local)] = local
    return out


def pack_segments_packed(
    segments: list[DeviceSegment],
) -> PackedPlane:
    """Concatenate several small DeviceSegments into one PackedPlane.

    Member order fixes the tenant-id dimension: member m owns doc range
    [doc_base[m], doc_base[m] + num_docs). Only inverted fields pack
    (doc-values / vectors / positions / nested stay per-tenant — the
    packed backend's eligibility gate routes queries needing them to the
    per-tenant path). Device arrays are concatenated on device; no host
    round-trip of postings.
    """
    doc_base: list[int] = []
    doc_count: list[int] = []
    n_total = 0
    for seg in segments:
        doc_base.append(n_total)
        doc_count.append(seg.num_docs)
        n_total += seg.num_docs
    field_names = sorted({n for seg in segments for n in seg.fields})
    fields: dict[str, PackedField] = {}
    for name in field_names:
        members = [
            (seg.fields.get(name), doc_base[m], seg.num_docs)
            for m, seg in enumerate(segments)
        ]
        pf = pack_field_packed(name, members, n_total)
        if pf is not None:
            fields[name] = pf
    live = jnp.concatenate([seg.live for seg in segments])
    return PackedPlane(
        num_docs=n_total,
        doc_base=doc_base,
        doc_count=doc_count,
        fields=fields,
        live=live,
    )


def packed_device_nbytes(plane: PackedPlane) -> int:
    """Device bytes the packed plane itself holds (it duplicates member
    postings — the price of one-launch multi-tenant scoring)."""
    total = plane.live.nbytes
    for pf in plane.fields.values():
        total += pf.doc_ids.nbytes + pf.tfs.nbytes + pf.tn.nbytes
        total += pf.norm_bytes.nbytes + pf.present.nbytes
    return int(total)


def tile_doc_bounds(
    doc_ids: np.ndarray, num_docs: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-tile (min, max) doc id over a host postings array, padded the
    way pack_field pads (sentinel num_docs; bounds stay conservative).
    The host-side planning twin of DeviceField.tile_doc_lo/hi for paths
    that never pack a DeviceField (the sharded compiler's _PlanField)."""
    padded = _pad_to_tile(doc_ids.astype(np.int32), np.int32(num_docs))
    tiles = padded.reshape(-1, TILE)
    return tiles.min(axis=1), tiles.max(axis=1)


def _fit_bool(present: np.ndarray, norm_bytes: np.ndarray, num_docs: int) -> np.ndarray:
    # FieldIndex instances predating the presence bitmap (direct
    # construction, old serialized forms) fall back to norm-byte presence —
    # the same fallback the oracle uses, so the two sides never diverge
    # silently. Padding docs (sharded stacking) are never present.
    src = present if len(present) else norm_bytes > 0
    out = np.zeros(num_docs, dtype=bool)
    out[: len(src)] = src[:num_docs]
    return out


def device_nbytes(seg: DeviceSegment) -> int:
    """Actual device bytes held by a packed segment (HBM accounting)."""
    total = seg.live.nbytes
    for f in seg.fields.values():
        total += f.doc_ids.nbytes + f.tfs.nbytes + f.tn.nbytes
        total += f.norm_bytes.nbytes + f.present.nbytes
        if f.ord_terms is not None:
            total += f.ord_terms.nbytes
        if f.pos_doc is not None:
            total += f.pos_doc.nbytes + f.pos_val.nbytes
    for col in seg.doc_values.values():
        total += col.nbytes
    for mat in seg.vectors.values():
        total += mat.nbytes
    for inner, parent_of in seg.nested.values():
        total += device_nbytes(inner) + parent_of.nbytes
    return int(total)


def estimate_segment_device_bytes(segment: Segment) -> int:
    """Upper-ish estimate of a host Segment's packed device footprint,
    computed BEFORE the pack so the HBM breaker can reject the upload
    instead of OOMing the device."""
    n = segment.num_docs
    total = n  # live mask
    for f in segment.fields.values():
        p_pad = (len(f.doc_ids) // TILE + 2) * TILE
        total += p_pad * 12  # doc_ids + tfs + tn (i32/f32/f32)
        total += (n + 1) + n  # norm bytes + present
        if not f.has_norms and len(f.df):
            total += p_pad * 4  # keyword ordinals plane
        if f.positions is not None:
            pp_pad = (len(f.positions) // TILE + 2) * TILE
            total += pp_pad * 8  # pos_doc + pos_val
    total += 4 * n * len(segment.doc_values)
    for mat in segment.vectors.values():
        total += 4 * n * mat.shape[1]
    for block in segment.nested.values():
        total += estimate_segment_device_bytes(block.seg)
        total += 4 * block.seg.num_docs  # parent_of plane
    return int(total)


def pack_segment(
    segment: Segment,
    device=None,
    deleted: np.ndarray | None = None,
    pad_docs_to: int = 0,
    field_min_tiles: dict[str, int] | None = None,
    field_avgdl: dict[str, float] | None = None,
    k1: float = 1.2,
    b: float = 0.75,
    field_pos_min_tiles: dict[str, int] | None = None,
    stack_axis: bool = False,
) -> DeviceSegment:
    """Upload a whole Segment to the device (the 'refresh' step).

    `pad_docs_to` / `field_min_tiles` pad doc and tile axes so that several
    shards' segments stack into one leading-axis array for mesh sharding
    (padding docs are dead: live=False, doc values NaN, never present).
    `field_avgdl` supplies the statistics scope for precomputed impacts.
    `stack_axis` uploads every plane as a [1, ...] shard row (pack_field).
    """
    n = max(segment.num_docs, pad_docs_to)
    put = _putter(device, stack_axis)
    min_tiles = field_min_tiles or {}
    avgdls = field_avgdl or {}
    pos_min_tiles = field_pos_min_tiles or {}
    fields = {
        name: pack_field(
            f,
            n,
            device,
            min_tiles.get(name, 0),
            avgdls.get(name),
            k1,
            b,
            pos_min_tiles.get(name, 0),
            stack_axis,
        )
        for name, f in segment.fields.items()
    }
    doc_values = {}
    for name, col in segment.doc_values.items():
        padded = np.full(n, np.nan, dtype=np.float32)
        padded[: len(col)] = col.astype(np.float32)
        doc_values[name] = put(padded)
    vectors = {}
    for name, mat in segment.vectors.items():
        padded = np.zeros((n, mat.shape[1]), dtype=np.float32)
        padded[: len(mat)] = mat
        vectors[name] = put(padded)
    live = np.zeros(n, dtype=bool)
    live[: segment.num_docs] = True
    if deleted is not None and len(deleted):
        live[deleted] = False
    nested = {
        path: (
            pack_segment(
                block.seg, device=device, k1=k1, b=b, stack_axis=stack_axis
            ),
            put(block.parent_of.astype(np.int32)),
        )
        for path, block in segment.nested.items()
    }
    return DeviceSegment(
        num_docs=n,
        fields=fields,
        doc_values=doc_values,
        vectors=vectors,
        live=put(live),
        sources=segment.sources,
        ids=segment.ids,
        nested=nested,
    )


