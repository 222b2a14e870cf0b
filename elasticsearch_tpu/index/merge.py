"""Tokenization-free segment merges: posting concatenation as array ops.

The delta-scaled replacement for the re-analysis merge loop (ROADMAP
item 4): where the old `Engine._merge_segments` pushed every live doc
back through `SegmentBuilder.add` — a full tokenizer pass over the whole
shard for a one-doc write — this module rebuilds the merged `Segment`
purely from the source segments' existing arrays, the way a Lucene merge
concatenates postings and remaps doc ids as sequential I/O
(reference: `index/engine/InternalEngine.java` refresh/merge path; Lucene
`SegmentMerger` never re-invokes the analysis chain).

Two composable primitives:

- `compact_segment(segment, live)` — one segment with its dead docs
  purged and locals renumbered (`np.flatnonzero(live)` gather). Pure
  per-segment work, so the mesh view caches the result per
  (handle uid, live epoch) and a refresh only compacts NEW handles.
- `concat_segments(segments)` — several all-live segments concatenated
  into one: per-field term-dictionary union, doc ids rebased by
  cumulative offsets, postings re-sorted term-major with a single stable
  argsort, stats folded arithmetically.

`merged_live_segment` is the one-call composition the engine merge uses.

The output is BIT-IDENTICAL to what `SegmentBuilder` would produce from
re-adding the same live docs in the same order (tests/test_merge_concat.py
asserts structural equality array-by-array, dtypes included), so search
behavior over a concat-merged segment is indistinguishable from the
re-analysis merge — same scores, same top-k, same totals — and the
existing merge/parity suites gate it. One documented edge: a vectors
field whose only surviving rows are explicit all-zero l2 vectors drops
where the builder would keep a zero matrix — behaviorally identical,
since every kNN kernel treats zero rows as vector-absent (see
compact_segment). No tokenizer runs anywhere in this module
(hook-counted via `estpu_analysis_calls_total`).
"""

from __future__ import annotations

from dataclasses import replace as dc_replace

import numpy as np

from .segment import FieldIndex, NestedBlock, Segment
from .store import concat_sources, take_sources


def _csr_term_of(fi: FieldIndex) -> np.ndarray:
    """int64[P]: owning term id of every posting (CSR expansion)."""
    return np.repeat(
        np.arange(fi.num_terms, dtype=np.int64),
        np.diff(fi.offsets).astype(np.int64),
    )


def _terms_by_tid(fi: FieldIndex) -> list[str]:
    """Term names indexed by term id (inverse of the terms dict)."""
    names: list[str] = [""] * fi.num_terms
    for term, tid in fi.terms.items():
        names[tid] = term
    return names


def _gather_csr(
    values: np.ndarray, offsets: np.ndarray, order: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Reorder a CSR payload by a row permutation/selection.

    `offsets` is int64[R+1] over rows; `order` names the surviving rows in
    output order. Returns (values', offsets') where row i of the output is
    the payload of input row order[i]. Fully vectorized (no per-row loop).
    """
    counts = np.diff(offsets).astype(np.int64)[order]
    out_off = np.zeros(len(order) + 1, dtype=np.int64)
    np.cumsum(counts, out=out_off[1:])
    total = int(out_off[-1])
    if total == 0:
        return values[:0].copy(), out_off
    starts = offsets[:-1][order]
    idx = (
        np.repeat(starts, counts)
        + np.arange(total, dtype=np.int64)
        - np.repeat(out_off[:-1], counts)
    )
    return values[idx], out_off


def _field_present(fi: FieldIndex) -> np.ndarray:
    """The presence bitmap, with the legacy norm-byte fallback the packer
    uses (tiles._fit_bool) so the two sides can never diverge."""
    if len(fi.present):
        return fi.present
    return fi.norm_bytes > 0


def compact_field(
    fi: FieldIndex, keep: np.ndarray, old_to_new: np.ndarray, n_new: int
) -> FieldIndex | None:
    """Live-only copy of one field, locals renumbered via `old_to_new`.

    Returns None when no surviving doc carries the field — exactly the
    condition under which a SegmentBuilder re-add would not register the
    field at all.
    """
    keep_idx = np.flatnonzero(keep)
    present = _field_present(fi)[keep_idx]
    post_keep = keep[fi.doc_ids]
    if not present.any() and not post_keep.any():
        return None
    term_of = _csr_term_of(fi)[post_keep]
    doc_ids = old_to_new[fi.doc_ids[post_keep]].astype(np.int32)
    tfs = fi.tfs[post_keep]
    df_full = np.bincount(term_of, minlength=fi.num_terms)
    keep_terms = df_full > 0
    # Surviving terms keep their sorted relative order, so renumbering is
    # a prefix-sum — the terms dict stays insertion-sorted like a fresh
    # SegmentBuilder build.
    new_tid = np.cumsum(keep_terms) - 1
    names = _terms_by_tid(fi)
    terms = {
        names[tid]: int(new_tid[tid]) for tid in np.flatnonzero(keep_terms)
    }
    df = df_full[keep_terms].astype(np.int32)
    offsets = np.zeros(len(df) + 1, dtype=np.int64)
    np.cumsum(df, out=offsets[1:])
    pos_offsets = positions = None
    if fi.positions is not None:
        positions, pos_offsets = _gather_csr(
            fi.positions, fi.pos_offsets, np.flatnonzero(post_keep)
        )
    norm_bytes = fi.norm_bytes[keep_idx]
    doc_count = int(np.count_nonzero(np.bincount(doc_ids, minlength=n_new)))
    sum_total_tf = int(round(float(tfs.astype(np.float64).sum())))
    return FieldIndex(
        name=fi.name,
        terms=terms,
        df=df,
        offsets=offsets,
        doc_ids=doc_ids,
        tfs=tfs,
        norm_bytes=norm_bytes,
        doc_count=doc_count,
        sum_total_tf=sum_total_tf,
        has_norms=fi.has_norms,
        present=present.copy(),
        pos_offsets=pos_offsets,
        positions=positions,
    )


def compact_segment(segment: Segment, live: np.ndarray) -> Segment:
    """Purge dead docs from one segment; locals renumber densely.

    `live` is bool[num_docs]; the output doc order is ascending old local
    id over live docs — the same order the re-analysis merge visits them.
    Nested blocks compact with their parents (an inner doc survives iff
    its parent does); inner ids regenerate as str(local) exactly like a
    fresh sub-builder.
    """
    live = np.asarray(live, dtype=bool)
    if live.all():
        return segment
    keep_idx = np.flatnonzero(live)
    n_new = len(keep_idx)
    old_to_new = np.full(segment.num_docs, -1, dtype=np.int64)
    old_to_new[keep_idx] = np.arange(n_new, dtype=np.int64)
    fields: dict[str, FieldIndex] = {}
    for name, fi in segment.fields.items():
        out = compact_field(fi, live, old_to_new, n_new)
        if out is not None:
            fields[name] = out
    doc_values = {}
    for name, col in segment.doc_values.items():
        new_col = col[keep_idx]
        if not np.all(np.isnan(new_col)):
            doc_values[name] = new_col
    vectors = {}
    for name, mat in segment.vectors.items():
        new_mat = mat[keep_idx]
        # Keep-iff-any-nonzero mirrors the kernels' uniform zero-row ⇒
        # no-vector rule (ops/ann_device._exact_inner, ann.py
        # build_partitions). DOCUMENTED EDGE vs the re-analysis oracle:
        # a doc that explicitly supplied an all-zero l2_norm vector is
        # indistinguishable from a doc without one at the array level,
        # so if ONLY such docs survive, the builder would keep an
        # all-zero matrix where this drops the field — behaviorally
        # identical everywhere (zero rows never enter a kNN hit set and
        # a missing field skips the segment the same way).
        if np.any(new_mat):
            vectors[name] = new_mat
    versions = (
        segment.versions[keep_idx]
        if segment.versions is not None
        else np.ones(n_new, dtype=np.int64)
    )
    seqnos = (
        segment.seqnos[keep_idx]
        if segment.seqnos is not None
        else np.full(n_new, -1, dtype=np.int64)
    )
    nested: dict[str, NestedBlock] = {}
    for path, block in segment.nested.items():
        inner_live = live[block.parent_of]
        inner = compact_segment(block.seg, inner_live)
        if inner.num_docs == 0:
            continue
        parent_of = old_to_new[
            block.parent_of[np.flatnonzero(inner_live)]
        ].astype(np.int32)
        inner = dc_replace(
            inner, ids=[str(i) for i in range(inner.num_docs)]
        )
        nested[path] = NestedBlock(seg=inner, parent_of=parent_of)
    completion = {}
    for name, entries in segment.completion.items():
        kept = [
            (norm, surface, weight, int(old_to_new[doc]))
            for norm, surface, weight, doc in entries
            if live[doc]
        ]
        if kept:
            completion[name] = sorted(kept)
    percolator = {}
    for name, entries in segment.percolator.items():
        kept = [
            (int(old_to_new[doc]), query)
            for doc, query in entries
            if live[doc]
        ]
        if kept:
            percolator[name] = kept
    return Segment(
        num_docs=n_new,
        fields=fields,
        doc_values=doc_values,
        vectors=vectors,
        sources=take_sources(segment.sources, keep_idx),
        ids=[segment.ids[int(i)] for i in keep_idx],
        versions=versions,
        seqnos=seqnos,
        nested=nested,
        completion=completion,
        percolator=percolator,
    )


def _concat_fields(
    members: list[tuple[FieldIndex | None, int, int]], union_names: list[str]
) -> FieldIndex:
    """Merge one field across members: (field or None, doc base, member
    doc count) per member, in member order; `union_names` is this field's
    sorted cross-member term vocabulary."""
    union = {name: i for i, name in enumerate(union_names)}
    t_union = len(union_names)
    term_parts, doc_parts, tf_parts = [], [], []
    pos_count_parts, pos_parts = [], []
    norm_parts, present_parts = [], []
    doc_count = 0
    sum_total_tf = 0
    has_norms = True
    # Text fields always carry (possibly empty) position arrays; every
    # member of one field shares the mapping, so either all non-None
    # members have them or none do.
    with_positions = any(
        fi is not None and fi.positions is not None for fi, _b, _n in members
    )
    for fi, base, n_member in members:
        if fi is None:
            norm_parts.append(np.zeros(n_member, dtype=np.uint8))
            present_parts.append(np.zeros(n_member, dtype=bool))
            continue
        has_norms = fi.has_norms
        names = _terms_by_tid(fi)
        tid_map = np.fromiter(
            (union[t] for t in names), dtype=np.int64, count=len(names)
        )
        term_parts.append(tid_map[_csr_term_of(fi)])
        doc_parts.append(fi.doc_ids.astype(np.int64) + base)
        tf_parts.append(fi.tfs)
        if with_positions:
            if fi.positions is not None:
                pos_count_parts.append(
                    np.diff(fi.pos_offsets).astype(np.int64)
                )
                pos_parts.append(fi.positions)
            else:  # defensive: a positionless member of a text field
                pos_count_parts.append(
                    np.zeros(len(fi.doc_ids), dtype=np.int64)
                )
        norm_parts.append(fi.norm_bytes)
        present_parts.append(_field_present(fi))
        doc_count += fi.doc_count
        sum_total_tf += fi.sum_total_tf
    term_of = (
        np.concatenate(term_parts)
        if term_parts
        else np.empty(0, dtype=np.int64)
    )
    # Stable sort: within a term, member order (ascending doc bases) and
    # each member's ascending locals are preserved — the merged postings
    # come out doc-ascending per term, exactly the builder layout.
    order = np.argsort(term_of, kind="stable")
    doc_ids = (
        np.concatenate(doc_parts)[order].astype(np.int32)
        if doc_parts
        else np.empty(0, dtype=np.int32)
    )
    tfs = (
        np.concatenate(tf_parts)[order]
        if tf_parts
        else np.empty(0, dtype=np.float32)
    )
    df = np.bincount(term_of, minlength=t_union).astype(np.int32)
    offsets = np.zeros(t_union + 1, dtype=np.int64)
    np.cumsum(df, out=offsets[1:])
    pos_offsets = positions = None
    if with_positions:
        counts = (
            np.concatenate(pos_count_parts)
            if pos_count_parts
            else np.empty(0, dtype=np.int64)
        )
        flat = (
            np.concatenate(pos_parts)
            if pos_parts
            else np.empty(0, dtype=np.int32)
        )
        src_off = np.zeros(len(counts) + 1, dtype=np.int64)
        np.cumsum(counts, out=src_off[1:])
        positions, pos_offsets = _gather_csr(flat, src_off, order)
    norm_bytes = np.concatenate(norm_parts)
    present = np.concatenate(present_parts)
    return FieldIndex(
        name=next(fi.name for fi, _b, _n in members if fi is not None),
        terms=union,
        df=df,
        offsets=offsets,
        doc_ids=doc_ids,
        tfs=tfs,
        norm_bytes=norm_bytes,
        doc_count=doc_count,
        sum_total_tf=sum_total_tf,
        has_norms=has_norms,
        present=present,
        pos_offsets=pos_offsets,
        positions=positions,
    )


def concat_segments(segments: list[Segment]) -> Segment:
    """Concatenate all-live segments into one (doc ids rebased in order).

    The pure-concatenation half of a merge: pair with `compact_segment`
    (dead docs already purged) to reproduce the re-analysis merge result
    exactly. A single input passes through untouched.
    """
    if len(segments) == 1:
        return segments[0]
    if not segments:  # an empty shard merges to an empty segment
        return Segment(
            num_docs=0,
            fields={},
            doc_values={},
            vectors={},
            sources=[],
            ids=[],
            versions=np.empty(0, dtype=np.int64),
            seqnos=np.empty(0, dtype=np.int64),
        )
    bases: list[int] = []
    n_total = 0
    for seg in segments:
        bases.append(n_total)
        n_total += seg.num_docs
    field_names = sorted({n for seg in segments for n in seg.fields})
    fields: dict[str, FieldIndex] = {}
    for name in field_names:
        vocab = sorted(
            {
                t
                for seg in segments
                if name in seg.fields
                for t in seg.fields[name].terms
            }
        )
        fields[name] = _concat_fields(
            [
                (seg.fields.get(name), bases[m], seg.num_docs)
                for m, seg in enumerate(segments)
            ],
            vocab,
        )
    doc_values: dict[str, np.ndarray] = {}
    for name in sorted({n for seg in segments for n in seg.doc_values}):
        col = np.full(n_total, np.nan, dtype=np.float64)
        for m, seg in enumerate(segments):
            src = seg.doc_values.get(name)
            if src is not None:
                col[bases[m] : bases[m] + seg.num_docs] = src
        doc_values[name] = col
    vectors: dict[str, np.ndarray] = {}
    for name in sorted({n for seg in segments for n in seg.vectors}):
        dim = next(
            seg.vectors[name].shape[1]
            for seg in segments
            if name in seg.vectors
        )
        mat = np.zeros((n_total, dim), dtype=np.float32)
        for m, seg in enumerate(segments):
            src = seg.vectors.get(name)
            if src is not None:
                mat[bases[m] : bases[m] + seg.num_docs] = src
        vectors[name] = mat
    versions = np.concatenate(
        [
            seg.versions
            if seg.versions is not None
            else np.ones(seg.num_docs, dtype=np.int64)
            for seg in segments
        ]
    )
    seqnos = np.concatenate(
        [
            seg.seqnos
            if seg.seqnos is not None
            else np.full(seg.num_docs, -1, dtype=np.int64)
            for seg in segments
        ]
    )
    nested: dict[str, NestedBlock] = {}
    for path in sorted({p for seg in segments for p in seg.nested}):
        inner_segs = []
        parent_parts = []
        for m, seg in enumerate(segments):
            block = seg.nested.get(path)
            if block is None:
                continue
            inner_segs.append(block.seg)
            parent_parts.append(
                block.parent_of.astype(np.int64) + bases[m]
            )
        inner = concat_segments(inner_segs)
        inner = dc_replace(
            inner, ids=[str(i) for i in range(inner.num_docs)]
        )
        nested[path] = NestedBlock(
            seg=inner,
            parent_of=np.concatenate(parent_parts).astype(np.int32),
        )
    completion: dict[str, list[tuple]] = {}
    for name in sorted({n for seg in segments for n in seg.completion}):
        entries: list[tuple] = []
        for m, seg in enumerate(segments):
            for norm, surface, weight, doc in seg.completion.get(name, ()):
                entries.append((norm, surface, weight, doc + bases[m]))
        completion[name] = sorted(entries)
    percolator: dict[str, list[tuple]] = {}
    for name in sorted({n for seg in segments for n in seg.percolator}):
        entries = []
        for m, seg in enumerate(segments):
            for doc, query in seg.percolator.get(name, ()):
                entries.append((doc + bases[m], query))
        percolator[name] = entries
    sources = concat_sources([seg.sources for seg in segments])
    ids: list[str] = []
    for seg in segments:
        ids.extend(seg.ids)
    return Segment(
        num_docs=n_total,
        fields=fields,
        doc_values=doc_values,
        vectors=vectors,
        sources=sources,
        ids=ids,
        versions=versions,
        seqnos=seqnos,
        nested=nested,
        completion=completion,
        percolator=percolator,
    )


def merged_live_segment(
    segments: list[Segment], live_masks: list[np.ndarray]
) -> Segment:
    """One live-docs-only segment from several (segment, live mask) pairs
    — the tokenization-free replacement for the SegmentBuilder re-add
    loop in `Engine._merge_segments` and `MeshView._merged_segment`."""
    return concat_segments(
        [
            compact_segment(seg, live)
            for seg, live in zip(segments, live_masks)
        ]
    )
