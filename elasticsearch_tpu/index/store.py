"""On-disk segment store + commit points.

The durability half of the engine: immutable segments persist as
npz + JSON metadata + JSONL sources (the analog of Lucene segment files
written under FsDirectoryFactory, reference server/src/main/java/org/
elasticsearch/index/store/FsDirectoryFactory.java:36), and a commit point
records the live segment set plus the highest persisted seqno (the analog
of InternalEngine.commitIndexWriter embedding translog metadata in the
Lucene commit user-data). Commits replace atomically via tmp+rename, so a
crash mid-flush falls back to the previous consistent commit.

Layout under the shard data path:
    seg-<id>.npz        posting/doc-value arrays (immutable)
    seg-<id>.meta.json  term dicts, stats, doc ids (immutable)
    seg-<id>.src.jsonl  stored _source per local doc (immutable)
    seg-<id>.live.npz   live-docs mask (rewritten per flush: deletions)
    commit.json         {"segments": [...], "max_seqno": N}
    translog/           WAL (see translog.py)
"""

from __future__ import annotations

import json
import os
from collections.abc import Sequence
from typing import Any

import numpy as np

from .segment import FieldIndex, NestedBlock, Segment

_COMMIT = "commit.json"


def _segment_arrays(
    segment: Segment, key_prefix: str = ""
) -> tuple[dict[str, np.ndarray], dict[str, Any]]:
    """Flatten one segment into (npz arrays, JSON meta); nested blocks
    recurse with a path-indexed key prefix so everything lives in the same
    npz/meta pair."""
    arrays: dict[str, np.ndarray] = {}
    meta: dict[str, Any] = {
        "num_docs": segment.num_docs,
        "ids": segment.ids,
        "fields": {},
        "doc_values": list(segment.doc_values),
        "vectors": list(segment.vectors),
    }
    for i, (name, fld) in enumerate(sorted(segment.fields.items())):
        pre = f"{key_prefix}f{i}"
        meta["fields"][name] = {
            "key": pre,
            "terms": fld.terms,
            "doc_count": fld.doc_count,
            "sum_total_tf": fld.sum_total_tf,
            "has_norms": fld.has_norms,
        }
        arrays[f"{pre}_df"] = fld.df
        arrays[f"{pre}_offsets"] = fld.offsets
        arrays[f"{pre}_doc_ids"] = fld.doc_ids
        arrays[f"{pre}_tfs"] = fld.tfs
        arrays[f"{pre}_norm_bytes"] = fld.norm_bytes
        arrays[f"{pre}_present"] = fld.present
        if fld.positions is not None:
            arrays[f"{pre}_pos_offsets"] = fld.pos_offsets
            arrays[f"{pre}_positions"] = fld.positions
    for j, (name, col) in enumerate(sorted(segment.doc_values.items())):
        arrays[f"{key_prefix}dv{j}"] = col
    for j, (name, mat) in enumerate(sorted(segment.vectors.items())):
        arrays[f"{key_prefix}vec{j}"] = mat
    if segment.versions is not None:
        arrays[f"{key_prefix}doc_versions"] = segment.versions
    if segment.seqnos is not None:
        arrays[f"{key_prefix}doc_seqnos"] = segment.seqnos
    if segment.completion:
        meta["completion"] = {
            f: [list(e) for e in entries]
            for f, entries in segment.completion.items()
        }
    if segment.percolator:
        meta["percolator"] = {
            f: [[int(doc), q] for doc, q in entries]
            for f, entries in segment.percolator.items()
        }
    if segment.nested:
        meta["nested"] = {}
        for ni, (npath, block) in enumerate(sorted(segment.nested.items())):
            npre = f"{key_prefix}n{ni}_"
            sub_arrays, sub_meta = _segment_arrays(block.seg, npre)
            # Nested object sources are NOT persisted: every object already
            # exists verbatim inside its parent's _source in the jsonl
            # sidecar, and the inner segment's sources are not consulted at
            # search time (fetch reads parent sources).
            arrays.update(sub_arrays)
            arrays[f"{npre}parent_of"] = block.parent_of
            meta["nested"][npath] = {"key": npre, "meta": sub_meta}
    return arrays, meta


def _segment_from(
    data, meta: dict[str, Any], key_prefix: str = "", sources=None
) -> Segment:
    """Inverse of _segment_arrays (sources supplied out-of-band for the
    top level, inline in meta for nested blocks)."""
    fields: dict[str, FieldIndex] = {}
    for name, fm in meta["fields"].items():
        pre = fm["key"]
        fields[name] = FieldIndex(
            name=name,
            terms=fm["terms"],
            df=data[f"{pre}_df"],
            offsets=data[f"{pre}_offsets"],
            doc_ids=data[f"{pre}_doc_ids"],
            tfs=data[f"{pre}_tfs"],
            norm_bytes=data[f"{pre}_norm_bytes"],
            doc_count=fm["doc_count"],
            sum_total_tf=fm["sum_total_tf"],
            has_norms=fm["has_norms"],
            present=data[f"{pre}_present"],
            pos_offsets=(
                data[f"{pre}_pos_offsets"]
                if f"{pre}_pos_offsets" in data
                else None
            ),
            positions=(
                data[f"{pre}_positions"]
                if f"{pre}_positions" in data
                else None
            ),
        )
    doc_values = {
        name: data[f"{key_prefix}dv{j}"]
        for j, name in enumerate(sorted(meta["doc_values"]))
    }
    vectors = {
        name: data[f"{key_prefix}vec{j}"]
        for j, name in enumerate(sorted(meta["vectors"]))
    }
    completion = {
        f: [tuple(e) for e in entries]
        for f, entries in (meta.get("completion") or {}).items()
    }
    percolator = {
        f: [(int(doc), q) for doc, q in entries]
        for f, entries in (meta.get("percolator") or {}).items()
    }
    nested = {}
    for npath, entry in (meta.get("nested") or {}).items():
        npre = entry["key"]
        sub_meta = entry["meta"]
        nested[npath] = NestedBlock(
            seg=_segment_from(data, sub_meta, npre, sources=[]),
            parent_of=data[f"{npre}parent_of"],
        )
    return Segment(
        num_docs=meta["num_docs"],
        fields=fields,
        doc_values=doc_values,
        vectors=vectors,
        sources=sources if sources is not None else [],
        ids=list(meta["ids"]),
        versions=(
            data[f"{key_prefix}doc_versions"]
            if f"{key_prefix}doc_versions" in data
            else None
        ),
        seqnos=(
            data[f"{key_prefix}doc_seqnos"]
            if f"{key_prefix}doc_seqnos" in data
            else None
        ),
        nested=nested,
        completion=completion,
        percolator=percolator,
    )


def _run_len(run) -> int:
    return len(run) if isinstance(run, list) else len(run[1])


class StoredSources(Sequence):
    """A segment's `_source` per local doc once the segment was loaded
    from disk: the stored JSON lines, decoded when a doc's source is read
    (fetch, GET, reindex), so loading a shard parses none of them — most
    of the host time of a restore or recovery. Each read of a stored line
    returns a fresh object. Compaction (`take_sources`) and concatenation
    with other segments (`concat_sources`) keep the lines undecoded; a
    concatenated in-memory segment's sources ride along as a list run."""

    def __init__(self, runs: list):
        # Each run: (blob, line starts, line ends), or a list of sources.
        self._runs = [r for r in runs if _run_len(r)]
        self._bounds = np.cumsum([0] + [_run_len(r) for r in self._runs])

    @classmethod
    def from_lines(cls, blob: bytes) -> "StoredSources":
        ends = np.flatnonzero(np.frombuffer(blob, dtype=np.uint8) == 10) + 1
        starts = np.concatenate([[0], ends[:-1]]) if len(ends) else ends
        return cls([(blob, starts.astype(np.int64), ends.astype(np.int64))])

    def __len__(self) -> int:
        return int(self._bounds[-1])

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        n = len(self)
        if i < 0:
            i += n
        if not 0 <= i < n:
            raise IndexError("source index out of range")
        r = int(np.searchsorted(self._bounds, i, side="right")) - 1
        run, j = self._runs[r], i - int(self._bounds[r])
        if isinstance(run, list):
            return run[j]
        blob, starts, ends = run
        return json.loads(blob[starts[j]:ends[j]])

    def __iter__(self):
        for run in self._runs:
            if isinstance(run, list):
                yield from run
            else:
                blob, starts, ends = run
                for a, b in zip(starts.tolist(), ends.tolist()):
                    yield json.loads(blob[a:b])

    def __eq__(self, other) -> bool:
        if not isinstance(other, Sequence) or isinstance(other, (str, bytes)):
            return NotImplemented
        return len(self) == len(other) and all(a == b for a, b in zip(self, other))

    def _take(self, idx: np.ndarray) -> "StoredSources":
        runs = []
        for r, run in enumerate(self._runs):
            lo, hi = self._bounds[r], self._bounds[r + 1]
            sel = idx[(idx >= lo) & (idx < hi)] - lo
            if isinstance(run, list):
                runs.append([run[j] for j in sel.tolist()])
            else:
                blob, starts, ends = run
                runs.append((blob, starts[sel], ends[sel]))
        return StoredSources(runs)

    def _lines(self):
        for run in self._runs:
            if isinstance(run, list):
                yield from _json_lines(run)
            else:
                blob, starts, ends = run
                for a, b in zip(starts.tolist(), ends.tolist()):
                    yield blob[a:b]


def _json_lines(sources):
    for src in sources:
        yield (json.dumps(src, separators=(",", ":")) + "\n").encode()


def take_sources(sources, idx: np.ndarray):
    """The sources of the docs `idx` (ascending local ids), stored lines
    left undecoded."""
    if isinstance(sources, StoredSources):
        return sources._take(np.asarray(idx, dtype=np.int64))
    return [sources[int(i)] for i in idx]


def concat_sources(seqs: list):
    """Several segments' sources in order, stored lines left undecoded."""
    if not any(isinstance(s, StoredSources) for s in seqs):
        return [src for s in seqs for src in s]
    runs: list = []
    for s in seqs:
        runs.extend(s._runs if isinstance(s, StoredSources) else [list(s)])
    return StoredSources(runs)


def persist_segment(path: str, seg_id: int, segment: Segment) -> None:
    """Write one immutable segment (postings + doc values + sources)."""
    arrays, meta = _segment_arrays(segment)
    base = os.path.join(path, f"seg-{seg_id}")
    with open(base + ".npz", "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    sources = segment.sources
    with open(base + ".src.jsonl", "wb") as f:
        for line in (
            sources._lines() if isinstance(sources, StoredSources)
            else _json_lines(sources)
        ):
            f.write(line)
        f.flush()
        os.fsync(f.fileno())
    with open(base + ".meta.json", "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())


def persist_live(path: str, seg_id: int, live: np.ndarray) -> None:
    """Rewrite a segment's live-docs mask (deletions since last flush)."""
    target = os.path.join(path, f"seg-{seg_id}.live.npz")
    tmp = target + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, live=live)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, target)


def load_segment(path: str, seg_id: int) -> tuple[Segment, np.ndarray]:
    """Load (segment, live_mask) written by persist_segment/persist_live."""
    base = os.path.join(path, f"seg-{seg_id}")
    with open(base + ".meta.json") as f:
        meta = json.load(f)
    data = np.load(base + ".npz")
    with open(base + ".src.jsonl", "rb") as f:
        sources = StoredSources.from_lines(f.read())
    segment = _segment_from(data, meta, sources=sources)
    live_path = base + ".live.npz"
    if os.path.exists(live_path):
        live = np.load(live_path)["live"]
    else:
        live = np.ones(segment.num_docs, dtype=bool)
    return segment, live


def write_commit(path: str, commit: dict[str, Any]) -> None:
    tmp = os.path.join(path, _COMMIT + ".tmp")
    with open(tmp, "w") as f:
        json.dump(commit, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(path, _COMMIT))


def read_commit(path: str) -> dict[str, Any] | None:
    target = os.path.join(path, _COMMIT)
    if not os.path.exists(target):
        return None
    with open(target) as f:
        return json.load(f)


def gc_segments(path: str, referenced: set[int]) -> None:
    """Delete segment files not referenced by the current commit."""
    for name in os.listdir(path):
        if not name.startswith("seg-"):
            continue
        try:
            seg_id = int(name.split("-")[1].split(".")[0])
        except (IndexError, ValueError):
            continue
        if seg_id not in referenced:
            try:
                os.remove(os.path.join(path, name))
            except FileNotFoundError:
                pass
