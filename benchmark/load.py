"""Load the corpus into the node through REST snapshot restore.

Re-ingesting 2.2M passages through `_bulk` would cost ~9 minutes per run
on the chip host (~244 us/doc, PERF.md), so the node takes the index from
an fs snapshot repository with `POST /_snapshot/<repo>/<snap>/_restore`,
the path users take to bring an index back.

The repository is written once per checkout and kept under
benchmark/.cache/, keyed by the corpus key and the hash of this file. The
program writes all of it: each shard is what its own `SegmentBuilder`
builds from the passages' text (analysis, inversion, norms, `_source`),
persisted by its `store.persist_segment`, and the manifest is its snapshot
of the same index created empty (uuid, settings, mappings). The one edit
is the manifest's list of each shard's segments, which gains that shard's
blob: one segment, as `_forcemerge` to one segment leaves it.

The index has the configuration's `number_of_shards`, laid out as `_bulk`
lays it out: passage d, `_id` str(d), goes to shard floorMod(murmur3(
str(d)), N) (`shard_of`, the program's `parallel/routing.shard_for_id`
in numpy), in ascending d, numbered by sequence number from 0 in its
shard. Shards build in worker processes, as many at once as the host's
cores and memory allow.
"""

from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import shutil
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass

import numpy as np

from .gen.corpus import Corpus, term_name
from .gen.queries import INDEX

REPO = "bench"
SNAPSHOT = "corpus"
DONE = "COMPLETE"
# Host bytes a build worker holds per passage at its peak: ~4.3 KB
# measured on a TPU v5e host at 2.2M passages a shard, with room.
BUILD_BYTES_PER_PASSAGE = 5120


def mappings() -> dict:
    return {"properties": {"body": {"type": "text"}, "tag": {"type": "keyword"}}}


def n_shards(settings: dict) -> int:
    return int(settings.get("index", {}).get("number_of_shards", 1))


# ------------------------------------------------------------------ routing

_C1, _C2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _mixed(chars: np.ndarray) -> np.ndarray:
    """murmur3's mix of one block k."""
    return _rotl(chars * _C1, 15) * _C2


# The mixed block of each two-digit pair "ab", at 10 a + b (UTF-16-LE:
# a, 0, b, 0, so k = a | b << 16), and of each one-digit tail (a, 0: k = a).
_DIGITS = np.arange(10, dtype=np.uint32) + 48
_PAIR = _mixed((_DIGITS[:, None] | _DIGITS[None, :] << np.uint32(16)).ravel())
_TAIL = _mixed(_DIGITS)


def _murmur3_ids(ids: np.ndarray, digits: int) -> np.ndarray:
    """uint32 murmur3_32 (seed 0) of str(d) as UTF-16-LE, for ids of
    `digits` decimal digits."""
    x = ids.astype(np.uint32)
    tail = None
    if digits % 2:
        x, tail = np.divmod(x, np.uint32(10))
    pairs = []
    for _ in range(digits // 2):
        x, p = np.divmod(x, np.uint32(100))
        pairs.append(p)
    h = np.zeros(len(ids), dtype=np.uint32)
    for p in reversed(pairs):
        h = _rotl(h ^ _PAIR[p], 13) * np.uint32(5) + np.uint32(0xE6546B64)
    if tail is not None:
        h ^= _TAIL[tail]
    h ^= np.uint32(2 * digits)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def shard_of(n_docs: int, shards: int) -> np.ndarray:
    """int32[n_docs]: the shard of passage d's `_id` str(d) in an index of
    `shards` shards, as the program routes `_bulk` and GET
    (`parallel.routing.shard_for_id`), checked against it on a sample."""
    if shards == 1:
        return np.zeros(n_docs, dtype=np.int32)
    out = np.empty(n_docs, dtype=np.int32)
    lo = 0
    for digits in range(1, len(str(max(n_docs - 1, 0))) + 1):
        hi = min(n_docs, 10 ** digits)
        signed = _murmur3_ids(np.arange(lo, hi, dtype=np.int64), digits).view(np.int32)
        out[lo:hi] = np.mod(signed.astype(np.int64), shards)
        lo = hi
    from elasticsearch_tpu.parallel.routing import shard_for_id

    sample = np.unique(np.linspace(0, n_docs - 1, min(n_docs, 4096)).astype(np.int64))
    wrong = [int(d) for d in sample if out[d] != shard_for_id(str(d), shards)]
    if wrong:
        raise RuntimeError(f"shard_of disagrees with the program's routing on _id {wrong[:8]}")
    return out


def shard_counts(n_docs: int, shards: int) -> list[int]:
    """Passages the routing puts in each shard."""
    return np.bincount(shard_of(n_docs, shards), minlength=shards).tolist()


# ------------------------------------------------------------------ shards


@dataclass
class Shard:
    """The passages of one shard, in ascending passage number."""

    ids: np.ndarray  # int64[n]: passage numbers, each passage's `_id`
    tokens: np.ndarray  # uint16[T]: their tokens, passage after passage
    lengths: np.ndarray  # int64[n]
    tags: np.ndarray  # int8[n]
    vocab: int
    tag_values: tuple[str, ...]


def split(corpus: Corpus, shards: int) -> list[Shard]:
    """The corpus's passages, shard by shard as the routing lays them out."""
    of = shard_of(corpus.n_docs, shards)
    of_token = np.repeat(of.astype(np.min_scalar_type(shards)), corpus.lengths)
    parts = []
    for i in range(shards):
        ids = np.flatnonzero(of == i)
        parts.append(Shard(ids, corpus.tokens[of_token == i], corpus.lengths[ids],
                           corpus.tags[ids], corpus.vocab, corpus.tag_values))
    return parts


def sources(shard: Shard):
    """Each passage's `_source`: its text, space-joined terms, and its tag."""
    names = np.array([term_name(t) for t in range(shard.vocab)])
    offsets = np.zeros(len(shard.lengths) + 1, dtype=np.int64)
    np.cumsum(shard.lengths, out=offsets[1:])
    for i in range(len(shard.lengths)):
        yield {"body": " ".join(names[shard.tokens[offsets[i]:offsets[i + 1]]]),
               "tag": shard.tag_values[shard.tags[i]]}


def build_segment(shard: Shard):
    """The program's own segment of one shard: `SegmentBuilder` over its
    passages, `_id` the passage number, sequence numbers 0, 1, ... in
    passage order, as `_bulk` numbers a shard's operations."""
    from elasticsearch_tpu.index import Mappings, SegmentBuilder

    builder = SegmentBuilder(Mappings.from_json(mappings()))
    for seqno, (d, src) in enumerate(zip(shard.ids.tolist(), sources(shard))):
        builder.add(src, str(d), seqno=seqno)
    return builder.build()


def _persist(blob_dir: str, shard: Shard) -> None:
    from elasticsearch_tpu.index import store

    store.persist_segment(blob_dir, 1, build_segment(shard))


def _worker_init() -> None:
    # The parent holds the chip; a worker builds on the host alone.
    os.environ["JAX_PLATFORMS"] = "cpu"


def _workers(parts: list[Shard]) -> int:
    """Build processes to run at once: one per non-empty shard, within the
    host's cores and three quarters of its available memory."""
    with open("/proc/meminfo") as f:
        avail = next(int(line.split()[1]) * 1024 for line in f if line.startswith("MemAvailable:"))
    biggest = max(len(p.ids) for p in parts) * BUILD_BYTES_PER_PASSAGE + 1
    busy = sum(len(p.ids) > 0 for p in parts)
    return max(1, min(busy, os.cpu_count() or 1, avail * 3 // 4 // biggest))


def build_shards(parts: list[Shard], blob_dirs: list[str]) -> None:
    """Persist each non-empty shard's segment into its blob directory."""
    todo = [(d, p) for d, p in zip(blob_dirs, parts) if len(p.ids)]
    for d, _ in todo:
        os.makedirs(d)
    workers = _workers(parts)
    if workers == 1:
        for d, p in todo:
            _persist(d, p)
        return
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx, initializer=_worker_init) as pool:
        for f in [pool.submit(_persist, d, p) for d, p in todo]:
            f.result()


# -------------------------------------------------------------- repository


def repo_key(corpus_key: str, settings: dict) -> str:
    h = hashlib.sha256((corpus_key + json.dumps(settings, sort_keys=True)).encode())
    with open(os.path.abspath(__file__), "rb") as f:
        h.update(f.read())
    return h.hexdigest()[:16]


def ensure_repo(call, corpus: Corpus, settings: dict, path: str) -> bool:
    """Write the snapshot repository at `path` unless a complete one is
    there; True when it was. `call(method, path, body)` is the client's
    JSON call."""
    if os.path.exists(os.path.join(path, DONE)):
        return True
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    call("PUT", f"/{INDEX}", {"settings": settings, "mappings": mappings()})
    call("PUT", f"/_snapshot/{REPO}", {"type": "fs", "settings": {"location": path}})
    call("PUT", f"/_snapshot/{REPO}/{SNAPSHOT}", {"indices": INDEX})
    call("DELETE", f"/{INDEX}")
    shards = n_shards(settings)
    parts = split(corpus, shards)
    blobs = ["corpus"] if shards == 1 else [f"corpus-s{i}" for i in range(shards)]
    build_shards(parts, [os.path.join(path, "blobs", b) for b in blobs])
    manifest_path = os.path.join(path, "snapshots", f"{SNAPSHOT}.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    for i, (blob, part) in enumerate(zip(blobs, parts)):
        shard = manifest["indices"][INDEX]["shards"][i]
        if len(part.ids):
            live = f"{INDEX}-s{i}-0.live.npy"
            np.save(os.path.join(path, "snapshots", SNAPSHOT, live),
                    np.ones(len(part.ids), dtype=bool), allow_pickle=False)
            shard["segments"] = [{"blob": blob, "live": live}]
        shard["max_seqno"] = len(part.ids) - 1
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(path, DONE), "w") as f:
        f.write("1\n")
    return False


def restore(call, path: str) -> None:
    """Restore the index from the repository at `path`."""
    call("PUT", f"/_snapshot/{REPO}", {"type": "fs", "settings": {"location": path}})
    call("POST", f"/_snapshot/{REPO}/{SNAPSHOT}/_restore", {"indices": INDEX})
