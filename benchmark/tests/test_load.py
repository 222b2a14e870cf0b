"""The snapshot repository the benchmark keeps holds what `_bulk` +
`_refresh` + `_forcemerge` would have built from the same passages, shard
by shard as the program routes each `_id`; a run restores it from the
cache the first run left, and refuses an index laid out otherwise."""

import copy
import glob
import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import load, run
from benchmark.gen import corpus as C
from benchmark.gen.queries import INDEX

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "configs", "msmarco-passage-1of4.json")
# `_digest` of the repository the loader wrote at 400 passages in one
# shard before it routed passages to shards: one shard must stay as it was.
ONE_SHARD_400 = "62111b098a275e6374f606b20b1a9ac4165f0eb9726401023e82ed8baae0410a"


def _config(docs: int, shards: int = 1) -> dict:
    cfg = copy.deepcopy(json.load(open(CONFIG)))
    cfg["corpus"]["docs"] = docs
    cfg["index"]["settings"]["index"]["number_of_shards"] = shards
    return cfg


def _manifest_shards(repo: str) -> list[dict]:
    [path] = glob.glob(os.path.join(repo, "snapshots", "*.json"))
    return json.load(open(path))["indices"][INDEX]["shards"]


def _blob(repo: str, blob: str):
    base = os.path.join(repo, "blobs", blob, "seg-1")
    return (dict(np.load(base + ".npz")), json.load(open(base + ".meta.json")),
            open(base + ".src.jsonl").read())


def _digest(repo: str) -> str:
    """sha256 of every blob and live file, by path, and the manifest's
    shard entries (the rest of the manifest holds a uuid and times)."""
    h = hashlib.sha256()
    for sub in ("blobs", os.path.join("snapshots", load.SNAPSHOT)):
        for dp, _, files in sorted(os.walk(os.path.join(repo, sub))):
            for f in sorted(files):
                p = os.path.join(dp, f)
                h.update(os.path.relpath(p, repo).encode() + b"\0")
                with open(p, "rb") as fh:
                    h.update(fh.read())
    h.update(json.dumps(_manifest_shards(repo), sort_keys=True).encode())
    return h.hexdigest()


def _bulk(node, corpus) -> None:
    lines = []
    for d in range(corpus.n_docs):
        toks = corpus.tokens[corpus.offsets[d]:corpus.offsets[d + 1]]
        lines.append(json.dumps({"index": {"_id": str(d)}}))
        lines.append(json.dumps({"body": " ".join(C.term_name(int(t)) for t in toks),
                                 "tag": corpus.tag_values[corpus.tags[d]]}))
    for lo in range(0, len(lines), 200):
        resp = node.bulk("\n".join(lines[lo:lo + 200]) + "\n", default_index=INDEX)
        assert not resp["errors"]


@pytest.mark.parametrize("shards", [1, 4])
def test_snapshot_blob_equals_a_bulk_built_segment(tmp_path, shards):
    cfg = _config(400, shards)
    settings = cfg["index"]["settings"]
    corpus = C.generate(cfg)
    bulk_repo, my_repo = str(tmp_path / "bulk"), str(tmp_path / "mine")
    s = run.Server()
    try:
        node = s.rest.node
        node.create_index(INDEX, {"settings": settings, "mappings": load.mappings()})
        _bulk(node, corpus)
        node.refresh(INDEX)
        node.force_merge(INDEX, max_num_segments=1)
        node.put_repository("r", {"type": "fs", "settings": {"location": bulk_repo}})
        node.create_snapshot("r", "s", {"indices": INDEX})
        s.call("DELETE", f"/{INDEX}")
        assert load.ensure_repo(s.call, corpus, settings, my_repo) is False
    finally:
        s.close()
    bulk_shards, my_shards = _manifest_shards(bulk_repo), _manifest_shards(my_repo)
    assert len(bulk_shards) == len(my_shards) == shards
    for bulk, mine in zip(bulk_shards, my_shards):
        assert mine["max_seqno"] == bulk["max_seqno"]
        [bulk_seg], [my_seg] = bulk["segments"], mine["segments"]
        b_arrays, b_meta, b_src = _blob(bulk_repo, bulk_seg["blob"])
        m_arrays, m_meta, m_src = _blob(my_repo, my_seg["blob"])
        assert m_src == b_src and m_meta == b_meta
        assert sorted(m_arrays) == sorted(b_arrays)
        differ = [k for k in m_arrays if m_arrays[k].dtype != b_arrays[k].dtype
                  or not np.array_equal(m_arrays[k], b_arrays[k])]
        assert not differ
        b_live = np.load(os.path.join(bulk_repo, "snapshots", "s", bulk_seg["live"]))
        m_live = np.load(os.path.join(my_repo, "snapshots", load.SNAPSHOT, my_seg["live"]))
        assert np.array_equal(m_live, b_live)
    assert sum(mine["max_seqno"] + 1 for mine in my_shards) == corpus.n_docs


def test_one_shard_writes_what_it_wrote_before_routing(tmp_path):
    cfg = _config(400)
    corpus = C.generate(cfg)
    s = run.Server()
    try:
        load.ensure_repo(s.call, corpus, cfg["index"]["settings"], str(tmp_path / "repo"))
    finally:
        s.close()
    assert _digest(str(tmp_path / "repo")) == ONE_SHARD_400


def test_shard_of_is_the_programs_routing():
    from elasticsearch_tpu.parallel.routing import shard_for_id

    for shards in (1, 2, 3, 4, 8):
        of = load.shard_of(20000, shards)
        assert [int(x) for x in of] == [shard_for_id(str(d), shards) for d in range(20000)]
    big = load.shard_of(8_841_823, 4)
    sample = np.random.default_rng(7).integers(0, 8_841_823, 2000)
    assert all(big[d] == shard_for_id(str(int(d)), 4) for d in sample)
    assert load.shard_counts(400, 4) == [94, 81, 117, 108]


def test_four_shards_restore_with_each_passage_where_its_id_routes(tmp_path):
    cfg = _config(400, 4)
    corpus = C.generate(cfg)
    repo = str(tmp_path / "repo")
    s = run.Server()
    try:
        load.ensure_repo(s.call, corpus, cfg["index"]["settings"], repo)
        load.restore(s.call, repo)
        docs = {int(r["shard"]): int(r["docs"]) for r in s.call("GET", "/_cat/shards?format=json")
                if r["index"] == INDEX}
        assert [docs[i] for i in range(4)] == load.shard_counts(400, 4) == [94, 81, 117, 108]
        for d in np.random.default_rng(3).choice(400, 40, replace=False).tolist():
            got = s.call("GET", f"/{INDEX}/_doc/{d}")
            toks = corpus.tokens[corpus.offsets[d]:corpus.offsets[d + 1]]
            assert got["found"], d
            assert got["_source"]["body"] == " ".join(C.term_name(int(t)) for t in toks)
    finally:
        s.close()


@pytest.mark.parametrize("shards", [1, 4])
def test_repository_is_built_once_and_restored_from_the_cache(tmp_path, shards):
    cfg = _config(300, shards)
    settings = cfg["index"]["settings"]
    c1, hit1 = C.load(cfg, str(tmp_path))
    c2, hit2 = C.load(cfg, str(tmp_path))
    assert (hit1, hit2) == (False, True)
    assert (c1.tokens == c2.tokens).all() and (c1.df == c2.df).all() and (c1.tags == c2.tags).all()
    repo = str(tmp_path / "repo")
    answers = []
    for want_hit in (False, True):
        s = run.Server()
        try:
            assert load.ensure_repo(s.call, c1, settings, repo) is want_hit
            load.restore(s.call, repo)
            assert s.call("GET", f"/{INDEX}/_count")["count"] == 300
            answers.append(s.call("POST", f"/{INDEX}/_search",
                                  {"query": {"match": {"body": "t00000 t00005 t00300"}}, "size": 10}))
        finally:
            s.close()
    hits = [[(h["_id"], h["_score"]) for h in a["hits"]["hits"]] for a in answers]
    assert hits[0] == hits[1] and len(hits[0]) == 10


def test_open_session_refuses_a_layout_the_routing_does_not_make(tmp_path, monkeypatch):
    """Every passage in shard 0 of a four-shard index, as the loader laid
    a repository out before it routed passages: `_count` is right, and
    the run still refuses before any traffic."""
    spec = run.load_spec()
    cell = spec["workloads"][0]["name"]
    cfg = _config(2000, 4)

    def all_in_shard_0(corpus, shards):
        [whole] = load.split(corpus, 1)
        empty = load.Shard(whole.ids[:0], whole.tokens[:0], whole.lengths[:0], whole.tags[:0],
                           whole.vocab, whole.tag_values)
        return [whole] + [empty] * (shards - 1)

    monkeypatch.setattr(run, "CACHE_ROOT", str(tmp_path))
    real_split = load.split
    monkeypatch.setattr(load, "split", lambda corpus, shards: (
        all_in_shard_0(corpus, shards) if shards > 1 else real_split(corpus, shards)))
    with pytest.raises(RuntimeError, match=r"docs per shard \[2000, 0, 0, 0\]"):
        run.open_session(cell, spec=spec, config=cfg, require_tpu=False)
