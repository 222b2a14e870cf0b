"""Snapshot/restore: fs repository, incremental blobs, rename, GC.

Reference: repositories/blobstore/BlobStoreRepository.java:157,
repositories/fs/FsRepository.java, RestoreService.
"""

import json
import os

import pytest

from elasticsearch_tpu.node import ApiError, Node
from elasticsearch_tpu.rest.server import RestServer

MAPPINGS = {
    "properties": {
        "t": {"type": "text"},
        "k": {"type": "keyword"},
        "n": {"type": "long"},
    }
}


def seed(node, index, n=30, n_shards=1):
    node.create_index(
        index,
        {
            "settings": {"index": {"number_of_shards": n_shards}},
            "mappings": MAPPINGS,
        },
    )
    for i in range(n):
        node.index_doc(
            index, {"t": f"w{i % 4} text", "k": f"k{i % 3}", "n": i}, f"d{i}"
        )
    node.refresh(index)


def test_snapshot_restore_roundtrip(tmp_path):
    node = Node()
    seed(node, "src", n=40, n_shards=2)
    node.delete_doc("src", "d7", refresh=True)
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    out = node.create_snapshot("repo", "snap1", {})
    assert out["snapshot"]["state"] == "SUCCESS"
    assert out["snapshot"]["indices"] == ["src"]

    out = node.restore_snapshot(
        "repo",
        "snap1",
        {"rename_pattern": "src", "rename_replacement": "copy"},
    )
    assert out["snapshot"]["indices"] == ["copy"]
    r_src = node.search("src", {"query": {"match": {"t": "w2"}}, "size": 50})
    r_copy = node.search("copy", {"query": {"match": {"t": "w2"}}, "size": 50})
    assert r_copy["hits"]["total"]["value"] == r_src["hits"]["total"]["value"]
    assert {h["_id"] for h in r_copy["hits"]["hits"]} == {
        h["_id"] for h in r_src["hits"]["hits"]
    }
    assert node.get_doc("copy", "d7")["found"] is False  # delete survived
    # versions/seqnos preserved through restore
    a = node.get_doc("src", "d3")
    b = node.get_doc("copy", "d3")
    assert a["_version"] == b["_version"] and a["_seq_no"] == b["_seq_no"]
    # restored index accepts writes with seqno continuity
    resp = node.index_doc("copy", {"t": "new", "n": 99}, "d3")
    assert resp["_seq_no"] > b["_seq_no"]


def test_shards_restore_side_by_side_with_every_count_kept(tmp_path):
    """An index's shards restore in threads: under a short switch
    interval, every shard's docs, the breaker and the HBM ledger still
    agree with a one-at-a-time build."""
    import sys

    node = Node()
    seed(node, "wide", n=400, n_shards=8)
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s", {})
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        node.restore_snapshot(
            "repo", "s",
            {"rename_pattern": "wide", "rename_replacement": "copy"},
        )
    finally:
        sys.setswitchinterval(interval)
    src, copy = node.get_index("wide"), node.get_index("copy")
    assert [e.num_docs for e in copy.engines] == [e.num_docs for e in src.engines]
    assert sum(e.num_docs for e in copy.engines) == 400
    hbm = node.nodes_stats()["nodes"][node.node_name]["device"]["hbm"]
    assert hbm["breaker_drift_bytes"] == 0
    assert node.hbm_ledger.bytes_for("segment") == sum(
        e.device_bytes for e in src.engines + copy.engines
    )
    body = {"query": {"match": {"t": "w1"}}, "size": 200}
    assert sorted(h["_id"] for h in node.search("copy", body)["hits"]["hits"]) == sorted(
        h["_id"] for h in node.search("wide", body)["hits"]["hits"]
    )


def test_restore_collision_and_missing(tmp_path):
    node = Node()
    seed(node, "a", n=5)
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s1", {})
    with pytest.raises(ApiError):  # existing open index
        node.restore_snapshot("repo", "s1", {})
    with pytest.raises(ApiError):
        node.get_snapshot("repo", "nope")
    with pytest.raises(ApiError):
        node.create_snapshot("repo", "s1", {})  # duplicate name
    with pytest.raises(ApiError):
        node.create_snapshot("repo", "s2", {"indices": "missing_index"})


def test_incremental_blobs_and_gc(tmp_path):
    node = Node()
    seed(node, "inc", n=20)
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s1", {})
    blob_root = tmp_path / "r" / "blobs"
    blobs_after_s1 = set(os.listdir(blob_root))
    # second snapshot with no changes: shares every blob
    node.create_snapshot("repo", "s2", {})
    assert set(os.listdir(blob_root)) == blobs_after_s1
    # new segment -> exactly the new blobs are added
    node.index_doc("inc", {"t": "fresh", "n": 999}, "new", refresh=True)
    node.create_snapshot("repo", "s3", {})
    blobs_after_s3 = set(os.listdir(blob_root))
    assert blobs_after_s1 < blobs_after_s3
    # deleting s3 GCs only its unshared blobs
    node.delete_snapshot("repo", "s3")
    assert set(os.listdir(blob_root)) == blobs_after_s1
    node.delete_snapshot("repo", "s1")
    node.delete_snapshot("repo", "s2")
    assert set(os.listdir(blob_root)) == set()
    with pytest.raises(ApiError):
        node.delete_snapshot("repo", "s1")


def test_snapshot_rest_and_repo_persistence(tmp_path):
    node = Node(data_path=str(tmp_path / "data"))
    rest = RestServer(node=node)
    seed(node, "r1", n=10)
    status, resp = rest.dispatch(
        "PUT",
        "/_snapshot/backups",
        {},
        json.dumps(
            {"type": "fs", "settings": {"location": str(tmp_path / "repo")}}
        ),
    )
    assert status == 200 and resp["acknowledged"]
    status, resp = rest.dispatch(
        "PUT", "/_snapshot/backups/nightly", {}, json.dumps({"indices": "r1"})
    )
    assert status == 200
    status, resp = rest.dispatch("GET", "/_snapshot/backups/_all", {}, "")
    assert status == 200
    assert [s["snapshot"] for s in resp["snapshots"]] == ["nightly"]
    node.flush("r1")
    node.close()

    # repository registration survives restart; restore over REST works
    node2 = Node(data_path=str(tmp_path / "data"))
    rest2 = RestServer(node=node2)
    status, resp = rest2.dispatch("GET", "/_snapshot/backups", {}, "")
    assert status == 200 and "backups" in resp
    status, resp = rest2.dispatch(
        "POST",
        "/_snapshot/backups/nightly/_restore",
        {},
        json.dumps(
            {"rename_pattern": "r1", "rename_replacement": "r1_restored"}
        ),
    )
    assert status == 200
    r = node2.search("r1_restored", {"query": {"match_all": {}}, "size": 0})
    assert r["hits"]["total"]["value"] == 10
    # restored into a durable node: survives another restart
    node2.flush("r1_restored")
    node2.close()
    node3 = Node(data_path=str(tmp_path / "data"))
    assert node3.get_index("r1_restored").num_docs == 10
    node3.close()


def test_restore_preserves_tombstones_and_seqno_highwater(tmp_path):
    node = Node()
    node.create_index("s", {"mappings": MAPPINGS})
    node.index_doc("s", {"t": "x", "n": 1}, "doc1")  # seqno 0
    node.delete_doc("s", "doc1")  # seqno 1 (tombstone only)
    node.refresh("s")
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s1", {})
    node.restore_snapshot(
        "repo", "s1", {"rename_pattern": "^s$", "rename_replacement": "s2"}
    )
    # next write must take a FRESH seqno (the delete op's seqno lived only
    # in the op maps) and continue doc1's version line
    resp = node.index_doc("s2", {"t": "y", "n": 2}, "doc1")
    assert resp["_seq_no"] >= 2
    assert resp["_version"] == 3  # v1 index, v2 delete, v3 re-create


def test_restore_validates_all_targets_first(tmp_path):
    node = Node()
    seed(node, "a", n=4)
    seed(node, "b", n=4)
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s1", {})
    node.delete_index("a")  # "a" restorable, "b" collides
    with pytest.raises(ApiError):
        node.restore_snapshot("repo", "s1", {"indices": "a,b"})
    # nothing was partially restored
    assert "a" not in node.indices


def test_blob_dedup_survives_restart(tmp_path):
    node = Node(data_path=str(tmp_path / "data"))
    seed(node, "p", n=15)
    node.flush("p")
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s1", {})
    blobs1 = set(os.listdir(tmp_path / "r" / "blobs"))
    node.close()
    node2 = Node(data_path=str(tmp_path / "data"))
    node2.create_snapshot("repo", "s2", {})
    assert set(os.listdir(tmp_path / "r" / "blobs")) == blobs1
    node2.close()


def test_restore_rejects_duplicate_and_bad_rename_targets(tmp_path):
    node = Node()
    seed(node, "aa", n=3)
    seed(node, "bb", n=3)
    node.put_repository(
        "repo", {"type": "fs", "settings": {"location": str(tmp_path / "r")}}
    )
    node.create_snapshot("repo", "s1", {})
    with pytest.raises(ApiError):  # both rename to "same" — duplicate
        node.restore_snapshot(
            "repo", "s1",
            {"rename_pattern": "..", "rename_replacement": "same"},
        )
    assert "same" not in node.indices  # nothing partially restored
    with pytest.raises(ApiError):  # malformed regex -> 400, not 500
        node.restore_snapshot(
            "repo", "s1",
            {"rename_pattern": "[", "rename_replacement": "x"},
        )


def test_unsupported_repo_type_rejected():
    node = Node()
    with pytest.raises(ApiError):
        node.put_repository("s3repo", {"type": "s3", "settings": {}})
    with pytest.raises(ApiError):
        node.put_repository("bad", {"type": "fs", "settings": {}})
