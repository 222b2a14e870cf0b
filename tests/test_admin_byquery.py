"""Aliases, dynamic settings, admin surface, and by-query operations.

Reference: aliases (metadata/AliasMetadata + TransportIndicesAliases),
update-settings action, cat APIs, and the reindex module
(delete_by_query/update_by_query/reindex).
"""

import json

import jax
import pytest

from elasticsearch_tpu.node import ApiError, Node
from elasticsearch_tpu.rest.server import RestServer

MAPPINGS = {"properties": {"t": {"type": "text"}, "n": {"type": "long"}}}


def seed(node, index="a", n=30, **extra):
    node.create_index(index, {"mappings": MAPPINGS, **extra})
    for i in range(n):
        node.index_doc(index, {"t": f"w{i % 3} body", "n": i}, f"d{i}")
    node.refresh(index)


def test_alias_crud_and_resolution():
    node = Node()
    seed(node, "logs-1")
    node.update_aliases(
        {"actions": [{"add": {"index": "logs-1", "alias": "logs"}}]}
    )
    # search/doc APIs resolve the alias
    r = node.search("logs", {"query": {"match_all": {}}, "size": 0})
    assert r["hits"]["total"]["value"] == 30
    assert node.get_doc("logs", "d3")["found"]
    node.index_doc("logs", {"t": "via alias", "n": 99}, "extra", refresh=True)
    assert node.get_doc("logs-1", "extra")["found"]
    # listing
    out = node.get_aliases()
    assert out["logs-1"]["aliases"] == {"logs": {}}
    # ambiguous alias rejects
    seed(node, "logs-2", n=3)
    node.update_aliases(
        {"actions": [{"add": {"index": "logs-2", "alias": "logs"}}]}
    )
    with pytest.raises(ApiError):
        node.search("logs", {})
    node.delete_alias("logs-2", "logs")
    assert node.search("logs", {"size": 0})["hits"]["total"]["value"] == 31
    # deleting the index drops its aliases
    node.delete_index("logs-1")
    assert "logs" not in node.aliases


def test_alias_name_collisions():
    node = Node()
    seed(node, "x", n=1)
    seed(node, "y", n=1)
    with pytest.raises(ApiError):
        node.update_aliases(
            {"actions": [{"add": {"index": "x", "alias": "y"}}]}
        )
    node.update_aliases({"actions": [{"add": {"index": "x", "alias": "al"}}]})
    with pytest.raises(ApiError):
        node.create_index("al", {})


def test_create_index_with_aliases_and_persistence(tmp_path):
    node = Node(data_path=str(tmp_path))
    node.create_index("base", {"aliases": {"current": {}}})
    node.index_doc("current", {"t": "hello"}, "1", refresh=True)
    node.close()
    node2 = Node(data_path=str(tmp_path))
    assert node2.get_doc("current", "1")["found"]
    node2.close()


def test_dynamic_settings():
    node = Node()
    seed(node)
    node.put_pipeline(
        "tagger", {"processors": [{"set": {"field": "tagged", "value": 1}}]}
    )
    node.put_settings("a", {"index": {"default_pipeline": "tagger"}})
    node.index_doc("a", {"t": "x", "n": 1}, "new", refresh=True)
    assert node.get_doc("a", "new")["_source"]["tagged"] == 1
    out = node.get_settings("a")
    assert out["a"]["settings"]["index"]["default_pipeline"] == "tagger"
    # dotted form + merge settings reach the engines
    node.put_settings("a", {"index.merge.max_segment_count": 3})
    assert node.get_index("a").engines[0].max_segments == 3
    with pytest.raises(ApiError):  # static setting
        node.put_settings("a", {"index": {"number_of_shards": 4}})


def test_index_info_and_cat_apis():
    node = Node()
    seed(node, "info", n=5, settings={"index": {"number_of_shards": 2}})
    rest = RestServer(node=node)
    status, r = rest.dispatch("GET", "/info", {}, "")
    assert status == 200
    assert r["info"]["settings"]["index"]["number_of_shards"] == "2"  # settings serialize as strings, like the reference
    assert "t" in r["info"]["mappings"]["properties"]
    status, _ = rest.dispatch("HEAD", "/info", {}, "")
    assert status == 200
    status, _ = rest.dispatch("HEAD", "/missing", {}, "")
    assert status == 404
    status, r = rest.dispatch("GET", "/_cat/health", {}, "")
    assert r[0]["status"] == "green"
    status, r = rest.dispatch("GET", "/_cat/count/info", {}, "")
    assert r[0]["count"] == "5"
    status, r = rest.dispatch("GET", "/_cat/shards", {}, "")
    assert len([x for x in r if x["index"] == "info"]) == 2
    status, r = rest.dispatch("GET", "/_cat/segments", {}, "")
    assert any(x["index"] == "info" for x in r)
    status, r = rest.dispatch("GET", "/_cluster/stats", {}, "")
    assert r["indices"]["count"] >= 1
    status, r = rest.dispatch("GET", "/_nodes", {}, "")
    assert "node-0" in r["nodes"]
    accelerator = r["nodes"]["node-0"]["accelerator"]
    assert accelerator["platform"] == "cpu" and accelerator["device_count"] == 8
    assert accelerator["device_kind"] == jax.devices()[0].device_kind


@pytest.mark.parametrize("n_shards", [1, 3])
def test_delete_by_query(n_shards):
    node = Node()
    seed(node, n=30, settings={"index": {"number_of_shards": n_shards}})
    out = node.delete_by_query(
        "a", {"query": {"match": {"t": "w1"}}}, refresh=True
    )
    expected = len([i for i in range(30) if i % 3 == 1])
    assert out["deleted"] == out["total"] == expected
    r = node.search("a", {"query": {"match_all": {}}, "size": 0})
    assert r["hits"]["total"]["value"] == 30 - expected
    # no matches second time
    out = node.delete_by_query("a", {"query": {"match": {"t": "w1"}}})
    assert out["deleted"] == 0


def test_update_by_query_with_pipeline():
    node = Node()
    seed(node, n=12)
    node.put_pipeline(
        "mark", {"processors": [{"set": {"field": "marked", "value": True}}]}
    )
    out = node.update_by_query(
        "a", {"query": {"range": {"n": {"lt": 5}}}},
        refresh=True, pipeline="mark",
    )
    assert out["updated"] == out["total"] == 5
    r = node.search(
        "a", {"query": {"term": {"marked": True}}, "size": 0}
    )
    # marked is dynamically mapped boolean
    assert r["hits"]["total"]["value"] == 5
    with pytest.raises(ApiError):
        node.update_by_query("a", {"script": {"source": "x"}})


def test_reindex_with_query_and_pipeline():
    node = Node()
    seed(node, "src9", n=20)
    node.put_pipeline(
        "stamp", {"processors": [{"set": {"field": "copied", "value": 1}}]}
    )
    out = node.reindex(
        {
            "source": {"index": "src9", "query": {"range": {"n": {"gte": 10}}}},
            "dest": {"index": "dst9", "pipeline": "stamp"},
        },
        refresh=True,
    )
    assert out["created"] == out["total"] == 10
    r = node.search("dst9", {"query": {"match_all": {}}, "size": 0})
    assert r["hits"]["total"]["value"] == 10
    assert node.get_doc("dst9", "d15")["_source"]["copied"] == 1
    # reindex again: existing ids update, not duplicate
    out = node.reindex(
        {"source": {"index": "src9"}, "dest": {"index": "dst9"}},
        refresh=True,
    )
    assert out["updated"] == 10 and out["created"] == 10
    with pytest.raises(ApiError):
        node.reindex({"source": {"index": "missing"}, "dest": {"index": "x"}})


def test_aliases_atomic_and_delete_protection():
    node = Node()
    seed(node, "at1", n=2)
    with pytest.raises(ApiError):  # second action invalid -> nothing applies
        node.update_aliases(
            {
                "actions": [
                    {"add": {"index": "at1", "alias": "ok"}},
                    {"add": {"index": "missing", "alias": "bad"}},
                ]
            }
        )
    assert "ok" not in node.aliases
    with pytest.raises(ApiError):  # remove of absent alias -> 404
        node.update_aliases(
            {"actions": [{"remove": {"index": "at1", "alias": "nope"}}]}
        )
    node.update_aliases({"actions": [{"add": {"index": "at1", "alias": "al"}}]})
    with pytest.raises(ApiError):  # deleting via alias is rejected
        node.delete_index("al")
    assert "at1" in node.indices
    with pytest.raises(ApiError):  # GET missing index aliases -> 404
        node.get_aliases("zzz")


def test_reindex_edge_cases():
    node = Node()
    seed(node, "re1", n=4)
    out = node.reindex(
        {
            "source": {"index": "re1", "query": {"term": {"t": "absent"}}},
            "dest": {"index": "fresh"},
        }
    )
    assert out["total"] == 0 and "fresh" in node.indices  # 200, dest created
    with pytest.raises(ApiError):
        node.reindex({"source": {"index": "re1"}, "dest": {"index": "re1"}})
    node.update_aliases({"actions": [{"add": {"index": "re1", "alias": "rale"}}]})
    with pytest.raises(ApiError):  # alias resolving to the source
        node.reindex({"source": {"index": "re1"}, "dest": {"index": "rale"}})


def test_max_result_window_enforced():
    node = Node()
    seed(node, n=5)
    with pytest.raises(ApiError):
        node.search("a", {"from": 9995, "size": 10})
    node.put_settings("a", {"index": {"max_result_window": 50}})
    with pytest.raises(ApiError):
        node.search("a", {"size": 60})
    assert node.search("a", {"size": 50})["hits"]["total"]["value"] == 5


def test_update_by_query_collects_per_doc_failures():
    node = Node()
    node.create_index("f", {"mappings": {"properties": {"n": {"type": "long"}}}})
    node.index_doc("f", {"n": 1}, "1", refresh=True)
    node.put_pipeline(
        "breaker",
        {"processors": [{"set": {"field": "n", "value": "not-a-number"}}]},
    )
    out = node.update_by_query("f", {}, refresh=True, pipeline="breaker")
    assert out["updated"] == 0
    assert len(out["failures"]) == 1 and out["failures"][0]["id"] == "1"


def test_byquery_rest_routes():
    rest = RestServer()
    seed(rest.node, "r", n=9)
    status, r = rest.dispatch(
        "POST",
        "/r/_delete_by_query",
        {"refresh": "true"},
        json.dumps({"query": {"range": {"n": {"lt": 3}}}}),
    )
    assert status == 200 and r["deleted"] == 3
    status, r = rest.dispatch(
        "POST", "/r/_update_by_query", {"refresh": "true"}, ""
    )
    assert status == 200 and r["updated"] == 6
    status, r = rest.dispatch(
        "POST",
        "/_reindex",
        {"refresh": "true"},
        json.dumps({"source": {"index": "r"}, "dest": {"index": "r2"}}),
    )
    assert status == 200 and r["created"] == 6
