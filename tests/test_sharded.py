"""Sharded (multi-device) search over the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import Mappings
from elasticsearch_tpu.parallel.routing import murmur3_hash, shard_for_id
from elasticsearch_tpu.parallel.sharded import ShardedIndex
from elasticsearch_tpu.query.dsl import parse_query
from elasticsearch_tpu.search.service import SearchRequest, SearchService

VOCAB = [
    "alpha", "bravo", "charlie", "delta", "echo", "foxtrot", "golf",
    "hotel", "india", "juliet", "kilo", "lima",
]


def make_docs(n=200, seed=11):
    rng = np.random.default_rng(seed)
    mappings = Mappings(
        properties={
            "body": {"type": "text"},
            "tag": {"type": "keyword"},
            "rank": {"type": "long"},
        }
    )
    docs = []
    for i in range(n):
        docs.append(
            (
                f"doc{i}",
                {
                    "body": " ".join(rng.choice(VOCAB, rng.integers(3, 30))),
                    "tag": str(rng.choice(["red", "green", "blue"])),
                    "rank": int(rng.integers(0, 100)),
                },
            )
        )
    return mappings, docs


@pytest.fixture(scope="module")
def mesh():
    devices = np.array(jax.devices()[:8])
    return Mesh(devices, ("shard",))


@pytest.fixture(scope="module")
def sharded(mesh):
    mappings, docs = make_docs()
    return ShardedIndex.from_docs(docs, mappings, mesh), mappings, docs


def single_engine_reference(mappings, docs, query_json, k):
    """Single-shard reference via the engine/service path."""
    engine = Engine(mappings)
    for doc_id, src in docs:
        engine.index(src, doc_id)
    engine.refresh()
    service = SearchService(engine)
    resp = service.search(SearchRequest.from_json({"query": query_json, "size": k}))
    return resp


QUERIES = [
    {"match": {"body": "alpha"}},
    {"match": {"body": "alpha bravo charlie"}},
    {"bool": {"must": [{"match": {"body": "delta"}}], "filter": [{"term": {"tag": "red"}}]}},
    {"bool": {"must": [{"match": {"body": "echo foxtrot"}}], "must_not": [{"range": {"rank": {"lt": 50}}}]}},
    {"match_all": {}},
]


@pytest.mark.parametrize("query_json", QUERIES)
def test_sharded_matches_single_shard(sharded, query_json):
    """8-way sharded search must agree with the single-shard engine on
    totals, scores, and hit ids (global DFS stats make scores identical)."""
    index, mappings, docs = sharded
    k = 10
    scores, gids, total = index.search(parse_query(query_json), k)
    ref = single_engine_reference(mappings, docs, query_json, k)
    assert total == ref.total
    got_ids = []
    for g in gids:
        shard, local = index.locate(g)
        got_ids.append(index.segments[shard].ids[local])
    ref_ids = [h.doc_id for h in ref.hits]
    ref_scores = [h.score for h in ref.hits]
    # Scores must match to fp32 tolerance; ids must match except where equal
    # scores allow different (but valid) tie orders across shard layouts.
    np.testing.assert_allclose(scores, ref_scores[: len(scores)], rtol=1e-5, atol=1e-6)
    for got, want, s_got, s_want in zip(got_ids, ref_ids, scores, ref_scores):
        if got != want:
            assert s_got == pytest.approx(s_want, rel=1e-5), (
                f"different doc {got} vs {want} without a score tie"
            )


def test_sharded_total_and_k_trim(sharded):
    index, mappings, docs = sharded
    scores, gids, total = index.search(parse_query({"match": {"body": "zzz"}}), 10)
    assert total == 0 and len(scores) == 0


def test_murmur3_known_values():
    """Murmur3 x86_32 reference vectors (public algorithm test vectors)."""
    from elasticsearch_tpu.parallel.routing import murmur3_32

    assert murmur3_32(b"") == 0
    assert murmur3_32(b"hello") == 613153351
    # String routing hashes UTF-16-LE bytes, matching the reference's
    # Murmur3HashFunction two-bytes-per-char layout.
    assert murmur3_hash("") == 0
    assert murmur3_hash("hello") == murmur3_32("hello".encode("utf-16-le"))
    # Distribution sanity + floorMod semantics for negative hashes.
    shards = [shard_for_id(f"doc{i}", 8) for i in range(1000)]
    counts = np.bincount(shards, minlength=8)
    assert counts.min() > 60  # roughly uniform
    assert all(0 <= s < 8 for s in shards)


def test_routing_is_stable(sharded):
    index, mappings, docs = sharded
    for doc_id, _ in docs[:20]:
        s = shard_for_id(doc_id, index.n_shards)
        assert doc_id in index.segments[s].ids


def test_pack_plan_round_trips_every_leaf_bit_for_bit():
    """A stacked plan travels as one int32 buffer a shard; inside the
    program each leaf comes back with its dtype, shape and bits."""
    from elasticsearch_tpu.parallel.sharded import _unpack_plan, pack_plan

    rng = np.random.default_rng(3)
    arrays = {
        "tile_ids": rng.integers(0, 1 << 20, (4, 64)).astype(np.int32),
        "weights": rng.standard_normal((4, 64)).astype(np.float32),
        "n_starts": rng.integers(0, 9, (4, 2)).astype(np.int32),
        "ub_n": np.array([0.5, np.nan, -0.0, 1e30], dtype=np.float32),
        "total_floor": np.arange(4, dtype=np.int32),
    }
    buf, layout = pack_plan(arrays)
    assert buf.shape == (4, 64 + 64 + 2 + 1 + 1) and buf.dtype == np.int32
    for s in range(4):
        got = jax.jit(lambda row: _unpack_plan(row, layout))(buf[s])
        for key, want in arrays.items():
            assert got[key].dtype == want.dtype and got[key].shape == want.shape[1:]
            assert np.asarray(got[key]).tobytes() == want[s].tobytes(), key
    with pytest.raises(TypeError):
        pack_plan({"mask": np.zeros((4, 8), dtype=bool)})
