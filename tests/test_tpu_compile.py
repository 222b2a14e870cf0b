"""The served path's device programs compile for a TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a described `v5e:2x2`
topology, so what the chip's compiler would refuse (an unaligned slice, a
program that does not fit, a sharding it cannot partition) fails here at no
chip time. Nothing runs: these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and every test worker imports this file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from elasticsearch_tpu.index.mapping import Mappings
from elasticsearch_tpu.index.tiles import pack_segment
from elasticsearch_tpu.ops import bm25_device
from elasticsearch_tpu.parallel.sharded import (
    ShardedIndex,
    sharded_execute,
    pack_plan,
    sharded_execute_maxscore,
    stack_maxscore,
)
from elasticsearch_tpu.query.compile import Compiler
from elasticsearch_tpu.query.dsl import parse_query
from elasticsearch_tpu.utils.corpus import (
    build_zipf_segment,
    keyword_field,
    pick_query_terms,
)

TAGS = ("amber", "blue", "green", "red", "violet")
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topology = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topology


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding),
        tree,
    )


def _compile_single(one_chip, fn, seg_tree, compiled, *static):
    out = fn.lower(
        _shapes(seg_tree, one_chip), compiled.spec,
        _shapes(compiled.arrays, one_chip), *static,
    ).compile()
    mem = out.memory_analysis()
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    return out


@pytest.fixture(scope="module")
def million():
    """1M Zipf docs, packed."""
    mappings, segment = build_zipf_segment(1_000_000, vocab_size=30_000)
    return mappings, segment, pack_segment(segment)


def test_sparse_disjunction_at_1m_docs(one_chip, million):
    """cfg2's shape: a 4-term `match` disjunction over 1M Zipf docs, whose
    head term reaches the widest worklist bucket the smoke serves."""
    mappings, segment, dev = million
    terms = pick_query_terms(segment, np.random.default_rng(1), 1, 4)[0]
    compiled = Compiler(dev.fields, dev.doc_values, mappings).compile(
        parse_query({"match": {"body": " ".join(terms)}})
    )
    assert bm25_device.supports_sparse(compiled.spec)
    assert compiled.spec[2] >= 128  # worklist tiles of the head term
    _compile_single(
        one_chip, bm25_device.execute_sparse,
        bm25_device.segment_tree(dev), compiled, 10,
    )


def test_maxscore_disjunction_at_1m_docs(one_chip, million):
    """A stop word and three rare terms under the default track_total_hits:
    the MaxScore program reads the stop word's span in place, so its
    scratch memory stays well below one copy of the postings plane."""
    mappings, segment, dev = million
    fld = segment.fields["body"]
    by_df = sorted(fld.terms, key=lambda t: -fld.df[fld.terms[t]])
    compiled = Compiler(dev.fields, dev.doc_values, mappings).compile_maxscore(
        parse_query({"match": {"body": " ".join(
            [by_df[0], by_df[2000], by_df[9000], by_df[20000]]
        )}}),
        10_000,
    )
    assert compiled.spec[0] == "terms_maxscore"
    out = _compile_single(
        one_chip, bm25_device.execute_maxscore,
        bm25_device.segment_tree(dev), compiled, 10,
    )
    plane = dev.fields["body"].doc_ids
    assert out.memory_analysis().temp_size_in_bytes < plane.nbytes / 2


@pytest.fixture(scope="module")
def tagged():
    """A 200k-doc segment with the smoke's `tag` keyword field, packed."""
    n = 200_000
    _, segment = build_zipf_segment(n, vocab_size=30_000)
    tags = np.random.default_rng(2).integers(0, len(TAGS), n)
    segment.fields["tag"] = keyword_field("tag", tags, TAGS)
    mappings = Mappings(
        properties={"body": {"type": "text"}, "tag": {"type": "keyword"}}
    )
    dev = pack_segment(segment)
    return segment, dev, Compiler(dev.fields, dev.doc_values, mappings)


def test_bool_conjunction(one_chip, tagged):
    """bool(must 2-term match, filter term): the sparse conjunction."""
    segment, dev, compiler = tagged
    terms = pick_query_terms(segment, np.random.default_rng(3), 1, 2)[0]
    compiled = compiler.compile(parse_query({"bool": {
        "must": [{"match": {"body": " ".join(terms)}}],
        "filter": [{"term": {"tag": "red"}}],
    }}))
    assert compiled.spec[0] == "bool"
    assert bm25_device.supports_sparse(compiled.spec)
    _compile_single(
        one_chip, bm25_device.execute_sparse,
        bm25_device.segment_tree(dev), compiled, 10,
    )


def test_filter_mask(one_chip, tagged):
    """The filter cache's mask-plane program for a `term` filter."""
    _, dev, compiler = tagged
    compiled = compiler.compile(parse_query({"term": {"tag": "red"}}))
    _compile_single(
        one_chip, bm25_device.compute_filter_mask,
        bm25_device.segment_tree(dev), compiled,
    )


def test_mesh_four_shards(topo):
    """The 4-shard SPMD program MeshView serves plain searches with, laid
    out over the 2x2 chips: per-device memory fits, and the merge runs as
    collectives across the mesh."""
    n_shards = 4
    segments = [
        build_zipf_segment(50_000, vocab_size=30_000, seed=20 + s)[1]
        for s in range(n_shards)
    ]
    cpu_mesh = Mesh(np.array(jax.devices()[:n_shards]), ("shard",))
    index = ShardedIndex.from_segments(
        segments, Mappings(properties={"body": {"type": "text"}}), cpu_mesh
    )
    terms = pick_query_terms(segments[0], np.random.default_rng(4), 1, 4)[0]
    compiled = index.compile(parse_query({"match": {"body": " ".join(terms)}}))
    chip_mesh = Mesh(np.array(topo.devices[:n_shards]), ("shard",))
    sharded = NamedSharding(chip_mesh, P("shard"))
    out = sharded_execute.lower(
        chip_mesh, "shard", _shapes(index.seg_stacked, sharded),
        _shapes(compiled.arrays, sharded), compiled.spec, 10,
        index.docs_per_shard,
    ).compile()
    mem = out.memory_analysis()
    stacked = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(index.seg_stacked)
    )
    # Per-device arguments: one shard's planes, not the whole stack.
    assert mem.argument_size_in_bytes < stacked / 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = out.as_text()
    assert "all-reduce" in text or "all-gather" in text


def test_mesh_maxscore_four_shards(topo):
    """The 4-shard SPMD MaxScore program MeshView serves stop-word
    disjunctions with: each shard's own MaxScore plan, stacked and packed
    into one buffer, laid out over the 2x2 chips; per-device memory fits,
    and the merge and the check's `pmax` run as collectives."""
    n_shards = 4
    segments = [
        build_zipf_segment(50_000, vocab_size=30_000, seed=20 + s)[1]
        for s in range(n_shards)
    ]
    mappings = Mappings(properties={"body": {"type": "text"}})
    cpu_mesh = Mesh(np.array(jax.devices()[:n_shards]), ("shard",))
    index = ShardedIndex.from_segments(segments, mappings, cpu_mesh)
    fld = segments[0].fields["body"]
    by_df = sorted(fld.terms, key=lambda t: -fld.df[fld.terms[t]])
    query = parse_query({"match": {"body": " ".join(
        [by_df[0], by_df[2000], by_df[9000]]
    )}})
    plans = []
    for seg in segments:
        dev = pack_segment(seg)
        plans.append(
            Compiler(dev.fields, dev.doc_values, mappings).compile_maxscore(
                query, 1_000
            )
        )
    compiled = stack_maxscore(plans)
    assert compiled.spec[0] == "terms_maxscore"
    buf, layout = pack_plan(compiled.arrays)
    chip_mesh = Mesh(np.array(topo.devices[:n_shards]), ("shard",))
    sharded = NamedSharding(chip_mesh, P("shard"))
    out = sharded_execute_maxscore.lower(
        chip_mesh, "shard", _shapes(index.seg_stacked, sharded),
        _shapes(buf, sharded), compiled.spec, 10, index.docs_per_shard, layout,
    ).compile()
    mem = out.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = out.as_text()
    assert "all-reduce" in text or "all-gather" in text
