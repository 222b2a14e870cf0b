"""A loaded segment's `_source`s (index/store.StoredSources): kept as the
stored JSON lines and decoded per read, so a restore or recovery parses
no source, yet every read equals what was indexed — also after the
segment is compacted, concatenated with in-memory segments and
persisted again."""

import numpy as np
import pytest

from elasticsearch_tpu.index import Mappings, SegmentBuilder
from elasticsearch_tpu.index.merge import merged_live_segment
from elasticsearch_tpu.index.store import (
    StoredSources,
    concat_sources,
    load_segment,
    persist_segment,
    take_sources,
)

MAPPINGS = Mappings.from_json({"properties": {"body": {"type": "text"}}})
DOCS = [
    {"body": "alpha beta", "n": 1},
    {"body": "line\nbreak and \"quotes\"", "tags": ["x", "y"]},
    {"body": "unicode: naïve café ✓"},
    {},
]
FRESH = [{"body": "fresh one"}, {"body": "fresh two", "n": 2.5}]


def build(docs, prefix):
    b = SegmentBuilder(MAPPINGS)
    for i, d in enumerate(docs):
        b.add(d, f"{prefix}{i}")
    return b.build()


@pytest.fixture
def loaded(tmp_path):
    persist_segment(str(tmp_path), 0, build(DOCS, "d"))
    seg, live = load_segment(str(tmp_path), 0)
    assert live.all()
    return seg


def test_loaded_sources_read_back_as_indexed(loaded):
    src = loaded.sources
    assert isinstance(src, StoredSources)
    assert len(src) == len(DOCS) == loaded.num_docs
    assert [src[i] for i in range(len(DOCS))] == DOCS
    assert list(src) == DOCS and src == DOCS
    assert src[-1] == DOCS[-1] and src[1:3] == DOCS[1:3]
    with pytest.raises(IndexError):
        src[len(DOCS)]


def test_each_read_is_a_fresh_object(loaded):
    first = loaded.sources[0]
    first["body"] = "changed by a caller"
    assert loaded.sources[0] == DOCS[0]


def test_take_and_concat_keep_stored_lines_and_order(loaded):
    fresh = build(FRESH, "f").sources
    both = concat_sources([loaded.sources, fresh, loaded.sources])
    assert isinstance(both, StoredSources)
    assert list(both) == DOCS + FRESH + DOCS
    keep = np.array([0, 2, 4, 5, 9])
    assert take_sources(both, keep) == [(DOCS + FRESH + DOCS)[i] for i in keep]
    # In-memory sources stay the same objects; lists stay lists.
    assert both[4] is fresh[0]
    assert concat_sources([fresh, fresh]) == FRESH + FRESH
    assert take_sources(fresh, np.array([1])) == [FRESH[1]]


def test_merged_and_persisted_again_reads_the_same(loaded, tmp_path):
    fresh = build(FRESH, "f")
    live = np.array([True, False, True, True])
    merged = merged_live_segment([loaded, fresh], [live, np.ones(2, bool)])
    assert isinstance(merged.sources, StoredSources)
    want = [DOCS[0], DOCS[2], DOCS[3]] + FRESH
    assert merged.sources == want and merged.ids == ["d0", "d2", "d3", "f0", "f1"]
    persist_segment(str(tmp_path), 1, merged)
    again, _ = load_segment(str(tmp_path), 1)
    assert again.sources == want


def test_empty_segment_has_no_sources():
    empty = StoredSources.from_lines(b"")
    assert len(empty) == 0 and empty == [] and list(empty) == []
