"""Native indexing core: parity with the pure-Python builder.

The contract: for any corpus (ASCII or Unicode, single- or multi-value),
the FieldIndex built through native/text_indexer.cpp is IDENTICAL to the
pure-Python path — same term dict, CSR arrays, positions, norms. Scoring
parity then follows from the existing oracle/device suites.
"""

import numpy as np
import pytest

from elasticsearch_tpu.index.mapping import Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.native import available, tokenize_ascii

pytestmark = pytest.mark.skipif(
    not available(), reason="native library unavailable"
)

MAPPINGS = Mappings.from_json(
    {"properties": {"t": {"type": "text"}, "k": {"type": "keyword"}}}
)


def build_pair(docs):
    native = SegmentBuilder(MAPPINGS)
    python = SegmentBuilder(MAPPINGS)
    python._native_ok = {"t": False, "k": False}  # force the Python path
    for i, d in enumerate(docs):
        native.add(d, f"d{i}")
        python.add(d, f"d{i}")
    ns, ps = native.build(), python.build()
    assert native._native_accs and not python._native_accs
    return ns, ps


def assert_field_equal(a, b):
    assert a.terms == b.terms
    np.testing.assert_array_equal(a.df, b.df)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
    np.testing.assert_array_equal(a.tfs, b.tfs)
    np.testing.assert_array_equal(a.norm_bytes, b.norm_bytes)
    np.testing.assert_array_equal(a.present, b.present)
    assert a.doc_count == b.doc_count
    assert a.sum_total_tf == b.sum_total_tf
    np.testing.assert_array_equal(a.pos_offsets, b.pos_offsets)
    np.testing.assert_array_equal(a.positions, b.positions)


def test_ascii_corpus_parity():
    rng = np.random.default_rng(3)
    words = ["alpha", "Beta", "GAMMA_2", "d-e", "42", "x"]
    docs = [
        {"t": " ".join(rng.choice(words, rng.integers(1, 12))),
         "k": "tag"}
        for _ in range(120)
    ]
    docs.append({"t": ""})  # zero tokens
    docs.append({"t": "!!! ---"})  # punctuation only
    ns, ps = build_pair(docs)
    assert_field_equal(ns.fields["t"], ps.fields["t"])
    assert_field_equal(ns.fields["k"], ps.fields["k"])


def test_unicode_falls_back_into_same_accumulator():
    docs = [
        {"t": "plain ascii words"},
        {"t": "héllo wörld café"},  # Unicode: Python analyzer tokenizes
        {"t": "mixed ascii and héllo again"},
        {"t": "汉字 分词 测试"},
    ]
    ns, ps = build_pair(docs)
    assert_field_equal(ns.fields["t"], ps.fields["t"])


def test_multivalue_position_gaps_parity():
    docs = [
        {"t": ["first value", "second value"]},
        {"t": ["a b", "c", "d e f"]},
    ]
    ns, ps = build_pair(docs)
    assert_field_equal(ns.fields["t"], ps.fields["t"])
    # the gap itself: "value"@{1} then second value base 2+100
    f = ns.fields["t"]
    assert list(f.term_positions("second", 0)) == [102]


def test_tokenizer_matches_python_regex_on_ascii():
    rng = np.random.default_rng(7)
    import re

    word_re = re.compile(r"[\w]+", re.UNICODE)
    chars = list("abz AZ09_ .,-!/")
    for _ in range(200):
        text = "".join(rng.choice(chars, rng.integers(0, 40)))
        r = tokenize_ascii(text)
        assert r is not None
        buf, offs = r
        got = [
            buf[offs[i] : offs[i + 1]].tobytes().decode()
            for i in range(len(offs) - 1)
        ]
        assert got == [t.lower() for t in word_re.findall(text)]
    assert tokenize_ascii("naïve") is None  # non-ASCII refused


def test_library_name_tracks_its_source(tmp_path, monkeypatch):
    """A copied build directory never serves a library of other source:
    the library's name carries its source's hash, so changed source names
    a library that does not exist yet, and it is built."""
    import hashlib
    import os
    import shutil

    from elasticsearch_tpu.native import loader

    for name in ("Makefile", "text_indexer.cpp"):
        shutil.copy(os.path.join(loader._NATIVE_DIR, name), tmp_path / name)
    src = tmp_path / "text_indexer.cpp"
    monkeypatch.setattr(loader, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(loader, "_SRC", str(src))
    first = loader._build()
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert first == str(tmp_path / "build" / f"libestpu_native-{digest}.so")
    assert os.path.exists(first)
    src.write_text(src.read_text() + "\n// changed\n")
    second = loader._build()
    assert second != first and os.path.exists(second)
