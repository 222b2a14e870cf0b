"""Parity tests for the candidate-centric (sparse) kernel and block-max.

The sparse path must be bit-exact with the oracle: stable sort + left-fold
run sums reproduce the oracle's per-term fp32 accumulation order, and
top-k tie-breaks (equal score -> lower doc id) must match.
"""

import numpy as np
import pytest

from elasticsearch_tpu.index.mapping import Mappings
from elasticsearch_tpu.index.tiles import pack_segment
from elasticsearch_tpu.ops import bm25_device
from elasticsearch_tpu.ops.bm25 import search_field
from elasticsearch_tpu.query.compile import Compiler
from elasticsearch_tpu.query.dsl import MatchQuery
from elasticsearch_tpu.utils.corpus import build_zipf_segment, pick_query_terms


@pytest.fixture(scope="module")
def corpus():
    mappings, seg = build_zipf_segment(4000, vocab_size=900, seed=5)
    dev = pack_segment(seg)
    compiler = Compiler(dev.fields, dev.doc_values, mappings)
    seg_tree = bm25_device.segment_tree(dev)
    return mappings, seg, dev, compiler, seg_tree


def _oracle(seg, terms, k):
    fld = seg.fields["body"]
    return search_field(fld, terms, seg.num_docs, k)


class TestSparseParity:
    def test_spec_is_sparse_capable(self, corpus):
        _, _, _, compiler, _ = corpus
        c = compiler.compile(MatchQuery("body", "t1 t2 t3"))
        assert bm25_device.supports_sparse(c.spec)
        assert len(c.spec) == 4  # (kind, field, NT, T_pad)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_randomized_exact_parity(self, corpus, seed):
        mappings, seg, dev, compiler, seg_tree = corpus
        rng = np.random.default_rng(seed)
        for terms in pick_query_terms(seg, rng, 8, terms_per_query=4):
            c = compiler.compile(MatchQuery("body", " ".join(terms)))
            assert bm25_device.supports_sparse(c.spec)
            d_s, d_i, d_tot = map(
                np.asarray,
                bm25_device.execute_sparse(seg_tree, c.spec, c.arrays, 10),
            )
            o_s, o_i = _oracle(seg, terms, 10)
            n = len(o_i)
            assert int(d_tot) == int(
                np.count_nonzero(
                    _matched_mask(seg, terms)
                )
            )
            assert list(d_i[:n]) == list(o_i)
            # Bit-exact scores (same fp32 accumulation order as the oracle)
            assert np.array_equal(d_s[:n], o_s), (d_s[:n], o_s)

    def test_duplicate_terms_run_fold(self, corpus):
        # Duplicate query terms double a doc's contributions -> exercises
        # run lengths up to the full term-occurrence count.
        mappings, seg, dev, compiler, seg_tree = corpus
        terms = ["t1", "t1", "t2", "t1"]
        c = compiler.compile(MatchQuery("body", " ".join(terms)))
        d_s, d_i, d_tot = map(
            np.asarray, bm25_device.execute_sparse(seg_tree, c.spec, c.arrays, 10)
        )
        o_s, o_i = _oracle(seg, terms, 10)
        n = len(o_i)
        assert list(d_i[:n]) == list(o_i)
        assert np.array_equal(d_s[:n], o_s)

    def test_matches_dense_path(self, corpus):
        mappings, seg, dev, compiler, seg_tree = corpus
        c = compiler.compile(MatchQuery("body", "t0 t5 t11"))
        s1, i1, t1 = map(
            np.asarray, bm25_device.execute_sparse(seg_tree, c.spec, c.arrays, 17)
        )
        s2, i2, t2 = map(
            np.asarray, bm25_device.execute(seg_tree, c.spec, c.arrays, 17)
        )
        assert int(t1) == int(t2)
        n = min(17, int(t1))
        assert list(i1[:n]) == list(i2[:n])
        assert np.array_equal(s1[:n], s2[:n])

    def test_deleted_docs_excluded(self, corpus):
        import jax

        mappings, seg, dev, compiler, seg_tree = corpus
        c = compiler.compile(MatchQuery("body", "t1 t2"))
        s0, i0, _ = map(
            np.asarray, bm25_device.execute_sparse(seg_tree, c.spec, c.arrays, 5)
        )
        victim = int(i0[0])
        live = np.ones(seg.num_docs, dtype=bool)
        live[victim] = False
        seg_tree2 = dict(seg_tree)
        seg_tree2["live"] = jax.device_put(live)
        s1, i1, _ = map(
            np.asarray,
            bm25_device.execute_sparse(seg_tree2, c.spec, c.arrays, 5),
        )
        assert victim not in list(i1)

    def test_k_larger_than_candidates(self, corpus):
        mappings, seg, dev, compiler, seg_tree = corpus
        # Rare term: few candidates; ask for far more.
        rare = min(
            seg.fields["body"].terms,
            key=lambda t: seg.fields["body"].df[seg.fields["body"].terms[t]],
        )
        c = compiler.compile(MatchQuery("body", rare))
        d_s, d_i, d_tot = map(
            np.asarray,
            bm25_device.execute_sparse(seg_tree, c.spec, c.arrays, 3000),
        )
        o_s, o_i = _oracle(seg, [rare], 3000)
        n = len(o_i)
        assert list(d_i[:n]) == list(o_i)


def _matched_mask(seg, terms):
    fld = seg.fields["body"]
    m = np.zeros(seg.num_docs, dtype=bool)
    for t in terms:
        docs, _ = fld.postings(t)
        m[docs] = True
    return m


class TestBlockmax:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_blockmax_exact_topk(self, corpus, seed):
        mappings, seg, dev, compiler, seg_tree = corpus
        rng = np.random.default_rng(seed)
        queries = pick_query_terms(seg, rng, 8, terms_per_query=4)
        compiled = [
            compiler.compile(MatchQuery("body", " ".join(t))) for t in queries
        ]
        # Group by spec (blockmax needs one spec per batch)
        from collections import defaultdict

        groups = defaultdict(list)
        for q, c in zip(queries, compiled):
            groups[c.spec].append((q, c))
        for spec, items in groups.items():
            arrays_list = [c.arrays for _, c in items]
            s, i, t, rel = bm25_device.execute_batch_blockmax(
                seg_tree, spec, arrays_list, 10
            )
            assert rel in ("eq", "gte")
            for row, (terms, _c) in enumerate(items):
                o_s, o_i = _oracle(seg, terms, 10)
                true_total = int(np.count_nonzero(_matched_mask(seg, terms)))
                n = len(o_i)
                assert list(i[row][:n]) == list(o_i)
                assert np.array_equal(s[row][:n], o_s)
                # totals: exact when eq, lower bound (>= k) when gte
                assert int(t[row]) <= true_total
                if rel == "eq":
                    assert int(t[row]) == true_total
                else:
                    assert int(t[row]) >= min(10, true_total)

    def test_blockmax_prunes_on_skewed_corpus(self, corpus):
        """On a Zipf corpus with one dominant term the tail tiles of the
        head term should actually get pruned (the mechanism is live)."""
        mappings, seg, dev, compiler, seg_tree = corpus
        fld = seg.fields["body"]
        by_df = sorted(fld.terms, key=lambda t: -fld.df[fld.terms[t]])
        terms = [by_df[0], by_df[len(by_df) // 2], by_df[len(by_df) // 2 + 1]]
        c = compiler.compile(MatchQuery("body", " ".join(terms)))
        if c.spec[2] < 32:
            pytest.skip("worklist too small to exercise pruning")
        s, i, t, rel = bm25_device.execute_batch_blockmax(
            seg_tree, c.spec, [c.arrays], 10
        )
        o_s, o_i = _oracle(seg, terms, 10)
        assert list(i[0][: len(o_i)]) == list(o_i)
        assert np.array_equal(s[0][: len(o_i)], o_s)


class TestSequentialKernel:
    """execute_sequential_sparse must be bit-identical to the per-query
    kernel — the latency bench's parity contract (bench.py)."""

    def test_sequential_matches_per_query(self, corpus):
        mappings, seg, dev, compiler, seg_tree = corpus
        import jax

        rng = np.random.default_rng(7)
        queries = [
            compiler.compile(MatchQuery("body", " ".join(t)))
            for t in pick_query_terms(seg, rng, 6, terms_per_query=3)
        ]
        spec = queries[0].spec
        same_spec = [c for c in queries if c.spec == spec]
        assert len(same_spec) >= 2
        stacked = jax.tree.map(
            lambda *xs: np.stack(xs), *[c.arrays for c in same_spec]
        )
        s_b, i_b, t_b = map(
            np.asarray,
            bm25_device.execute_sequential_sparse(seg_tree, spec, stacked, 10),
        )
        for row, c in enumerate(same_spec):
            s1, i1, t1 = map(
                np.asarray,
                bm25_device.execute_sparse(seg_tree, c.spec, c.arrays, 10),
            )
            assert np.array_equal(s_b[row], s1)
            assert np.array_equal(i_b[row], i1)
            assert int(t_b[row]) == int(t1)


# ---------------------------------------------------------------------------
# MaxScore disjunctions: candidates from the essential terms' postings only,
# the stop words' contributions looked up per candidate.
# ---------------------------------------------------------------------------

STOPWORD_DOCS = 12_000  # the two most frequent terms exceed 10,000 postings


@pytest.fixture(scope="module")
def stopword_corpus():
    mappings, seg = build_zipf_segment(STOPWORD_DOCS, vocab_size=900, seed=3)
    dev = pack_segment(seg)
    compiler = Compiler(dev.fields, dev.doc_values, mappings)
    fld = seg.fields["body"]
    by_df = sorted(fld.terms, key=lambda t: -fld.df[fld.terms[t]])
    assert fld.df[fld.terms[by_df[1]]] > 10_000
    return mappings, seg, compiler, bm25_device.segment_tree(dev), by_df


def _stopword_queries(by_df, seed, n=6):
    """1-2 of the two stop words plus 1-5 rarer terms, shuffled."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        terms = [str(t) for t in rng.choice(by_df[:2], rng.integers(1, 3), replace=False)]
        terms += [
            str(t) for t in rng.choice(by_df[40:], rng.integers(1, 6), replace=False)
        ]
        rng.shuffle(terms)
        out.append(terms)
    return out


def _maxscore_engine(seg, mappings):
    from elasticsearch_tpu.index.engine import Engine

    engine = Engine(mappings)
    engine.restore_segments([(seg, np.ones(seg.num_docs, dtype=bool))])
    return engine


def _maxscore_service(engine, planner=None):
    from elasticsearch_tpu.obs.metrics import DeviceInstruments, MetricsRegistry
    from elasticsearch_tpu.search.service import SearchService

    return SearchService(
        engine, "ms", device=DeviceInstruments(MetricsRegistry()),
        planner=planner,
    )


def _hopeless(lower):
    """`lower` with a non-essential bound no candidate can beat, so the
    kernel's threshold check fails."""

    def lowered(*a, **kw):
        spec, arrays = lower(*a, **kw)
        return spec, {**arrays, "ub_n": np.float32(1e30)}

    return lowered


def _outcomes(service):
    return service.device.snapshot()["maxscore"]


def _hits(resp):
    return [(h.doc_id, h.score) for h in resp.hits], resp.total, resp.total_relation


class TestMaxScore:
    @pytest.mark.parametrize("threshold", [10, 10_000])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_exact_against_oracle_and_exhaustive(
        self, stopword_corpus, seed, threshold
    ):
        from elasticsearch_tpu.search.service import clamp_total

        mappings, seg, compiler, seg_tree, by_df = stopword_corpus
        served = 0
        for terms in _stopword_queries(by_df, seed):
            q = MatchQuery("body", " ".join(terms))
            m = compiler.compile_maxscore(q, threshold)
            assert m is not None and m.spec[0] == "terms_maxscore"
            s, i, t, ok = map(
                np.asarray,
                bm25_device.execute_maxscore(seg_tree, m.spec, m.arrays, 10),
            )
            e = compiler.compile_exhaustive(q)
            es, ei, et = map(
                np.asarray,
                bm25_device.execute_sparse(seg_tree, e.spec, e.arrays, 10),
            )
            o_s, o_i = _oracle(seg, terms, 10)
            exact = int(np.count_nonzero(_matched_mask(seg, terms)))
            assert int(et) == exact
            if not ok:
                continue
            served += 1
            n = len(o_i)
            assert list(i[:n]) == list(o_i) == list(ei[:n])
            assert np.array_equal(s[:n], o_s) and np.array_equal(s[:n], es[:n])
            assert int(t) <= exact
            assert clamp_total(int(t), threshold) == clamp_total(exact, threshold)
        assert served >= 4, served

    def test_split_keeps_rare_terms_essential(self, stopword_corpus):
        from elasticsearch_tpu.query.compile import span_rows_bucket

        _, seg, compiler, _, by_df = stopword_corpus
        terms = [by_df[0], by_df[200], by_df[1], by_df[300]]
        m = compiler.compile_maxscore(MatchQuery("body", " ".join(terms)), 10_000)
        assert sorted(m.arrays["n_term"].tolist()) == [0, 2]
        assert set(m.arrays["term_of"][m.arrays["ends"] > 0].tolist()) == {1, 3}
        # Only the rare terms' tiles are enumerated; the lookup bound is
        # the segment's, the tile rows of its longest span.
        dfield = compiler.fields["body"]
        longest = max(seg.fields["body"].df)
        assert m.spec[2] == 64 and m.spec[3] == 16
        assert m.spec[4] == span_rows_bucket(dfield)
        assert longest <= (m.spec[4] - 1) * 256 < 4 * longest
        # A term absent from the segment takes neither side.
        with_absent = compiler.compile_maxscore(
            MatchQuery("body", " ".join(["zzz"] + terms)), 10_000
        )
        assert with_absent.spec[0] == "terms_maxscore"
        assert sorted(with_absent.arrays["n_term"].tolist()) == [1, 3]
        # At most two terms are non-essential: the third stop word stays.
        three = compiler.compile_maxscore(
            MatchQuery("body", " ".join(by_df[:3] + [by_df[300]])), 10
        )
        n_terms = set(three.arrays["n_term"].tolist())
        essential = set(three.arrays["term_of"][three.arrays["ends"] > 0].tolist())
        assert len(n_terms) == 2 and n_terms < {0, 1, 2}
        assert essential == {0, 1, 2, 3} - n_terms

    @pytest.mark.parametrize(
        "text, threshold",
        [
            ("rare", 10_000),  # no span past the threshold
            ("stop", STOPWORD_DOCS),  # nor here
            ("one", 10),  # one term: not a disjunction
            ("and", 10),  # a conjunction
        ],
    )
    def test_other_traffic_keeps_its_plan(self, stopword_corpus, text, threshold):
        _, _, compiler, _, by_df = stopword_corpus
        q = {
            "rare": MatchQuery("body", f"{by_df[300]} {by_df[400]}"),
            "stop": MatchQuery("body", f"{by_df[0]} {by_df[300]}"),
            "one": MatchQuery("body", by_df[0]),
            "and": MatchQuery("body", f"{by_df[0]} {by_df[300]}", operator="and"),
        }[text]
        assert compiler.compile_maxscore(q, threshold) is None

    def test_declined_split_takes_the_padded_exhaustive_plan(
        self, stopword_corpus, monkeypatch
    ):
        from elasticsearch_tpu.query import compile as compile_mod

        mappings, seg, compiler, seg_tree, by_df = stopword_corpus
        terms = [by_df[0]] + by_df[40:44]
        q = MatchQuery("body", " ".join(terms))
        assert compiler.compile_maxscore(q, 10_000).spec[2] == 64
        monkeypatch.setattr(compile_mod, "MAXSCORE_NT_MAX", 32)
        declined = compiler.compile_maxscore(q, 10_000)
        assert declined.spec == compiler.compile_exhaustive(q).spec
        plain = compiler.compile(q)
        rows = compile_mod.span_rows_bucket(compiler.fields["body"])
        assert declined.spec == ("terms", "body", max(rows, plain.spec[2]), 16)
        # The padding changes the shape, not the answer.
        got = bm25_device.execute_sparse(seg_tree, declined.spec, declined.arrays, 10)
        want = bm25_device.execute_sparse(seg_tree, plain.spec, plain.arrays, 10)
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_batched_lanes_match_single(self, stopword_corpus):
        import jax

        _, _, compiler, seg_tree, by_df = stopword_corpus
        plans = [
            compiler.compile_maxscore(MatchQuery("body", " ".join(t)), 10_000)
            for t in _stopword_queries(by_df, 7, n=12)
        ]
        spec = plans[0].spec
        same = [p for p in plans if p.spec == spec]
        assert len(same) >= 2
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *[p.arrays for p in same])
        out_b = [
            np.asarray(x)
            for x in bm25_device.execute_batch_maxscore(seg_tree, spec, stacked, 10)
        ]
        for row, p in enumerate(same):
            single = bm25_device.execute_maxscore(seg_tree, spec, p.arrays, 10)
            for b, one in zip(out_b, single):
                assert np.array_equal(b[row], np.asarray(one))

    def test_failed_check_is_reported(self, stopword_corpus):
        _, _, compiler, seg_tree, by_df = stopword_corpus
        terms = [by_df[0], by_df[100]]
        m = compiler.compile_maxscore(MatchQuery("body", " ".join(terms)), 10)
        *_, ok = bm25_device.execute_maxscore(seg_tree, m.spec, m.arrays, 10)
        assert bool(ok)
        m.arrays["ub_n"] = np.float32(1e30)
        *_, ok = bm25_device.execute_maxscore(seg_tree, m.spec, m.arrays, 10)
        assert not bool(ok)

    @pytest.mark.parametrize("batched", [False, True])
    def test_service_falls_back_when_check_fails(
        self, stopword_corpus, monkeypatch, batched
    ):
        from elasticsearch_tpu.query import compile as compile_mod
        from elasticsearch_tpu.search.service import SearchRequest

        mappings, seg, _, _, by_df = stopword_corpus
        service = _maxscore_service(_maxscore_engine(seg, mappings))
        terms = [by_df[1], by_df[60], by_df[0], by_df[500]]
        body = {"query": {"match": {"body": " ".join(terms)}}}

        def run():
            req = SearchRequest.from_json(body)
            return (
                service.search_many([req])[0] if batched else service.search(req)
            )

        served = run()
        assert _outcomes(service) == {"served": 1}
        monkeypatch.setattr(
            compile_mod, "_maxscore_arrays", _hopeless(compile_mod._maxscore_arrays)
        )
        fell_back = run()
        assert _outcomes(service) == {"served": 1, "fallback": 1}
        assert _hits(fell_back) == _hits(served)
        o_s, o_i = _oracle(seg, terms, 10)
        assert [h.doc_id for h in served.hits] == [seg.ids[d] for d in o_i]
        assert [h.score for h in served.hits] == [float(x) for x in o_s]
        assert (served.total, served.total_relation) == (10_000, "gte")

    @pytest.mark.parametrize(
        "extra",
        [
            {"track_total_hits": True},
            {"track_total_hits": False},
            {"aggs": {"all": {"filter": {"match_all": {}}}}},
            {"sort": [{"_score": "desc"}]},
            "deletes",
        ],
        ids=["tth_true", "tth_false", "aggs", "sort", "deletes"],
    )
    def test_other_shapes_bypass(self, stopword_corpus, extra):
        from elasticsearch_tpu.search.service import SearchRequest

        mappings, seg, _, _, by_df = stopword_corpus
        engine = _maxscore_engine(seg, mappings)
        body = {"query": {"match": {"body": f"{by_df[0]} {by_df[70]} {by_df[80]}"}}}
        if extra == "deletes":
            engine.delete(seg.ids[5])
            engine.refresh()
        else:
            body.update(extra)
        service = _maxscore_service(engine)
        resp = service.search(SearchRequest.from_json(body))
        assert _outcomes(service) == {"bypass": 1}
        # The same answer the exhaustive path gives in a fresh service.
        ref = _maxscore_service(engine).search(SearchRequest.from_json(body))
        assert _hits(resp) == _hits(ref)

    @pytest.mark.parametrize("batched", [False, True])
    def test_oracle_routing_is_counted(self, stopword_corpus, batched):
        from elasticsearch_tpu.exec.planner import ExecPlanner
        from elasticsearch_tpu.search.service import SearchRequest

        mappings, seg, _, _, by_df = stopword_corpus
        engine = _maxscore_engine(seg, mappings)
        planner = ExecPlanner()
        planner.decide = lambda *a, **kw: "oracle"
        service = _maxscore_service(engine, planner=planner)
        body = {"query": {"match": {"body": f"{by_df[0]} {by_df[90]} {by_df[95]}"}}}
        req = SearchRequest.from_json(body)
        resp = service.search_many([req])[0] if batched else service.search(req)
        assert _outcomes(service) == {"oracle": 1}
        ref = _maxscore_service(engine).search(SearchRequest.from_json(body))
        assert _hits(resp) == _hits(ref)

    def test_compiling_launch_is_no_cost_sample(self, stopword_corpus):
        """The class's first device launch carries its trace and compile:
        the planner counts it but keeps it out of the estimate, so the
        class is priced by warm launches, not routed to the oracle by it."""
        from elasticsearch_tpu.exec.planner import ExecPlanner
        from elasticsearch_tpu.search.service import SearchRequest

        mappings, seg, _, _, by_df = stopword_corpus
        planner = ExecPlanner()
        service = _maxscore_service(_maxscore_engine(seg, mappings), planner)
        body = {"query": {"match": {"body": f"{by_df[0]} {by_df[85]} {by_df[87]}"}}}
        for _ in range(6):
            service.search_many([SearchRequest.from_json(body)])
        (table,) = [
            row for key, row in planner.cost.snapshot().items()
            if "terms_maxscore" in key
        ]
        launched = planner.decisions["device_batched"]
        assert launched >= 3
        assert table["device_batched"]["observations"] == launched - 1
        assert _outcomes(service)["served"] == launched

    def test_counter_outcomes_on_metrics(self, stopword_corpus, monkeypatch):
        from elasticsearch_tpu.query import compile as compile_mod
        from elasticsearch_tpu.search.service import SearchRequest

        mappings, seg, _, _, by_df = stopword_corpus
        service = _maxscore_service(_maxscore_engine(seg, mappings))
        text = f"{by_df[0]} {by_df[90]} {by_df[95]}"
        service.search(SearchRequest.from_json({"query": {"match": {"body": text}}}))
        service.search(
            SearchRequest.from_json(
                {"query": {"match": {"body": text}}, "track_total_hits": True}
            )
        )
        monkeypatch.setattr(
            compile_mod, "_maxscore_arrays", _hopeless(compile_mod._maxscore_arrays)
        )
        service.search(SearchRequest.from_json({"query": {"match": {"body": text}}}))
        # A single term is no disjunction: counted nowhere.
        service.search(
            SearchRequest.from_json({"query": {"match": {"body": by_df[0]}}})
        )
        assert _outcomes(service) == {"served": 1, "fallback": 1, "bypass": 1}
        exposition = service.device.registry.exposition()
        for outcome in ("served", "fallback", "bypass"):
            assert f'estpu_sparse_maxscore_total{{outcome="{outcome}"}} 1' in exposition


class TestMaxScoreRest:
    def test_default_match_with_stop_word_matches_exhaustive(self, monkeypatch):
        import json

        from elasticsearch_tpu.rest.server import RestServer
        from elasticsearch_tpu.search.service import SearchService

        # The served path of a large index: no small-index packing, and
        # no planner routing a first search to the CPU oracle.
        monkeypatch.setenv("ESTPU_EXEC_PACKED", "0")
        monkeypatch.setenv("ESTPU_EXEC_PLANNER", "0")
        rest = RestServer()

        def call(method, path, body=None, query=None):
            payload = body if isinstance(body, str) else json.dumps(body)
            return rest.dispatch(method, path, query or {}, payload)

        call("PUT", "/m", {"mappings": {"properties": {"t": {"type": "text"}}}})
        lines = []
        for i in range(10_500):  # "the" in every doc: past the 10,000 default
            lines.append(json.dumps({"index": {"_id": str(i)}}))
            extra = " the" if i % 3 == 0 else ""
            lines.append(json.dumps({"t": f"the x{i % 97} y{i % 1009}{extra}"}))
        status, _ = call("POST", "/m/_bulk", "\n".join(lines) + "\n", {"refresh": "true"})
        assert status == 200
        body = {"query": {"match": {"t": "the x5 y7 y11"}}}
        status, fast = call("POST", "/m/_search", body)
        assert status == 200
        assert rest.node.device.snapshot()["maxscore"].get("served") == 1
        monkeypatch.setattr(
            SearchService, "_maxscore_threshold", lambda self, r, h: None
        )
        status, slow = call("POST", "/m/_search", body)
        assert status == 200
        assert rest.node.device.snapshot()["maxscore"].get("bypass") == 1
        fast.pop("took")
        slow.pop("took")
        assert fast == slow
        assert fast["hits"]["total"] == {"value": 10_000, "relation": "gte"}
