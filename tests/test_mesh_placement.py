"""An index of N shards served by the SPMD mesh, on N of the suite's 8
virtual CPU devices: where its planes live, that its REST `_search`
answers are the exact BM25 answers over the whole corpus, the spans
inside the mesh path, and the HBM ledger's device column.

Shard i's engine packs its planes on mesh device i, and the mesh's
stacked planes are assembled from one [1, ...] row per shard on that
shard's device: no device holds another shard's planes, and the mesh
keeps one copy of each shard. A 1-shard index, or one with more shards
than devices, stays on the default device and the host loop."""

import gc
import json
import math

import jax
import numpy as np
import pytest

from elasticsearch_tpu.index.tiles import device_nbytes
from elasticsearch_tpu.obs.device import HbmLedger, device_label
from elasticsearch_tpu.rest.server import RestServer

N_DOCS = 3000
N_SHARDS = 4
VOCAB = 400
STOP = 10  # the most frequent terms: in most documents
K1, B = 1.2, 0.75
SPANS = ("mesh.refresh", "mesh.compile", "mesh.dispatch", "mesh.wait", "mesh.fetch")


def term(i: int) -> str:
    """Letters only, so the standard analyzer keeps each term whole."""
    return "q" + "".join(chr(97 + (i // 26**p) % 26) for p in range(2))


def corpus(seed: int = 31) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(N_DOCS):
        n = int(rng.integers(4, 40))
        ranks = rng.zipf(1.15, size=4 * n) - 1
        docs.append([int(r) for r in ranks[ranks < VOCAB][:n]] or [0])
    return docs


def bulk(rest, index: str, docs: list[list[int]]) -> None:
    lines = []
    for i, toks in enumerate(docs):
        lines.append(json.dumps({"index": {"_id": str(i)}}))
        lines.append(json.dumps({"body": " ".join(term(t) for t in toks)}))
    status, resp = rest.dispatch(
        "POST", f"/{index}/_bulk", {"refresh": "true"}, "\n".join(lines)
    )
    assert status == 200 and not resp["errors"]


def create(rest, index: str, shards: int) -> None:
    status, _ = rest.dispatch("PUT", f"/{index}", {}, json.dumps({
        "settings": {"index": {"number_of_shards": shards}},
        "mappings": {"properties": {"body": {"type": "text"}}},
    }))
    assert status == 200


# ------------------------------------------------------ plain reference

_FREE = 24  # SmallFloat.intToByte4: lengths below this are exact


def _decode(b: np.ndarray) -> np.ndarray:
    b = b.astype(np.int64)
    i = b - _FREE
    shift = (i >> 3) - 1
    long = np.where(shift < 0, i & 7, ((i & 7) | 8) << np.maximum(shift, 0))
    return np.where(b < _FREE, b, _FREE + long)


_LENGTHS = _decode(np.arange(256))


def reference_scores(docs: list[list[int]], terms: list[int]) -> dict[int, float]:
    """Lucene's LegacyBM25Similarity in float64 over the whole corpus:
    doc -> score for every doc holding a query term."""
    n = len(docs)
    lengths = np.array([len(d) for d in docs])
    avgdl = lengths.sum() / n
    norm_byte = np.searchsorted(_LENGTHS, lengths, side="right") - 1
    dl = _LENGTHS[norm_byte].astype(np.float64)
    out: dict[int, float] = {}
    for t in set(terms):
        tf = np.array([d.count(t) for d in docs], dtype=np.float64)
        df = int((tf > 0).sum())
        if not df:
            continue
        idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
        for d in np.flatnonzero(tf):
            part = (K1 + 1) * idf * tf[d] / (tf[d] + K1 * (1 - B + B * dl[d] / avgdl))
            out[int(d)] = out.get(int(d), 0.0) + part
    return out


def queries(seed: int = 7, n: int = 16) -> list[list[int]]:
    """2-6-term disjunctions, 1-2 of them stop words, as the benchmark's
    or-closed1 mix draws them."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        stops = rng.choice(STOP, size=int(rng.integers(1, 3)), replace=False)
        rest = rng.choice(np.arange(STOP, VOCAB), size=int(rng.integers(1, 5)),
                          replace=False)
        out.append([int(t) for t in np.concatenate([stops, rest])])
    return out


# --------------------------------------------------------------- fixture


@pytest.fixture(scope="module")
def served():
    rest = RestServer()
    docs = corpus()
    create(rest, "mesh4", N_SHARDS)
    bulk(rest, "mesh4", docs)
    yield rest, docs
    rest.close()


def _metrics(rest) -> str:
    status, text = rest.dispatch("GET", "/_metrics", {}, "")
    assert status == 200
    return text.text


def _counter(text: str, series: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(series):
            total += float(line.rpartition(" ")[2])
    return total


def _launches(text: str, backend: str) -> float:
    """Completed launches of a backend: one `estpu_launch_ms` sample per
    launch outside the `queue` phase."""
    total = 0.0
    for line in text.splitlines():
        if (line.startswith("estpu_launch_ms_count{")
                and f'backend="{backend}"' in line and 'phase="queue"' not in line):
            total += float(line.rpartition(" ")[2])
    return total


def _arrays(tree):
    return [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)]


def _planes(dev) -> list:
    """Every device array of a packed DeviceSegment."""
    out = [dev.live, *dev.doc_values.values(), *dev.vectors.values()]
    for f in dev.fields.values():
        out += [f.doc_ids, f.tfs, f.tn, f.norm_bytes, f.present]
        out += [a for a in (f.ord_terms, f.pos_doc, f.pos_val) if a is not None]
    return out


def _buffer_bytes() -> dict[int, int]:
    """Bytes of the distinct live device buffers, by device id."""
    seen: dict[int, tuple[int, int]] = {}
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            ptr = shard.data.unsafe_buffer_pointer()
            seen[ptr] = (shard.device.id, shard.data.nbytes)
    out: dict[int, int] = {}
    for dev_id, nbytes in seen.values():
        out[dev_id] = out.get(dev_id, 0) + nbytes
    return out


# ------------------------------------------------------------- placement


class TestPlacement:
    def test_engine_i_packs_on_mesh_device_i(self, served):
        rest, _ = served
        svc = rest.node.get_index("mesh4")
        mesh_devices = list(svc.search.mesh_view.mesh.devices.reshape(-1))
        assert mesh_devices == jax.devices()[:N_SHARDS]
        for i, engine in enumerate(svc.engines):
            assert engine.device == mesh_devices[i]
            assert engine.segments
            for handle in engine.segments:
                for arr in _planes(handle.device):
                    assert arr.devices() == {mesh_devices[i]}

    def test_mesh_search_keeps_each_shard_on_its_own_device_once(self):
        rest = RestServer()
        try:
            create(rest, "place", N_SHARDS)
            bulk(rest, "place", corpus(seed=5)[:1200])
            mv = rest.node.get_index("place").search.mesh_view
            gc.collect()
            before = _buffer_bytes()
            status, resp = rest.dispatch("POST", "/place/_search", {}, json.dumps(
                {"query": {"match": {"body": f"{term(0)} {term(40)}"}}}))
            assert status == 200 and mv.served == 1
            gc.collect()
            after = _buffer_bytes()
            devices = list(mv.mesh.devices.reshape(-1))
            for i, dev in enumerate(devices):
                # The snapshot's shard row and the stacked arrays' shard
                # i are shard i's device buffers.
                assert all(a.devices() == {dev} for a in _planes(mv._devs[i]))
                for arr in _arrays(mv._snap.index.seg_stacked):
                    (held,) = [s for s in arr.addressable_shards if s.index[0].start == i]
                    assert held.device == dev
                # One copy: what the first search added on the device is
                # this shard's mesh planes (the stacked view shares them),
                # not a second, stacked copy beside them.
                grew = after.get(dev.id, 0) - before.get(dev.id, 0)
                assert grew <= 1.2 * device_nbytes(mv._devs[i]), (i, grew)
            for dev in jax.devices()[N_SHARDS:]:
                assert after.get(dev.id, 0) <= before.get(dev.id, 0)
        finally:
            rest.close()

    def test_one_shard_index_stays_on_the_default_device(self, served):
        rest, docs = served
        create(rest, "solo", 1)
        bulk(rest, "solo", docs[:200])
        svc = rest.node.get_index("solo")
        (engine,) = svc.engines
        assert engine.device is None
        for handle in engine.segments:
            for arr in _planes(handle.device):
                assert arr.devices() == {jax.devices()[0]}

    def test_more_shards_than_devices_is_placed_as_before(self, served):
        rest, docs = served
        n = len(jax.devices()) + 1
        create(rest, "wide", n)
        bulk(rest, "wide", docs[:400])
        svc = rest.node.get_index("wide")
        assert svc.search.mesh_view is None
        for engine in svc.engines:
            assert engine.device is None
            for handle in engine.segments:
                for arr in _planes(handle.device):
                    assert arr.devices() == {jax.devices()[0]}
        status, resp = rest.dispatch("POST", "/wide/_search", {}, json.dumps(
            {"query": {"match": {"body": term(1)}}}))
        assert status == 200 and resp["_shards"]["total"] == n


# ----------------------------------------------------------- correctness


class TestMeshAnswers:
    @pytest.mark.parametrize("tth", [None, 700])
    def test_rest_search_equals_float64_bm25_over_the_corpus(self, served, tth):
        rest, docs = served
        mv = rest.node.get_index("mesh4").search.mesh_view
        text0 = _metrics(rest)
        served0 = mv.served
        qs = queries(seed=7 if tth is None else 8)
        for q in qs:
            body = {"query": {"match": {"body": " ".join(term(t) for t in q)}},
                    "size": 10, "_source": False}
            if tth is not None:
                body["track_total_hits"] = tth
            status, resp = rest.dispatch("POST", "/mesh4/_search",
                                         {"request_cache": "false"}, json.dumps(body))
            assert status == 200, resp
            ref = reference_scores(docs, q)
            limit = 10_000 if tth is None else tth
            want_total = {"value": min(len(ref), limit),
                          "relation": "eq" if len(ref) <= limit else "gte"}
            assert resp["hits"]["total"] == want_total
            hits = resp["hits"]["hits"]
            best = sorted(ref.values(), reverse=True)[:10]
            assert len(hits) == len(best)
            ids = [int(h["_id"]) for h in hits]
            assert len(set(ids)) == len(ids)
            for rank, h in enumerate(hits):
                own = ref[int(h["_id"])]
                # Each hit's own score, and the score at each rank.
                assert abs(h["_score"] - own) <= 1e-4 * own
                assert abs(h["_score"] - best[rank]) <= 1e-4 * best[rank]
        # Every search was one SPMD launch; nothing took the host loop.
        assert mv.served - served0 == len(qs)
        text1 = _metrics(rest)
        for backend in ("mesh_spmd", "device_batched", "oracle"):
            want = len(qs) if backend == "mesh_spmd" else 0
            assert _launches(text1, backend) - _launches(text0, backend) == want

    def test_spans_nest_under_mesh_serve_and_feed_their_counters(self, served):
        rest, _ = served
        before = _metrics(rest)
        status, resp = rest.dispatch("POST", "/mesh4/_search", {}, json.dumps(
            {"query": {"match": {"body": f"{term(2)} {term(77)} {term(123)}"}}}))
        assert status == 200
        trace_id = rest._tl.response_headers.get("X-Trace-Id")
        status, tree = rest.dispatch("GET", f"/_traces/{trace_id}", {}, "")
        assert status == 200
        spans = tree["spans"]
        (serve,) = [s for s in spans if s["name"] == "mesh.serve"]
        children = {s["name"] for s in spans if s["parent_id"] == serve["span_id"]}
        assert set(SPANS) <= children
        after = _metrics(rest)
        for name in SPANS:
            for series in ("estpu_spans_total", "estpu_span_seconds_total"):
                key = f'{series}{{span="{name}"}}'
                assert _counter(after, key) > _counter(before, key), key


def _maxscore(text: str) -> dict[str, float]:
    """estpu_sparse_maxscore_total by outcome."""
    out = {}
    for o in ("served", "fallback", "oracle", "bypass"):
        out[o] = _counter(text, f'estpu_sparse_maxscore_total{{outcome="{o}"}}')
    return out


def _search(rest, index: str, terms: list[int], tth) -> dict:
    body = {"query": {"match": {"body": " ".join(term(t) for t in terms)}},
            "size": 10, "_source": False, "track_total_hits": tth}
    status, resp = rest.dispatch("POST", f"/{index}/_search",
                                 {"request_cache": "false"}, json.dumps(body))
    assert status == 200, resp
    return resp


def _hits(resp: dict) -> list[tuple[str, float]]:
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


class TestMeshMaxScore:
    """Stop-word disjunctions under an integer track_total_hits below a
    stop word's span take the MaxScore plan on every shard
    (parallel/sharded.sharded_execute_maxscore), in one SPMD launch."""

    TTH = 100  # below every stop word's postings in a 750-doc shard

    def _check(self, resp: dict, docs, q: list[int]) -> None:
        ref = reference_scores(docs, q)
        assert resp["hits"]["total"] == {"value": self.TTH, "relation": "gte"}
        best = sorted(ref.values(), reverse=True)[:10]
        hits = resp["hits"]["hits"]
        assert len(hits) == len(best)
        for rank, h in enumerate(hits):
            own = ref[int(h["_id"])]
            assert abs(h["_score"] - own) <= 1e-4 * own
            assert abs(h["_score"] - best[rank]) <= 1e-4 * best[rank]

    def test_answers_equal_float64_bm25_and_the_exhaustive_plan(self, served):
        rest, docs = served
        mv = rest.node.get_index("mesh4").search.mesh_view
        text0 = _metrics(rest)
        served0 = mv.served
        qs = queries(seed=11)
        for q in qs:
            resp = _search(rest, "mesh4", q, self.TTH)
            self._check(resp, docs, q)
            # The exhaustive plan (exact totals) ranks the same hits with
            # the same float32 scores.
            assert _hits(resp) == _hits(_search(rest, "mesh4", q, True))
        text1 = _metrics(rest)
        counts = {o: v - _maxscore(text0)[o] for o, v in _maxscore(text1).items()}
        assert counts["served"] >= len(qs) // 2, counts
        assert counts["oracle"] == 0
        # Every search, MaxScore or exhaustive, stayed on the mesh: one
        # launch each, and one more for each failed check.
        assert mv.served - served0 == 2 * len(qs)
        assert (_launches(text1, "mesh_spmd") - _launches(text0, "mesh_spmd")
                == 2 * len(qs) + counts["fallback"])
        for backend in ("device_batched", "oracle"):
            assert _launches(text1, backend) == _launches(text0, backend)

    def test_failed_check_re_plans_exhaustively_on_the_mesh(self, served, monkeypatch):
        from elasticsearch_tpu.parallel import mesh_serving

        rest, docs = served
        q = queries(seed=12, n=1)[0]
        sound = _search(rest, "mesh4", q, self.TTH)
        stack = mesh_serving.stack_maxscore

        def hopeless(plans):
            # A non-essential bound no candidate beats: the check fails.
            out = stack(plans)
            out.arrays["ub_n"] = np.full_like(out.arrays["ub_n"], 1e30)
            return out

        monkeypatch.setattr(mesh_serving, "stack_maxscore", hopeless)
        text0 = _metrics(rest)
        resp = _search(rest, "mesh4", q, self.TTH)
        text1 = _metrics(rest)
        assert _hits(resp) == _hits(sound)
        self._check(resp, docs, q)
        counts = {o: v - _maxscore(text0)[o] for o, v in _maxscore(text1).items()}
        assert counts == {"served": 0, "fallback": 1, "oracle": 0, "bypass": 0}
        assert _launches(text1, "mesh_spmd") - _launches(text0, "mesh_spmd") == 2

    @pytest.mark.parametrize("declines", ["every_shard", "one_shard"])
    def test_declined_split_takes_the_exhaustive_plan(self, served, monkeypatch,
                                                      declines):
        from elasticsearch_tpu.query import compile as compile_mod

        rest, docs = served
        q = queries(seed=13, n=1)[0]
        sound = _search(rest, "mesh4", q, self.TTH)
        split = compile_mod._maxscore_arrays
        calls = []

        def declining(*a, **kw):
            # Shards plan in order: decline on all of them, or on shard 2.
            calls.append(1)
            if declines == "every_shard" or len(calls) % N_SHARDS == 3:
                return None
            return split(*a, **kw)

        monkeypatch.setattr(compile_mod, "_maxscore_arrays", declining)
        text0 = _metrics(rest)
        resp = _search(rest, "mesh4", q, self.TTH)
        text1 = _metrics(rest)
        assert len(calls) == N_SHARDS
        assert _hits(resp) == _hits(sound)
        self._check(resp, docs, q)
        counts = {o: v - _maxscore(text0)[o] for o, v in _maxscore(text1).items()}
        assert counts == {"served": 0, "fallback": 0, "oracle": 0, "bypass": 1}
        assert _launches(text1, "mesh_spmd") - _launches(text0, "mesh_spmd") == 1


# ------------------------------------------------------------ HBM ledger


class TestLedgerDevices:
    def test_cat_hbm_names_the_chip_of_each_shard(self, served):
        rest, _ = served
        rest.dispatch("POST", "/mesh4/_search", {}, json.dumps(
            {"query": {"match": {"body": term(3)}}}))
        svc = rest.node.get_index("mesh4")
        mv = svc.search.mesh_view
        status, rows = rest.dispatch("GET", "/_cat/hbm", {"format": "json"}, "")
        assert status == 200
        mine = {(r["label"], r["device"]): int(r["bytes"])
                for r in rows if r["index"] == "mesh4"}
        devices = list(mv.mesh.devices.reshape(-1))
        for i, dev in enumerate(devices):
            assert mine[("segment", device_label(dev))] == svc.engines[i].device_bytes
            assert mine[("mesh_plane", device_label(dev))] == device_nbytes(mv._devs[i])
        assert {dev for _, dev in mine} == {device_label(d) for d in devices}
        text = _metrics(rest)
        for dev in devices:
            series = (f'estpu_hbm_bytes{{device="{device_label(dev)}",'
                      f'index="mesh4",label="mesh_plane"}}')
            assert _counter(text, series) == device_nbytes(mv._devs[dev.id])

    def test_placing_a_scope_moves_its_earlier_bytes(self):
        ledger = HbmLedger()
        dev = jax.devices()[3]
        ledger.register("segment", 7, 1000)  # before placement: default
        ledger.place_scope(7, dev)
        ledger.register("segment", 7, 500)
        ledger.register("mesh_plane", ("m",), 64, device=jax.devices()[2])
        rows = {(r["label"], r["device"]): r["bytes"]
                for r in ledger.snapshot()["by_label_index"]}
        assert rows == {("segment", "cpu:3"): 1500, ("mesh_plane", "cpu:2"): 64}
        ledger.release("segment", 7, 1500)
        ledger.release("mesh_plane", ("m",), 64, device=jax.devices()[2])
        assert ledger.total_bytes == 0 and ledger.snapshot()["by_label_index"] == []
