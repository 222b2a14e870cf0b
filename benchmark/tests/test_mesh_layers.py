"""The four-shard cell's per-layer readers (benchmark/layers/mesh_*.py)
on fabricated counter and span deltas: what each reads, and None where
the program has no such counter or span (a program that predates the
mesh spans, or a window that served nothing on the mesh)."""

import pytest

from benchmark import run, scrape

SPAN_READERS = {
    "mesh_plan_ms_per_search.closed": ("mesh.refresh", "mesh.compile"),
    "mesh_wait_ms_per_search.closed": ("mesh.dispatch", "mesh.wait"),
    "mesh_fetch_ms_per_search.closed": ("mesh.fetch",),
}
CELL = "msmarco4shard.or-closed1"


def reader(name):
    """The reader, checked against its BENCHMARK.json entry as a run
    checks it."""
    entry = next(m for m in run.load_spec()["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "search_qps"
    return run.load_reader(entry)


def span(name, seconds):
    return {("estpu_span_seconds_total", (("span", name),)): seconds}


def window(before, after, completed=10):
    return run.Window(completed=completed, counters=scrape.Delta(before, after),
                      trace=None, postings=0.0)


def served(shape, n):
    return {("estpu_mesh_served_total", (("shape", shape),)): n}


def fallback(reason, n):
    return {("estpu_mesh_fallback_total", (("reason", reason),)): n}


def test_served_share_is_served_over_served_and_fallbacks():
    r = reader("mesh_served_share.closed")
    before = {**served("plain", 100), **fallback("breaker", 1)}
    after = {**served("plain", 130), **served("count_only", 2), **fallback("breaker", 1),
             **fallback("knn", 8)}
    assert r.read(window(before, after)) == pytest.approx(32 / 40)
    assert r.read(window(before, {**after, **fallback("knn", 0)})) == 1.0


def test_served_share_reads_nothing_without_the_counters_or_mesh_searches():
    r = reader("mesh_served_share.closed")
    assert r.read(window({}, span("search", 1.0))) is None
    still = {**served("plain", 5), **fallback("knn", 1)}
    assert r.read(window(still, dict(still))) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_is_window_wall_ms_per_search(name):
    spans = SPAN_READERS[name]
    before, after = {}, {}
    for i, s in enumerate(spans):
        before.update(span(s, 1.0 + i))
        after.update(span(s, 1.0 + i + 0.02 * (i + 1)))
    # Spans of other layers never count.
    before.update(span("device.fetch", 0.0))
    after.update(span("device.fetch", 9.0))
    want = sum(0.02 * (i + 1) for i in range(len(spans))) * 1e3 / 10
    assert reader(name).read(window(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_nothing_without_its_spans_or_searches(name):
    r = reader(name)
    host_loop = {**span("device.fetch", 3.0), **span("search.assemble", 1.0)}
    assert r.read(window({}, host_loop)) is None
    mine = {}
    for s in SPAN_READERS[name]:
        mine.update(span(s, 2.0))
    assert r.read(window({}, mine, completed=0)) is None
    assert r.read(window({}, mine)) == pytest.approx(
        2.0 * len(SPAN_READERS[name]) * 1e3 / 10)
