"""The span reduction (benchmark/spans.py) on small traces, beside the
accepted trace reduction, and the four span readers."""

import importlib.util
import json
import os

import pytest

from benchmark import scrape, spans as S, trace_reduce as T
from benchmark.run import Window

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS = os.path.join(os.path.dirname(HERE), "layers")
ACCEPTED = ("device_idle_share.closed", "device_ms_per_search.closed",
            "device_served_share.closed", "postings_per_device_us.closed")
SPAN_READERS = ("rest_ms_per_search.closed", "plan_ms_per_search.closed",
                "stage_ms_per_search.closed", "fetch_ms_per_search.closed")


def reader(name):
    spec = importlib.util.spec_from_file_location("r_" + name.replace(".", "_"),
                                                  os.path.join(LAYERS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def host(*lines, ops=()):
    return S.Host(lines=[list(ln) for ln in lines], ops={0: list(ops)})


def test_self_time_is_total_less_nested_spans_on_the_thread():
    line = [("estpu/outer", 0.0, 100.0), ("estpu/a", 10.0, 40.0),
            ("estpu/deep", 20.0, 30.0), ("estpu/b", 50.0, 60.0)]
    other = [("estpu/x", 0.0, 100.0)]  # another thread: never nested
    r = S.reduce_spans(host([(T.WINDOW, 0.0, 100.0)], line, other, ops=[("o", 0.0, 1.0)]), 1)
    t = r["spans"]
    assert t["outer"]["total_s"] == pytest.approx(100e-9)
    assert t["outer"]["self_s"] == pytest.approx(60e-9)
    assert t["a"]["self_s"] == pytest.approx(20e-9)
    assert t["deep"]["self_s"] == t["deep"]["total_s"] == pytest.approx(10e-9)
    assert t["x"]["self_s"] == pytest.approx(100e-9)
    assert {n: row["count"] for n, row in t.items()} == {"outer": 1, "a": 1, "deep": 1, "b": 1, "x": 1}


def test_spans_are_clipped_to_the_window():
    line = [("estpu/long", -50.0, 150.0), ("estpu/in", -10.0, 10.0), ("estpu/out", 200.0, 300.0)]
    r = S.reduce_spans(host([(T.WINDOW, 0.0, 100.0)], line, ops=[("o", 0.0, 1.0)]), 1)
    assert r["spans"]["long"]["total_s"] == pytest.approx(100e-9)
    assert r["spans"]["long"]["self_s"] == pytest.approx(90e-9)
    assert "out" not in r["spans"]


def test_idle_goes_to_the_latest_started_span_on_any_thread():
    client = [(T.WINDOW, 0.0, 1000.0), (T.REQUEST, 0.0, 200.0)]
    handler = [("estpu/rest.request", 0.0, 200.0), ("estpu/batcher.await", 20.0, 180.0)]
    scheduler = [("estpu/batcher.launch", 30.0, 170.0), ("estpu/device.fetch", 120.0, 150.0)]
    tie = [("estpu/long", 60.0, 90.0), ("estpu/short", 60.0, 70.0)]
    # The device runs over [40, 60): in-flight idle is [0, 40) + [60, 200).
    r = S.reduce_spans(host(client, handler, scheduler, tie, ops=[("k", 40.0, 60.0)]), 1)
    idle = dict(r["idle_s"])
    assert idle == pytest.approx({
        "rest.request": 20e-9 + 20e-9,   # [0, 20) and [180, 200)
        "batcher.await": 10e-9 + 10e-9,  # [20, 30) and [170, 180)
        "batcher.launch": 60e-9,         # [30, 40), [90, 120), [150, 170)
        "short": 10e-9,                  # [60, 70): ties go to the shortest
        "long": 20e-9,                   # [70, 90)
        "device.fetch": 30e-9,           # [120, 150)
    })
    assert r["untraced_idle_s"] == 0.0
    assert r["idle_total_s"] == pytest.approx(180e-9)


def test_idle_no_span_covers_is_untraced():
    client = [(T.WINDOW, 0.0, 100.0), (T.REQUEST, 0.0, 100.0)]
    handler = [("estpu/rest.request", 10.0, 90.0)]
    r = S.reduce_spans(host(client, handler, ops=[("k", 40.0, 50.0)]), 1)
    assert dict(r["idle_s"]) == pytest.approx({"rest.request": 70e-9})
    assert r["untraced_idle_s"] == pytest.approx(20e-9)


def test_op_time_by_named_scope():
    assert S.op_scope("jit(f)/vmap(bm25.score)/sort") == "bm25.score"
    assert S.op_scope("a/bm25.score/bm25.topk/x") == "bm25.topk"
    assert S.op_scope("jit(f)/copy") == S.UNSCOPED
    ops = [("bm25.score", 0.0, 30.0), ("bm25.topk", 30.0, 40.0), ("bm25.score", 90.0, 120.0)]
    r = S.reduce_spans(host([(T.WINDOW, 0.0, 100.0)], ops=ops), 1)
    assert dict(r["scopes_s"]) == pytest.approx({"bm25.score": 40e-9, "bm25.topk": 10e-9})


def test_scopes_from_the_captured_hlo_modules(tmp_path):
    """The profiler keeps each program's HLO module in its metadata plane;
    instructions map to the named scopes of their name metadata."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(x):
        with jax.named_scope("bm25.score"):
            y = jnp.sort(x * 2.0)
        with jax.named_scope("bm25.total"):
            return y, jnp.sum(y > 1.0)

    x = jnp.arange(4096, dtype=jnp.float32)
    jax.block_until_ready(kernel(x))
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(kernel(x))
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    scopes = S.hlo_scopes(path.read_bytes())
    mine = {instr: scope for (program, instr), scope in scopes.items() if program == "jit_kernel"}
    assert any(instr.startswith("sort") and scope == "bm25.score" for instr, scope in mine.items())
    assert "bm25.total" in mine.values()


def test_nothing_to_read_gives_none():
    assert S.reduce_spans(host([("estpu/x", 0.0, 1.0)], ops=[("k", 0.0, 1.0)]), 1) is None
    assert S.reduce_spans(S.Host(lines=[[(T.WINDOW, 0.0, 1.0)]]), 1) is None


def recorded(with_spans: bool):
    """recorded_trace.json as a Trace, optionally with a program span
    tree over every request (a handler root, a scheduler launch)."""
    fx = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    tr = T.Trace()
    tr.host = [tuple(fx["window"])] + [tuple(h) for h in fx["host"]]
    tr.device_ops = {0: [tuple(o) for o in fx["device_ops"]]}
    if with_spans:
        for _t, n, s, e in list(tr.host):
            if n == T.REQUEST:
                tr.host.append(("handler", "estpu/rest.request", s + 50e3, e - 50e3))
                tr.host.append(("scheduler", "estpu/batcher.launch", s + 100e3, e - 100e3))
    return tr


def window(trace):
    return Window(completed=100, counters=scrape.Delta({}, {}), trace=trace, postings=1e9)


def test_recorded_trace_spans_leave_the_accepted_numbers_as_they_were():
    before, after = T.reduce(recorded(False), 1), T.reduce(recorded(True), 1)
    for key in ("window_s", "busy_s", "inflight_s", "idle_share", "device_ops"):
        assert after[key] == before[key], key
    # The gaps are the same; only their labels may name a program span.
    assert sum(v for _, v in after["idle_gaps"]) == pytest.approx(sum(v for _, v in before["idle_gaps"]))
    for name in ACCEPTED:
        r = reader(name)
        assert r.read(window(after)) == r.read(window(before)), name


def test_recorded_trace_idle_by_span_sums_to_the_gaps():
    tr = recorded(True)
    lines: dict[str, list] = {}
    for t, n, s, e in tr.host:
        lines.setdefault(t, []).append((n, s, e))
    red = S.reduce_spans(S.Host(lines=list(lines.values()),
                                ops={0: [("k", s, e) for _, s, e in tr.device_ops[0]]}), 1)
    gaps = sum(v for _, v in T.reduce(tr, 1)["idle_gaps"])
    assert red["idle_total_s"] == pytest.approx(gaps)
    assert sum(v for _, v in red["idle_s"]) + red["untraced_idle_s"] == pytest.approx(gaps)
    assert set(dict(red["idle_s"])) == {"rest.request", "batcher.launch"}


def counters(**series):
    """A Delta whose `after` holds the span counters given as
    {metric: {span: value}}."""
    after = {(m, (("span", span),)): v for m, by in series.items() for span, v in by.items()}
    return scrape.Delta({}, after)


def test_span_readers():
    w = Window(completed=10, trace=None, postings=0.0, counters=counters(
        estpu_span_self_seconds_total={"rest.request": 0.02, "search": 0.01, "device.stage": 9.0},
        estpu_span_seconds_total={"query.compile": 0.03, "exec.plan": 0.01, "device.stage": 0.02,
                                  "device.dispatch": 0.05, "device.fetch": 0.004,
                                  "search.assemble": 0.006, "rest.request": 9.0},
    ))
    got = {n: reader(n).read(w) for n in SPAN_READERS}
    assert got == pytest.approx({"rest_ms_per_search.closed": 3.0, "plan_ms_per_search.closed": 4.0,
                                 "stage_ms_per_search.closed": 7.0, "fetch_ms_per_search.closed": 1.0})


def test_span_readers_read_nothing_without_the_programs_counters():
    w = Window(completed=10, trace=None, postings=0.0,
               counters=counters(estpu_exec_planner_decisions_total={"x": 1.0}))
    assert all(reader(n).read(w) is None for n in SPAN_READERS)
    w.counters = counters(estpu_span_seconds_total={"device.fetch": 1.0})
    w.completed = 0
    assert reader("fetch_ms_per_search.closed").read(w) is None


def test_span_readers_match_their_benchmark_entries():
    spec = json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")))
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in SPAN_READERS:
        r, m = reader(name), entries[name]
        assert (r.UNIT, r.LAYER, r.MOVES) == (m["unit"], m["layer"], m["moves"])
