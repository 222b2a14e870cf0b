"""End-to-end distributed tracing (obs/tracing.py).

The acceptance contract: one `_search` against a replicated multi-shard
cluster yields ONE connected trace — root REST span → gateway → per-shard
(remote, via transport payload propagation) → per-segment launch spans —
including under injected faults and copy-retry reroutes; the trace
exports as valid Chrome trace-event JSON; `profile: true` inlines the
request's own span tree; cache hits are tagged and report an honest
nonzero took; slowlog lines carry trace_id + took_breakdown.
"""

import json
import logging
import os
import threading
import time

import pytest

from elasticsearch_tpu.faults import REGISTRY, FaultSpec
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.obs.tracing import (
    TRACER,
    Tracer,
    format_traceparent,
    parse_traceparent,
)
from elasticsearch_tpu.rest.server import RestServer


@pytest.fixture(autouse=True)
def _clean_obs():
    REGISTRY.clear()
    TRACER.clear()
    yield
    REGISTRY.clear()
    TRACER.clear()


def _assert_connected(spans):
    """Every span parents (transitively) to the single root."""
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 1, [s["name"] for s in roots]
    root_id = roots[0]["span_id"]
    for s in spans:
        seen = set()
        cur = s
        while cur["parent_id"] is not None:
            assert cur["parent_id"] in by_id, (
                f"span [{s['name']}] dangles at [{cur['name']}]"
            )
            assert cur["span_id"] not in seen, "parent cycle"
            seen.add(cur["span_id"])
            cur = by_id[cur["parent_id"]]
        assert cur["span_id"] == root_id
    return roots[0]


def _seed(rest, index="obs", shards=2, replicas=2, n=16):
    status, _ = rest.dispatch(
        "PUT",
        f"/{index}",
        {},
        json.dumps(
            {
                "settings": {
                    "index": {
                        "number_of_shards": shards,
                        "number_of_replicas": replicas,
                    }
                },
                "mappings": {"properties": {"b": {"type": "text"}}},
            }
        ),
    )
    assert status == 200
    lines = []
    for i in range(n):
        lines.append(json.dumps({"index": {"_index": index, "_id": f"d{i}"}}))
        lines.append(json.dumps({"b": f"alpha w{i % 3} filler{i}"}))
    status, resp = rest.dispatch("POST", "/_bulk", {}, "\n".join(lines))
    assert status == 200 and not resp["errors"]
    rest.dispatch("POST", f"/{index}/_refresh", {}, "")


@pytest.fixture
def replicated(monkeypatch):
    monkeypatch.setenv("ESTPU_MESH_SERVING", "0")
    rest = RestServer(replication_nodes=3)
    _seed(rest)
    yield rest
    rest.close()


class TestReplicatedTrace:
    def _search_trace(self, rest, body=None, headers=None):
        status, resp = rest.dispatch(
            "POST",
            "/obs/_search",
            {},
            json.dumps(body or {"query": {"match": {"b": "alpha"}}}),
            headers=headers,
        )
        trace_id = rest._tl.response_headers.get("X-Trace-Id")
        assert trace_id, "dispatch must return X-Trace-Id"
        return status, resp, trace_id

    def test_single_search_yields_one_connected_trace(self, replicated):
        """3 nodes, 2 shards, 2 replicas: root REST span → search →
        gateway → per-shard → remote execution → per-segment launches,
        every span parenting to the root."""
        status, resp, trace_id = self._search_trace(replicated)
        assert status == 200
        assert resp["_shards"]["failed"] == 0
        status, tree = replicated.dispatch(
            "GET", f"/_traces/{trace_id}", {}, ""
        )
        assert status == 200
        spans = tree["spans"]
        root = _assert_connected(spans)
        assert root["name"] == "rest.request"
        names = [s["name"] for s in spans]
        assert "search" in names
        assert "gateway.search" in names
        # Per-shard scatter on the cluster coordinator.
        assert names.count("cluster.shard") == 2
        # The wire hop (payload-propagated context)...
        assert any(n == "transport.shard_search" for n in names)
        # ...and the REMOTE node's execution parenting through it, down
        # to per-segment kernel launches.
        assert any(n == "cluster.shard_search" for n in names)
        assert any(n == "search.segment" for n in names)

    def test_trace_listed_in_ring(self, replicated):
        _status, _resp, trace_id = self._search_trace(replicated)
        status, listing = replicated.dispatch("GET", "/_traces", {}, "")
        assert status == 200
        assert any(t["trace_id"] == trace_id for t in listing["traces"])
        entry = next(
            t for t in listing["traces"] if t["trace_id"] == trace_id
        )
        assert entry["root"] == "rest.request"
        assert entry["spans"] >= 5

    def test_unknown_trace_404(self, replicated):
        status, resp = replicated.dispatch(
            "GET", "/_traces/deadbeef", {}, ""
        )
        assert status == 404
        assert resp["error"]["type"] == "resource_not_found_exception"

    def test_connected_under_faults_and_copy_retries(self, replicated):
        """An armed transport fault: the trace stays ONE connected tree,
        faulted spans are tagged injected_fault, and copy retries show as
        events on the shard spans."""
        status, _ = replicated.dispatch(
            "POST",
            "/_fault",
            {},
            json.dumps(
                {
                    "site": "transport.send.shard_search",
                    "error_rate": 0.6,
                    "error": "transport",
                    "seed": 11,
                }
            ),
        )
        assert status == 200
        saw_injected = saw_retry = False
        for _ in range(8):
            status, _resp, trace_id = self._search_trace(replicated)
            if status != 200:
                continue  # all-copies-dead 503: no result to trace-check
            spans = TRACER.export(trace_id)["spans"]
            _assert_connected(spans)
            for s in spans:
                if s.get("tags", {}).get("injected_fault"):
                    assert s["status"] == "error"
                    saw_injected = True
                for ev in s.get("events", []):
                    if ev["name"] == "search.copy_retry":
                        saw_retry = True
            if saw_injected and saw_retry:
                break
        assert saw_injected, "no span carried the injected_fault tag"
        assert saw_retry, "no copy_retry event reached the trace"

    def test_chrome_export_is_valid_trace_event_json(self, replicated):
        _status, _resp, trace_id = self._search_trace(replicated)
        status, chrome = replicated.dispatch(
            "GET", f"/_traces/{trace_id}", {"format": "chrome"}, ""
        )
        assert status == 200
        # Round-trips as JSON and carries the trace-event shape Perfetto
        # loads: complete events with microsecond ts/dur.
        blob = json.loads(json.dumps(chrome))
        events = blob["traceEvents"]
        assert events
        for ev in events:
            assert ev["ph"] == "X"
            assert isinstance(ev["name"], str) and ev["name"]
            assert ev["ts"] > 0 and ev["dur"] > 0
            assert "pid" in ev and "tid" in ev
            assert "span_id" in ev["args"]

    def test_traceparent_header_continues_callers_trace(self, replicated):
        parent = format_traceparent("ab" * 16, "cd" * 8)
        assert parse_traceparent(parent) == ("ab" * 16, "cd" * 8)
        _status, _resp, trace_id = self._search_trace(
            replicated, headers={"traceparent": parent}
        )
        assert trace_id == "ab" * 16
        spans = TRACER.export(trace_id)["spans"]
        root = next(s for s in spans if s["name"] == "rest.request")
        assert root["parent_id"] == "cd" * 8

    def test_opaque_id_tags_root(self, replicated):
        _status, _resp, trace_id = self._search_trace(
            replicated, headers={"X-Opaque-Id": "req-42"}
        )
        spans = TRACER.export(trace_id)["spans"]
        root = next(s for s in spans if s["name"] == "rest.request")
        assert root["tags"]["opaque_id"] == "req-42"


class TestLocalTrace:
    @pytest.fixture
    def node(self, monkeypatch):
        monkeypatch.setenv("ESTPU_MESH_SERVING", "0")
        node = Node()
        node.create_index(
            "t",
            {
                "mappings": {"properties": {"b": {"type": "text"}}},
                "settings": {"index": {"number_of_shards": 2}},
            },
        )
        for i in range(12):
            node.index_doc("t", {"b": f"alpha w{i % 3}"}, f"d{i}")
        node.refresh("t")
        return node

    def _last_trace(self):
        traces = TRACER.traces()
        assert traces
        return TRACER.export(traces[0]["trace_id"])["spans"]

    def test_coordinator_shard_fault_tags_span(self, node):
        """An injected coordinator.shard fault: the search degrades to a
        partial 200, the trace stays connected, and the failed shard's
        span is error + injected_fault."""
        REGISTRY.put(
            FaultSpec(site="coordinator.shard", error_rate=1.0, count=1)
        )
        out = node.search(
            "t", {"query": {"match": {"b": "alpha"}}, "profile": True}
        )
        assert out["_shards"]["failed"] == 1
        spans = self._last_trace()
        _assert_connected(spans)
        failed = [
            s
            for s in spans
            if s["name"] == "coordinator.shard" and s["status"] == "error"
        ]
        assert len(failed) == 1
        assert failed[0]["tags"]["injected_fault"] is True
        # The surviving shard still bottomed out in segment launches.
        assert any(s["name"] == "search.segment" for s in spans)

    def test_batcher_queue_and_launch_spans(self, node):
        """A batchable search rides the micro-batcher: its trace carries
        the queue-wait span and the coalesced-launch span."""
        out = node.search("t", {"query": {"match": {"b": "alpha"}}})
        assert out["hits"]["hits"]
        spans = self._last_trace()
        _assert_connected(spans)
        names = [s["name"] for s in spans]
        assert "batcher.queue" in names
        launch = next(s for s in spans if s["name"] == "batcher.launch")
        assert launch["tags"]["batch_size"] >= 1
        assert "launch_id" in launch["tags"]

    def test_coalesced_launch_span_shared_across_batchmates(self, node):
        """Concurrent same-shape searches that coalesce share ONE launch:
        their traces carry batcher.launch spans with the same launch_id."""
        barrier = threading.Barrier(3)
        trace_ids = []
        lock = threading.Lock()

        def one():
            with TRACER.start_trace("test.client") as root:
                with lock:
                    trace_ids.append(root.trace_id)
                barrier.wait()
                node.search("t", {"query": {"match": {"b": "alpha"}}})

        threads = [threading.Thread(target=one) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        launch_ids = {}
        for tid in trace_ids:
            spans = TRACER.export(tid)["spans"]
            _assert_connected(spans)
            for s in spans:
                if s["name"] == "batcher.launch":
                    launch_ids.setdefault(
                        s["tags"]["launch_id"], 0
                    )
                    launch_ids[s["tags"]["launch_id"]] += 1
        # Every rider got a launch span; coalesced riders share an id
        # (timing may split them across 1-3 launches, never more).
        assert sum(launch_ids.values()) == 3
        assert len(launch_ids) <= 3

    def test_profile_inlines_own_span_tree(self, node):
        out = node.search(
            "t", {"query": {"match": {"b": "alpha"}}, "profile": True}
        )
        tree = out["profile"]["trace"]
        assert tree["spans"]
        names = [s["name"] for s in tree["spans"]]
        assert "search" in names and "search.segment" in names
        # The root search span is still open at inline time.
        search_span = next(s for s in tree["spans"] if s["name"] == "search")
        assert search_span.get("in_progress") is True

    def test_cache_hit_honest_took_and_tag(self, node):
        body = {"query": {"match": {"b": "alpha"}}, "size": 0}
        first = node.search("t", dict(body))
        assert first["hits"]["total"]["value"] > 0
        hit = node.search("t", dict(body))
        # Honest nonzero took measured on THIS request, not a replay of
        # the cached execution's timing.
        assert hit["took"] >= 1
        assert node.request_cache.stats()["hit_count"] == 1
        spans = self._last_trace()
        search_span = next(s for s in spans if s["name"] == "search")
        assert search_span["tags"]["cache_hit"] is True
        # The hit's trace has no kernel work under the search span.
        assert not any(s["name"] == "search.segment" for s in spans)

    def test_slowlog_line_has_trace_id_and_breakdown(
        self, monkeypatch, caplog
    ):
        monkeypatch.setenv("ESTPU_MESH_SERVING", "0")
        monkeypatch.setenv("ESTPU_EXEC_BATCHER", "0")  # unbatched: phases
        node = Node()
        node.create_index(
            "s",
            {
                "mappings": {"properties": {"b": {"type": "text"}}},
                "settings": {
                    "index": {
                        "search": {
                            "slowlog": {
                                "threshold": {"query": {"warn": "0ms"}}
                            }
                        }
                    }
                },
            },
        )
        node.index_doc("s", {"b": "alpha"}, "d0")
        node.refresh("s")
        with caplog.at_level(
            logging.WARNING, logger="elasticsearch_tpu.slowlog.search"
        ):
            node.search("s", {"query": {"match": {"b": "alpha"}}})
        assert caplog.records
        msg = caplog.records[0].getMessage()
        assert "trace_id[" in msg and "trace_id[-]" not in msg
        assert "took_breakdown[" in msg
        assert "execute_ms" in msg

    def test_indexing_slowlog_fires(self, monkeypatch, caplog):
        node = Node()
        node.create_index(
            "w",
            {
                "mappings": {"properties": {"b": {"type": "text"}}},
                "settings": {
                    "index": {
                        "indexing": {
                            "slowlog": {
                                "threshold": {"index": {"warn": "0ms"}}
                            }
                        }
                    }
                },
            },
        )
        with caplog.at_level(
            logging.WARNING, logger="elasticsearch_tpu.slowlog.index"
        ):
            node.index_doc("w", {"b": "alpha"}, "d0")
        assert caplog.records
        msg = caplog.records[0].getMessage()
        assert "id[d0]" in msg and "took[" in msg

    def test_indexing_slowlog_threshold_dynamic(self, caplog):
        node = Node()
        node.create_index(
            "w2", {"mappings": {"properties": {"b": {"type": "text"}}}}
        )
        node.put_settings(
            "w2",
            {
                "index": {
                    "indexing": {
                        "slowlog": {"threshold": {"index": {"warn": "0ms"}}}
                    }
                }
            },
        )
        with caplog.at_level(
            logging.WARNING, logger="elasticsearch_tpu.slowlog.index"
        ):
            node.index_doc("w2", {"b": "x"}, "d1")
        assert caplog.records


class TestSpanCounters:
    def test_self_time_excludes_spans_nested_on_the_thread(self):
        tracer = Tracer()
        with tracer.annotate("outer"):
            time.sleep(0.02)
            with tracer.span("inner"):  # untraced: counters, no ring
                time.sleep(0.03)
            seen = []
            worker = threading.Thread(  # another thread: not nested
                target=lambda: seen.append(tracer.annotate("other"))
            )
            worker.start()
            worker.join()
            with seen[0]:
                pass
        with tracer.start_trace("root"):
            pass
        counts = tracer.metrics.label_values("estpu_spans_total", "span")
        wall = tracer.metrics.label_values("estpu_span_seconds_total", "span")
        own = tracer.metrics.label_values(
            "estpu_span_self_seconds_total", "span"
        )
        assert counts == {"outer": 1, "inner": 1, "other": 1, "root": 1}
        assert wall["inner"] >= 0.03 and own["inner"] == wall["inner"]
        assert wall["outer"] >= 0.05
        assert own["outer"] == pytest.approx(
            wall["outer"] - wall["inner"] - wall["other"]
        )
        # Only the traced root reached the ring.
        assert [t["root"] for t in tracer.traces()] == ["root"]

    def test_concurrent_spans_lose_no_count(self):
        """Threads that open their first spans of a name at once share one
        series and lose no update."""
        import sys

        tracer = Tracer()
        n_threads, per_thread = 16, 500
        barrier = threading.Barrier(n_threads)

        def work():
            barrier.wait(timeout=30)
            for _ in range(per_thread):
                with tracer.annotate("outer"):
                    with tracer.span("inner"):
                        pass

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        counts = tracer.metrics.label_values("estpu_spans_total", "span")
        assert counts == {
            "outer": n_threads * per_thread,
            "inner": n_threads * per_thread,
        }
        own = tracer.metrics.label_values(
            "estpu_span_self_seconds_total", "span"
        )
        wall = tracer.metrics.label_values("estpu_span_seconds_total", "span")
        # Self time never counts another thread's nested span.
        assert own["outer"] == pytest.approx(
            wall["outer"] - wall["inner"], abs=1e-6
        )

    def test_rest_metrics_expose_span_time(self):
        rest = RestServer()
        try:
            status, _ = rest.dispatch("GET", "/_cluster/health", {}, "")
            assert status == 200
            status, text = rest.dispatch("GET", "/_metrics", {}, "")
        finally:
            rest.close()
        assert status == 200
        assert 'estpu_spans_total{span="rest.request"}' in text.text
        assert 'estpu_span_self_seconds_total{span="rest.request"}' in (
            text.text
        )


def _host_lines(trace_dir) -> list[list[tuple[str, float, float]]]:
    """`estpu/` host events of a jax.profiler capture, one list per line
    of the `/host:CPU` plane — one line per thread, keyed by position
    (every Python thread's line is named `python`)."""
    from jax.profiler import ProfileData

    path = next(
        os.path.join(root, f)
        for root, _d, files in os.walk(trace_dir)
        for f in files
        if f.endswith(".xplane.pb")
    )
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            return [
                [
                    (
                        ev.name[len("estpu/"):],
                        float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns),
                    )
                    for ev in line.events
                    if ev.name.startswith("estpu/")
                ]
                for line in plane.lines
            ]
    return []


class TestProfilerSpans:
    """The batched `_search` path in a jax.profiler capture: every step
    is an `estpu/<span>` host event, nested on its own thread."""

    # (child, parent) on the HTTP handler's thread, then on the
    # micro-batcher's scheduler thread.
    HANDLER = (("search", "rest.request"), ("batcher.await", "search"))
    SCHEDULER = tuple(
        (name, "batcher.launch")
        for name in (
            "query.compile", "exec.plan", "search.oracle", "device.stage",
            "device.dispatch", "device.wait", "device.fetch",
            "search.assemble",
        )
    )

    def test_batched_search_spans_nest_per_thread(
        self, monkeypatch, tmp_path
    ):
        import http.client

        import jax

        from elasticsearch_tpu.rest.server import create_server

        monkeypatch.setenv("ESTPU_MESH_SERVING", "0")
        monkeypatch.setenv("ESTPU_EXEC_PACKED", "0")  # SearchService lanes
        http_server, rest = create_server(port=0)
        serving = threading.Thread(
            target=http_server.serve_forever, daemon=True
        )
        serving.start()
        conn = http.client.HTTPConnection(
            "127.0.0.1", http_server.server_address[1], timeout=120
        )
        body = json.dumps({"query": {"match": {"b": "alpha w1"}}})

        def search():
            conn.request(
                "POST", "/cap/_search", body,
                {"Content-Type": "application/json"},
            )
            resp = json.loads(conn.getresponse().read())
            hits = resp["hits"]
            return hits["total"], [
                (h["_id"], h["_score"]) for h in hits["hits"]
            ]

        try:
            _seed(rest, index="cap", shards=1, replicas=0)
            # The planner explores both backends of the plan class (the
            # CPU oracle and the device launch) within these searches.
            jax.profiler.start_trace(str(tmp_path))
            try:
                captured = [search() for _ in range(5)]
            finally:
                jax.profiler.stop_trace()
            plain = [search() for _ in range(5)]
        finally:
            conn.close()
            http_server.shutdown()
            http_server.server_close()
            rest.close()
        assert captured == plain and captured[0][1]

        lines = [ln for ln in _host_lines(tmp_path) if ln]
        names = {name for ln in lines for name, _s, _e in ln}
        for child, parent in self.HANDLER + self.SCHEDULER:
            assert {child, parent} <= names, (child, parent, names)
        handler = [
            ln for ln in lines if any(n == "rest.request" for n, *_ in ln)
        ]
        scheduler = [
            ln for ln in lines if any(n == "batcher.launch" for n, *_ in ln)
        ]
        assert handler and scheduler
        assert not any(ln in scheduler for ln in handler)
        for group, pairs in (
            (handler, self.HANDLER), (scheduler, self.SCHEDULER)
        ):
            for ln in group:
                for child, parent in pairs:
                    for name, s, e in ln:
                        if name != child:
                            continue
                        assert any(
                            n == parent and ps <= s and e <= pe
                            for n, ps, pe in ln
                        ), f"{child} at {s} outside every {parent}"


class TestTasksApi:
    def test_running_time_is_monotonic_based(self):
        node = Node()
        task = node.tasks.register("indices:data/read/search", "test")
        # Wall-clock poisoning start_ms must not affect the runtime (the
        # old implementation derived nanos from it).
        task.start_ms -= 3_600_000.0
        j = task.to_json()
        assert 0 <= j["running_time_in_nanos"] < int(60e9)
        node.tasks.unregister(task)

    def test_list_tasks_detailed_reports_span(self):
        node = Node()
        task = node.tasks.register("indices:data/read/search", "probing")
        task.span_name = "search.segment"
        out = node.list_tasks(detailed=True)
        entry = out["nodes"][node.node_name]["tasks"][task.id]
        assert entry["span"] == "search.segment"
        assert entry["description"] == "probing"
        plain = node.list_tasks()["nodes"][node.node_name]["tasks"][task.id]
        assert "description" not in plain
        assert plain["running_time_in_nanos"] >= 0
        node.tasks.unregister(task)

    def test_cat_tasks_route(self):
        rest = RestServer()
        task = rest.node.tasks.register("indices:data/read/search", "x")
        task.span_name = "batcher.queue"
        status, rows = rest.dispatch("GET", "/_cat/tasks", {}, "")
        assert status == 200
        assert any(
            r["task_id"] == task.id and r["span"] == "batcher.queue"
            for r in rows
        )
        status, detailed = rest.dispatch(
            "GET", "/_tasks", {"detailed": "true"}, ""
        )
        assert status == 200
        assert task.id in detailed["nodes"][rest.node.node_name]["tasks"]
        rest.node.tasks.unregister(task)
