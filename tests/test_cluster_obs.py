"""Cluster-scope observability (ISSUE 13): wire-fanned `_nodes/stats`,
federated `/_metrics`, distributed trace assembly, and hot-threads
sampling.

Three surfaces, three topologies:

- standalone Node: same `_nodes` header shape with total=1;
- in-memory LocalCluster behind the REST server (hub AND tcp transports:
  one response shape across both — the PR-11 interception-parity rule
  applied to observability);
- ProcCluster (2 spawned OS worker processes + tiebreaker): the
  acceptance topology — per-node sections cross real sockets, remote
  span bodies live in worker rings until trace assembly splices them,
  and `kill -9` of a worker yields a NAMED failure entry within the
  per-send deadline, never a hang.
"""

import json
import os
import tempfile
import time

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.obs.hot_threads import hot_threads_text
from elasticsearch_tpu.obs.tracing import chrome_trace, splice_spans
from elasticsearch_tpu.rest.server import RestServer

REPLICATED_INDEX = json.dumps(
    {
        "settings": {
            "index": {"number_of_shards": 2, "number_of_replicas": 1}
        },
        "mappings": {"properties": {"b": {"type": "text"}}},
    }
)

# Sections every ClusterNode's node_stats wire payload must carry — the
# one-shape-across-transports contract.
MEMBER_SECTIONS = {
    "name",
    "roles",
    "master",
    "process",
    "accelerator",
    "indices",
    "search_resilience",
    "cluster_state",
    "step_errors",
    "transport",
    # Per-node device.hbm section (ISSUE 14): computed from component
    # stats on workers (no write-through ledger there), fanned so the
    # coordinating front's /_cat/hbm shows every member's residency.
    "device",
}


def _member_sections(stats: dict, node_id: str) -> set:
    return set(stats["nodes"][node_id]) & MEMBER_SECTIONS


class TestStandaloneShape:
    def test_nodes_header_present_single_node(self):
        node = Node()
        stats = node.nodes_stats()
        assert stats["_nodes"] == {
            "total": 1,
            "successful": 1,
            "failed": 0,
        }
        assert node.node_name in stats["nodes"]
        # Pre-PR consumers keep working: the local sections are intact.
        assert "device" in stats["nodes"][node.node_name]
        assert "obs" in stats["nodes"][node.node_name]

    def test_cluster_obs_section_shape(self):
        node = Node()
        obs = node.nodes_stats()["nodes"][node.node_name]["obs"]["cluster"]
        for key in (
            "fanouts",
            "fan_failures",
            "fan_latency_ms",
            "trace_fragments_collected",
            "hot_threads_samples",
        ):
            assert key in obs

    def test_cat_nodes_single_row(self):
        node = Node()
        rows = node.cat_nodes()
        assert len(rows) == 1
        assert rows[0]["name"] == node.node_name
        assert rows[0]["master"] == "*"
        assert rows[0]["node.role"] == "dim"

    def test_hot_threads_samples_own_process(self):
        node = Node()
        text = node.hot_threads(interval_s=0.05, snapshots=2)
        assert f"::: {{{node.node_name}}} pid[{os.getpid()}]" in text
        assert "busiestThreads=3" in text
        obs = node.nodes_stats()["nodes"][node.node_name]["obs"]["cluster"]
        assert obs["hot_threads_samples"] >= 2


class TestLocalClusterFanIn:
    @pytest.fixture(scope="class")
    def rest(self):
        mesh = os.environ.get("ESTPU_MESH_SERVING")
        os.environ["ESTPU_MESH_SERVING"] = "0"
        server = RestServer(replication_nodes=3)
        yield server
        server.close()
        if mesh is None:
            os.environ.pop("ESTPU_MESH_SERVING", None)
        else:
            os.environ["ESTPU_MESH_SERVING"] = mesh

    def test_header_and_per_node_sections(self, rest):
        status, stats = rest.dispatch("GET", "/_nodes/stats", {}, "")
        assert status == 200
        assert stats["_nodes"]["total"] == 4  # 3 members + coordinator
        assert stats["_nodes"]["successful"] == 4
        assert stats["_nodes"]["failed"] == 0
        for node_id in ("node-1", "node-2"):
            assert _member_sections(stats, node_id) == MEMBER_SECTIONS
            assert stats["nodes"][node_id]["roles"] == ["data", "master"]
        # The coordinator entry (name-shared with member node-0) carries
        # BOTH the local sections and the grafted member sections.
        merged = stats["nodes"]["node-0"]
        assert "replication" in merged and "roles" in merged
        # Exactly one elected master across the members.
        masters = [
            node_id
            for node_id, section in stats["nodes"].items()
            if section.get("master") is True
        ]
        assert len(masters) == 1

    def test_trace_assembly_one_spliced_tree(self, rest):
        rest.dispatch("PUT", "/obsx", {}, REPLICATED_INDEX)
        rest.dispatch(
            "PUT", "/obsx/_doc/1", {}, json.dumps({"b": "alpha"})
        )
        rest.dispatch("POST", "/obsx/_refresh", {}, "")
        status, _ = rest.dispatch(
            "POST",
            "/obsx/_search",
            {},
            json.dumps({"query": {"match": {"b": "alpha"}}}),
        )
        assert status == 200
        trace_id = rest._tl.response_headers["X-Trace-Id"]
        status, tree = rest.dispatch(
            "GET", f"/_traces/{trace_id}", {}, ""
        )
        assert status == 200
        assert tree["_nodes"]["failed"] == 0
        spans = tree["spans"]
        roots = [s for s in spans if s["parent_id"] is None]
        assert len(roots) == 1  # ONE spliced tree, no duplicate spans
        assert len({s["span_id"] for s in spans}) == len(spans)
        names = [s["name"] for s in spans]
        assert "cluster.shard_search" in names
        assert "search.segment" in names

    def test_unknown_trace_404_with_fan(self, rest):
        status, resp = rest.dispatch(
            "GET", "/_traces/deadbeefdeadbeef", {}, ""
        )
        assert status == 404
        assert resp["error"]["type"] == "resource_not_found_exception"

    def test_metrics_node_labeled_with_cluster_fold(self, rest):
        status, payload = rest.dispatch("GET", "/_metrics", {}, "")
        assert status == 200
        text = payload.text
        for node_id in ("node-0", "node-1", "node-2"):
            assert f'node="{node_id}"' in text
        # Counters without a per-node label fold into cluster totals.
        assert 'node="_cluster"' in text
        # The fold never double-counts series that are ALREADY per-node:
        # the coordinator degraded-search counter keeps its 3 node
        # samples and gains no _cluster twin.
        lines = [
            line
            for line in text.splitlines()
            if line.startswith("estpu_cluster_search_resilience_total")
        ]
        assert lines and not any('node="_cluster"' in line for line in lines)

    def test_cat_nodes_roles_master_load(self, rest):
        status, rows = rest.dispatch("GET", "/_cat/nodes", {}, "")
        assert status == 200
        by_name = {r["name"]: r for r in rows}
        assert set(by_name) == {"node-0", "node-1", "node-2"}
        assert all(r["node.role"] == "dm" for r in rows)
        assert sum(r["master"] == "*" for r in rows) == 1
        for row in rows:
            int(row["load"]), int(row["docs"]), int(row["step_errors"])

    def test_hot_threads_fans_over_members(self, rest):
        status, payload = rest.dispatch(
            "GET",
            "/_nodes/hot_threads",
            {"interval": "50ms", "snapshots": "2", "threads": "2"},
            "",
        )
        assert status == 200
        text = payload.text
        for node_id in ("node-0", "node-1", "node-2"):
            assert f"::: {{{node_id}}}" in text
        # The member sharing the coordinating front's name reports ONCE
        # (same interpreter — the nodes_stats merge rule).
        assert text.count("::: {node-0}") == 1

    def test_hot_threads_bad_interval_400(self, rest):
        status, resp = rest.dispatch(
            "GET", "/_nodes/hot_threads", {"interval": "bogus"}, ""
        )
        assert status == 400
        assert resp["error"]["type"] == "illegal_argument_exception"

    def test_killed_member_named_failure_within_deadline(self, rest):
        rest.cluster.kill("node-2")
        try:
            t0 = time.monotonic()
            status, stats = rest.dispatch("GET", "/_nodes/stats", {}, "")
            elapsed = time.monotonic() - t0
            assert status == 200
            from elasticsearch_tpu.node import NODES_FAN_TIMEOUT_S

            assert elapsed < NODES_FAN_TIMEOUT_S + 3.0
            assert stats["_nodes"]["failed"] == 1
            failure = stats["_nodes"]["failures"][0]
            assert failure["node"] == "node-2"
            assert failure["reason"]
            # Survivors still ship full sections.
            assert _member_sections(stats, "node-1") == MEMBER_SECTIONS
            assert "node-2" not in stats["nodes"]
            # The fan failure is counted (estpu_nodes_stats_fan_failures).
            obs = next(iter(stats["nodes"].values()))["obs"]["cluster"]
            assert obs["fan_failures"].get("node_stats", 0) >= 1
        finally:
            rest.cluster.restart("node-2")


def test_fan_in_parity_hub_vs_tcp():
    """One response shape across transports: the per-member sections of
    `_nodes/stats` are identical over the in-memory hub and real loopback
    sockets (and both carry the `_nodes` header)."""
    sections = {}
    for transport in ("hub", "tcp"):
        server = RestServer(
            replication_nodes=2, cluster_transport=transport
        )
        try:
            status, stats = server.dispatch(
                "GET", "/_nodes/stats", {}, ""
            )
            assert status == 200
            assert stats["_nodes"]["failed"] == 0
            sections[transport] = _member_sections(stats, "node-1")
        finally:
            server.close()
    assert sections["hub"] == sections["tcp"] == MEMBER_SECTIONS


class TestSpliceAndRender:
    def test_splice_dedups_and_prefers_finished(self):
        frag_a = [
            {
                "trace_id": "t",
                "span_id": "s1",
                "parent_id": None,
                "name": "root",
                "start_time_in_millis": 10,
                "duration_ms": 5.0,
                "in_progress": True,
            }
        ]
        frag_b = [
            dict(frag_a[0], in_progress=False),
            {
                "trace_id": "t",
                "span_id": "s2",
                "parent_id": "s1",
                "name": "child",
                "start_time_in_millis": 11,
                "duration_ms": 1.0,
            },
        ]
        spans = splice_spans([frag_a, frag_b, frag_b])
        assert [s["span_id"] for s in spans] == ["s1", "s2"]
        assert not spans[0].get("in_progress", False)

    def test_chrome_lanes_by_node_tag(self):
        spans = [
            {
                "span_id": "a",
                "parent_id": None,
                "name": "root",
                "start_time_in_millis": 1,
                "duration_ms": 2.0,
            },
            {
                "span_id": "b",
                "parent_id": "a",
                "name": "remote",
                "start_time_in_millis": 2,
                "duration_ms": 1.0,
                "tags": {"node": "node-1"},
            },
        ]
        chrome = chrome_trace(spans)
        events = chrome["traceEvents"]
        assert len(events) == 2
        assert events[0]["tid"] != events[1]["tid"]
        assert all(e["ph"] == "X" and e["dur"] >= 1.0 for e in events)

    def test_hot_threads_text_renders_stacks(self):
        import threading

        stop = threading.Event()

        def spin():
            while not stop.is_set():
                sum(range(100))

        worker = threading.Thread(target=spin, daemon=True, name="spinner")
        worker.start()
        try:
            text = hot_threads_text(
                node_name="n", threads=2, interval_s=0.05, snapshots=2
            )
        finally:
            stop.set()
            worker.join(timeout=2)
        assert text.startswith("::: {n} pid[")
        assert "snapshots sharing following" in text
        assert "busy in thread 'spinner'" in text


@pytest.fixture(scope="module")
def procs():
    from elasticsearch_tpu.cluster.procs import ProcCluster

    cluster = ProcCluster(
        2, data_path=tempfile.mkdtemp(prefix="estpu-obs-procs-")
    )
    yield cluster
    cluster.close()


class TestProcClusterObservability:
    """The acceptance topology: 2 spawned OS data processes + a
    voting-only tiebreaker, all collection over the `_ctl` socket path.
    One cluster boot for the whole class (workers pay a full JAX import);
    the kill -9 scenario runs LAST."""

    def test_nodes_stats_sections_cross_real_sockets(self, procs):
        procs.create_index(
            "obs",
            n_shards=1,
            n_replicas=1,
            mappings={"properties": {"b": {"type": "text"}}},
        )
        for i in range(8):
            procs.write("obs", f"d{i}", {"b": f"alpha term{i % 3}"})
        # The primary refreshes serving this (num_docs counts searchable
        # docs, not the unrefreshed buffer).
        out = procs.search("obs", {"query": {"match_all": {}}, "size": 1})
        assert out["hits"]["total"]["value"] == 8
        stats = procs.nodes_stats()
        assert stats["_nodes"] == {
            "total": 3,
            "successful": 3,
            "failed": 0,
        }
        supervisor_pid = os.getpid()
        for worker in procs.workers:
            section = stats["nodes"][worker]
            assert set(section) & MEMBER_SECTIONS == MEMBER_SECTIONS
            # A REAL worker process, not an in-process stand-in.
            assert section["process"]["pid"] != supervisor_pid
            assert section["roles"] == ["data", "master"]
            assert section["transport"]["kind"] == "tcp"
            # Workers serve from the platform they were given: the CPU.
            assert section["accelerator"]["platform"] == "cpu"
            assert section["accelerator"]["device_kind"]
        tiebreaker = stats["nodes"]["tiebreaker"]
        assert tiebreaker["roles"] == ["master", "voting_only"]
        assert tiebreaker["indices"]["shards"]["count"] == 0
        # Docs live in the worker-owned copies, never the tiebreaker
        # (the searched primary has refreshed them searchable).
        docs = sum(
            stats["nodes"][w]["indices"]["docs"]["count"]
            for w in procs.workers
        )
        assert docs >= 8

    def test_trace_assembly_splices_remote_worker_spans(self, procs):
        out, trace_id = procs.search_traced(
            "obs", {"query": {"match": {"b": "alpha"}}, "size": 5}
        )
        assert out["_shards"]["failed"] == 0
        tree = procs.trace(trace_id)
        assert tree is not None and tree["_nodes"]["failed"] == 0
        spans = tree["spans"]
        roots = [s for s in spans if s["parent_id"] is None]
        assert len(roots) == 1 and roots[0]["name"] == "procs.search"
        names = [s["name"] for s in spans]
        # Remote execution spans whose BODIES lived in a worker's ring
        # until assembly: the shard search and its per-segment launch.
        assert "cluster.shard_search" in names
        assert "search.segment" in names
        remote_nodes = {
            (s.get("tags") or {}).get("node")
            for s in spans
            if s["name"] == "cluster.shard_search"
        }
        assert remote_nodes & set(procs.workers)
        chrome = procs.trace(trace_id, fmt="chrome")
        assert chrome["traceEvents"]
        # Worker spans render on their own Perfetto track.
        assert len({e["tid"] for e in chrome["traceEvents"]}) >= 2
        assert procs.trace("0" * 32) is None

    def test_metrics_federated_with_node_labels(self, procs):
        text = procs.metrics_text(max_age_s=0.0)
        for worker in procs.workers:
            assert f'node="{worker}"' in text
        assert 'node="tiebreaker"' in text
        assert 'node="_cluster"' in text
        # Worker-process transport counters crossed the wire.
        assert "estpu_transport_frames_total" in text
        # Scrape cache: an immediate re-scrape inside the TTL is the
        # cached text (no second fan).
        fanouts_before = procs._ctl.metrics.value(
            "estpu_nodes_stats_fanouts_total", action="metrics_wire"
        )
        procs.metrics_text(max_age_s=60.0)
        assert (
            procs._ctl.metrics.value(
                "estpu_nodes_stats_fanouts_total", action="metrics_wire"
            )
            == fanouts_before
        )

    def test_hot_threads_samples_worker_interpreters(self, procs):
        text = procs.hot_threads(interval_s=0.2, snapshots=4)
        pids = set()
        for line in text.splitlines():
            if line.startswith("::: {"):
                pids.add(int(line.split("pid[", 1)[1].rstrip("]")))
        assert "::: {tiebreaker}" in text
        for worker in procs.workers:
            assert f"::: {{{worker}}}" in text
        # Three distinct interpreters sampled themselves.
        assert len(pids) == 3

    def test_kill9_named_failure_within_deadline(self, procs):
        """The acceptance scenario: SIGKILL one data process mid-flight;
        `_nodes/stats` answers within the transport deadline with
        `_nodes.failed == 1` (named, with reason) and full sections from
        every survivor."""
        victim = procs.workers[1]
        procs.kill_9(victim)
        t0 = time.monotonic()
        stats = procs.nodes_stats()
        elapsed = time.monotonic() - t0
        assert elapsed < (procs.send_timeout_s or 5.0) + 3.0
        assert stats["_nodes"]["failed"] == 1
        failure = stats["_nodes"]["failures"][0]
        assert failure["node"] == victim
        assert failure["reason"]
        survivor = procs.workers[0]
        assert (
            set(stats["nodes"][survivor]) & MEMBER_SECTIONS
            == MEMBER_SECTIONS
        )
        assert "tiebreaker" in stats["nodes"]
        # The federated scrape degrades the same way: survivors' series
        # still present, no hang.
        text = procs.metrics_text(max_age_s=0.0)
        assert f'node="{survivor}"' in text
