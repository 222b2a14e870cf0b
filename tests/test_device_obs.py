"""Device observability (ISSUE 14): the HBM ledger consistency law, the
profiler capture API, per-launch timing, and the retrace census.

The consistency law under test: `device.hbm` ledger totals equal the sum
of each component's OWN byte stats — engine segments, filter-cache
planes, ANN tiles, packed planes, mesh snapshots — through refresh /
evict / `_cache/clear` / delete_index cycles, with zero drift between
the ledger and the breaker it writes through. A seeded shape-polymorphic
plan key must trip `estpu_device_retraces_total`.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common.breaker import CircuitBreaker
from elasticsearch_tpu.node import ApiError, Node
from elasticsearch_tpu.obs import device as device_obs
from elasticsearch_tpu.obs.device import HbmLedger, LEDGER_LABELS
from elasticsearch_tpu.obs.metrics import DeviceInstruments, MetricsRegistry


def _make_node(monkeypatch, **env):
    for key, value in env.items():
        monkeypatch.setenv(key, str(value))
    return Node()


def _index_docs(node, index, n, seed=0, vectors=False, dims=8):
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n):
        doc = {
            "body": f"alpha beta {'gamma' if i % 3 else 'delta'} tok{i % 11}",
            "rank": float(rng.random()),
        }
        if vectors:
            doc["vec"] = [float(x) for x in rng.standard_normal(dims)]
        ops.append((str(i), doc))
    for doc_id, doc in ops:
        node.index_doc(index, doc, doc_id)
    node.refresh(index)


def _mappings(vectors=False, dims=8):
    props = {"body": {"type": "text"}, "rank": {"type": "float"}}
    if vectors:
        props["vec"] = {
            "type": "dense_vector",
            "dims": dims,
            "similarity": "l2_norm",
        }
    return {"mappings": {"properties": props}}


def _assert_ledger_law(node):
    """The consistency law: per-label ledger totals == component stats,
    breaker drift zero."""
    ledger = node.hbm_ledger
    seg_bytes = sum(
        e.device_bytes for svc in node.indices.values() for e in svc.engines
    )
    assert ledger.bytes_for("segment") == seg_bytes
    if node.filter_cache is not None:
        assert (
            ledger.bytes_for("filter_cache")
            == node.filter_cache.stats()["bytes_resident"]
        )
    if node.ann_cache is not None:
        assert (
            ledger.bytes_for("ann_cache")
            == node.ann_cache.stats()["bytes_resident"]
        )
    if node.packed_exec is not None:
        assert (
            ledger.bytes_for("packed_plane")
            == node.packed_exec.stats()["plane_bytes"]
        )
    mesh_bytes = 0
    for svc in node.indices.values():
        mv = getattr(svc.search, "mesh_view", None)
        if mv is not None:
            mesh_bytes += mv.plane_bytes
    assert ledger.bytes_for("mesh_plane") == mesh_bytes
    snap = ledger.snapshot()
    assert snap["breaker_drift_bytes"] == 0
    assert snap["total_bytes"] == sum(snap["by_label"].values())
    assert snap["high_watermark_bytes"] >= snap["total_bytes"]


# ---------------------------------------------------------------- ledger law


class TestLedgerConsistency:
    def test_segment_bytes_track_engines_through_refresh_and_merge(
        self, tmp_path, monkeypatch
    ):
        node = _make_node(monkeypatch)
        node.create_index("law", _mappings())
        for round_i in range(4):
            for i in range(20):
                node.index_doc(
                    "law",
                    {"body": f"w{i} alpha", "rank": 0.5},
                    f"r{round_i}-d{i}",
                )
            node.refresh("law")
            _assert_ledger_law(node)
        node.force_merge("law", 1)
        _assert_ledger_law(node)
        assert node.hbm_ledger.bytes_for("segment") > 0

    def test_fuzzed_refresh_evict_clear_delete_cycles(self, monkeypatch):
        """The acceptance-criteria fuzz: a random op sequence over
        refresh / filter-admission+eviction / `_cache/clear` /
        delete_index keeps the ledger bit-equal to component stats at
        every step."""
        node = _make_node(
            monkeypatch,
            ESTPU_FILTER_CACHE_MIN_FREQ=1,
            ESTPU_FILTER_CACHE_BYTES=4096,  # tiny: constant evictions
            ESTPU_ANN_MIN_DOCS=128,
        )
        rng = np.random.default_rng(5)
        node.create_index("fuzz", _mappings(vectors=True))
        _index_docs(node, "fuzz", 200, vectors=True)
        _assert_ledger_law(node)
        for step in range(60):
            op = rng.integers(0, 10)
            if op < 4:
                # Distinct range filters: admit (min_freq=1) and evict
                # under the 4KB budget.
                lo = round(float(rng.random()) * 0.8, 3)
                node.search(
                    "fuzz",
                    {
                        "query": {
                            "bool": {
                                "must": [{"match": {"body": "alpha"}}],
                                "filter": [
                                    {"range": {"rank": {"gte": lo}}}
                                ],
                            }
                        }
                    },
                )
            elif op < 6:
                node.search(
                    "fuzz",
                    {
                        "knn": {
                            "field": "vec",
                            "query_vector": [
                                float(x)
                                for x in rng.standard_normal(8)
                            ],
                            "k": 3,
                            "num_candidates": 32,
                        }
                    },
                )
            elif op < 8:
                node.index_doc(
                    "fuzz",
                    {"body": f"fresh alpha s{step}", "rank": 0.1},
                    f"new-{step}",
                )
                node.refresh("fuzz")
            elif op == 8:
                node.clear_cache("fuzz")
            else:
                node.delete_index("fuzz")
                assert node.hbm_ledger.total_bytes == 0
                node.create_index("fuzz", _mappings(vectors=True))
                _index_docs(node, "fuzz", 150, vectors=True, seed=step)
            _assert_ledger_law(node)

    def test_eviction_burst_race_stays_consistent(self, monkeypatch):
        """Threads hammering filter admissions under a tiny budget while
        another clears: the ledger must end bit-equal to the cache's own
        stats (the _drop_locked release path and the put path race)."""
        node = _make_node(
            monkeypatch,
            ESTPU_FILTER_CACHE_MIN_FREQ=1,
            ESTPU_FILTER_CACHE_BYTES=2048,
        )
        node.create_index("burst", _mappings())
        _index_docs(node, "burst", 150)
        errors: list[Exception] = []

        def hammer(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(30):
                    lo = round(float(rng.random()) * 0.9, 4)
                    node.search(
                        "burst",
                        {
                            "query": {
                                "bool": {
                                    "must": [{"match": {"body": "alpha"}}],
                                    "filter": [
                                        {"range": {"rank": {"gte": lo}}}
                                    ],
                                }
                            }
                        },
                    )
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        def clearer():
            try:
                for _ in range(10):
                    node.clear_cache("burst")
                    time.sleep(0.002)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(s,)) for s in range(3)
        ] + [threading.Thread(target=clearer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors
        _assert_ledger_law(node)
        assert node.hbm_ledger.bytes_for("filter_cache") >= 0

    def test_mesh_plane_bytes_register_and_release(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.create_index(
            "meshed", {**_mappings(), "settings": {"number_of_shards": 2}}
        )
        _index_docs(node, "meshed", 60)
        mv = node.indices["meshed"].search.mesh_view
        assert mv is not None and mv.ledger is node.hbm_ledger
        # A plain search engages the SPMD path and builds the snapshot.
        node.search("meshed", {"query": {"match": {"body": "alpha"}}})
        assert mv.plane_bytes > 0
        _assert_ledger_law(node)
        # Refresh: the registration swaps to the new snapshot, no leak.
        node.index_doc("meshed", {"body": "alpha new", "rank": 0.2}, "x1")
        node.refresh("meshed")
        node.search("meshed", {"query": {"match": {"body": "alpha"}}})
        _assert_ledger_law(node)
        node.delete_index("meshed")
        assert node.hbm_ledger.bytes_for("mesh_plane") == 0
        assert node.hbm_ledger.total_bytes == 0

    def test_packed_plane_bytes_register(self, monkeypatch):
        node = _make_node(monkeypatch)
        if node.packed_exec is None:
            pytest.skip("packed executor disabled")
        for t in range(3):
            node.create_index(f"tenant{t}", _mappings())
            for i in range(8):
                node.index_doc(
                    f"tenant{t}", {"body": f"alpha t{t}", "rank": 0.1},
                    f"d{i}",
                )
            node.refresh(f"tenant{t}")
        out = node.packed_exec._ensure_plane(
            [node.indices[f"tenant{t}"] for t in range(3)]
        )
        assert out is not None
        assert node.hbm_ledger.bytes_for("packed_plane") > 0
        _assert_ledger_law(node)

    def test_hbm_gauges_exposed(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.create_index("gauges", _mappings())
        _index_docs(node, "gauges", 30)
        text = node.metrics_text()
        # One series per device holding the bytes: a one-shard index
        # packs on the default device.
        assert (
            'estpu_hbm_bytes{device="cpu:0",index="gauges",label="segment"}'
            in text
        )
        assert "estpu_hbm_high_watermark_bytes" in text

    def test_breaker_writes_through_any_ledger(self):
        ledger = HbmLedger()
        breaker = CircuitBreaker(10_000, ledger=ledger)
        breaker.add(1000, label="segment", scope=1)
        breaker.add_unchecked(500, label="segment", scope=1)
        breaker.release(300, label="segment", scope=1)
        assert ledger.bytes_for("segment", scope=1) == 1200
        assert breaker.used == 1200
        assert ledger.snapshot()["breaker_drift_bytes"] == 0
        # Decorated labels collapse onto their registered base label.
        breaker.add(100, label="segment[42 docs]", scope=2)
        assert ledger.bytes_for("segment") == 1300
        assert all(
            label in LEDGER_LABELS
            for label in ledger.snapshot()["by_label"]
        )


# ------------------------------------------------------------ disabled mode


class TestDisabledMode:
    def test_estpu_device_obs_zero_is_inert_but_serving(self, monkeypatch):
        node = _make_node(monkeypatch, ESTPU_DEVICE_OBS=0)
        assert node.device is None
        node.create_index("off", _mappings())
        _index_docs(node, "off", 40)
        resp = node.search("off", {"query": {"match": {"body": "alpha"}}})
        assert resp["hits"]["total"]["value"] > 0
        section = node.nodes_stats()["nodes"][node.node_name]["device"]
        assert section["enabled"] is False
        assert section["hbm"]["enabled"] is False
        assert section["hbm"]["total_bytes"] == 0


# ---------------------------------------------------------- retrace census


class TestRetraceCensus:
    def test_seeded_shape_polymorphic_key_trips_retraces(self):
        import jax
        import jax.numpy as jnp

        registry = MetricsRegistry()
        instruments = DeviceInstruments(registry)
        f = jax.jit(lambda x: x * 2 + 1)
        with instruments.timed("poly", ("poly", 1), "device") as t:
            t.dispatched(f(jnp.ones(3)))
        assert t.first and instruments.retraces_total() == 0
        # Same key, same shape: cache hit, still no retrace.
        with instruments.timed("poly", ("poly", 1), "device") as t:
            t.dispatched(f(jnp.ones(3)))
        assert t.compiles == 0
        assert instruments.retraces_total() == 0
        # The seeded defect: the SAME plan key launches a NEW shape — the
        # key failed to capture the varying dimension, XLA recompiles,
        # and the census flags it.
        before = device_obs.process_census()["retraces"]
        with instruments.timed("poly", ("poly", 1), "device") as t:
            t.dispatched(f(jnp.ones(7)))
        assert t.compiles >= 1
        assert instruments.retraces_total() >= 1
        assert (
            registry.value(
                "estpu_device_retraces_total", plan_class="poly"
            )
            >= 1
        )
        census = instruments.compile_census()
        assert "poly" in census["retraced_plan_classes"]
        assert device_obs.process_census()["retraces"] > before

    def test_census_surfaces_in_nodes_stats(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.create_index("census", _mappings())
        _index_docs(node, "census", 30)
        node.search("census", {"query": {"match": {"body": "alpha"}}})
        section = node.nodes_stats()["nodes"][node.node_name]["device"]
        compile_section = section["compile"]
        assert "retraces_total" in compile_section
        assert "attributed_xla_compiles" in compile_section
        assert "retraced_plan_classes" in compile_section

    def test_launch_histograms_have_phases(self, monkeypatch):
        # Planner off: the planner's first decisions of a plan class go
        # to the CPU oracle, which launches nothing.
        node = _make_node(monkeypatch, ESTPU_EXEC_PLANNER="0")
        node.create_index("hist", _mappings())
        _index_docs(node, "hist", 50)
        # Same-shape concurrent searches coalesce through the batcher
        # into _device_batch's timed launch (queue/execute split).
        body = {"query": {"match": {"body": "alpha beta"}}}
        threads = [
            threading.Thread(
                target=lambda: node.search("hist", dict(body))
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        node.search("hist", dict(body))
        family = node.metrics.family("estpu_launch_ms")
        assert family is not None and family[0] == "histogram"
        phases = {dict(key).get("phase") for key in family[2]}
        assert {"queue", "execute"} <= phases
        assert sum(snap["count"] for snap in family[2].values()) > 0


# -------------------------------------------------------------- profiler API


class TestProfilerCapture:
    def test_round_trip_produces_perfetto_dir_and_ring_stamp(
        self, monkeypatch, tmp_path
    ):
        from jax.profiler import ProfileData

        from elasticsearch_tpu.rest.server import RestServer

        rest = RestServer()
        node = rest.node
        node.create_index("prof", _mappings())
        _index_docs(node, "prof", 40)
        start = node.profiler_start(
            {"duration_s": 60, "trace_dir": str(tmp_path / "cap")}
        )
        assert start["acknowledged"] and start["trace_dir"]
        assert node.profiler_status()["running"] is True
        status, _ = rest.dispatch(
            "POST", "/prof/_search", {},
            '{"query": {"match": {"body": "alpha"}}}',
        )
        assert status == 200
        stop = node.profiler_stop()
        rest.close()
        assert stop["trace_dir"] == start["trace_dir"]
        files = [
            os.path.join(root, f)
            for root, _d, fs in os.walk(stop["trace_dir"])
            for f in fs
        ]
        assert any(f.endswith(".trace.json.gz") for f in files)
        assert node.profiler_status()["running"] is False
        # The capture itself holds the request's spans, on its own clock.
        xplane = next(f for f in files if f.endswith(".xplane.pb"))
        names = {
            event.name
            for plane in ProfileData.from_file(xplane).planes
            for line in plane.lines
            for event in line.events
        }
        assert {"estpu/rest.request", "estpu/search"} <= names

    def test_double_start_409_and_stop_without_start_400(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.profiler_start({"duration_s": 60})
        try:
            with pytest.raises(ApiError) as exc:
                node.profiler_start({"duration_s": 60})
            assert exc.value.status == 409
        finally:
            node.profiler_stop()
        with pytest.raises(ApiError) as exc:
            node.profiler_stop()
        assert exc.value.status == 400

    def test_bounded_duration_auto_stops(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.profiler_start({"duration_s": 0.2})
        deadline = time.monotonic() + 10
        while (
            node.profiler_status()["running"]
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert node.profiler_status()["running"] is False
        # The watchdog's stop frees the single-flight slot.
        started = node.profiler_start({"duration_s": 60})
        assert started["acknowledged"]
        node.profiler_stop()

    def test_duration_clamped_to_bound_and_validated(self, monkeypatch):
        monkeypatch.setenv("ESTPU_PROFILER_MAX_S", "5")
        node = Node()
        out = node.profiler_start({"duration_s": 9999})
        assert out["max_duration_s"] == 5.0
        node.profiler_stop()
        with pytest.raises(ApiError) as exc:
            node.profiler_start({"duration_s": "soon"})
        assert exc.value.status == 400

    def test_rest_routes(self, monkeypatch, tmp_path):
        from elasticsearch_tpu.rest.server import RestServer

        rest = RestServer()
        status, out = rest.dispatch(
            "POST", "/_profiler/start", {}, "{}"
        )
        assert status == 200 and out["acknowledged"]
        status, out = rest.dispatch("POST", "/_profiler/start", {}, "{}")
        assert status == 409
        status, out = rest.dispatch("GET", "/_profiler", {}, "")
        assert status == 200 and out["running"] is True
        status, out = rest.dispatch("POST", "/_profiler/stop", {}, "")
        assert status == 200 and out["trace_dir"]
        status, out = rest.dispatch("POST", "/_profiler/stop", {}, "")
        assert status == 400
        rest.close()


# -------------------------------------------------------------- cat surfaces


class TestCatSurfaces:
    def test_cat_hbm_rows_match_ledger(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.create_index("cat", _mappings())
        _index_docs(node, "cat", 40)
        rows = node.cat_hbm()
        seg_rows = [r for r in rows if r["label"] == "segment"]
        assert seg_rows and seg_rows[0]["index"] == "cat"
        assert int(seg_rows[0]["bytes"]) == node.hbm_ledger.bytes_for(
            "segment"
        )
        total_row = next(r for r in rows if r["label"] == "_total")
        assert int(total_row["bytes"]) == node.hbm_ledger.total_bytes
        assert int(total_row["high_watermark"]) >= int(total_row["bytes"])

    def test_cat_segments_device_bytes_column(self, monkeypatch):
        node = _make_node(monkeypatch)
        node.create_index("catseg", _mappings())
        _index_docs(node, "catseg", 40)
        rows = node.cat_segments()
        assert rows and all("device.bytes" in r for r in rows)
        total = sum(
            int(r["device.bytes"]) for r in rows if r["index"] == "catseg"
        )
        assert total == node.hbm_ledger.bytes_for("segment")

    def test_cat_hbm_rest_route(self, monkeypatch):
        from elasticsearch_tpu.rest.server import RestServer

        rest = RestServer()
        rest.node.create_index("viacat", _mappings())
        rest.node.index_doc("viacat", {"body": "alpha", "rank": 0.5}, "1")
        rest.node.refresh("viacat")
        status, rows = rest.dispatch(
            "GET", "/_cat/hbm", {"format": "json"}, ""
        )
        assert status == 200
        assert any(r["label"] == "segment" for r in rows)
        rest.close()


# ---------------------------------------------------------- profile response


class TestProfileDeviceBlock:
    def test_profile_true_carries_per_segment_device_block(
        self, monkeypatch
    ):
        node = _make_node(monkeypatch)
        node.create_index("pblock", _mappings())
        _index_docs(node, "pblock", 40)
        resp = node.search(
            "pblock",
            {"query": {"match": {"body": "alpha"}}, "profile": True},
        )
        segments = resp["profile"]["shards"][0]["searches"][0]["query"][0][
            "breakdown"
        ]["segments"]
        assert segments
        block = segments[0]["device"]
        assert {"launch_ms", "compile", "h2d_bytes"} <= set(block)
        assert block["launch_ms"] >= 0
        assert isinstance(block["compile"], bool)

    def test_knn_profile_device_block_has_split(self, monkeypatch):
        node = _make_node(monkeypatch, ESTPU_ANN_MIN_DOCS=64)
        node.create_index("pknn", _mappings(vectors=True))
        _index_docs(node, "pknn", 120, vectors=True)
        rng = np.random.default_rng(3)
        resp = node.search(
            "pknn",
            {
                "knn": {
                    "field": "vec",
                    "query_vector": [
                        float(x) for x in rng.standard_normal(8)
                    ],
                    "k": 3,
                    "num_candidates": 16,
                },
                "profile": True,
            },
        )
        segments = resp["profile"]["shards"][0]["searches"][0]["query"][0][
            "breakdown"
        ]["segments"]
        block = segments[0]["device"]
        assert {"launch_ms", "queue_ms", "execute_ms", "compile"} <= set(
            block
        )


# ----------------------------------------------------------- clustered stats


class TestClusterFan:
    def test_cluster_node_sections_carry_device_hbm(self):
        from elasticsearch_tpu.cluster import LocalCluster

        cluster = LocalCluster(n_nodes=2)
        try:
            cluster.create_index("fanned", n_shards=1, n_replicas=1)
            node = Node(replication=cluster)
            stats = node.nodes_stats()
            assert stats["_nodes"]["failed"] == 0
            member_sections = [
                section
                for name, section in stats["nodes"].items()
                if name != node.node_name
            ]
            assert member_sections
            for section in member_sections:
                hbm = section["device"]["hbm"]
                assert hbm["enabled"] is True
                assert hbm["total_bytes"] == sum(
                    hbm["by_label"].values()
                )
            # The coordinating front's cat view renders every member row.
            nodes_in_cat = {row["node"] for row in node.cat_hbm()}
            assert len(nodes_in_cat) >= 2
        finally:
            cluster.close()
