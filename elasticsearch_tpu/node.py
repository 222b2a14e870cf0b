"""Node: the in-process root that owns every index and service.

The analog of the reference's Node (server/src/main/java/org/elasticsearch/
node/Node.java:202, wiring IndicesService → IndexService → IndexShard) plus
the coordinator-side behavior of the core document/search/bulk transport
actions, collapsed to a single-process form: each index is one Engine (one
shard) fronted by a SearchService. The REST layer (rest/) calls into this
object the way the reference's REST handlers call NodeClient.

Versioned concurrency, replication, and multi-node membership live in later
layers (parallel/ has the device-mesh story; host-level clustering is a
control-plane concern).
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import threading
import time
import uuid as uuid_mod
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from .common.breaker import BreakerError, CircuitBreaker
from .common.indexing_pressure import IndexingPressureRejected
from .common.request_cache import RequestCache
from .common.tasks import TaskCancelledError, TaskManager
from .faults import REGISTRY as FAULTS
from .faults import FaultSpec, InjectedFaultError
from .index.engine import Engine, InvalidCasError, VersionConflictError
from .index.ann import (
    DEFAULT_MAX_BYTES as ANN_DEFAULT_BYTES,
    DEFAULT_MIN_DOCS as ANN_DEFAULT_MIN_DOCS,
    AnnCache,
    clear_index_ann,
)
from .index.filter_cache import (
    DEFAULT_MAX_BYTES as FILTER_CACHE_DEFAULT_BYTES,
    DEFAULT_MIN_FREQ as FILTER_CACHE_DEFAULT_MIN_FREQ,
    FilterCache,
    clear_index_planes,
    mesh_cache_scope,
)
from .index.mapping import Mappings
from .obs.device import (
    HbmLedger,
    ProfilerCapture,
    ProfilerConflictError,
    ProfilerInactiveError,
)
from .obs.health import (
    INDICATORS,
    HealthContext,
    HealthService,
    shard_summary,
    status_at_least,
)
from .obs.insights import QueryInsights
from .obs.metrics import DeviceInstruments, MetricsRegistry
from .obs.tracing import TRACER
from .ops.bm25 import BM25Params
from .parallel.routing import shard_for_id
from .search.coordinator import ShardedSearchCoordinator
from .search.service import (
    SearchPhaseFailedError,
    SearchRequest,
    SearchService,
    _iso_millis,
)


# Per-send deadline for cluster-wide observability scatters
# (`_nodes/stats`, trace-fragment collection, hot-threads sampling): a
# dead or wedged member yields a named failure entry within this bound.
NODES_FAN_TIMEOUT_S = float(
    os.environ.get("ESTPU_NODES_FAN_TIMEOUT_S", "5") or 5
)


class ApiError(Exception):
    """An error with an HTTP status, rendered ES-style by the REST layer.
    `headers` (e.g. Retry-After on 429s) ride to the HTTP response."""

    def __init__(
        self,
        status: int,
        err_type: str,
        reason: str,
        headers: dict[str, str] | None = None,
    ):
        super().__init__(reason)
        self.status = status
        self.err_type = err_type
        self.reason = reason
        self.headers = headers or {}


def index_not_found(name: str) -> ApiError:
    return ApiError(404, "index_not_found_exception", f"no such index [{name}]")


def _parse_keepalive(value: str) -> float:
    """ES time value ('30s', '1m', ...) → seconds, as a 400 on bad input."""
    from .common.units import parse_duration_s

    try:
        return parse_duration_s(value)
    except ValueError as e:
        raise ApiError(400, "illegal_argument_exception", str(e)) from None


_INDEX_NAME_RE = re.compile(r"^[a-z0-9][a-z0-9_\-.]*$")

# Search slow log (the reference's index.search.slowlog.*): queries over a
# configured threshold log here with their source.
slowlog = logging.getLogger("elasticsearch_tpu.slowlog.search")

# Indexing slow log (index.indexing.slowlog.threshold.index.*): document
# writes over a configured threshold log here with their id + source.
indexing_slowlog = logging.getLogger("elasticsearch_tpu.slowlog.index")


def _refresh_after_write(engine) -> bool:
    """Refresh after an already-acked (durably applied) write.

    Under HBM pressure the refresh is SKIPPED rather than failing the
    request: a 429 after the translog fsync would invite client retries
    that duplicate the document. Returns the forced_refresh flag; explicit
    /_refresh still surfaces the breaker as 429."""
    try:
        engine.refresh()
        return True
    except BreakerError:
        return False


@dataclass
class IndexService:
    """One index: mappings + N shard engines + search entry + settings.

    Shard count follows `settings.index.number_of_shards` (default 1);
    documents route to shards by ES-compatible murmur3 over _id
    (cluster/routing/OperationRouting.java:245 via parallel/routing.py),
    and multi-shard search goes through the ShardedSearchCoordinator.
    """

    name: str
    mappings: Mappings
    engines: list[Engine]
    search: SearchService | ShardedSearchCoordinator
    settings: dict[str, Any] = field(default_factory=dict)
    created_at: float = field(default_factory=time.time)
    # Unique per index INCARNATION: delete-and-recreate must not collide in
    # the request cache (generations restart from scratch).
    uuid: str = field(default_factory=lambda: uuid_mod.uuid4().hex)
    _auto_counter: int = -1  # lazy-initialized from recovered engines
    _auto_lock: threading.Lock = field(default_factory=threading.Lock)
    scroll_coordinator: Any = None  # cached 1-shard scroll coordinator

    @property
    def engine(self) -> Engine:
        """The sole engine of a 1-shard index (back-compat accessor)."""
        if len(self.engines) != 1:
            raise ValueError(
                f"index [{self.name}] has {len(self.engines)} shards; "
                f"use route()/engines"
            )
        return self.engines[0]

    @property
    def n_shards(self) -> int:
        return len(self.engines)

    def route(self, doc_id: str) -> Engine:
        """Shard engine owning doc_id (murmur3 routing, ES-compatible)."""
        if len(self.engines) == 1:
            return self.engines[0]
        return self.engines[shard_for_id(doc_id, len(self.engines))]

    def next_auto_id(self) -> str:
        """Node-generated _id for id-less writes, collision-free across
        restarts (seeded from every shard's recovered auto-id counter) and
        across concurrent REST threads (ThreadingHTTPServer dispatches
        writes concurrently; the engine lock sits below this counter)."""
        with self._auto_lock:
            if self._auto_counter < 0:
                self._auto_counter = max(e._auto_id for e in self.engines)
            doc_id = f"_auto_{self._auto_counter}"
            self._auto_counter += 1
            return doc_id

    def mesh_snapshot(self, mesh, axis: str = "shard"):
        """Stack this index's live docs onto a device mesh for SPMD serving
        (parallel/sharded.py): one segment per shard on the mesh axis, the
        scatter-gather collapsed into collectives. A point-in-time snapshot
        — writes after it don't appear until re-snapshot."""
        from .index.segment import SegmentBuilder
        from .parallel.sharded import ShardedIndex

        if mesh.shape[axis] != len(self.engines):
            raise ValueError(
                f"mesh axis [{axis}] has {mesh.shape[axis]} devices; index "
                f"[{self.name}] has {len(self.engines)} shards"
            )
        segments = []
        for engine in self.engines:
            # Snapshot the refreshed state: pending buffers and soft deletes
            # become visible first, so the mesh view equals what the
            # coordinator path serves.
            engine.refresh()
            builder = SegmentBuilder(self.mappings)
            for handle in engine.segments:
                for local in np.flatnonzero(handle.live_host):
                    local = int(local)
                    builder.add(
                        handle.segment.sources[local],
                        handle.segment.ids[local],
                    )
            segments.append(builder.build())
        return ShardedIndex.from_segments(
            segments, self.mappings, mesh, axis, self.engines[0].params
        )

    @property
    def num_docs(self) -> int:
        return sum(e.num_docs for e in self.engines)


class Node:
    def __init__(
        self,
        node_name: str = "node-0",
        cluster_name: str = "es-tpu",
        data_path: str | None = None,
        breaker_limit_bytes: int | None = None,
        plugins: list[str] | None = None,
        replication=None,
    ):
        self.node_name = node_name
        self.cluster_name = cluster_name
        self.data_path = data_path
        # Replicated serving topology: with a cluster attached, document
        # writes/reads/searches route through the host replication layer
        # (cluster/gateway.py) — acknowledged writes are seqno-replicated
        # to every in-sync copy before the 200 returns, and reads/searches
        # fail over across copies. Without it (the default), this Node
        # serves its local engines single-process, exactly as before.
        self.replication = None
        if replication is not None:
            from .cluster import LocalCluster, ReplicationGateway

            if isinstance(replication, LocalCluster):
                replication = ReplicationGateway(replication)
            self.replication = replication
        self.indices: dict[str, IndexService] = {}
        # Live scroll contexts (search/SearchService.java:167 analog);
        # bounded like the reference's search.max_open_scroll_context.
        self._scrolls: dict[str, Any] = {}
        self._scroll_lock = threading.Lock()
        self.max_open_scrolls = 500
        # Unified metrics registry (obs/metrics.py): THE write path for
        # this node's operational counters — `GET /_nodes/stats` and the
        # Prometheus exposition at `GET /_metrics` are both views over
        # it. Device-level launch instruments (XLA compile count/ms,
        # padding waste, H2D bytes, launch-ms histograms) hang off the
        # same registry. ESTPU_DEVICE_OBS=0 disables the per-launch
        # timing wrapper AND the HBM ledger (the bench's instruments-off
        # baseline); the breaker itself always enforces.
        self.metrics = MetricsRegistry()
        self.device_obs_enabled = (
            os.environ.get("ESTPU_DEVICE_OBS", "1") != "0"
        )
        self.device = (
            DeviceInstruments(self.metrics)
            if self.device_obs_enabled
            else None
        )
        # HBM ledger (obs/device.py): the single source of truth for
        # device-resident bytes by (label, index). The node breaker
        # writes through it, so breaker and ledger accounting cannot
        # drift; packed planes and mesh snapshots register directly.
        self.hbm_ledger = HbmLedger(
            metrics=self.metrics, enabled=self.device_obs_enabled
        )
        # On-demand profiler capture (POST /_profiler/start|stop):
        # single-flight jax.profiler trace windows, stamped into the obs
        # trace ring.
        self.profiler = ProfilerCapture()
        # Node-level HBM breaker shared by every shard engine (the parent
        # breaker of HierarchyCircuitBreakerService) + the shard request
        # cache (IndicesRequestCache).
        if breaker_limit_bytes is None:
            breaker_limit_bytes = int(
                os.environ.get("ESTPU_HBM_LIMIT_BYTES", 8 << 30)
            )
        self.breaker = CircuitBreaker(
            breaker_limit_bytes, ledger=self.hbm_ledger
        )
        self.metrics.gauge(
            "estpu_faults_armed",
            "Armed fault-injection specs (faults/registry.py)",
            fn=lambda: len(FAULTS._armed),
        )
        self.metrics.gauge(
            "estpu_traces_buffered",
            "Finished traces held in the /_traces ring buffer",
            fn=lambda: TRACER.stats()["buffered_traces"],
        )
        # Health report (obs/health.py, GET /_health_report): rule-based
        # indicators over the rolling windows + cluster state — the
        # interpretation layer over every raw surface above.
        self.health = HealthService(metrics=self.metrics)
        # Query insights ring (obs/insights.py, GET /_insights/queries):
        # bounded top-N slowest searches, fed from the slowlog's
        # SearchResponse.phases hook.
        self.insights = QueryInsights(
            capacity=int(os.environ.get("ESTPU_INSIGHTS_CAPACITY", 100)),
            metrics=self.metrics,
        )
        self.request_cache = RequestCache(metrics=self.metrics)
        # Filter/bitset cache (index/filter_cache.py): device-resident
        # mask planes for repeated filter-context subtrees, charged
        # against the node HBM breaker, usage-tracking admission + LRU
        # eviction. ESTPU_FILTER_CACHE=0 opts out (every path recomputes).
        self.filter_cache = None
        if os.environ.get("ESTPU_FILTER_CACHE", "1") != "0":
            self.filter_cache = FilterCache(
                max_bytes=int(
                    os.environ.get(
                        "ESTPU_FILTER_CACHE_BYTES",
                        FILTER_CACHE_DEFAULT_BYTES,
                    )
                ),
                min_freq=int(
                    os.environ.get(
                        "ESTPU_FILTER_CACHE_MIN_FREQ",
                        FILTER_CACHE_DEFAULT_MIN_FREQ,
                    )
                ),
                breaker=self.breaker,
                metrics=self.metrics,
            )
        # ANN partition cache (index/ann.py): IVF planes for the `knn`
        # section, built per (segment, dense_vector field) on first use,
        # HBM-charged, invalidated like filter-cache planes. ESTPU_ANN=0
        # opts out (every knn serves the exact brute-force kernel).
        self.ann_cache = None
        if os.environ.get("ESTPU_ANN", "1") != "0":
            self.ann_cache = AnnCache(
                max_bytes=int(
                    os.environ.get("ESTPU_ANN_BYTES", ANN_DEFAULT_BYTES)
                ),
                min_docs=int(
                    os.environ.get(
                        "ESTPU_ANN_MIN_DOCS", ANN_DEFAULT_MIN_DOCS
                    )
                ),
                breaker=self.breaker,
                metrics=self.metrics,
            )
        self.tasks = TaskManager(node_name)
        # Degraded-mode serving counters (GET /_nodes/stats
        # search_resilience): partial responses served, shard failures
        # absorbed, partial-disallowed 503s. Registry-backed; the
        # `search_resilience` property renders the stats view.
        self._resilience_counters = {
            key: self.metrics.counter(
                "estpu_search_resilience_total",
                "Degraded-mode serving events",
                kind=key,
            )
            for key in (
                "partial_responses",
                "shard_failures",
                "search_phase_failures",
            )
        }
        self.repositories: dict[str, Any] = {}
        self.pipelines: dict[str, Any] = {}  # ingest.Pipeline by id
        self._broken_pipelines: dict[str, Any] = {}  # unloadable, preserved
        self.aliases: dict[str, set[str]] = {}  # alias -> concrete indices
        # Composable index templates (cluster/metadata/
        # MetadataIndexTemplateService.java:83): name -> {index_patterns,
        # priority, template:{settings,mappings,aliases}} — applied at
        # (auto-)creation, request body winning over the template.
        self.index_templates: dict[str, dict[str, Any]] = {}
        # Stored scripts (script/ScriptService.java cluster-state scripts):
        # id -> {"lang": "painless"|"mustache", "source": str}. Referenced
        # by {"script": {"id": ...}} in queries and by _search/template.
        self.stored_scripts: dict[str, dict[str, Any]] = {}
        # Indexing backpressure: node-wide in-flight write-byte budget
        # (index/IndexingPressure.java); ESTPU_INDEXING_PRESSURE_BYTES
        # overrides the default limit.
        from .common.indexing_pressure import IndexingPressure

        self.indexing_pressure = IndexingPressure(
            int(os.environ.get("ESTPU_INDEXING_PRESSURE_BYTES", 0)) or None
        )
        # Adaptive query-execution subsystem (exec/): a node-wide
        # cost-based planner routing each (shard, query) among the device
        # kernels / block-max / CPU-oracle backends, and a continuous
        # micro-batching scheduler coalescing concurrent same-plan-class
        # searches into one padded device launch. ESTPU_EXEC_PLANNER=0 /
        # ESTPU_EXEC_BATCHER=0 opt out.
        from .exec import ExecPlanner, MicroBatcher, PackedExecutor
        from .exec.qos import QosController

        self.exec_planner = (
            ExecPlanner(metrics=self.metrics)
            if os.environ.get("ESTPU_EXEC_PLANNER", "1") != "0"
            else None
        )
        # Per-tenant QoS (exec/qos.py): weighted admission lanes keyed by
        # X-Opaque-Id (ESTPU_QOS_HEADER). The batcher drains lanes by
        # deficit-round-robin and sheds the over-quota lane first; the
        # non-batched paths (replicated, direct) admit through the same
        # controller, so one flooding tenant meets the same ceiling
        # everywhere.
        self.qos = QosController(metrics=self.metrics)
        self.exec_batcher = (
            MicroBatcher(metrics=self.metrics, qos=self.qos)
            if os.environ.get("ESTPU_EXEC_BATCHER", "1") != "0"
            else None
        )
        # Packed multi-tenant execution (exec/packed.py): small single-
        # shard indices share ONE device plane and one coalesced launch —
        # the batcher group key that finally spans DIFFERENT indices.
        # Rides the micro-batcher, so it inherits its opt-out;
        # ESTPU_EXEC_PACKED=0 opts out independently.
        self.packed_exec = (
            PackedExecutor(
                metrics=self.metrics,
                planner=self.exec_planner,
                device=self.device,
                ledger=self.hbm_ledger,
            )
            if self.exec_batcher is not None
            and os.environ.get("ESTPU_EXEC_PACKED", "1") != "0"
            else None
        )
        # Async search (exec/async_search.py): the bounded store behind
        # POST /{index}/_async_search — registered tasks whose per-shard
        # results reduce progressively into queryable partials.
        from .exec.async_search import AsyncSearchService

        self.async_search = AsyncSearchService(self)
        # Trailing-window searched-index tracking (bounded dict): the
        # remediation lifecycle loop must never demote an index that is
        # being searched right now.
        self._search_seen: dict[str, float] = {}
        # Self-driving remediation (cluster/remediation.py): plans off
        # the SAME HealthContext the indicators render and actuates
        # through this node's own surfaces (force-merge, demotion,
        # shard moves, cache retunes). ESTPU_REMEDIATION=0 disarms it;
        # ESTPU_REMEDIATION_DRY_RUN=1 plans without actuating.
        from .cluster.remediation import RemediationService

        self.remediation = RemediationService(self, metrics=self.metrics)
        if self.replication is not None:
            # Re-home the gateway's counters onto this node's registry
            # (still zero at this point) so `GET /_metrics` exposes them.
            self.replication.bind_metrics(self.metrics)
            cluster = self.replication.cluster
            if hasattr(cluster, "remediation_hook"):
                # In-process LocalCluster: the remediation tick rides
                # the master's stepper (self-rate-limited by its own
                # interval). The async form keeps the context fan's
                # per-send deadline off the control-plane step loop —
                # a partitioned member must never stall elections or
                # recoveries. The proc-clustered form has no in-process
                # master to ride — POST /_remediation drives it there.
                cluster.remediation_hook = self.remediation.tick_async
        # Flight recorder + incident autopsy (obs/incidents.py): rides
        # the health poll as the HealthService transition hook — every
        # report records a recorder frame and screens for non-green
        # transitions to freeze evidence capsules. The remediation
        # action hook links in-window actions onto open capsules live.
        # ESTPU_INCIDENTS=0 disarms (present-but-inert).
        from .obs.incidents import IncidentService

        self.incidents = IncidentService(self, metrics=self.metrics)
        self.health.transition_hook = self.incidents.on_report
        self.remediation.action_hook = self.incidents.on_remediation_record
        if self._procs is not None:
            # Proc topology: health reports run through the gateway's
            # own HealthService (procs.health_report), not self.health —
            # hand it the same hook so the recorder cadence and capture
            # law hold there too.
            self._procs.health_transition_hook = self.incidents.on_report
        # Extension system (plugins.py): analyzers / ingest processors /
        # query types contributed by ESTPU_PLUGINS or the plugins param.
        from .plugins import load_plugins

        self.plugin_names = load_plugins(plugins)
        # Warm the native indexing core off the request path: the first
        # use would otherwise run a synchronous g++ build under the engine
        # write lock.
        from .native import available as _native_available

        _native_available()
        if data_path is not None:
            os.makedirs(data_path, exist_ok=True)
            self._load_templates()
            self._load_scripts()
            self._recover_indices()
            self._load_repositories()
            self._load_pipelines()
            self._load_aliases()

    def _recover_indices(self) -> None:
        """Boot recovery: re-open every index with persisted metadata
        (the GatewayService/GatewayMetaState analog — cluster state here is
        the set of index_meta.json files under the data path)."""
        for name in sorted(os.listdir(self.data_path)):
            meta_path = os.path.join(self.data_path, name, "index_meta.json")
            if not os.path.exists(meta_path):
                continue
            with open(meta_path) as f:
                meta = json.load(f)
            self._open_index(
                name,
                meta.get("mappings"),
                meta.get("settings", {}),
                uuid=meta.get("uuid"),
            )

    def _index_dir(self, name: str) -> str | None:
        if self.data_path is None:
            return None
        return os.path.join(self.data_path, name)

    def _save_index_meta(self, svc: IndexService) -> None:
        idx_dir = self._index_dir(svc.name)
        if idx_dir is None:
            return
        os.makedirs(idx_dir, exist_ok=True)
        tmp = os.path.join(idx_dir, "index_meta.json.tmp")
        with open(tmp, "w") as f:
            json.dump(
                {
                    "mappings": svc.mappings.to_json(),
                    "settings": svc.settings,
                    # The incarnation uuid must survive restarts: snapshot
                    # blob digests key on it (incremental dedup breaks if
                    # it regenerates every boot).
                    "uuid": svc.uuid,
                },
                f,
            )
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(idx_dir, "index_meta.json"))

    def _open_index(
        self,
        name: str,
        mappings_json,
        settings: dict[str, Any],
        uuid: str | None = None,
    ) -> IndexService:
        params = BM25Params()
        sim = settings.get("index", {}).get("similarity", {}).get("default", {})
        if sim.get("type") in (None, "BM25"):
            params = BM25Params(
                k1=float(sim.get("k1", 1.2)), b=float(sim.get("b", 0.75))
            )
        # Custom analyzers from settings.analysis.analyzer (the reference
        # nests them under settings.index.analysis too).
        analysis_cfg = (
            settings.get("analysis")
            or settings.get("index", {}).get("analysis")
            or {}
        )
        try:
            from .analysis import AnalysisRegistry

            registry = AnalysisRegistry(analysis_cfg.get("analyzer"))
            mappings = Mappings.from_json(mappings_json, analysis=registry)
        except ValueError as e:
            raise ApiError(400, "mapper_parsing_exception", str(e)) from None
        settings = self._normalize_index_settings(settings)
        durability = (
            settings.get("index", {}).get("translog", {}).get(
                "durability", "request"
            )
        )
        try:
            n_shards = int(
                settings.get("index", {}).get("number_of_shards", 1)
            )
        except (TypeError, ValueError):
            raise ApiError(
                400,
                "illegal_argument_exception",
                "index.number_of_shards must be an integer",
            ) from None
        if n_shards < 1 or n_shards > 1024:
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"index.number_of_shards must be in [1, 1024], got {n_shards}",
            )
        merge_cfg = settings.get("index", {}).get("merge", {})
        idx_dir = self._index_dir(name)
        engines = []
        for shard in range(n_shards):
            shard_path = idx_dir
            if idx_dir is not None and n_shards > 1:
                shard_path = os.path.join(idx_dir, f"shard_{shard}")
            engines.append(
                Engine(
                    mappings,
                    params=params,
                    data_path=shard_path,
                    durability=durability,
                    max_segments=int(merge_cfg.get("max_segment_count", 10)),
                    merge_factor=int(merge_cfg.get("merge_factor", 8)),
                    breaker=self.breaker,
                    metrics=self.metrics,
                )
            )
        # HBM-ledger scope naming: every component keys its device bytes
        # by engine uid (or the mesh scope tuple); naming them here makes
        # `estpu_hbm_bytes{label,index}` and `/_cat/hbm` render the index
        # name instead of `_node`.
        for engine in engines:
            self.hbm_ledger.name_scope(engine.uid, name)
        self.hbm_ledger.name_scope(mesh_cache_scope(engines), name)
        search: SearchService | ShardedSearchCoordinator
        if n_shards == 1:
            search = SearchService(
                engines[0], name, planner=self.exec_planner,
                device=self.device, filter_cache=self.filter_cache,
                ann_cache=self.ann_cache,
            )
        else:
            search = ShardedSearchCoordinator(
                engines, name, planner=self.exec_planner,
                device=self.device, filter_cache=self.filter_cache,
                ann_cache=self.ann_cache,
            )
            from .parallel.mesh_serving import maybe_mesh_view

            search.mesh_view = maybe_mesh_view(
                engines, mappings, params, filter_cache=self.filter_cache
            )
            if search.mesh_view is not None:
                # SPMD servings feed the same cost model/counters so
                # `_nodes/stats` shows every backend's traffic share, and
                # mesh served/fallback counters land on the node registry
                # (Prometheus `/_metrics` + `_nodes/stats` mesh_serving).
                search.mesh_view.planner = self.exec_planner
                search.mesh_view.metrics = self.metrics
                # Per-launch timing + mesh-snapshot HBM registration.
                search.mesh_view.device = self.device
                search.mesh_view.ledger = self.hbm_ledger
        svc = IndexService(
            name=name,
            mappings=mappings,
            engines=engines,
            search=search,
            settings=settings,
        )
        if uuid is not None:
            svc.uuid = uuid
        self.indices[name] = svc
        return svc

    # -------------------------------------------------------------- indices

    # ------------------------------------------------------ index templates

    def put_index_template(self, name: str, body: dict[str, Any]) -> dict:
        """PUT /_index_template/{name} (composable templates,
        MetadataIndexTemplateService.java:83)."""
        body = body or {}
        patterns = body.get("index_patterns")
        if isinstance(patterns, str):
            patterns = [patterns]
        if not patterns or not isinstance(patterns, list):
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"index template [{name}] must have [index_patterns]",
            )
        template = body.get("template") or {}
        # Validate the mappings/analysis up front so a broken template
        # can't poison future auto-creates.
        try:
            Mappings.from_json(template.get("mappings"))
            # dynamic_templates mapping bodies must parse too, or a broken
            # rule would reject documents at index time instead of here.
            for rule_entry in (template.get("mappings") or {}).get(
                "dynamic_templates", []
            ):
                if isinstance(rule_entry, dict) and len(rule_entry) == 1:
                    ((_, rule),) = rule_entry.items()
                    mapping = (rule or {}).get("mapping")
                    if isinstance(mapping, dict):
                        Mappings._parse_field("_probe", mapping)
        except ValueError as e:
            raise ApiError(
                400, "mapper_parsing_exception", str(e)
            ) from None
        self.index_templates[name] = {
            "index_patterns": [str(p) for p in patterns],
            "priority": int(body.get("priority", 0)),
            "template": template,
        }
        self._save_templates()
        return {"acknowledged": True}

    def get_index_template(self, name: str | None = None) -> dict:
        if name is not None:
            entry = self.index_templates.get(name)
            if entry is None:
                raise ApiError(
                    404,
                    "resource_not_found_exception",
                    f"index template matching [{name}] not found",
                )
            entries = {name: entry}
        else:
            entries = self.index_templates
        return {
            "index_templates": [
                {"name": n, "index_template": dict(t)}
                for n, t in sorted(entries.items())
            ]
        }

    def delete_index_template(self, name: str) -> dict:
        if name not in self.index_templates:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"index template matching [{name}] not found",
            )
        del self.index_templates[name]
        self._save_templates()
        return {"acknowledged": True}

    def _matching_template(self, index_name: str) -> dict[str, Any] | None:
        """Highest-priority template whose pattern matches the name (ties
        break by name for determinism, like the reference's comparator)."""
        import fnmatch

        best = None
        best_key = None
        for name, entry in self.index_templates.items():
            if any(
                fnmatch.fnmatchcase(index_name, p)
                for p in entry["index_patterns"]
            ):
                key = (entry["priority"], name)
                if best_key is None or key > best_key:
                    best, best_key = entry, key
        return best

    @staticmethod
    def _deep_merge(base: dict, override: dict) -> dict:
        out = dict(base)
        for k, v in override.items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = Node._deep_merge(out[k], v)
            else:
                out[k] = v
        return out

    def _apply_template(
        self, name: str, body: dict[str, Any]
    ) -> dict[str, Any]:
        """Compose the matching template under the create-request body
        (request wins key-by-key; mappings properties merge per field)."""
        entry = self._matching_template(name)
        if entry is None:
            return body
        return self._deep_merge(entry["template"], body)

    def _templates_file(self) -> str | None:
        if self.data_path is None:
            return None
        return os.path.join(self.data_path, "_index_templates.json")

    def _save_templates(self) -> None:
        path = self._templates_file()
        if path is None:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.index_templates, f)
        os.replace(tmp, path)

    def _load_templates(self) -> None:
        path = self._templates_file()
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                self.index_templates = json.load(f)
        except (json.JSONDecodeError, OSError):
            # Broken persisted state is never a node-fatal boot error
            # (same convention as aliases/pipelines/repositories).
            self.index_templates = {}

    # ---------------------------------------------------------------------
    # Stored scripts + search templates (script/ScriptService.java,
    # modules/lang-mustache TransportSearchTemplateAction)

    def _scripts_file(self) -> str | None:
        if self.data_path is None:
            return None
        return os.path.join(self.data_path, "_stored_scripts.json")

    def _save_scripts(self) -> None:
        path = self._scripts_file()
        if path is None:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.stored_scripts, f)
        os.replace(tmp, path)

    def _load_scripts(self) -> None:
        path = self._scripts_file()
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                self.stored_scripts = json.load(f)
        except (json.JSONDecodeError, OSError):
            self.stored_scripts = {}

    def put_script(self, script_id: str, body: dict[str, Any]) -> dict:
        script = (body or {}).get("script")
        if not isinstance(script, dict) or "source" not in script:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "must specify [script] with a [source]",
            )
        lang = str(script.get("lang", "painless"))
        source = script["source"]
        if lang == "mustache":
            if isinstance(source, dict):
                source = json.dumps(source)
            from .script.mustache import TemplateError, render

            try:  # compile-validate now, not at first use
                render(str(source), {})
            except TemplateError as e:
                raise ApiError(400, "script_exception", str(e)) from None
        elif lang == "painless":
            from .script import compile_script

            try:
                compile_script(str(source))
            except ValueError as e:
                raise ApiError(400, "script_exception", str(e)) from None
        else:
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"unable to parse language [{lang}]",
            )
        self.stored_scripts[script_id] = {"lang": lang, "source": str(source)}
        self._save_scripts()
        return {"acknowledged": True}

    def get_script(self, script_id: str) -> dict:
        entry = self.stored_scripts.get(script_id)
        if entry is None:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"unable to find script [{script_id}]",
            )
        return {"_id": script_id, "found": True, "script": dict(entry)}

    def delete_script(self, script_id: str) -> dict:
        if script_id not in self.stored_scripts:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"unable to find script [{script_id}]",
            )
        del self.stored_scripts[script_id]
        self._save_scripts()
        return {"acknowledged": True}

    def _resolve_stored_script(self, ref: dict[str, Any]) -> dict[str, Any]:
        entry = self.stored_scripts.get(str(ref["id"]))
        if entry is None:
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"unable to find script [{ref['id']}]",
            )
        out = {"source": entry["source"]}
        if "params" in ref:
            out["params"] = ref["params"]
        return out

    def resolve_script_refs(self, body):
        """Replace {"script"/"...script": {"id": X}} references with their
        stored sources anywhere in a request body (the reference resolves
        stored scripts in ScriptService.compile)."""
        if isinstance(body, list):
            return [self.resolve_script_refs(v) for v in body]
        if not isinstance(body, dict):
            return body
        out = {}
        for k, v in body.items():
            if (
                (k == "script" or k.endswith("_script"))
                and isinstance(v, dict)
                and "id" in v
                and "source" not in v
            ):
                out[k] = self._resolve_stored_script(v)
            else:
                out[k] = self.resolve_script_refs(v)
        return out

    def render_template(self, body: dict[str, Any]) -> dict:
        """POST /_render/template — rendered search body without running
        it (RestRenderSearchTemplateAction)."""
        return {"template_output": self._render_search_template(body or {})}

    def _render_search_template(self, body: dict[str, Any]) -> dict:
        from .script.mustache import TemplateError, render

        source = body.get("source")
        if source is None and "id" in body:
            entry = self.stored_scripts.get(str(body["id"]))
            if entry is None or entry.get("lang") != "mustache":
                raise ApiError(
                    404,
                    "resource_not_found_exception",
                    f"unable to find search template [{body.get('id')}]",
                )
            source = entry["source"]
        if source is None:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "template is missing: specify [source] or [id]",
            )
        if isinstance(source, dict):
            source = json.dumps(source)
        try:
            rendered = render(str(source), body.get("params") or {})
        except TemplateError as e:
            raise ApiError(400, "script_exception", str(e)) from None
        try:
            parsed = json.loads(rendered)
        except json.JSONDecodeError as e:
            raise ApiError(
                400,
                "json_parse_exception",
                f"rendered template is not valid JSON: {e}",
            ) from None
        if not isinstance(parsed, dict):
            raise ApiError(
                400,
                "illegal_argument_exception",
                "rendered template must be a JSON object",
            )
        return parsed

    def search_template(self, index: str, body: dict[str, Any]) -> dict:
        """GET/POST /{index}/_search/template (TransportSearchTemplateAction:
        render, then the ordinary search path)."""
        rendered = self._render_search_template(body or {})
        if (body or {}).get("explain"):
            rendered["explain"] = True
        if (body or {}).get("profile"):
            rendered["profile"] = True
        return self.search(index, rendered)

    def create_index(self, name: str, body: dict[str, Any] | None = None) -> dict:
        if name in self.indices:
            raise ApiError(
                400,
                "resource_already_exists_exception",
                f"index [{name}] already exists",
            )
        if not _INDEX_NAME_RE.match(name):
            raise ApiError(
                400, "invalid_index_name_exception", f"invalid index name [{name}]"
            )
        if name in self.aliases:
            raise ApiError(
                400,
                "invalid_index_name_exception",
                f"an alias with the name [{name}] already exists",
            )
        body = self._apply_template(name, body or {})
        # Validate the WHOLE request (aliases included) before creating
        # anything — a mid-request failure must not leave a half-created
        # index or unpersisted alias state.
        for alias in body.get("aliases") or {}:
            if alias in self.indices:
                raise ApiError(
                    400,
                    "invalid_alias_name_exception",
                    f"an index exists with the same name as the alias "
                    f"[{alias}]",
                )
        svc = self._open_index(
            name, body.get("mappings"), body.get("settings", {})
        )
        if self.replication is not None:
            from .cluster import ReplicationUnavailableError

            idx_settings = svc.settings.get("index", {})
            try:
                n_replicas = int(idx_settings.get("number_of_replicas", 1))
            except (TypeError, ValueError):
                n_replicas = 1
            try:
                self.replication.create_index(
                    name,
                    n_shards=svc.n_shards,
                    n_replicas=n_replicas,
                    mappings=svc.mappings.to_json(),
                )
            except ReplicationUnavailableError as e:
                # The index does not exist anywhere authoritative: undo
                # the local registration before failing the request.
                for engine in svc.engines:
                    engine.close()
                self.indices.pop(name, None)
                raise ApiError(
                    503, "master_not_discovered_exception", str(e)
                ) from None
            except ValueError:
                pass  # already registered cluster-side (re-create race)
        self._save_index_meta(svc)
        for alias in body.get("aliases") or {}:
            self.aliases.setdefault(alias, set()).add(name)
        if body.get("aliases"):
            self._save_aliases()
        return {"acknowledged": True, "shards_acknowledged": True, "index": name}

    def delete_index(self, name: str) -> dict:
        if name not in self.indices:
            if name in self.aliases:
                # The reference rejects alias expressions on index deletion
                # — implicitly dropping the backing index would be silent
                # data loss for a request clients consider safe-to-fail.
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"The provided expression [{name}] matches an alias, "
                    f"specify the corresponding concrete indices instead.",
                )
            raise index_not_found(name)
        if self.replication is not None:
            from .cluster import ReplicationUnavailableError

            try:
                self.replication.delete_index(name)
            except ReplicationUnavailableError as e:
                raise ApiError(
                    503, "master_not_discovered_exception", str(e)
                ) from None
        # Drop the index's filter-cache planes BEFORE closing: the engine
        # uids can never be looked up again, and orphaned planes would
        # stay charged to the shared HBM breaker until unrelated traffic
        # happens to LRU-evict them.
        svc = self.indices[name]
        clear_index_planes(self.filter_cache, svc.engines)
        clear_index_ann(self.ann_cache, svc.engines)
        # Mesh snapshot buffers die with the view: release their HBM
        # ledger registration so `device.hbm` can't carry ghost bytes.
        mesh_view = getattr(svc.search, "mesh_view", None)
        if mesh_view is not None:
            mesh_view.release_ledger()
        for engine in svc.engines:
            engine.close()
        for engine in svc.engines:
            self.hbm_ledger.forget_scope(engine.uid)
        self.hbm_ledger.forget_scope(mesh_cache_scope(svc.engines))
        del self.indices[name]
        # Aliases pointing only at the deleted index disappear with it.
        for alias in list(self.aliases):
            self.aliases[alias].discard(name)
            if not self.aliases[alias]:
                del self.aliases[alias]
        self._save_aliases()
        idx_dir = self._index_dir(name)
        if idx_dir is not None and os.path.isdir(idx_dir):
            shutil.rmtree(idx_dir, ignore_errors=True)
        return {"acknowledged": True}

    def default_index(self) -> str:
        """The target of index-less APIs (/_search, /_count): the single
        concrete index. ES fans out to every index; this node serves one
        index per request, so multi-index targets 400 (documented gap)."""
        if len(self.indices) == 1:
            return next(iter(self.indices))
        if not self.indices:
            raise index_not_found("_all")
        raise ApiError(
            400,
            "illegal_argument_exception",
            "searching multiple indices in one request is not supported "
            "yet; target a single index",
        )

    def refresh_all(self) -> dict:
        for name in list(self.indices):
            self.refresh(name)
        return {"_shards": {"failed": 0}}

    def clear_cache(self, index: str | None = None) -> dict:
        """POST [/{index}]/_cache/clear — drop filter-cache mask planes
        and request-cache entries (for one index/pattern, or node-wide),
        reporting per-cache cleared counts like the reference's
        ClearIndicesCacheResponse carries per-shard results."""
        if index is None:
            targets = sorted(self.indices)
        else:
            targets = self.expand_index_patterns(index)
            if index != "_all":
                # Concrete names 404 when missing — each element of a
                # comma list individually, like the reference; wildcards
                # matching nothing clear nothing successfully.
                for part in index.split(","):
                    if part and not any(ch in part for ch in "*?"):
                        self.get_index(part)  # raises index_not_found
        cleared_filter = 0
        cleared_request = 0
        cleared_ann = 0
        shards = 0
        for name in targets:
            svc = self.indices.get(name)
            if svc is None:
                continue
            shards += svc.n_shards
            cleared_filter += clear_index_planes(
                self.filter_cache, svc.engines
            )
            cleared_ann += clear_index_ann(self.ann_cache, svc.engines)
            cleared_request += self.request_cache.clear(svc.uuid)
        return {
            "_shards": {"total": shards, "successful": shards, "failed": 0},
            "cleared": {
                "filter_cache": cleared_filter,
                "request_cache": cleared_request,
                "ann": cleared_ann,
            },
        }

    def expand_index_patterns(self, name: str) -> list[str]:
        """_all / comma-lists / wildcards -> concrete index names
        (IndexNameExpressionResolver for the admin APIs)."""
        import fnmatch

        if name in ("_all", "*"):
            return sorted(self.indices)
        out: list[str] = []
        for part in name.split(","):
            part = part.strip()
            if "*" in part or "?" in part:
                out.extend(
                    i for i in sorted(self.indices)
                    if fnmatch.fnmatchcase(i, part)
                )
            elif part:
                out.append(self.resolve_index(part))
        return out

    def flush_all(self) -> dict:
        for name in list(self.indices):
            self.flush(name)
        return {"_shards": {"failed": 0}}

    def get_mapping_all(self) -> dict:
        return {
            name: {"mappings": svc.mappings.to_json()}
            for name, svc in sorted(self.indices.items())
        }

    def resolve_search_targets(self, name: str) -> list[str]:
        """Concrete indices a search-style request targets."""
        if name in ("_all", "*"):
            return sorted(self.indices)
        if "," in name or "*" in name or "?" in name:
            return self.expand_index_patterns(name)
        return [name]

    def get_index(self, name: str, auto_create: bool = False) -> IndexService:
        if name in ("_all", "*"):
            name = self.default_index()
        svc = self.indices.get(name)
        if svc is None:
            resolved = self.resolve_index(name)  # alias -> concrete index
            svc = self.indices.get(resolved)
        if svc is None:
            if not auto_create:
                raise index_not_found(name)
            # Dynamic index auto-creation on first document, like the
            # reference's TransportBulkAction auto-create step.
            self.create_index(name)
            svc = self.indices[name]
        return svc

    def get_mapping(self, name: str) -> dict:
        svc = self.get_index(name)
        return {name: {"mappings": svc.mappings.to_json()}}

    def put_mapping(self, name: str, body: dict[str, Any]) -> dict:
        svc = self.get_index(name)
        for fname, spec in (body.get("properties") or {}).items():
            existing = svc.mappings.get(fname)
            new = Mappings._parse_field(fname, spec)
            if existing is not None:
                if existing.type != new.type:
                    raise ApiError(
                        400,
                        "illegal_argument_exception",
                        f"mapper [{fname}] cannot be changed from type "
                        f"[{existing.type}] to [{new.type}]",
                    )
                if existing.type == "dense_vector":
                    # dims/similarity are the vector field's indexing
                    # contract (reference: both are non-updatable mapper
                    # parameters): resident vectors and IVF planes were
                    # built under them, so a silent change would score
                    # with the wrong metric or shape-fail in the kernel.
                    for param in ("dims", "similarity"):
                        if getattr(existing, param) != getattr(new, param):
                            raise ApiError(
                                400,
                                "illegal_argument_exception",
                                f"Mapper for [{fname}] conflicts with "
                                f"existing mapper: Cannot update parameter "
                                f"[{param}] from "
                                f"[{getattr(existing, param)}] to "
                                f"[{getattr(new, param)}]",
                            )
                # Multi-fields MERGE (the reference merges mappers): subs
                # absent from the update survive; type changes of an
                # existing sub are as illegal as for a root field.
                for sub_name, sub_new in new.fields.items():
                    sub_old = existing.fields.get(sub_name)
                    if sub_old is not None and sub_old.type != sub_new.type:
                        raise ApiError(
                            400,
                            "illegal_argument_exception",
                            f"mapper [{fname}.{sub_name}] cannot be changed "
                            f"from type [{sub_old.type}] to [{sub_new.type}]",
                        )
                merged_subs = dict(existing.fields)
                merged_subs.update(new.fields)
                new.fields = merged_subs
            svc.mappings.fields[fname] = new
        if self.replication is not None:
            from .cluster import ReplicationUnavailableError

            try:
                # Serving engines live in the cluster: the update must be
                # published there or it would only exist on this node.
                self.replication.put_mappings(
                    svc.name, svc.mappings.to_json()
                )
            except ReplicationUnavailableError as e:
                raise ApiError(
                    503, "master_not_discovered_exception", str(e)
                ) from None
        self._save_index_meta(svc)
        return {"acknowledged": True}

    # ------------------------------------------------- replicated serving

    def _remote_api_error(self, e) -> ApiError:
        """Map a replication-layer remote failure onto the ApiError the
        single-process path would have raised for the same condition."""
        remote_type = getattr(e, "remote_type", "")
        if remote_type == "VersionConflictError":
            return ApiError(409, "version_conflict_engine_exception", str(e))
        if remote_type == "InvalidCasError":
            return ApiError(400, "illegal_argument_exception", str(e))
        if remote_type == "ValueError":
            return ApiError(400, "mapper_parsing_exception", str(e))
        return ApiError(500, "replication_exception", str(e))

    def _replicated_copies(self, index: str, doc_id: str) -> tuple[int, int]:
        """(wanted copies, in-sync copies) for the shard owning doc_id —
        the honest `_shards` numbers for a replicated write response."""
        try:
            state = self.replication.coordinator().state
        except RuntimeError:
            return 1, 1
        meta = state.indices.get(index)
        if meta is None:
            return 1, 1
        routing = meta.shards.get(shard_for_id(doc_id, meta.n_shards))
        total = 1 + meta.n_replicas
        successful = len(routing.in_sync) if routing is not None else 1
        return total, max(1, min(successful, total))

    def _replicated_write(
        self,
        svc: IndexService,
        doc_id: str,
        source: dict[str, Any] | None,
        op: str,
        op_type: str = "index",
        refresh: bool = False,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
        timeout_s: float | None = None,
    ) -> dict:
        """One write through the replication layer, with the gateway's
        bounded retry-after-promotion behind it; errors map onto the same
        statuses the local path produces, plus 503 when no healthy
        primary emerged within the retry budget."""
        from .cluster import ReplicationUnavailableError
        from .cluster.transport import RemoteActionError

        index = svc.name
        try:
            result = self.replication.write(
                index, doc_id, source, op=op, op_type=op_type,
                if_seq_no=if_seq_no, if_primary_term=if_primary_term,
                timeout_s=timeout_s,
            )
        except ReplicationUnavailableError as e:
            raise ApiError(503, "unavailable_shards_exception", str(e)) from None
        except RemoteActionError as e:
            raise self._remote_api_error(e) from None
        except VersionConflictError as e:
            raise ApiError(
                409, "version_conflict_engine_exception", str(e)
            ) from None
        except InvalidCasError as e:
            raise ApiError(400, "illegal_argument_exception", str(e)) from None
        except ValueError as e:
            raise ApiError(400, "mapper_parsing_exception", str(e)) from None
        total, successful = self._replicated_copies(index, doc_id)
        out = {
            "_index": index,
            "_id": result.get("_id", doc_id),
            "_version": result.get("_version"),
            "result": result.get("result"),
            "_seq_no": result.get("_seq_no"),
            "_primary_term": result.get("_primary_term"),
            "_shards": {
                "total": total,
                "successful": successful,
                "failed": 0,
            },
        }
        if refresh:
            self.replication.refresh(index)
            out["forced_refresh"] = True
        return out

    def _replicated_read(self, svc: IndexService, doc_id: str) -> dict:
        from .cluster import ReplicationUnavailableError
        from .cluster.transport import RemoteActionError

        try:
            meta = self.replication.read(svc.name, doc_id)
        except ReplicationUnavailableError as e:
            raise ApiError(503, "unavailable_shards_exception", str(e)) from None
        except RemoteActionError as e:
            raise self._remote_api_error(e) from None
        if meta is None:
            return {"_index": svc.name, "_id": doc_id, "found": False}
        return {
            "_index": svc.name,
            "_id": doc_id,
            "_version": meta["_version"],
            "_seq_no": meta["_seq_no"],
            "_primary_term": meta["_primary_term"],
            "found": True,
            "_source": meta["_source"],
        }

    def _replicated_search(
        self, svc: IndexService, body: dict[str, Any] | None, scroll
    ) -> dict:
        from .cluster import ReplicationUnavailableError, ShardSearchFailedError
        from .cluster.transport import RemoteActionError

        body = dict(body or {})
        # allow_partial_search_results rides to the cluster coordinator as
        # a call argument, not a shard-level body key.
        from .search.service import parse_lenient_bool

        try:
            allow_partial = parse_lenient_bool(
                body.pop("allow_partial_search_results", True),
                "allow_partial_search_results",
            )
        except ValueError as e:
            raise ApiError(
                400, "illegal_argument_exception", str(e)
            ) from None
        if scroll is not None or body.get("suggest"):
            raise ApiError(
                400,
                "illegal_argument_exception",
                "scroll/suggest are not supported on replicated indices "
                "yet; disable replication for this workload",
            )
        t0 = time.monotonic()
        try:
            out = self.replication.search(
                svc.name, body, allow_partial=bool(allow_partial)
            )
        except ShardSearchFailedError as e:
            # A shard failed every copy with partial results disallowed:
            # honest 503, never a silently-partial 200.
            self._count_resilience("search_phase_failures")
            raise ApiError(
                503, "search_phase_execution_exception", str(e)
            ) from None
        except ReplicationUnavailableError as e:
            raise ApiError(
                503, "search_phase_execution_exception", str(e)
            ) from None
        except RemoteActionError as e:
            if e.remote_type == "ValueError":
                raise ApiError(
                    400, "search_phase_execution_exception", str(e)
                ) from None
            raise self._remote_api_error(e) from None
        except ValueError as e:
            raise ApiError(
                400, "search_phase_execution_exception", str(e)
            ) from None
        for hit in out["hits"]["hits"]:
            hit.setdefault("_index", svc.name)
        failed = out.get("_shards", {}).get("failed", 0)
        if failed:
            self._count_resilience("shard_failures", failed)
            self._count_resilience("partial_responses")
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False,
            **out,
        }

    def _replicated_update(
        self,
        svc: IndexService,
        doc_id: str,
        body: dict[str, Any],
        refresh: bool = False,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
    ) -> dict:
        """Partial update over the replication layer: failover read +
        merge + CAS'd replicated reindex. When the caller supplies no CAS
        of its own, the read's seqno/term become one, so a concurrent
        writer surfaces as 409 instead of silently losing this merge (the
        reference closes the same race with its internal CAS retry loop;
        here the retry is the client's)."""
        existing_meta = self._replicated_read(svc, doc_id)
        existing = (
            existing_meta["_source"] if existing_meta.get("found") else None
        )
        op_type = "index"
        if existing is None:
            if "upsert" in body:
                merged = dict(body["upsert"])
            elif body.get("doc_as_upsert") and "doc" in body:
                merged = dict(body["doc"])
            else:
                raise ApiError(
                    404,
                    "document_missing_exception",
                    f"[{doc_id}]: document missing",
                )
            # put-if-absent: a concurrent creator must 409, not be
            # overwritten by this upsert's stale merge.
            op_type = "create"
        else:
            merged = dict(existing)
            merged.update(body.get("doc", {}))
            if if_seq_no is None and if_primary_term is None:
                if_seq_no = existing_meta["_seq_no"]
                if_primary_term = existing_meta["_primary_term"]
        out = self._replicated_write(
            svc, doc_id, merged, op="index", op_type=op_type,
            refresh=refresh, if_seq_no=if_seq_no,
            if_primary_term=if_primary_term,
        )
        out["result"] = "updated" if existing is not None else "created"
        return out

    def _docs_count(self, svc: IndexService) -> int:
        if self.replication is not None:
            return self.replication.num_docs(svc.name)
        return svc.num_docs

    # ------------------------------------------------------------ documents

    def index_doc(
        self,
        index: str,
        source: dict[str, Any],
        doc_id: str | None = None,
        refresh: bool = False,
        sync: bool = True,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
        op_type: str = "index",
        pipeline: str | None = None,
        timeout_s: float | None = None,
    ) -> dict:
        write_t0 = time.monotonic()
        svc = self.get_index(index, auto_create=True)
        self._note_index_write(svc.name)
        source = self._apply_pipeline(svc, source, pipeline)
        if source is None:  # dropped by an ingest drop processor
            return {
                "_index": index,
                "_id": doc_id,
                "result": "noop",
                "_shards": {"total": 1, "successful": 0, "failed": 0},
            }
        if self.replication is not None:
            if doc_id is None:
                doc_id = svc.next_auto_id()
            out = self._replicated_write(
                svc, doc_id, source, op="index", op_type=op_type,
                refresh=refresh, if_seq_no=if_seq_no,
                if_primary_term=if_primary_term, timeout_s=timeout_s,
            )
            self._log_slow_indexing(
                svc, doc_id, (time.monotonic() - write_t0) * 1e3, source
            )
            return out
        if doc_id is None and svc.n_shards > 1:
            # Multi-shard: the id must exist before routing (the reference
            # generates the UUID in TransportBulkAction before routing too).
            doc_id = svc.next_auto_id()
        engine = svc.engines[0] if doc_id is None else svc.route(doc_id)
        try:
            result = engine.index(
                source, doc_id, if_seq_no=if_seq_no,
                if_primary_term=if_primary_term, op_type=op_type,
            )
        except VersionConflictError as e:
            raise ApiError(
                409, "version_conflict_engine_exception", str(e)
            ) from None
        except InvalidCasError as e:
            raise ApiError(400, "illegal_argument_exception", str(e)) from None
        except ValueError as e:
            raise ApiError(400, "mapper_parsing_exception", str(e)) from None
        if sync:  # request durability before the ack (bulk syncs once)
            engine.sync_translog()
        out = {
            "_index": index,
            "_id": result["_id"],
            "_version": result["_version"],
            "result": result["result"],
            "_seq_no": result["_seq_no"],
            "_primary_term": result["_primary_term"],
            "_shards": {"total": 1, "successful": 1, "failed": 0},
        }
        if refresh:
            out["forced_refresh"] = _refresh_after_write(engine)
        self._log_slow_indexing(
            svc, result["_id"], (time.monotonic() - write_t0) * 1e3, source
        )
        return out

    def get_doc(self, index: str, doc_id: str) -> dict:
        svc = self.get_index(index)
        if self.replication is not None:
            return self._replicated_read(svc, doc_id)
        meta = svc.route(doc_id).get_with_meta(doc_id)
        if meta is None:
            return {"_index": index, "_id": doc_id, "found": False}
        return {
            "_index": index,
            "_id": doc_id,
            "_version": meta["_version"],
            "_seq_no": meta["_seq_no"],
            "_primary_term": meta["_primary_term"],
            "found": True,
            "_source": meta["_source"],
        }

    def delete_doc(
        self,
        index: str,
        doc_id: str,
        refresh: bool = False,
        sync: bool = True,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
        timeout_s: float | None = None,
    ) -> dict:
        svc = self.get_index(index)
        self._note_index_write(svc.name)
        if self.replication is not None:
            out = self._replicated_write(
                svc, doc_id, None, op="delete", refresh=refresh,
                if_seq_no=if_seq_no, if_primary_term=if_primary_term,
                timeout_s=timeout_s,
            )
            if out["result"] != "deleted":
                out["result"] = "not_found"
            return out
        engine = svc.route(doc_id)
        try:
            result = engine.delete(
                doc_id, if_seq_no=if_seq_no, if_primary_term=if_primary_term
            )
        except VersionConflictError as e:
            raise ApiError(
                409, "version_conflict_engine_exception", str(e)
            ) from None
        except InvalidCasError as e:
            raise ApiError(400, "illegal_argument_exception", str(e)) from None
        if sync:
            engine.sync_translog()
        status = "deleted" if result["result"] == "deleted" else "not_found"
        out = {
            "_index": index,
            "_id": doc_id,
            "result": status,
            "_version": result["_version"],
            "_seq_no": result["_seq_no"],
            "_primary_term": result["_primary_term"],
            "_shards": {"total": 1, "successful": 1, "failed": 0},
        }
        if refresh:
            out["forced_refresh"] = _refresh_after_write(engine)
        return out

    def update_doc(
        self,
        index: str,
        doc_id: str,
        body: dict[str, Any],
        refresh: bool = False,
        sync: bool = True,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
    ) -> dict:
        """Partial update: realtime get + merge + reindex (the reference's
        TransportUpdateAction/UpdateHelper flow, action/update/)."""
        svc = self.get_index(index)
        if self.replication is not None:
            return self._replicated_update(
                svc, doc_id, body, refresh=refresh,
                if_seq_no=if_seq_no, if_primary_term=if_primary_term,
            )
        # The read-modify-write must be atomic against concurrent writers
        # (the reference achieves this with a seqno CAS + retry loop in
        # TransportUpdateAction; holding the engine write lock is the
        # single-process equivalent).
        engine = svc.route(doc_id)
        with engine.lock:
            existing = engine.get(doc_id)
            if existing is None:
                if "upsert" in body:
                    # The upsert document is indexed as-is when the doc is
                    # missing; `doc` only applies to an existing document
                    # (reference UpdateHelper.prepareUpsert semantics).
                    merged = dict(body["upsert"])
                elif body.get("doc_as_upsert") and "doc" in body:
                    merged = dict(body["doc"])
                else:
                    raise ApiError(
                        404,
                        "document_missing_exception",
                        f"[{doc_id}]: document missing",
                    )
            else:
                merged = dict(existing)
                merged.update(body.get("doc", {}))
            try:
                result = engine.index(
                    merged, doc_id, if_seq_no=if_seq_no,
                    if_primary_term=if_primary_term,
                )
            except VersionConflictError as e:
                raise ApiError(
                    409, "version_conflict_engine_exception", str(e)
                ) from None
            except InvalidCasError as e:
                raise ApiError(
                    400, "illegal_argument_exception", str(e)
                ) from None
            except ValueError as e:
                # Mapper rejection of the merged doc (e.g. a dense_vector
                # dims mismatch in the partial update) is a 400 like the
                # plain index path — it must not escape as a 500.
                raise ApiError(
                    400, "mapper_parsing_exception", str(e)
                ) from None
        if sync:
            engine.sync_translog()
        out = {
            "_index": index,
            "_id": doc_id,
            "result": "updated" if existing is not None else "created",
            "_seq_no": result["_seq_no"],
            "_version": result["_version"],
            "_primary_term": result["_primary_term"],
        }
        if refresh:
            out["forced_refresh"] = _refresh_after_write(engine)
        return out

    # ----------------------------------------------------------------- bulk

    def bulk(
        self,
        body: str,
        default_index: str | None = None,
        refresh=False,
        pipeline: str | None = None,
        nbytes: int | None = None,
    ) -> dict:
        """NDJSON bulk: index/create/delete/update action lines.

        Mirrors TransportBulkAction's per-item independent outcomes
        (action/bulk/TransportBulkAction.java): one bad item doesn't fail
        the request."""
        t0 = time.monotonic()
        from .common.indexing_pressure import IndexingPressureRejected

        if nbytes is None:
            # UTF-8 byte size: the budget guards heap bytes, and len() of
            # a str undercounts multi-byte text 3-4x. The REST layer
            # passes the wire Content-Length to avoid this re-encode.
            nbytes = len(body.encode("utf-8"))
        try:
            with self.indexing_pressure.acquire(nbytes):
                return self._bulk_inner(
                    body, default_index, refresh, pipeline, t0
                )
        except IndexingPressureRejected as e:
            raise ApiError(
                429, "es_rejected_execution_exception", str(e)
            ) from None

    def _bulk_inner(
        self,
        body: str,
        default_index: str | None,
        refresh,
        pipeline: str | None,
        t0: float,
    ) -> dict:
        lines = [ln for ln in body.split("\n") if ln.strip()]
        items = []
        errors = False
        touched: set[str] = set()
        i = 0
        while i < len(lines):
            try:
                action_line = json.loads(lines[i])
            except json.JSONDecodeError as e:
                raise ApiError(
                    400, "illegal_argument_exception", f"malformed action line: {e}"
                ) from None
            if not isinstance(action_line, dict) or len(action_line) != 1:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"Malformed action/metadata line [{i}], expected a "
                    f"single action object",
                )
            ((op, meta),) = action_line.items()
            index = meta.get("_index", default_index)
            doc_id = meta.get("_id")
            if doc_id is not None:
                doc_id = str(doc_id)  # ES coerces numeric _ids to strings
            i += 1
            try:
                if op in ("index", "create"):
                    source = json.loads(lines[i])
                    i += 1
                    # "create" enforces put-if-absent atomically inside the
                    # engine lock (no get-then-index race window).
                    resp = self.index_doc(
                        index, source, doc_id, sync=False, op_type=op,
                        pipeline=meta.get("pipeline", pipeline),
                    )
                    touched.add(index)
                    status = 201 if resp["result"] == "created" else 200
                    items.append({op: {**resp, "status": status}})
                elif op == "delete":
                    resp = self.delete_doc(index, doc_id, sync=False)
                    touched.add(index)
                    status = 200 if resp["result"] == "deleted" else 404
                    items.append({op: {**resp, "status": status}})
                elif op == "update":
                    body_line = json.loads(lines[i])
                    i += 1
                    resp = self.update_doc(index, doc_id, body_line, sync=False)
                    touched.add(index)
                    items.append({op: {**resp, "status": 200}})
                else:
                    raise ApiError(
                        400,
                        "illegal_argument_exception",
                        f"Malformed action/metadata line, expected one of "
                        f"[create, delete, index, update] but found [{op}]",
                    )
            except ApiError as e:
                errors = True
                items.append(
                    {
                        op: {
                            "_index": index,
                            "_id": doc_id,
                            "status": e.status,
                            "error": {"type": e.err_type, "reason": e.reason},
                        }
                    }
                )
        for index in touched:  # one fsync per bulk request, not per item
            if index in self.indices:
                for engine in self.indices[index].engines:
                    engine.sync_translog()
        if refresh:
            for index in touched:
                if index in self.indices:
                    for engine in self.indices[index].engines:
                        _refresh_after_write(engine)
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "errors": errors,
            "items": items,
        }

    # --------------------------------------------------------------- search

    def _count_resilience(self, key: str, n: int = 1) -> None:
        counter = self._resilience_counters.get(key)
        if counter is None:
            # counter() is idempotent get-or-create; caching the novel
            # key here keeps the search_resilience view complete.
            counter = self._resilience_counters[key] = self.metrics.counter(
                "estpu_search_resilience_total",
                "Degraded-mode serving events",
                kind=key,
            )
        counter.inc(n)

    @property
    def search_resilience(self) -> dict[str, int]:
        """Degraded-mode counters — a view over the metrics registry.
        list() snapshots the dict C-atomically against concurrent
        novel-key inserts."""
        return {
            key: int(c.value)
            for key, c in list(self._resilience_counters.items())
        }

    def search(
        self,
        index: str,
        body: dict[str, Any] | None,
        scroll: str | None = None,
        request_cache: bool | None = None,
        timeout_s: float | None = None,
        allow_partial: bool | None = None,
        tenant: str | None = None,
    ) -> dict:
        # Every search runs inside a span: a child of the REST root when
        # dispatched over HTTP, a fresh root trace when called directly —
        # either way the planner/batcher/segment spans below parent here.
        with TRACER.span("search", root=True, index=index):
            return self._search_inner(
                index,
                body,
                scroll=scroll,
                request_cache=request_cache,
                timeout_s=timeout_s,
                allow_partial=allow_partial,
                tenant=tenant,
            )

    def async_search_submit(
        self,
        index: str,
        body: dict[str, Any] | None,
        params: dict[str, Any] | None = None,
        tenant: str | None = None,
    ) -> dict:
        """POST /{index}/_async_search: register a stored progressive
        search, wait up to wait_for_completion_timeout, return the
        {id?, is_partial, is_running, response} envelope."""
        with TRACER.span("async_search", root=True, index=index):
            return self.async_search.submit(
                index, body, params=params, tenant=tenant
            )

    def async_search_get(
        self, id_: str, params: dict[str, Any] | None = None
    ) -> dict:
        return self.async_search.get(id_, params=params)

    def async_search_delete(self, id_: str) -> dict:
        return self.async_search.delete(id_)

    def _search_inner(
        self,
        index: str,
        body: dict[str, Any] | None,
        scroll: str | None = None,
        request_cache: bool | None = None,
        timeout_s: float | None = None,
        allow_partial: bool | None = None,
        tenant: str | None = None,
    ) -> dict:
        from .exec.qos import DEFAULT_LANE

        lane = tenant or DEFAULT_LANE
        search_t0 = time.monotonic()
        if allow_partial is not None:
            # ?allow_partial_search_results= on the URL wins over the body
            # key; folded in up front so every dispatch path (multi-index,
            # replicated, local, batched) honors it.
            body = dict(body or {})
            body["allow_partial_search_results"] = bool(allow_partial)
        if timeout_s is not None:
            # ?timeout= on the URL: fold into the body up front so every
            # dispatch path (multi-index fan-out, replicated serving, the
            # local path, the exec micro-batcher's queue deadline) honors
            # it. The stricter of URL and body wins.
            from .search.service import _parse_timeout

            body = dict(body or {})
            body_timeout = (
                _parse_timeout(body["timeout"]) if "timeout" in body else None
            )
            effective = (
                timeout_s
                if body_timeout is None
                else min(body_timeout, timeout_s)
            )
            body["timeout"] = int(effective * 1000)
        targets = self.resolve_search_targets(index)
        if not targets:
            # Only wildcard/_all expressions can resolve to nothing; the
            # reference's allow_no_indices default makes that an empty
            # SUCCESSFUL response, not a 404 (concrete missing names still
            # 404 below).
            return self._empty_search_response()
        if len(targets) > 1:
            return self._multi_index_search(targets, body, scroll)
        index = targets[0]
        svc = self.get_index(index)
        self._note_index_searched(svc)
        if body:
            body = self.resolve_script_refs(body)
        if self.replication is not None:
            # The replicated path never rides the micro-batcher, so its
            # QoS admission happens here: a flooding tenant queues (then
            # 429s) at the same per-lane quota the batched paths enforce.
            try:
                with self.qos.admit(lane):
                    out = self._replicated_search(svc, body, scroll)
            except IndexingPressureRejected as e:
                headers = {}
                retry_after = getattr(e, "retry_after_s", None)
                if retry_after is not None:
                    headers["Retry-After"] = str(int(retry_after))
                raise ApiError(
                    429, "es_rejected_execution_exception", str(e),
                    headers=headers,
                ) from None
            # Replicated searches slowlog too (no per-phase breakdown:
            # the cluster path reports one end-to-end took).
            self._log_slow_search(
                svc,
                body,
                out.get("took", 0),
                trace_id=TRACER.current_trace_id(),
            )
            self.insights.record(
                index=svc.name,
                took_ms=out.get("took", 0),
                shards=out.get("_shards"),
                trace_id=TRACER.current_trace_id(),
                source=body,
                tenant=lane,
            )
            return out
        if self._scrolls:
            # Reap expired scroll contexts opportunistically: they pin
            # frozen device segments, and a quiet scroll API must not keep
            # them alive forever (the reference runs a periodic reaper).
            self._purge_scrolls()
        # Shard request cache: size=0 requests (aggs/counts) cache their
        # serialized response, keyed on the body + every shard's refresh
        # generation (a refresh implicitly invalidates). Mirrors
        # IndicesRequestCache.canCache: non-scroll, size==0, opt-out via
        # ?request_cache=false.
        cacheable = (
            scroll is None
            and request_cache is not False
            and int((body or {}).get("size", 10)) == 0
        )
        cache_key = None
        if cacheable:
            cache_key = RequestCache.key(
                svc.uuid, body, tuple(e.generation for e in svc.engines)
            )
            cached = self.request_cache.get(cache_key)
            if cached is not None:
                # Honest accounting on a hit: report the time THIS
                # request actually took (the cache lookup), never replay
                # the cached execution's `took`; the trace says why it
                # was fast instead of pretending the kernels ran.
                TRACER.tag(cache_hit=True)
                cached["took"] = max(
                    1, int((time.monotonic() - search_t0) * 1000)
                )
                return cached
        try:
            request = SearchRequest.from_json(body)
            window = int(
                svc.settings.get("index", {}).get("max_result_window", 10_000)
            )
            if request.from_ + request.size > window:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"Result window is too large, from + size must be less "
                    f"than or equal to: [{window}] but was "
                    f"[{request.from_ + request.size}]. See the scroll api "
                    f"for a more efficient way to request large data sets.",
                )
            if scroll is not None and request.knn is not None:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    "[knn] cannot be used with [scroll]",
                )
            task = self.tasks.register(
                "indices:data/read/search",
                description=f"indices[{index}]",
                timeout_s=request.timeout_s,
            )
            try:
                if scroll is not None:
                    return self._start_scroll(
                        svc, index, request, scroll, task=task
                    )
                if request.knn is not None and self._batchable(
                    svc, request, body
                ):
                    # Coalesced kNN: same-shape knn searches (field, k,
                    # num_candidates, nprobe, unfiltered) group into ONE
                    # batched ANN/exact launch per segment.
                    knn = request.knn
                    response = self.exec_batcher.execute(
                        svc.search,
                        request,
                        task=task,
                        group_key=(
                            "_knn", svc.name, knn.field, knn.k,
                            knn.num_candidates, knn.nprobe,
                        ),
                        tenant_key=lane,
                    )
                elif self._batchable(svc, request, body):
                    from .exec.planner import ast_signature

                    if self.packed_exec is not None and self.packed_exec.eligible(
                        svc, request
                    ):
                        # Small-tenant searches share ONE batcher group
                        # across indices: the packed executor is the
                        # group's searcher, so concurrent searches on
                        # DIFFERENT small indices coalesce into one
                        # packed launch (per-tenant results unchanged).
                        response = self.exec_batcher.execute(
                            self.packed_exec,
                            self.packed_exec.wrap(svc, request, lane_key=lane),
                            task=task,
                            group_key=(
                                "_packed",
                                ast_signature(request.query),
                            ),
                            tenant_key=lane,
                        )
                    else:
                        response = self.exec_batcher.execute(
                            svc.search,
                            request,
                            task=task,
                            group_key=(
                                svc.name,
                                ast_signature(request.query),
                            ),
                            tenant_key=lane,
                        )
                else:
                    # Non-batchable local shapes (aggs, sorts, scripted
                    # scoring...) admit through the QoS controller
                    # directly — the shed raises IndexingPressureRejected
                    # into the same 429 mapping below.
                    with self.qos.admit(lane):
                        response = svc.search.search(request, task=task)
            finally:
                self.tasks.unregister(task)
        except TaskCancelledError as e:
            raise ApiError(400, "task_cancelled_exception", str(e)) from None
        except SearchPhaseFailedError as e:
            # Every shard failed, or a shard failed with partial results
            # disallowed: the honest status is 503, never a silently-
            # partial 200 (the reference's SearchPhaseExecutionException).
            self._count_resilience("search_phase_failures")
            raise ApiError(
                503, "search_phase_execution_exception", str(e)
            ) from None
        except InjectedFaultError as e:
            # A fault that no degraded path could absorb (e.g. the only
            # shard of an unreplicated index): all shards failed.
            self._count_resilience("search_phase_failures")
            raise ApiError(
                503, "search_phase_execution_exception", str(e)
            ) from None
        except IndexingPressureRejected as e:
            # Micro-batcher load shedding: the same 429 rejection contract
            # the write path uses (es_rejected_execution_exception), plus
            # a Retry-After back-off hint derived from queue-wait p50.
            headers = {}
            retry_after = getattr(e, "retry_after_s", None)
            if retry_after is not None:
                headers["Retry-After"] = str(int(retry_after))
            raise ApiError(
                429, "es_rejected_execution_exception", str(e),
                headers=headers,
            ) from None
        except ValueError as e:
            raise ApiError(400, "search_phase_execution_exception", str(e)) from None
        out = response.to_json(index)
        if response.failed:
            # Degraded-mode accounting: a 200 that omitted failed shards.
            self._count_resilience("shard_failures", response.failed)
            self._count_resilience("partial_responses")
        self._log_slow_search(
            svc,
            body,
            out.get("took", 0),
            trace_id=TRACER.current_trace_id(),
            breakdown=getattr(response, "phases", None),
        )
        # Structured slowlog sibling: the insights ring samples the
        # slowest searches with the SAME phases hook plus shard math and
        # the trace id as an exemplar.
        self.insights.record(
            index=index,
            took_ms=out.get("took", 0),
            shards=out.get("_shards"),
            trace_id=TRACER.current_trace_id(),
            phases=getattr(response, "phases", None),
            source=body,
            tenant=lane,
        )
        if request.profile and "profile" in out:
            # The ES profile-API analog of a trace dump: `profile: true`
            # responses inline the request's own span tree so far.
            trace_id = TRACER.current_trace_id()
            tree = (
                TRACER.export(trace_id) if trace_id is not None else None
            )
            if tree is not None:
                out["profile"]["trace"] = tree
        if body and body.get("suggest"):
            from .search.suggest import run_suggest

            stats = (
                svc.search.global_stats()
                if isinstance(svc.search, ShardedSearchCoordinator)
                else svc.engines[0].field_stats()
            )
            try:
                out["suggest"] = run_suggest(
                    body["suggest"], svc.mappings, stats,
                    engines=svc.engines,
                )
            except ValueError as e:
                raise ApiError(
                    400, "search_phase_execution_exception", str(e)
                ) from None
        if cache_key is not None and not response.timed_out and not response.failed:
            # Partial responses must never be cached: a later healthy
            # request would be served the degraded result.
            self.request_cache.put(cache_key, out)
        return out

    def _batchable(self, svc: IndexService, request: SearchRequest, body) -> bool:
        """May this search ride the exec micro-batcher? Plain score-sorted
        query phases only; requests the SPMD mesh path can serve keep
        their one-launch collective path instead."""
        if self.exec_batcher is None:
            return False
        if (
            request.aggs is not None
            or request.sort is not None
            or request.rescore
            or request.search_after is not None
            or request.profile
        ):
            return False
        if request.knn is not None:
            # kNN coalescing: unfiltered same-shape knn on a single-shard
            # service (the coalesced kernel batches query vectors; a
            # per-lane filter mask or a shard scatter keeps its solo
            # path).
            return (
                request.knn.filter is None
                and isinstance(svc.search, SearchService)
                and max(0, request.size) > 0
            )
        if max(0, request.from_) + max(0, request.size) <= 0:
            return False
        if body and body.get("suggest"):
            return False
        mv = getattr(svc.search, "mesh_view", None)
        if mv is not None and not mv.disabled and mv.eligible(request):
            return False
        return True

    @staticmethod
    def _empty_search_response() -> dict:
        """The allow_no_indices success shape: zero shards, zero hits."""
        return {
            "took": 0,
            "timed_out": False,
            "_shards": {
                "total": 0,
                "successful": 0,
                "skipped": 0,
                "failed": 0,
            },
            "hits": {
                "total": {"value": 0, "relation": "eq"},
                "max_score": None,
                "hits": [],
            },
        }

    def _multi_index_search(
        self, targets: list[str], body: dict[str, Any] | None, scroll
    ) -> dict:
        """Search several indices and merge pages by score (the
        coordinator's cross-index reduce, TransportSearchAction over
        multiple target indices). Aggs/scroll/suggest across indices are
        not supported yet."""
        body = dict(body or {})
        if scroll is not None or body.get("aggs") or body.get(
            "aggregations"
        ) or body.get("suggest") or body.get("sort"):
            raise ApiError(
                400,
                "illegal_argument_exception",
                "aggregations/scroll/suggest/sort across multiple indices "
                "are not supported yet; target a single index",
            )
        from_ = max(0, int(body.get("from", 0)))
        size = max(0, int(body.get("size", 10)))
        sub_body = dict(body)
        sub_body["from"] = 0
        sub_body["size"] = from_ + size
        merged = []
        total = 0
        relation = "eq"
        max_score = None
        took = 0
        shards = 0
        skipped = 0
        failed = 0
        failures: list[dict] = []
        for rank_base, name in enumerate(targets):
            out = self.search(name, dict(sub_body))
            took += out.get("took", 0)
            sh = out.get("_shards", {})
            shards += sh.get("total", 1)
            skipped += sh.get("skipped", 0)
            failed += sh.get("failed", 0)
            failures.extend(sh.get("failures", []))
            tot = out["hits"].get("total")
            if tot is not None:
                total += tot["value"]
                if tot["relation"] == "gte":
                    relation = "gte"
            ms = out["hits"].get("max_score")
            if ms is not None:
                max_score = ms if max_score is None else max(max_score, ms)
            for rank, hit in enumerate(out["hits"]["hits"]):
                key = (
                    -hit["_score"] if hit.get("_score") is not None
                    else float("inf")
                )
                merged.append((key, hit["_index"], rank, hit))
        merged.sort(key=lambda t: (t[0], t[1], t[2]))
        page = [hit for *_, hit in merged[from_ : from_ + size]]
        shards_obj: dict[str, Any] = {
            "total": shards,
            "successful": max(0, shards - skipped - failed),
            "skipped": skipped,
            "failed": failed,
        }
        if failures:
            shards_obj["failures"] = failures
        out = {
            "took": took,
            "timed_out": False,
            "_shards": shards_obj,
            "hits": {
                "total": {"value": total, "relation": relation},
                "max_score": max_score,
                "hits": page,
            },
        }
        return out

    def count(self, index: str, body: dict[str, Any] | None) -> dict:
        body = dict(body or {})
        body["size"] = 0
        body["track_total_hits"] = True  # _count is always exact
        result = self.search(index, body)
        # The search already reports its shard accounting (including the
        # allow_no_indices zero-shard case and replicated partial results).
        shards = result.get("_shards") or {"total": 1, "successful": 1}
        return {
            "count": result["hits"]["total"]["value"],
            "_shards": {
                "total": shards.get("total", 1),
                "successful": shards.get("successful", 1),
                "skipped": shards.get("skipped", 0),
                "failed": shards.get("failed", 0),
            },
        }

    def explain(self, index: str, doc_id: str, body: dict[str, Any] | None) -> dict:
        """GET/POST /{index}/_explain/{id}: why (and how strongly) one doc
        matches a query (TransportExplainAction). The score comes from the
        same device kernel evaluated at that document via scores_at.

        Reads the CURRENT searchable view — never refreshes (a read API
        must not publish buffered docs or invalidate caches); a doc that
        is only in the unrefreshed buffer is not searchable yet and
        reports 404 like the reference's uid-term lookup."""
        if body:
            body = self.resolve_script_refs(body)
        from .ops import bm25_device

        svc = self.get_index(index)
        engine = svc.route(doc_id)
        # The (seg_idx, local) -> handle resolution must be atomic with the
        # lookup: a concurrent merge rebuilds the segment list and remaps
        # _live_ids in place.
        with engine.lock:
            loc = engine._live_ids.get(doc_id)
            handle = engine.segments[loc[0]] if loc is not None else None
        if loc is None:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"document [{doc_id}] does not exist",
            )
        try:
            request = SearchRequest.from_json(body)
        except ValueError as e:
            raise ApiError(
                400, "search_phase_execution_exception", str(e)
            ) from None
        _seg_idx, local = loc
        stats = (
            svc.search.global_stats()
            if isinstance(svc.search, ShardedSearchCoordinator)
            else engine.field_stats()
        )
        try:
            compiled = engine.compiler_for(handle, stats).compile(request.query)
        except ValueError as e:
            raise ApiError(
                400, "search_phase_execution_exception", str(e)
            ) from None
        seg_tree = bm25_device.segment_tree(handle.device)
        scores, matched = bm25_device.scores_at(
            seg_tree, compiled.spec, compiled.arrays, np.asarray([local])
        )
        is_match = bool(np.asarray(matched)[0])
        score = float(np.asarray(scores)[0])
        out = {
            "_index": svc.name,
            "_id": doc_id,
            "matched": is_match,
        }
        if is_match:
            out["explanation"] = {
                "value": score,
                "description": (
                    "score computed by the TPU query kernel "
                    "(Lucene-parity fp32 BM25 over the compiled plan)"
                ),
                "details": [],
            }
        else:
            out["explanation"] = {
                "value": 0.0,
                "description": "no matching clause for this document",
                "details": [],
            }
        return out

    def _log_slow_search(
        self,
        svc: IndexService,
        body,
        took_ms: int,
        trace_id: str | None = None,
        breakdown: dict[str, Any] | None = None,
    ) -> None:
        """index.search.slowlog.threshold.query.{warn,info,debug} — log the
        slowest level the took time crosses (SearchSlowLog analog). Lines
        carry the request's trace_id (join against `GET /_traces/{id}`)
        and the per-phase took breakdown."""
        cfg = (
            svc.settings.get("index", {})
            .get("search", {})
            .get("slowlog", {})
            .get("threshold", {})
            .get("query", {})
        )
        if not cfg:
            return
        for level, log in (
            ("warn", slowlog.warning),
            ("info", slowlog.info),
            ("debug", slowlog.debug),
        ):
            raw = cfg.get(level)
            if raw is None:
                continue
            try:
                threshold_ms = _parse_keepalive(raw) * 1000.0
            except ApiError:
                continue
            if took_ms >= threshold_ms:
                log(
                    "[%s] took[%dms], trace_id[%s], took_breakdown[%s], "
                    "source[%s]",
                    svc.name,
                    took_ms,
                    trace_id or "-",
                    (
                        json.dumps(breakdown, separators=(",", ":"))
                        if breakdown
                        else "-"
                    ),
                    json.dumps(body or {}, separators=(",", ":"))[:1000],
                )
                return

    def _log_slow_indexing(
        self, svc: IndexService, doc_id: str, took_ms: float, source
    ) -> None:
        """index.indexing.slowlog.threshold.index.{warn,info,debug} — the
        write-side sibling of the search slowlog (IndexingSlowLog
        analog): document writes over the threshold log with their id,
        trace_id and (truncated) source."""
        cfg = (
            svc.settings.get("index", {})
            .get("indexing", {})
            .get("slowlog", {})
            .get("threshold", {})
            .get("index", {})
        )
        if not cfg:
            return
        for level, log in (
            ("warn", indexing_slowlog.warning),
            ("info", indexing_slowlog.info),
            ("debug", indexing_slowlog.debug),
        ):
            raw = cfg.get(level)
            if raw is None:
                continue
            try:
                threshold_ms = _parse_keepalive(raw) * 1000.0
            except ApiError:
                continue
            if took_ms >= threshold_ms:
                log(
                    "[%s] took[%dms], trace_id[%s], id[%s], source[%s]",
                    svc.name,
                    int(took_ms),
                    TRACER.current_trace_id() or "-",
                    doc_id,
                    json.dumps(source or {}, separators=(",", ":"))[:1000],
                )
                return

    # --------------------------------------------------------------- scroll

    def _coordinator_for(self, svc: IndexService):
        if isinstance(svc.search, ShardedSearchCoordinator):
            return svc.search
        if svc.scroll_coordinator is None:
            # Cached: a fresh coordinator per scroll would recompute the
            # cross-segment statistics aggregate every open.
            svc.scroll_coordinator = ShardedSearchCoordinator(
                svc.engines, svc.name
            )
        return svc.scroll_coordinator

    def _purge_scrolls(self) -> None:
        now = time.monotonic()
        with self._scroll_lock:
            expired = [
                sid for sid, ctx in self._scrolls.items() if ctx.deadline < now
            ]
            for sid in expired:
                del self._scrolls[sid]

    def _start_scroll(
        self, svc: IndexService, index: str, request, scroll: str, task=None
    ) -> dict:
        if request.from_:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "[from] is not supported in a scroll context",
            )
        if request.rescore:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "[rescore] is not supported in a scroll context",
            )
        if request.size <= 0:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "[size] cannot be [0] in a scroll context",
            )
        self._purge_scrolls()
        coord = self._coordinator_for(svc)
        ctx = coord.open_scroll(index, request, _parse_keepalive(scroll))
        scroll_id = uuid_mod.uuid4().hex
        # Atomic check-and-insert enforces the cap exactly; the context is
        # registered before the first page so a failure cleans it up.
        with self._scroll_lock:
            if len(self._scrolls) >= self.max_open_scrolls:
                raise ApiError(
                    429,
                    "too_many_scroll_contexts_exception",
                    f"exceeded {self.max_open_scrolls} open scroll contexts",
                )
            self._scrolls[scroll_id] = ctx
        try:
            # Aggregations compute once, on the initial page (ES contract).
            aggregations = None
            if request.aggs is not None:
                from .search.aggs import Aggregator

                handles = [h for snap in ctx.snapshots for h in snap]
                _, aggregations = Aggregator(
                    svc.engines[0],
                    request.aggs,
                    handles=handles,
                    index_name=svc.name,
                ).run(request.query, stats=ctx.stats, task=task)
            with ctx.lock:
                page = coord.scroll_page(ctx, task=task)
        except Exception:
            with self._scroll_lock:
                self._scrolls.pop(scroll_id, None)
            raise
        page.scroll_id = scroll_id
        page.aggregations = aggregations
        return page.to_json(index)

    def scroll(self, body: dict[str, Any]) -> dict:
        scroll_id = body.get("scroll_id")
        if not scroll_id:
            raise ApiError(
                400, "illegal_argument_exception", "scroll_id is required"
            )
        self._purge_scrolls()
        with self._scroll_lock:
            ctx = self._scrolls.get(scroll_id)
        if ctx is None:
            raise ApiError(
                404,
                "search_context_missing_exception",
                f"No search context found for id [{scroll_id}]",
            )
        if body.get("scroll"):
            ctx.deadline = time.monotonic() + _parse_keepalive(body["scroll"])
        task = self.tasks.register(
            "indices:data/read/scroll", description=f"scroll[{scroll_id}]"
        )
        try:
            with ctx.lock:  # concurrent use of one scroll id serializes
                page = ctx.coordinator.scroll_page(ctx, task=task)
        except TaskCancelledError as e:
            raise ApiError(400, "task_cancelled_exception", str(e)) from None
        except (SearchPhaseFailedError, InjectedFaultError) as e:
            # Scroll continuation hit failed shards (all failed, or
            # partials disallowed): the same 503 contract as page one.
            self._count_resilience("search_phase_failures")
            raise ApiError(
                503, "search_phase_execution_exception", str(e)
            ) from None
        finally:
            self.tasks.unregister(task)
        page.scroll_id = scroll_id
        return page.to_json(ctx.index)

    def clear_scroll(self, body: dict[str, Any]) -> dict:
        ids = body.get("scroll_id", [])
        if isinstance(ids, str):
            ids = [ids]
        freed = 0
        with self._scroll_lock:
            if ids == ["_all"]:
                freed = len(self._scrolls)
                self._scrolls.clear()
            else:
                for sid in ids:
                    if self._scrolls.pop(sid, None) is not None:
                        freed += 1
        return {"succeeded": True, "num_freed": freed}

    # ------------------------------------------------- by-query operations

    def _replicated_scan(
        self, svc: IndexService, query_body, require_complete: bool = False
    ):
        """One refreshed scatter of matching hits for a by-query operation
        on a replicated index (page size = max_result_window). With
        `require_complete`, a match set larger than one page is a 400 —
        silently processing a truncated prefix would report success while
        skipping documents. delete_by_query instead re-scans until the
        match set drains, so it needs no completeness guarantee per page.
        Returns (hits, total_matched)."""
        self.replication.refresh(svc.name)
        window = int(
            svc.settings.get("index", {}).get("max_result_window", 10_000)
        )
        out = self._replicated_search(
            svc,
            {
                "query": query_body or {"match_all": {}},
                "size": window,
                "track_total_hits": True,
                # A by-query scan over a silently-partial match set would
                # report success while skipping a failed shard's docs:
                # any shard failure must fail the whole operation (503).
                "allow_partial_search_results": False,
            },
            None,
        )
        hits = out["hits"]["hits"]
        total = out["hits"]["total"]["value"]
        if require_complete and total > len(hits):
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"[{total}] documents match but only [{len(hits)}] fit one "
                f"scan page on a replicated index; narrow the query or "
                f"raise index.max_result_window",
            )
        return hits, total

    def _scan_hits(self, index: str, query_body, batch: int = 1000):
        """Iterate every matching hit over an internal scroll snapshot
        (stable under the mutations the caller is about to make)."""
        svc = self.get_index(index)
        coord = self._coordinator_for(svc)
        request = SearchRequest.from_json(
            {
                "query": query_body or {"match_all": {}},
                "size": batch,
                "track_total_hits": True,
                # Internal scans must never silently skip a failed
                # shard's docs — a by-query op reporting success over a
                # partial match set is data loss; fail loudly instead.
                "allow_partial_search_results": False,
            }
        )
        ctx = coord.open_scroll(svc.name, request, keep_alive_s=600.0)
        while True:
            page = coord.scroll_page(ctx)
            if not page.hits:
                break
            yield from page.hits

    def delete_by_query(
        self, index: str, body: dict[str, Any] | None, refresh: bool = False
    ) -> dict:
        """POST /{index}/_delete_by_query (reindex module's
        TransportDeleteByQueryAction: scroll + per-doc delete)."""
        t0 = time.monotonic()
        body = body or {}
        deleted = 0
        total = 0
        svc = self.get_index(index)
        if self.replication is not None:
            # Deleting shrinks the match set, so re-scan until it drains —
            # match sets past one page are handled, never truncated.
            while True:
                hits, _ = self._replicated_scan(svc, body.get("query"))
                if not hits:
                    break
                round_deleted = 0
                for hit in hits:
                    total += 1
                    out = self._replicated_write(
                        svc, hit["_id"], None, op="delete"
                    )
                    if out["result"] == "deleted":
                        deleted += 1
                        round_deleted += 1
                if round_deleted == 0:
                    break  # no progress: never spin on an undeletable set
            if refresh:
                self.replication.refresh(svc.name)
            return {
                "took": int((time.monotonic() - t0) * 1000),
                "timed_out": False,
                "total": total,
                "deleted": deleted,
                "version_conflicts": 0,
                "failures": [],
            }
        for hit in self._scan_hits(index, body.get("query")):
            total += 1
            result = svc.route(hit.doc_id).delete(hit.doc_id)
            if result["result"] == "deleted":
                deleted += 1
        for engine in svc.engines:
            engine.sync_translog()
            if refresh:
                _refresh_after_write(engine)
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False,
            "total": total,
            "deleted": deleted,
            "version_conflicts": 0,
            "failures": [],
        }

    def update_by_query(
        self,
        index: str,
        body: dict[str, Any] | None,
        refresh: bool = False,
        pipeline: str | None = None,
    ) -> dict:
        """POST /{index}/_update_by_query: reindex every matching doc in
        place — picking up mapping changes and the (request or default)
        ingest pipeline. Scripted updates are not supported yet
        (painless-lite is a scoring-expression subset)."""
        t0 = time.monotonic()
        body = body or {}
        if "script" in body:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "scripted update_by_query is not supported yet",
            )
        svc = self.get_index(index)
        updated = 0
        total = 0
        noops = 0
        failures: list[dict] = []
        if self.replication is not None:
            hits, _ = self._replicated_scan(
                svc, body.get("query"), require_complete=True
            )
            for hit in hits:
                total += 1
                try:
                    out = self._apply_pipeline(
                        svc, hit.get("_source") or {}, pipeline
                    )
                    if out is None:
                        noops += 1
                        continue
                    self._replicated_write(svc, hit["_id"], out, op="index")
                    updated += 1
                except ApiError as e:
                    failures.append({"id": hit["_id"], "cause": str(e)})
            if refresh:
                self.replication.refresh(svc.name)
            return {
                "took": int((time.monotonic() - t0) * 1000),
                "timed_out": False,
                "total": total,
                "updated": updated,
                "noops": noops,
                "version_conflicts": 0,
                "failures": failures,
            }
        try:
            for hit in self._scan_hits(index, body.get("query")):
                total += 1
                engine = svc.route(hit.doc_id)
                source = engine.get(hit.doc_id)
                if source is None:
                    continue  # deleted since the snapshot
                try:
                    out = self._apply_pipeline(svc, source, pipeline)
                    if out is None:
                        noops += 1
                        continue
                    engine.index(out, hit.doc_id)
                    updated += 1
                except (ApiError, ValueError, VersionConflictError) as e:
                    # Per-doc outcome, never a request-level 500: the
                    # by-query contract reports failures and keeps going.
                    failures.append({"id": hit.doc_id, "cause": str(e)})
        finally:
            for engine in svc.engines:
                engine.sync_translog()
                if refresh:
                    _refresh_after_write(engine)
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False,
            "total": total,
            "updated": updated,
            "noops": noops,
            "version_conflicts": 0,
            "failures": failures,
        }

    def reindex(self, body: dict[str, Any], refresh: bool = False) -> dict:
        """POST /_reindex {"source": {"index", "query"?},
        "dest": {"index", "pipeline"?}} — scroll the source snapshot and
        index into dest (the reindex module's core flow)."""
        t0 = time.monotonic()
        source = body.get("source") or {}
        dest = body.get("dest") or {}
        src_index = source.get("index")
        dest_index = dest.get("index")
        if not src_index or not dest_index:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "_reindex requires [source.index] and [dest.index]",
            )
        src_svc = self.get_index(src_index)  # 404 early
        dest_svc = self.get_index(dest_index, auto_create=True)
        if dest_svc is src_svc:
            raise ApiError(
                400,
                "action_request_validation_exception",
                "reindex cannot write into an index its reading from "
                f"[{dest_index}]",
            )
        created = 0
        updated = 0
        total = 0
        for hit in self._scan_hits(src_index, source.get("query")):
            if hit.source is None:
                continue
            total += 1
            resp = self.index_doc(
                dest_index,
                hit.source,
                hit.doc_id,
                sync=False,
                pipeline=dest.get("pipeline"),
            )
            if resp["result"] == "created":
                created += 1
            elif resp["result"] == "updated":
                updated += 1
        for engine in dest_svc.engines:
            engine.sync_translog()
            if refresh:
                _refresh_after_write(engine)
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False,
            "total": total,
            "created": created,
            "updated": updated,
            "version_conflicts": 0,
            "failures": [],
        }

    # ------------------------------------------------------- msearch / mget

    def msearch(
        self,
        body: str,
        default_index: str | None = None,
        allow_partial: bool | None = None,
    ) -> dict:
        """NDJSON multi-search: header/body line pairs, per-item outcomes
        (action/search/MultiSearchRequest.java:52). Each item carries the
        full degraded-mode contract — honest `_shards.failed`/`failures[]`
        and per-item 503s under allow_partial_search_results=false."""
        t0 = time.monotonic()
        lines = [ln for ln in body.split("\n") if ln.strip()]
        if len(lines) % 2:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "multi-search body must be header/body line pairs",
            )
        responses = []
        for i in range(0, len(lines), 2):
            try:
                header = json.loads(lines[i])
                search_body = json.loads(lines[i + 1])
            except json.JSONDecodeError as e:
                raise ApiError(
                    400, "parsing_exception", f"malformed msearch line: {e}"
                ) from None
            index = header.get("index", default_index)
            if isinstance(index, list):
                # ES accepts index arrays; this node serves one index per
                # item (multi-index search is a coordinator feature).
                index = index[0] if len(index) == 1 else index
            try:
                if not isinstance(index, str):
                    raise ApiError(
                        400,
                        "illegal_argument_exception",
                        "msearch item requires exactly one index",
                    )
                item = self.search(
                    index, search_body, allow_partial=allow_partial
                )
                item["status"] = 200
            except ApiError as e:
                item = {
                    "error": {"type": e.err_type, "reason": e.reason},
                    "status": e.status,
                }
            responses.append(item)
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "responses": responses,
        }

    def mget(self, body: dict[str, Any], default_index: str | None = None) -> dict:
        """Multi-get by id (action/get/MultiGetRequest semantics)."""
        specs = body.get("docs")
        if specs is None and "ids" in body:
            specs = [{"_id": i} for i in body["ids"]]
        if specs is None:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "mget requires [docs] or [ids]",
            )
        docs = []
        for spec in specs:
            index = spec.get("_index", default_index)
            doc_id = spec.get("_id")
            if doc_id is not None:
                doc_id = str(doc_id)  # ES coerces numeric _ids to strings
            if index is None or doc_id is None:
                docs.append(
                    {
                        "_index": index,
                        "_id": doc_id,
                        "error": {
                            "type": "illegal_argument_exception",
                            "reason": "mget doc needs _index and _id",
                        },
                    }
                )
                continue
            try:
                docs.append(self.get_doc(index, doc_id))
            except ApiError as e:
                docs.append(
                    {
                        "_index": index,
                        "_id": doc_id,
                        "error": {"type": e.err_type, "reason": e.reason},
                    }
                )
        return {"docs": docs}

    def refresh(self, index: str) -> dict:
        svc = self.get_index(index)
        if self._scrolls:
            self._purge_scrolls()
        if self.replication is not None:
            self.replication.refresh(svc.name)
        for engine in svc.engines:
            engine.refresh()
        self._prune_dead_cache_planes(svc)
        n = svc.n_shards
        return {"_shards": {"total": n, "successful": n, "failed": 0}}

    def _prune_dead_cache_planes(self, svc) -> None:
        """Eagerly drop filter/ANN planes of segment handles a refresh or
        merge just retired — merged-away uids can never be looked up
        again, so their HBM frees now instead of on the next store."""
        for engine in svc.engines:
            live = frozenset(h.uid for h in engine.segments)
            if self.filter_cache is not None:
                self.filter_cache.prune_dead(engine.uid, live)
            if self.ann_cache is not None:
                self.ann_cache.prune_dead(engine.uid, live)

    def flush(self, index: str) -> dict:
        svc = self.get_index(index)
        for engine in svc.engines:
            engine.flush()
        n = svc.n_shards
        return {"_shards": {"total": n, "successful": n, "failed": 0}}

    def force_merge(self, index: str, max_num_segments: int = 1) -> dict:
        svc = self.get_index(index)
        total_segments = 0
        for engine in svc.engines:
            out = engine.force_merge(max_num_segments)
            total_segments += out["num_segments"]
        self._prune_dead_cache_planes(svc)
        n = svc.n_shards
        return {
            "_shards": {"total": n, "successful": n, "failed": 0},
            "num_segments": total_segments,
        }

    def close(self) -> None:
        if self.exec_batcher is not None:
            self.exec_batcher.close()
        for svc in self.indices.values():
            for engine in svc.engines:
                engine.close()

    # -------------------------------------------------------------- aliases

    def _aliases_file(self) -> str | None:
        if self.data_path is None:
            return None
        return os.path.join(self.data_path, "aliases.json")

    def _load_aliases(self) -> None:
        path = self._aliases_file()
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                self.aliases = {
                    a: set(idx) for a, idx in json.load(f).items()
                }
        except (json.JSONDecodeError, OSError):
            return

    def _save_aliases(self) -> None:
        path = self._aliases_file()
        if path is None:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({a: sorted(i) for a, i in self.aliases.items()}, f)
        os.replace(tmp, path)

    def resolve_index(self, name: str) -> str:
        """Concrete index for a name that may be an alias.

        Aliases must resolve to exactly ONE index here (multi-index
        fan-out is a coordinator feature; the reference 400s writes the
        same way when no write index is set)."""
        if name in self.indices:
            return name
        targets = self.aliases.get(name)
        if targets:
            live = [t for t in sorted(targets) if t in self.indices]
            if len(live) == 1:
                return live[0]
            if len(live) > 1:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"alias [{name}] has more than one index associated "
                    f"with it [{live}]",
                )
        return name  # fall through to index_not_found in get_index

    def update_aliases(self, body: dict[str, Any]) -> dict:
        """POST /_aliases {"actions": [{"add"|"remove": {...}}]}.

        Atomic like the reference's TransportIndicesAliasesAction: every
        action validates and applies against a staged copy; the live map
        swaps (and persists) only if the whole request succeeds."""
        actions = body.get("actions")
        if not isinstance(actions, list):
            raise ApiError(
                400, "illegal_argument_exception", "[_aliases] requires [actions]"
            )
        staged = {a: set(t) for a, t in self.aliases.items()}
        for entry in actions:
            if not isinstance(entry, dict) or len(entry) != 1:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    "each aliases action is one add/remove object",
                )
            ((op, spec),) = entry.items()
            index = spec.get("index")
            alias = spec.get("alias")
            if op not in ("add", "remove") or not index or not alias:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"invalid aliases action [{op}]",
                )
            if op == "add":
                if index not in self.indices:
                    raise index_not_found(index)
                if alias in self.indices:
                    raise ApiError(
                        400,
                        "invalid_alias_name_exception",
                        f"an index exists with the same name as the alias "
                        f"[{alias}]",
                    )
                staged.setdefault(alias, set()).add(index)
            else:
                targets = staged.get(alias, set())
                if index not in targets:
                    raise ApiError(
                        404,
                        "aliases_not_found_exception",
                        f"aliases [{alias}] missing",
                    )
                targets.discard(index)
                if not targets:
                    staged.pop(alias, None)
        self.aliases = staged
        self._save_aliases()
        return {"acknowledged": True}

    def get_aliases(self, index: str | None = None) -> dict:
        if index is None:
            selected = set(self.indices)
        elif index in self.indices:
            selected = {index}
        elif index in self.aliases:
            # An alias filter lists EVERY member index (multi-target
            # aliases are valid for reads/listing).
            selected = {t for t in self.aliases[index] if t in self.indices}
        else:
            raise index_not_found(index)
        return {
            name: {
                "aliases": {
                    a: {} for a, t in self.aliases.items() if name in t
                }
            }
            for name in sorted(selected)
        }

    def delete_alias(self, index: str, alias: str) -> dict:
        return self.update_aliases(
            {"actions": [{"remove": {"index": index, "alias": alias}}]}
        )

    # ------------------------------------------------------------- settings

    @staticmethod
    def _normalize_index_settings(raw: dict) -> dict:
        """Accept every settings spelling the reference does — nested
        ({"index": {"number_of_shards": 5}}), flat ({"number_of_shards":
        5}), and dotted ({"index.number_of_shards": 5}) — normalized to
        the nested-under-"index" form the node reads."""
        flat: dict[str, Any] = {}

        def walk(prefix: str, val) -> None:
            if isinstance(val, dict) and val:
                for k, v in val.items():
                    walk(f"{prefix}.{k}" if prefix else str(k), v)
            else:
                flat[prefix] = val

        walk("", raw or {})
        out: dict[str, Any] = {}
        for key, val in flat.items():
            parts = key.split(".")
            if parts[0] != "index":
                parts = ["index"] + parts
            cur = out
            for p in parts[:-1]:
                cur = cur.setdefault(p, {})
            cur[parts[-1]] = val
        # analysis is consumed from the top level too; mirror it there.
        if "analysis" in out.get("index", {}):
            out.setdefault("analysis", out["index"]["analysis"])
        return out

    @staticmethod
    def _stringify_settings(obj):
        """GET-settings values serialize as strings (the reference's
        Settings x-content form: every leaf is a string)."""
        if isinstance(obj, dict):
            return {k: Node._stringify_settings(v) for k, v in obj.items()}
        if isinstance(obj, bool):
            return "true" if obj else "false"
        if isinstance(obj, (int, float)):
            return str(obj)
        return obj

    def get_settings(self, index: str) -> dict:
        svc = self.get_index(index)
        merged = dict(svc.settings)
        idx = dict(merged.get("index", {}))
        idx.setdefault("number_of_shards", svc.n_shards)
        idx.setdefault("number_of_replicas", 0)
        idx["uuid"] = svc.uuid
        idx["provided_name"] = svc.name
        merged["index"] = idx
        return {svc.name: {"settings": self._stringify_settings(merged)}}

    # Every entry here is READ somewhere: acknowledging a setting nothing
    # consumes would be a silent no-op.
    _DYNAMIC_SETTINGS = {
        "default_pipeline",  # _resolve_pipeline
        "merge",  # engine merge policy, applied below
        "translog",  # durability, applied below
        "max_result_window",  # from+size bound in search()
        "search",  # search.slowlog thresholds (_log_slow_search)
        "indexing",  # indexing.slowlog thresholds (_log_slow_indexing)
    }

    def put_settings(self, index: str, body: dict[str, Any]) -> dict:
        """Dynamic settings subset (the reference's update-settings action;
        static settings like number_of_shards reject with 400)."""
        svc = self.get_index(index)
        flat = body.get("index", body) or {}
        # accept dotted keys ("index.default_pipeline") and nested forms
        updates: dict[str, Any] = {}
        for key, value in flat.items():
            key = key.removeprefix("index.")
            top = key.split(".")[0]
            if top not in self._DYNAMIC_SETTINGS:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"setting [index.{key}] is not dynamically updateable",
                )
            updates[key] = value
        idx_settings = svc.settings.setdefault("index", {})
        for key, value in updates.items():
            parts = key.split(".")
            cur = idx_settings
            for part in parts[:-1]:
                cur = cur.setdefault(part, {})
            cur[parts[-1]] = value
        merge_cfg = idx_settings.get("merge", {})
        translog_cfg = idx_settings.get("translog", {})
        for engine in svc.engines:
            if "merge" in idx_settings:
                engine.max_segments = max(
                    1, int(merge_cfg.get("max_segment_count", engine.max_segments))
                )
                engine.merge_factor = max(
                    2, int(merge_cfg.get("merge_factor", engine.merge_factor))
                )
            if engine.translog is not None and "durability" in translog_cfg:
                engine.translog.durability = translog_cfg["durability"]
        self._save_index_meta(svc)
        return {"acknowledged": True}

    def get_index_info(self, index: str) -> dict:
        svc = self.get_index(index)
        return {
            svc.name: {
                "aliases": {
                    a: {} for a, t in self.aliases.items() if svc.name in t
                },
                "mappings": svc.mappings.to_json(),
                "settings": self.get_settings(index)[svc.name]["settings"],
            }
        }

    # --------------------------------------------------------------- ingest

    def _pipelines_file(self) -> str | None:
        if self.data_path is None:
            return None
        return os.path.join(self.data_path, "pipelines.json")

    def _load_pipelines(self) -> None:
        from .ingest import Pipeline, PipelineError

        path = self._pipelines_file()
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                entries = json.load(f)
        except (json.JSONDecodeError, OSError):
            return
        for pid, body in entries.items():
            try:
                self.pipelines[pid] = Pipeline(pid, body)
            except PipelineError:
                # Unusable, but its definition must survive the next save
                # (a newer build may load it; silently erasing durable
                # config is never acceptable).
                self._broken_pipelines[pid] = body

    def _save_pipelines(self) -> None:
        path = self._pipelines_file()
        if path is None:
            return
        data = dict(self._broken_pipelines)
        data.update({p.id: p.body for p in self.pipelines.values()})
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)

    def put_pipeline(self, pipeline_id: str, body: dict[str, Any]) -> dict:
        from .ingest import Pipeline, PipelineError

        try:
            self.pipelines[pipeline_id] = Pipeline(pipeline_id, body or {})
        except PipelineError as e:
            raise ApiError(400, "parse_exception", str(e)) from None
        self._save_pipelines()
        return {"acknowledged": True}

    def get_pipeline(self, pipeline_id: str | None = None) -> dict:
        if pipeline_id in (None, "*", "_all"):
            items = self.pipelines.values()
        else:
            p = self.pipelines.get(pipeline_id)
            if p is None:
                raise ApiError(
                    404,
                    "resource_not_found_exception",
                    f"pipeline [{pipeline_id}] is missing",
                )
            items = [p]
        return {p.id: p.body for p in items}

    def delete_pipeline(self, pipeline_id: str) -> dict:
        if self.pipelines.pop(pipeline_id, None) is None:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"pipeline [{pipeline_id}] is missing",
            )
        self._save_pipelines()
        return {"acknowledged": True}

    def simulate_pipeline(
        self, pipeline_id: str | None, body: dict[str, Any]
    ) -> dict:
        """POST /_ingest/pipeline/[{id}/]_simulate — run docs through the
        pipeline without indexing (SimulatePipelineRequest)."""
        from .ingest import Pipeline, PipelineError

        if pipeline_id is not None:
            pipeline = self.pipelines.get(pipeline_id)
            if pipeline is None:
                raise ApiError(
                    404,
                    "resource_not_found_exception",
                    f"pipeline [{pipeline_id}] is missing",
                )
        else:
            try:
                pipeline = Pipeline("_simulate", body.get("pipeline") or {})
            except PipelineError as e:
                raise ApiError(400, "parse_exception", str(e)) from None
        docs = []
        for entry in body.get("docs", []):
            source = entry.get("_source", {})
            try:
                out = pipeline.run(source)
            except PipelineError as e:
                docs.append(
                    {"error": {"type": "pipeline_error", "reason": str(e)}}
                )
                continue
            if out is None:
                docs.append({"doc": None})  # dropped
            else:
                docs.append({"doc": {"_source": out}})
        return {"docs": docs}

    def _resolve_pipeline(self, svc: IndexService, pipeline: str | None):
        """Request pipeline > index default_pipeline > none."""
        pid = pipeline
        if pid is None:
            pid = svc.settings.get("index", {}).get("default_pipeline")
        if pid in (None, "_none"):
            return None
        p = self.pipelines.get(pid)
        if p is None:
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"pipeline with id [{pid}] does not exist",
            )
        return p

    def _apply_pipeline(self, svc, source, pipeline: str | None):
        """(transformed source | None-if-dropped)."""
        from .ingest import PipelineError

        p = self._resolve_pipeline(svc, pipeline)
        if p is None:
            return source
        try:
            return p.run(source)
        except PipelineError as e:
            raise ApiError(
                400, "illegal_argument_exception", str(e)
            ) from None

    # ------------------------------------------------------------ snapshots

    def _repositories_file(self) -> str | None:
        if self.data_path is None:
            return None
        return os.path.join(self.data_path, "repositories.json")

    def _load_repositories(self) -> None:
        """Re-register persisted repositories; a broken registration (bad
        json, unreachable location) is an unusable repository, never a
        node-fatal boot error (the reference degrades the same way)."""
        from .snapshots import FsRepository

        path = self._repositories_file()
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                entries = json.load(f)
        except (json.JSONDecodeError, OSError):
            return
        for name, spec in entries.items():
            try:
                self.repositories[name] = FsRepository(
                    name, spec["settings"]["location"]
                )
            except (KeyError, TypeError, OSError):
                continue

    def _save_repositories(self) -> None:
        path = self._repositories_file()
        if path is None:
            return
        data = {
            name: {"type": "fs", "settings": {"location": repo.location}}
            for name, repo in self.repositories.items()
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)

    def put_repository(self, name: str, body: dict[str, Any]) -> dict:
        from .snapshots import FsRepository

        if body.get("type") != "fs":
            raise ApiError(
                400,
                "repository_exception",
                f"repository type [{body.get('type')}] does not exist "
                f"(only [fs] is supported)",
            )
        location = (body.get("settings") or {}).get("location")
        if not location:
            raise ApiError(
                400,
                "repository_exception",
                "[fs] repositories require [settings.location]",
            )
        self.repositories[name] = FsRepository(name, location)
        self._save_repositories()
        return {"acknowledged": True}

    def get_repository(self, name: str | None = None) -> dict:
        if name in (None, "_all"):
            items = self.repositories.items()
        else:
            repo = self.repositories.get(name)
            if repo is None:
                raise ApiError(
                    404,
                    "repository_missing_exception",
                    f"[{name}] missing",
                )
            items = [(name, repo)]
        return {
            n: {"type": "fs", "settings": {"location": r.location}}
            for n, r in items
        }

    def _repo(self, name: str):
        repo = self.repositories.get(name)
        if repo is None:
            raise ApiError(
                404, "repository_missing_exception", f"[{name}] missing"
            )
        return repo

    def create_snapshot(
        self, repo: str, snapshot: str, body: dict[str, Any] | None
    ) -> dict:
        from .snapshots import RepositoryError

        body = body or {}
        indices = body.get("indices")
        if isinstance(indices, str):
            indices = [i for i in indices.split(",") if i]
        try:
            manifest = self._repo(repo).create(snapshot, self, indices)
        except RepositoryError as e:
            raise ApiError(e.status, e.err_type, e.reason) from None
        return {"snapshot": self._render_snapshot(manifest)}

    @staticmethod
    def _render_snapshot(manifest: dict) -> dict:
        return {
            "snapshot": manifest["snapshot"],
            "state": manifest["state"],
            "indices": sorted(manifest["indices"]),
            "start_time_in_millis": manifest["start_time_in_millis"],
            "end_time_in_millis": manifest.get("end_time_in_millis"),
        }

    def get_snapshot(self, repo: str, snapshot: str | None = None) -> dict:
        from .snapshots import RepositoryError

        try:
            manifests = self._repo(repo).get(snapshot)
        except RepositoryError as e:
            raise ApiError(e.status, e.err_type, e.reason) from None
        return {
            "snapshots": [self._render_snapshot(m) for m in manifests]
        }

    def delete_snapshot(self, repo: str, snapshot: str) -> dict:
        from .snapshots import RepositoryError

        try:
            self._repo(repo).delete(snapshot)
        except RepositoryError as e:
            raise ApiError(e.status, e.err_type, e.reason) from None
        return {"acknowledged": True}

    def restore_snapshot(
        self, repo: str, snapshot: str, body: dict[str, Any] | None
    ) -> dict:
        from .snapshots import RepositoryError

        body = body or {}
        indices = body.get("indices")
        if isinstance(indices, str):
            indices = [i for i in indices.split(",") if i]
        try:
            return self._repo(repo).restore(
                snapshot,
                self,
                indices=indices,
                rename_pattern=body.get("rename_pattern"),
                rename_replacement=body.get("rename_replacement"),
            )
        except RepositoryError as e:
            raise ApiError(e.status, e.err_type, e.reason) from None

    # ---------------------------------------------------------------- tasks

    def list_tasks(
        self, actions: str | None = None, detailed: bool = False
    ) -> dict:
        """GET /_tasks[?detailed=true]: running tasks with monotonic
        running_time_in_nanos + current span name; detailed adds the
        description."""
        return {
            "nodes": {
                self.node_name: {
                    "name": self.node_name,
                    "tasks": {
                        t.id: t.to_json(detailed=detailed)
                        for t in self.tasks.list(actions)
                    },
                }
            }
        }

    def cat_tasks(self) -> list[dict]:
        """GET /_cat/tasks — the cat rendering of the task list."""
        rows = []
        for t in self.tasks.list():
            j = t.to_json(detailed=True)
            rows.append(
                {
                    "action": j["action"],
                    "task_id": t.id,
                    "type": j["type"],
                    "start_time": str(j["start_time_in_millis"]),
                    "running_time": f"{j['running_time_in_nanos'] / 1e6:.1f}ms",
                    "node": j["node"],
                    "span": j.get("span", "-"),
                }
            )
        return rows

    def get_task(self, task_id: str) -> dict:
        task = self.tasks.get(task_id)
        if task is None:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"task [{task_id}] isn't running and hasn't stored its results",
            )
        return {"completed": False, "task": task.to_json()}

    def cancel_task(self, task_id: str) -> dict:
        task = self.tasks.cancel(task_id)
        if task is None:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"task [{task_id}] is not found",
            )
        return {
            "nodes": {
                self.node_name: {
                    "name": self.node_name,
                    "tasks": {task.id: task.to_json()},
                }
            }
        }

    # ---------------------------------------------------------------- faults

    def put_fault(self, body: dict[str, Any]) -> dict:
        """POST /_fault — arm one fault spec (or {"faults": [specs]}),
        deterministic per spec via its seed. See faults/registry.py for
        the site roster."""
        body = body or {}
        specs = body.get("faults", [body])
        if not isinstance(specs, list):
            raise ApiError(
                400, "illegal_argument_exception", "[faults] must be a list"
            )
        for raw in specs:
            if not isinstance(raw, dict) or not raw.get("site"):
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    "each fault spec requires a [site]",
                )
            try:
                # A delay-only spec (delay_ms set, no [error] key) means
                # "slow", not "slow AND broken".
                default_error = (
                    None if float(raw.get("delay_ms", 0.0)) > 0
                    else "internal"
                )
                spec = FaultSpec(
                    site=str(raw["site"]),
                    error_rate=float(raw.get("error_rate", 1.0)),
                    error=raw.get("error", default_error),
                    delay_ms=float(raw.get("delay_ms", 0.0)),
                    count=(
                        None if raw.get("count") is None
                        else int(raw["count"])
                    ),
                    seed=int(raw.get("seed", 0)),
                )
                FAULTS.put(spec)
            except (TypeError, ValueError) as e:
                raise ApiError(
                    400, "illegal_argument_exception", str(e)
                ) from None
        return {"acknowledged": True, "faults": FAULTS.stats()}

    def get_faults(self) -> dict:
        """GET /_fault — armed specs with their live counters."""
        return FAULTS.stats()

    def clear_faults(self, site: str | None = None) -> dict:
        """DELETE /_fault[/{site}] — disarm one site pattern or all."""
        return {"acknowledged": True, "cleared": FAULTS.clear(site)}

    # -------------------------------------------------------- observability

    @property
    def _procs(self):
        """The ProcCluster behind a socketed gateway (ProcGateway), or
        None for standalone / in-process-LocalCluster fronts. The procs
        obs fans run over the never-intercepted `_ctl` socket path, so
        the front delegates to them instead of `_cluster_fan`: a
        partitioned data plane must still be OBSERVABLE (the report
        names the unreachable members; the scrape doesn't go dark)."""
        return getattr(self.replication, "procs", None)

    def _cluster_fan(
        self,
        action: str,
        payload: dict | None = None,
        timeout_s: float | None = None,
    ) -> tuple[dict, list[dict]]:
        """Scatter one wire action over every cluster member (the
        TransportNodesAction fan shape): parallel, per-send deadline,
        named failure entries — a dead or wedged node can never hang
        an observability request."""
        from .cluster.transport import scatter_nodes

        cluster = self.replication.cluster
        if timeout_s is None:
            timeout_s = NODES_FAN_TIMEOUT_S
        try:
            from_id = self.replication.coordinator().node_id
        except RuntimeError:
            # Every member dead: the sends still run (and fail, named)
            # so the caller gets a complete failure roster, not a 500.
            from_id = self.node_name

        def send(node_id: str):
            return cluster.hub.send(
                from_id, node_id, action, dict(payload or {}),
                timeout_s=timeout_s,
            )

        return scatter_nodes(
            sorted(cluster.nodes), send, action, timeout_s,
            metrics=self.metrics,
        )

    def hot_threads(
        self,
        threads: int = 3,
        interval_s: float = 0.5,
        snapshots: int = 10,
    ) -> str:
        """GET /_nodes/hot_threads — reference-style per-node thread
        stack sampling (monitor/jvm/HotThreads analog): this process
        samples itself, and when clustered the `hot_threads` wire action
        fans over every member so each process samples its OWN
        interpreter; blocks concatenate under `::: {node}` headers with
        a failure line for any node that could not be sampled."""
        from .obs.hot_threads import fan_text_blocks, hot_threads_text

        local_box: dict[str, str] = {}

        def sample_local() -> None:
            local_box["text"] = hot_threads_text(
                node_name=self.node_name,
                threads=threads,
                interval_s=interval_s,
                snapshots=snapshots,
                metrics=self.metrics,
            )

        if self.replication is None:
            sample_local()
            return local_box["text"]
        if self._procs is not None:
            # Front block first, then the procs fan (tiebreaker +
            # workers, each sampling its OWN interpreter).
            sample_local()
            return "\n".join(
                [
                    local_box["text"],
                    self._procs.hot_threads(
                        threads=threads,
                        interval_s=interval_s,
                        snapshots=snapshots,
                    ),
                ]
            )
        # The local sample runs CONCURRENTLY with the fan (each remote
        # handler samples for the same interval) so the request costs
        # one interval of wall clock, not two.
        sampler = threading.Thread(target=sample_local, daemon=True)
        sampler.start()
        results, failures = self._cluster_fan(
            "hot_threads",
            {
                "threads": threads,
                "interval_s": interval_s,
                "snapshots": snapshots,
            },
            timeout_s=NODES_FAN_TIMEOUT_S + float(interval_s),
        )
        sampler.join()
        # The member sharing the coordinating front's name is the SAME
        # interpreter the local block just sampled (the nodes_stats
        # merge rule): one block per node name.
        results.pop(self.node_name, None)
        blocks = [local_box.get("text", "")]
        blocks.extend(fan_text_blocks(results, failures))
        return "\n".join(blocks)

    def query_insights(self, size: int | None = None) -> dict:
        """GET /_insights/queries — the bounded top-N slowest-searches
        sample (obs/insights.py), slowest first."""
        return {
            **self.insights.stats(),
            "queries": self.insights.queries(size=size),
        }

    def get_traces(self, limit: int = 50) -> dict:
        """GET /_traces — newest-first summaries of the trace ring."""
        return {
            **TRACER.stats(),
            "traces": TRACER.traces(limit=limit),
        }

    def get_trace(self, trace_id: str, fmt: str | None = None) -> dict:
        """GET /_traces/{trace_id}[?format=chrome] — ONE spliced span
        tree. Remote span bodies stay in each node's ring (only parent
        ids cross with requests), so when clustered the coordinator fans
        the `trace_fragment` wire action over every member and splices
        the fragments with its own spans: one tree, and the chrome export
        covers the whole cluster (one track per node)."""
        from .obs.tracing import chrome_trace, collect_fragments

        if self._procs is not None:
            out = self._procs.trace(trace_id, fmt=fmt)
            if out is None:
                raise ApiError(
                    404,
                    "resource_not_found_exception",
                    f"trace [{trace_id}] is not buffered (ring keeps the "
                    f"last {TRACER.max_traces} traces)",
                )
            return out
        header = None
        results: dict = {}
        if self.replication is not None:
            results, failures = self._cluster_fan(
                "trace_fragment", {"trace_id": trace_id}
            )
            header = {
                "total": len(self.replication.cluster.nodes),
                "successful": len(results),
                "failed": len(failures),
            }
            if failures:
                header["failures"] = failures
        spans, collected = collect_fragments(TRACER.get(trace_id), results)
        if collected:
            self.metrics.counter(
                "estpu_trace_fragments_collected_total",
                "Trace-fragment spans collected from cluster nodes",
            ).inc(collected)
        if not spans:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"trace [{trace_id}] is not buffered (ring keeps the last "
                f"{TRACER.max_traces} traces)",
            )
        if fmt == "chrome":
            return chrome_trace(spans)
        out: dict[str, Any] = {"trace_id": trace_id, "spans": spans}
        if header is not None:
            out["_nodes"] = header
        return out

    # ------------------------------------------------------ profiler capture

    def profiler_start(self, body: dict[str, Any] | None = None) -> dict:
        """POST /_profiler/start — open a single-flight jax.profiler
        capture (409 while one is running; duration bounded)."""
        body = body or {}
        duration = body.get("duration_s")
        if duration is not None and not isinstance(
            duration, (int, float)
        ):
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"duration_s must be a number, got [{duration!r}]",
            )
        try:
            return self.profiler.start(
                duration_s=duration, trace_dir=body.get("trace_dir")
            )
        except ProfilerConflictError as e:
            raise ApiError(409, "status_exception", str(e)) from None
        except ValueError as e:
            raise ApiError(
                400, "illegal_argument_exception", str(e)
            ) from None

    def profiler_stop(self) -> dict:
        """POST /_profiler/stop — close the capture; returns the Perfetto
        trace directory + the obs-ring trace id of the stamped window."""
        try:
            return self.profiler.stop()
        except ProfilerInactiveError as e:
            raise ApiError(
                400, "illegal_argument_exception", str(e)
            ) from None

    def profiler_status(self) -> dict:
        """GET /_profiler — capture state."""
        return self.profiler.status()

    def metrics_text(self) -> str:
        """GET /_metrics — federated Prometheus text exposition: this
        node's registry merged with the replication gateway's, the
        cluster/hub-level registries, the process-wide analysis registry
        (estpu_analysis_calls_total), and every live cluster member's
        registry re-exposed with a `node=<id>` label per series —
        counters additionally folded into `node="_cluster"` totals.
        Federation happens only at scrape time (the same wire snapshot
        shape the procs `metrics_wire` action ships), never on the
        request hot path."""
        from .analysis.analyzers import ANALYSIS_METRICS
        from .obs.metrics import WireRegistrySnapshot, fold_cluster_counters

        if self._procs is not None:
            # The procs federation (worker fan over `_ctl`, TTL-cached)
            # plus this front's own registry as one more labeled
            # snapshot — the gateway's counters already live here via
            # bind_metrics.
            return self._procs.metrics_text(
                extra_snapshots=(
                    WireRegistrySnapshot(
                        self.metrics.to_wire(), node=self.node_name
                    ),
                )
            )
        others: list = [ANALYSIS_METRICS]
        if self.replication is not None:
            gw_metrics = getattr(self.replication, "metrics", None)
            if gw_metrics is not None and gw_metrics is not self.metrics:
                others.append(gw_metrics)
            cluster = self.replication.cluster
            cluster_metrics = getattr(cluster, "metrics", None)
            if cluster_metrics is not None:
                others.append(cluster_metrics)
            hub_metrics = getattr(cluster.hub, "metrics", None)
            if hub_metrics is not None:
                others.append(hub_metrics)
            snapshots = [
                WireRegistrySnapshot(
                    cnode.metrics.to_wire(), node=cnode.node_id
                )
                for cnode in cluster.nodes.values()
                if not cnode.closed
            ]
            others.extend(snapshots)
            others.append(fold_cluster_counters(snapshots))
        return self.metrics.exposition(*others)

    # --------------------------------------------------------- health report

    def _coordinator_state(self):
        """The published ClusterState, or None when no member answers."""
        if self.replication is None:
            return None
        try:
            return self.replication.coordinator().state
        except RuntimeError:
            return None

    def _recent_windows(self) -> dict[str, Any]:
        """Rolling-window snapshots off this node's registry — the
        recent-behavior half of the health inputs."""
        out: dict[str, Any] = {}
        queue_wait = self.metrics.window(
            "estpu_exec_batcher_queue_wait_recent_ms"
        )
        if queue_wait is not None:
            out["queue_wait_recent"] = queue_wait.snapshot()
        shed = self.metrics.window("estpu_exec_batcher_shed_recent")
        if shed is not None:
            out["shed_recent"] = shed.count()
        evictions: dict[str, int] = {}
        for cache, name in (
            ("filter", "estpu_filter_cache_evictions_recent"),
            ("ann", "estpu_ann_evictions_recent"),
        ):
            window = self.metrics.window(name)
            if window is not None:
                evictions[cache] = int(window.count())
        if evictions:
            out["evictions_recent"] = evictions
        outcomes: dict[str, dict[str, int]] = {}
        for labels, window in self.metrics.windows(
            "estpu_device_launch_recent"
        ):
            backend = labels.get("backend", "device")
            outcome = labels.get("outcome", "ok")
            entry = outcomes.setdefault(backend, {})
            entry[outcome] = entry.get(outcome, 0) + int(window.count())
        if outcomes:
            out["launch_outcomes_recent"] = outcomes
        return out

    def _health_inputs_local(self) -> dict[str, Any]:
        """This coordinating front's own health inputs: breaker/ledger
        accounting, the compile census, batcher state, the rolling
        windows, mesh circuit-breaker states, and (when clustered) the
        gateway transport's recent events."""
        out: dict[str, Any] = {
            "name": self.node_name,
            "breaker": self.breaker.stats(),
            "breaker_trips_recent": self.breaker.trips_recent(),
            "hbm": self.hbm_ledger.snapshot(),
            "device_compile": (
                self.device.compile_census()
                if self.device is not None
                else None
            ),
            "batcher": (
                self.exec_batcher.stats()
                if self.exec_batcher is not None
                else {"enabled": False}
            ),
            # Per-lane QoS windows: exec_saturation names the top shed
            # tenants from these instead of a bare node-wide count.
            "qos": self.qos.health_inputs(),
            "step_errors": 0,
        }
        # Cache budget/occupancy snapshots: the remediation budget loop
        # tunes filter/ANN/packed budgets against each other from these
        # (plus evictions_recent below).
        from .index.ann import AnnCache
        from .index.filter_cache import FilterCache

        caches: dict[str, Any] = {
            "filter": (
                self.filter_cache.stats()
                if self.filter_cache is not None
                else FilterCache.disabled_stats()
            ),
            "ann": (
                self.ann_cache.stats()
                if self.ann_cache is not None
                else AnnCache.disabled_stats()
            ),
        }
        if self.packed_exec is not None:
            caches["packed"] = self.packed_exec.stats()
        out["caches"] = caches
        writes: dict[str, int] = {}
        for labels, window in self.metrics.windows(
            "estpu_index_writes_recent"
        ):
            name = labels.get("index")
            if name:
                writes[name] = writes.get(name, 0) + int(window.count())
        out["writes_recent"] = writes
        out.update(self._recent_windows())
        mesh: dict[str, str] = {}
        for name, svc in sorted(self.indices.items()):
            mv = getattr(svc.search, "mesh_view", None)
            if mv is None:
                continue
            mesh[name] = mv.breaker.stats()["state"]
        if mesh:
            out["mesh_breakers"] = mesh
        if self.replication is not None:
            cluster = self.replication.cluster
            out["step_errors"] = int(
                getattr(cluster, "_step_errors", None).value
                if getattr(cluster, "_step_errors", None) is not None
                else 0
            )
            hub_metrics = getattr(cluster.hub, "metrics", None)
            if hub_metrics is not None:
                recent = hub_metrics.window_counts(
                    "estpu_transport_events_recent", "event"
                )
                if recent:
                    out["transport_events_recent"] = {
                        k: int(v) for k, v in recent.items()
                    }
            hub_stats = getattr(cluster.hub, "stats", None)
            if hub_stats is not None:
                out["transport"] = hub_stats()
        return out

    def health_report(
        self,
        verbose: bool = True,
        indicator: str | None = None,
    ) -> dict:
        """GET /_health_report — the rule-based indicator report
        (obs/health.py). Verbose reports fan `health_inputs` over every
        cluster member (per-send deadline, named failure entries — a
        dead node degrades the report, never hangs it);
        ``verbose=False`` is the cheap liveness probe: local inputs
        only, statuses + symptoms without the detail blocks."""
        if indicator is not None and indicator not in INDICATORS:
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"unknown health indicator [{indicator}]; expected one "
                f"of {list(INDICATORS)}",
            )
        if self._procs is not None:
            return self._procs.health_report(
                verbose=verbose,
                indicator=indicator,
                extra_inputs={
                    self.node_name: self._health_inputs_local()
                },
            )
        node_inputs = {self.node_name: self._health_inputs_local()}
        failures: list[dict] = []
        expected: tuple[str, ...] = ()
        fanned = False
        if self.replication is not None and verbose:
            fanned = True
            expected = tuple(sorted(self.replication.cluster.nodes))
            results, failures = self._cluster_fan("health_inputs", {})
            for node_id, section in results.items():
                if node_id == self.node_name:
                    # The member sharing the coordinating front's name is
                    # this same interpreter: keep the richer local entry,
                    # graft the member-only keys (roles, cluster_state).
                    merged = dict(section)
                    merged.update(node_inputs[node_id])
                    node_inputs[node_id] = merged
                else:
                    node_inputs[node_id] = section
        ctx = HealthContext(
            cluster_name=self.cluster_name,
            coordinator=self.node_name,
            standalone=self.replication is None,
            state=self._coordinator_state(),
            expected_nodes=expected,
            node_inputs=node_inputs,
            fan_failures=failures,
            fanned=fanned,
            local_indices=self.indices,
            **self._remediation_ctx_fields(),
        )
        report = self.health.report(
            ctx, verbose=verbose, indicator=indicator
        )
        return report

    # ------------------------------------------------------------ incidents

    def get_incidents(self, verbose: bool = True) -> dict:
        """GET /_incidents — the bounded incident ring (obs/incidents.py)
        plus, when verbose, a cluster fan of per-member flight-recorder
        summaries over BOTH cluster forms (the PR-13 scatter for the
        in-process cluster, the never-intercepted `_ctl` path for the
        proc cluster). ``verbose=False`` returns statuses/trigger lines
        only and skips capsule bodies AND the fan."""
        out: dict[str, Any] = {
            "enabled": self.incidents.enabled,
            "incidents": self.incidents.incidents(verbose=verbose),
            "recorder": self.incidents.recorder.stats(),
        }
        if (
            not verbose
            or not self.incidents.enabled
            or self.replication is None
        ):
            return out
        if self._procs is not None:
            expected = list(self._procs.workers)
            results, failures = self._procs._fan("incidents")
        else:
            expected = sorted(self.replication.cluster.nodes)
            results, failures = self._cluster_fan("incidents", {})
        nodes: dict[str, Any] = {
            self.node_name: {
                "node": self.node_name,
                "recorder": self.incidents.recorder.stats(),
                "open": self.incidents.stats()["open"],
            }
        }
        for node_id in expected:
            if node_id in results:
                nodes.setdefault(node_id, results[node_id])
        header: dict[str, Any] = {
            "total": 1 + len(expected),
            "successful": 1
            + len([n for n in expected if n in results]),
            "failed": len(failures),
        }
        if failures:
            header["failures"] = list(failures)
        out["_nodes"] = header
        out["nodes"] = nodes
        return out

    def get_incident(self, incident_id: str) -> dict:
        """GET /_incidents/{id} — one full capsule, or 404."""
        incident = self.incidents.get(incident_id)
        if incident is None:
            raise ApiError(
                404,
                "resource_not_found_exception",
                f"no incident [{incident_id}] in the ring (bounded; "
                "resolved incidents age out first)",
            )
        return incident

    def capture_incident(self, body: dict | None = None) -> dict:
        """POST /_incidents/_capture — manual evidence grab."""
        body = body or {}
        indicator = body.get("indicator")
        if indicator is not None and indicator not in INDICATORS:
            raise ApiError(
                400,
                "illegal_argument_exception",
                f"unknown health indicator [{indicator}]; expected one "
                f"of {list(INDICATORS)}",
            )
        return self.incidents.capture(
            indicator=indicator,
            reason=str(body.get("reason", "manual")),
        )

    def cat_incidents(self) -> list[dict]:
        """GET /_cat/incidents — one row per ring entry, newest first."""
        rows: list[dict] = []
        for summary in self.incidents.incidents(verbose=False):
            trigger = summary["trigger"]
            ttg = summary.get("time_to_green_ms")
            rows.append(
                {
                    "id": summary["id"],
                    "trigger": trigger.get("indicator")
                    or trigger.get("loop")
                    or trigger.get("burst")
                    or trigger["kind"],
                    "kind": trigger["kind"],
                    "status": summary["status"],
                    "start": _iso_millis(summary["started_at_ms"]),
                    "time_to_green_ms": (
                        "-" if ttg is None else str(int(ttg))
                    ),
                    "actions": str(summary.get("actions", 0)),
                }
            )
        return rows

    # ---------------------------------------------------------- remediation

    def _note_index_write(self, index: str) -> None:
        """Chokepoint for the per-index write-rate window: index_doc and
        delete_doc both land here (bulk routes through them), so the
        remediation lifecycle loop sees every mutation path."""
        self.metrics.windowed_counter(
            "estpu_index_writes_recent",
            "Document writes by index over the trailing window",
            index=index,
        ).inc()

    def _note_index_searched(self, svc) -> None:
        """Record that an index is actively searched (the lifecycle loop
        never demotes such an index) and transparently re-pack it if a
        prior demotion moved its planes off-device."""
        now = time.monotonic()
        seen = self._search_seen
        seen[svc.name] = now
        if len(seen) > 512:
            # Bounded: drop the stalest entry (staleness past the 60s
            # recency horizon makes the victim's identity irrelevant).
            seen.pop(min(seen, key=seen.get), None)
        promoted = False
        for engine in svc.engines:
            if getattr(engine, "demoted", False) and engine.ensure_device():
                promoted = True
        if promoted:
            self.remediation.note_on_demand_repack(svc.name)

    def _remediation_ctx_fields(self) -> dict[str, Any]:
        """HealthContext fields only the remediation loops consume —
        spliced into health_report's context too so `GET /_health_report`
        and the planner read the SAME view."""
        now = time.monotonic()
        recent = tuple(
            sorted(
                name
                for name, at in self._search_seen.items()
                if now - at <= 60.0
            )
        )
        return {
            "aliases": {
                a: tuple(sorted(t)) for a, t in self.aliases.items()
            },
            "recent_search_indices": recent,
            "scrolls_active": len(self._scrolls),
            "remediation": self.remediation.health_view(),
            # Wall clock feeds the rollover max-age policy only — never
            # differenced against monotonic stamps.
            "now": time.time(),  # staticcheck: ignore[wallclock-duration] policy clock, not a duration
        }

    def _remediation_context(self) -> HealthContext:
        """The planner's view: the same context shape health_report
        renders, built on the remediation stepper's cadence. Fans
        health_inputs over in-process cluster members so the allocation
        loop can compare nodes; the proc-clustered topology has no
        in-process stepper, so no fan is needed here."""
        node_inputs = {self.node_name: self._health_inputs_local()}
        failures: list[dict] = []
        expected: tuple[str, ...] = ()
        fanned = False
        if self.replication is not None and self._procs is None:
            fanned = True
            expected = tuple(sorted(self.replication.cluster.nodes))
            results, failures = self._cluster_fan("health_inputs", {})
            for node_id, section in results.items():
                if node_id == self.node_name:
                    merged = dict(section)
                    merged.update(node_inputs[node_id])
                    node_inputs[node_id] = merged
                else:
                    node_inputs[node_id] = section
        return HealthContext(
            cluster_name=self.cluster_name,
            coordinator=self.node_name,
            standalone=self.replication is None,
            state=self._coordinator_state(),
            expected_nodes=expected,
            node_inputs=node_inputs,
            fan_failures=failures,
            fanned=fanned,
            local_indices=self.indices,
            **self._remediation_ctx_fields(),
        )

    def rollover_alias(
        self, alias: str, old_index: str, new_index: str
    ) -> dict:
        """Actuate a lifecycle rollover: create the successor with the
        old index's mappings/settings and atomically repoint the alias.
        The old index stays searchable (and demotable once it goes
        cold)."""
        if new_index in self.indices:
            raise ApiError(
                400,
                "resource_already_exists_exception",
                f"index [{new_index}] already exists",
            )
        old = self.get_index(old_index)
        self.create_index(
            new_index,
            {
                "mappings": old.mappings.to_json(),
                "settings": {
                    "index": {"number_of_shards": old.n_shards}
                },
            },
        )
        self.aliases[alias] = {new_index}
        self._save_aliases()
        return {"acknowledged": True, "old_index": old_index,
                "new_index": new_index}

    def demote_index(self, index: str) -> dict:
        """Move an index's segment planes off-device (HBM -> host).
        Searches transparently re-pack on demand (_note_index_searched);
        hits stay bit-identical because device planes are a pure
        function of the host segments."""
        svc = self.get_index(index)
        freed = 0
        for engine in svc.engines:
            freed += engine.demote_device()
        self._prune_dead_cache_planes(svc)
        return {"acknowledged": True, "freed_bytes": int(freed)}

    def promote_index(self, index: str) -> dict:
        """Re-pack a demoted index's planes back onto the device."""
        svc = self.get_index(index)
        promoted = False
        for engine in svc.engines:
            if getattr(engine, "demoted", False) and engine.ensure_device():
                promoted = True
        return {"acknowledged": True, "promoted": promoted}

    def move_shard_replica(
        self, index: str, shard_id: int, from_node: str, to_node: str
    ) -> dict:
        """Actuate an allocation move via the elected master (replicas
        only — the master action rejects primary moves, so acked writes
        are never at risk)."""
        if self.replication is None:
            raise ApiError(
                400,
                "illegal_argument_exception",
                "shard moves require a cluster",
            )
        master = self.replication.cluster.master()
        if master is None:
            raise ApiError(
                503, "master_not_discovered_exception", "no elected master"
            )
        out = master.move_shard_replica(index, shard_id, from_node, to_node)
        if not out.get("acked"):
            raise ApiError(
                503,
                "cluster_block_exception",
                f"shard move [{index}][{shard_id}] not acked",
            )
        return out

    def retune_cache_budgets(
        self, filter_bytes: int, ann_bytes: int, reason: str = ""
    ) -> dict:
        """Actuate a budget-loop shift between the filter and ANN cache
        budgets; each cache records the retune as an event on its
        stats."""
        out: dict[str, Any] = {"acknowledged": True}
        if self.filter_cache is not None:
            out["filter"] = self.filter_cache.retune(
                int(filter_bytes), reason=reason
            )
        if self.ann_cache is not None:
            out["ann"] = self.ann_cache.retune(int(ann_bytes), reason=reason)
        return out

    def retune_packed_budget(
        self, max_plane_docs: int, reason: str = ""
    ) -> dict:
        """Actuate a packed-plane budget retune."""
        if self.packed_exec is None:
            return {"acknowledged": False}
        return {
            "acknowledged": True,
            "packed": self.packed_exec.retune(
                int(max_plane_docs), reason=reason
            ),
        }

    def get_remediation(self) -> dict:
        """GET /_remediation — planned-vs-executed history, per-loop
        advisory state, damping windows, and (when clustered) the
        remediation transitions published into cluster state."""
        out = self.remediation.status()
        if self.replication is not None:
            state = self._coordinator_state()
            published = getattr(state, "remediations", None)
            if published is not None:
                out["published"] = [dict(r) for r in published]
        return out

    def post_remediation(self, body: dict | None) -> dict:
        """POST /_remediation — toggle dry_run/enabled at runtime and/or
        force a planning tick (`{"tick": true}`), which is also how the
        proc-clustered topology (no in-process stepper) drives the
        loops."""
        body = body or {}
        svc = self.remediation
        for key in ("dry_run", "enabled"):
            if key in body:
                if not isinstance(body[key], bool):
                    raise ApiError(
                        400,
                        "illegal_argument_exception",
                        f"[{key}] must be a boolean",
                    )
                setattr(svc, key, body[key])
        out: dict[str, Any] = {
            "acknowledged": True,
            "enabled": svc.enabled,
            "dry_run": svc.dry_run,
        }
        if body.get("tick"):
            records = svc.tick(force=True)
            out["records"] = [dict(r) for r in records or []]
        return out

    # ---------------------------------------------------------------- admin

    def cluster_health(
        self,
        wait_for_status: str | None = None,
        timeout_s: float = 30.0,
    ) -> dict:
        """GET /_cluster/health — a VIEW over the health report's shard
        math (obs/health.shard_summary: one computation behind this, the
        `shards_availability` indicator, and `_cat/health`). With
        ``wait_for_status`` it blocks until the cluster reaches at least
        that status (green satisfies a yellow wait) or the timeout
        expires — then answers with ``timed_out: true`` instead of an
        error, like the reference."""
        if wait_for_status is not None:
            if wait_for_status not in ("green", "yellow", "red"):
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"unknown wait_for_status [{wait_for_status}]; "
                    f"expected green, yellow or red",
                )
            deadline = time.monotonic() + max(0.0, timeout_s)
            while True:
                out = self._cluster_health_now()
                if status_at_least(out["status"], wait_for_status):
                    return out
                if time.monotonic() >= deadline:
                    out["timed_out"] = True
                    return out
                time.sleep(0.05)
        return self._cluster_health_now()

    def _cluster_health_now(self) -> dict:
        if self.replication is None:
            shards = sum(s.n_shards for s in self.indices.values())
            summary = {
                "status": "green",
                "nodes": 1,
                "active_primaries": shards,
                "active_shards": shards,
                "unassigned_shards": 0,
                "desired_shards": shards,
                "initializing_shards": 0,
            }
        else:
            summary = shard_summary(self._coordinator_state())
        desired = summary["desired_shards"]
        return {
            "cluster_name": self.cluster_name,
            "status": summary["status"],
            "timed_out": False,
            "number_of_nodes": summary["nodes"],
            "number_of_data_nodes": summary["nodes"],
            "active_primary_shards": summary["active_primaries"],
            "active_shards": summary["active_shards"],
            "relocating_shards": 0,
            "initializing_shards": summary["initializing_shards"],
            "unassigned_shards": summary["unassigned_shards"],
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": (
                100.0
                if not desired
                else 100.0 * summary["active_shards"] / desired
            ),
        }

    def cat_indices(self) -> list[dict]:
        return [
            {
                "health": "green",
                "status": "open",
                "index": name,
                "pri": str(svc.n_shards),
                "rep": "0",
                "docs.count": str(self._docs_count(svc)),
            }
            for name, svc in sorted(self.indices.items())
        ]

    def cat_health(self) -> list[dict]:
        # A view over the same shard math as /_cluster/health and the
        # shards_availability indicator (obs/health.shard_summary).
        h = self.cluster_health()
        return [
            {
                "cluster": h["cluster_name"],
                "status": h["status"],
                "node.total": str(h["number_of_nodes"]),
                "shards": str(h["active_shards"]),
                "pri": str(h["active_primary_shards"]),
                "unassign": str(h["unassigned_shards"]),
            }
        ]

    def cat_count(self, index: str | None = None) -> list[dict]:
        if index is not None:
            count = self._docs_count(self.get_index(index))
        else:
            count = sum(self._docs_count(s) for s in self.indices.values())
        return [{"count": str(count)}]

    def cat_shards(self) -> list[dict]:
        rows = []
        for name, svc in sorted(self.indices.items()):
            for shard_idx, engine in enumerate(svc.engines):
                rows.append(
                    {
                        "index": name,
                        "shard": str(shard_idx),
                        "prirep": "p",
                        "state": "STARTED",
                        "docs": str(engine.num_docs),
                        "node": self.node_name,
                    }
                )
        return rows

    def cat_nodes(self) -> list[dict]:
        """GET /_cat/nodes — id, role letters (d=data, i=ingest,
        m=master-eligible, v=voting-only tiebreaker), the elected-master
        marker, and load columns read from the fanned per-node stats
        (nodes_stats); a member that failed the fan gets no row, exactly
        like the reference's cat view over a partial nodes response."""
        role_letters = {
            "data": "d",
            "ingest": "i",
            "master": "m",
            "voting_only": "v",
        }
        rows = []
        for name, section in self.nodes_stats()["nodes"].items():
            roles = section.get("roles")
            if roles is None:
                # The standalone / coordinating front (no cluster role
                # payload): the single-process reference shape.
                roles = ["data", "ingest", "master"]
            master = section.get("master")
            if master is None:
                master = self.replication is None
            process = section.get("process") or {}
            indices = section.get("indices") or {}
            rows.append(
                {
                    "id": name,
                    "name": name,
                    "node.role": "".join(
                        sorted(role_letters.get(r, "-") for r in roles)
                    ),
                    "master": "*" if master else "-",
                    "load": str(int(process.get("inflight_searches", 0))),
                    "docs": str(
                        int((indices.get("docs") or {}).get("count", 0))
                    ),
                    "step_errors": str(int(section.get("step_errors", 0))),
                }
            )
        return rows

    def cat_segments(self) -> list[dict]:
        rows = []
        for name, svc in sorted(self.indices.items()):
            for shard_idx, engine in enumerate(svc.engines):
                for handle in engine.segments:
                    rows.append(
                        {
                            "index": name,
                            "shard": str(shard_idx),
                            "segment": f"_{handle.seg_id or 0}",
                            "docs.count": str(handle.live_count),
                            "docs.deleted": str(
                                handle.segment.num_docs - handle.live_count
                            ),
                            "size.memory": str(handle.nbytes),
                            # Device bytes this segment's packed planes
                            # hold — per index these sum to the HBM
                            # ledger's "segment" bytes (the /_cat/hbm
                            # consistency surface).
                            "device.bytes": str(handle.nbytes),
                        }
                    )
        return rows

    def cat_hbm(self) -> list[dict]:
        """GET /_cat/hbm — the HBM ledger's per-(label, index) resident
        device bytes, one row per sample, read from the FANNED per-node
        `device.hbm` sections (nodes_stats), so a clustered front shows
        every member's residency; `?format=json` behaves like every cat
        handler (the response is the row list)."""
        rows: list[dict] = []
        for node_name, section in sorted(self.nodes_stats()["nodes"].items()):
            hbm = (section.get("device") or {}).get("hbm") or {}
            for entry in hbm.get("by_label_index", []):
                rows.append(
                    {
                        "node": node_name,
                        "label": str(entry.get("label", "")),
                        "index": str(entry.get("index", "")),
                        "bytes": str(int(entry.get("bytes", 0))),
                    }
                )
            total_row = {
                "node": node_name,
                "label": "_total",
                "index": "_all",
                "bytes": str(int(hbm.get("total_bytes", 0))),
            }
            # Computed member sections carry no high watermark (the
            # instantaneous total is not a peak); only ledger-backed
            # sections render the column.
            if "high_watermark_bytes" in hbm:
                total_row["high_watermark"] = str(
                    int(hbm["high_watermark_bytes"])
                )
            rows.append(total_row)
        return rows

    def cluster_stats(self) -> dict:
        return {
            "cluster_name": self.cluster_name,
            "status": "green",
            "indices": {
                "count": len(self.indices),
                "shards": {
                    "total": sum(s.n_shards for s in self.indices.values())
                },
                "docs": {
                    "count": sum(s.num_docs for s in self.indices.values())
                },
            },
            "nodes": {"count": {"total": 1, "data": 1}},
        }

    def nodes_info(self) -> dict:
        from .obs.device import accelerator_info

        return {
            "cluster_name": self.cluster_name,
            "nodes": {
                self.node_name: {
                    "name": self.node_name,
                    "version": "8.0.0-tpu",
                    "roles": ["data", "ingest", "master"],
                    "accelerator": accelerator_info(),
                    "indexing_pressure": self.indexing_pressure.stats(),
                }
            },
        }

    def _batcher_resilience_stats(self) -> dict:
        if self.exec_batcher is None:
            return {"enabled": False}
        stats = self.exec_batcher.stats()  # one consistent snapshot
        return {
            k: stats[k]
            for k in (
                "retried_individually",
                "groups_quarantined",
                "quarantine_hits",
                "quarantined_now",
            )
        }

    def _refresh_merge_stats(self, engines) -> tuple[dict, dict]:
        """(refresh, merges) stats blocks over a set of engines — the
        reference's RefreshStats/MergeStats shapes, fed by the engine's
        posting-concatenation merge accounting."""
        refresh = {
            "total": sum(e.refresh_total for e in engines),
            "total_time_in_millis": int(
                sum(e.refresh_ms_total for e in engines)
            ),
        }
        merges = {
            "total": sum(e.merges_total for e in engines),
            "total_docs": sum(e.merge_docs_total for e in engines),
            "total_time_in_millis": int(
                sum(e.merge_ms_total for e in engines)
            ),
        }
        return refresh, merges

    def _cluster_obs_stats(self) -> dict:
        """The obs.cluster section: fan-in rounds/failures/latency plus
        trace-fragment and hot-threads accounting (views over the
        estpu_nodes_stats_* / estpu_trace_fragments_* /
        estpu_hot_threads_* instruments)."""
        from .obs.metrics import NODES_FAN_LATENCY_MS_BUCKETS

        latency = self.metrics.histogram(
            "estpu_nodes_stats_fan_latency_ms",
            NODES_FAN_LATENCY_MS_BUCKETS,
            "Wall-clock fan-in latency of stats/obs scatter rounds",
        ).snapshot()
        count = latency["count"]
        return {
            "fanouts": {
                action: int(v)
                for action, v in sorted(
                    self.metrics.label_values(
                        "estpu_nodes_stats_fanouts_total", "action"
                    ).items()
                )
            },
            "fan_failures": {
                action: int(v)
                for action, v in sorted(
                    self.metrics.label_values(
                        "estpu_nodes_stats_fan_failures_total", "action"
                    ).items()
                )
            },
            "fan_latency_ms": {
                "count": int(count),
                "mean": (
                    round(latency["sum"] / count, 3) if count else 0.0
                ),
            },
            "trace_fragments_collected": int(
                self.metrics.value("estpu_trace_fragments_collected_total")
            ),
            "hot_threads_samples": int(
                self.metrics.value("estpu_hot_threads_samples_total")
            ),
        }

    def nodes_stats(self) -> dict:
        """GET /_nodes/stats — cluster-scoped scatter/fan-in (the
        reference's TransportNodesStatsAction shape): the coordinating
        node's own sections plus, when clustered, one reference-shaped
        section per member collected over the `node_stats` wire action,
        under a `_nodes: {total, successful, failed}` header. A dead or
        wedged member becomes a NAMED failure entry within the per-send
        deadline — never a hang. The in-memory LocalCluster and the
        multi-process ProcCluster paths ship the SAME per-node payload
        (ClusterNode.node_stats_local), so the response shape is one
        across transports."""
        if self._procs is not None:
            return self._procs.nodes_stats(
                extra={self.node_name: self._local_node_stats()}
            )
        header: dict[str, Any] = {
            "total": 1,
            "successful": 1,
            "failed": 0,
        }
        results: dict[str, Any] = {}
        member_ids: list[str] = []
        if self.replication is not None:
            # Fan BEFORE snapshotting the local sections, so this very
            # round's fan counters (a failure entry just recorded) are
            # visible in the response's own obs.cluster view.
            member_ids = sorted(self.replication.cluster.nodes)
            results, failures = self._cluster_fan("node_stats", {})
            header = {
                "total": 1 + len(member_ids),
                "successful": 1 + len(results),
                "failed": len(failures),
            }
            if failures:
                header["failures"] = failures
        nodes: dict[str, Any] = {self.node_name: self._local_node_stats()}
        for node_id in member_ids:
            section = results.get(node_id)
            if section is None:
                continue
            if node_id in nodes:
                # The coordinating front shares this member's name (the
                # default LocalCluster layout): keep the local keys and
                # graft the member-only sections in.
                merged = dict(section)
                merged.update(nodes[node_id])
                nodes[node_id] = merged
            else:
                nodes[node_id] = section
        return {
            "_nodes": header,
            "cluster_name": self.cluster_name,
            "nodes": nodes,
        }

    def _local_node_stats(self) -> dict:
        """This coordinating node's own `_nodes/stats` sections:
        serving-resilience counters, SPMD mesh circuit-breaker state and
        disable/re-enable events per index, plus replication gateway
        retry/failover counts when clustered."""
        mesh_views: dict[str, Any] = {}
        disable_events = 0
        reenable_events = 0
        for name, svc in sorted(self.indices.items()):
            mv = getattr(svc.search, "mesh_view", None)
            if mv is None:
                continue
            breaker = mv.breaker.stats()
            disable_events += breaker["disable_events"]
            reenable_events += breaker["reenable_events"]
            mesh_views[name] = {
                **breaker,
                "served": mv.served,
                "packs": mv.packs,
                "segment_reuses": mv.seg_reuses,
                "rebuilds": mv.rebuilds,
                "exec_failures": mv.exec_failures,
                # Host-loop fallbacks by reason (estpu_mesh_fallback_total
                # view): a mesh decline is never silent.
                "fallbacks": {
                    k: v for k, v in sorted(mv.fallbacks.items())
                },
            }
        from .analysis.analyzers import analysis_calls_total

        all_engines = [
            e for svc in self.indices.values() for e in svc.engines
        ]
        refresh_stats, merge_stats = self._refresh_merge_stats(all_engines)
        merge_stats["mesh_segments_packed"] = int(
            self.metrics.value("estpu_mesh_segments_packed_total")
        )
        merge_stats["mesh_segments_reused"] = int(
            self.metrics.value("estpu_mesh_segments_reused_total")
        )
        node_stats: dict[str, Any] = {
            "name": self.node_name,
            "indices": {
                "docs": {
                    "count": sum(
                        self._docs_count(svc)
                        for svc in self.indices.values()
                    )
                },
                # Refresh/merge accounting (RefreshStats/MergeStats
                # analog): merges are posting-concatenation compactions —
                # estpu_refresh_*/estpu_merge_* views.
                "refresh": refresh_stats,
                "merges": merge_stats,
                # Analysis-call accounting: the hook behind the
                # "merges never re-tokenize" invariant
                # (estpu_analysis_calls_total view).
                "analysis": {
                    "analysis_calls_total": analysis_calls_total()
                },
                # Shard request cache hit/miss/eviction counters
                # (indices/IndicesRequestCache stats analog).
                "request_cache": self.request_cache.stats(),
                # Filter/bitset cache (indices/IndicesQueryCache analog):
                # mask-plane hits/misses/admissions/evictions + resident
                # HBM bytes. Present (inert) under ESTPU_FILTER_CACHE=0
                # so dashboards keep their panel.
                "filter_cache": (
                    self.filter_cache.stats()
                    if self.filter_cache is not None
                    else FilterCache.disabled_stats()
                ),
            },
            # ANN serving state (the `knn` section): resident IVF planes,
            # build/eviction counters, per-backend search counts, probe
            # totals, recall-gate outcomes. Present (inert) under
            # ESTPU_ANN=0.
            "search": {
                "ann": (
                    self.ann_cache.stats()
                    if self.ann_cache is not None
                    else AnnCache.disabled_stats()
                ),
            },
            "breakers": {"hbm": self.breaker.stats()},
            "indexing_pressure": self.indexing_pressure.stats(),
            "mesh_serving": {
                "disable_events": disable_events,
                "reenable_events": reenable_events,
                # Node-wide one-launch servings by request shape
                # (estpu_mesh_served_total view).
                "served_by_shape": {
                    shape: int(v)
                    for shape, v in sorted(
                        self.metrics.label_values(
                            "estpu_mesh_served_total", "shape"
                        ).items()
                    )
                },
                "views": mesh_views,
            },
            # Adaptive query-execution subsystem: planner decision
            # counters + per-plan-class EWMA snapshots, and the micro-
            # batcher's occupancy histogram / queue-wait percentiles.
            "exec": {
                "planner": (
                    self.exec_planner.stats()
                    if self.exec_planner is not None
                    else {"enabled": False}
                ),
                "batcher": (
                    self.exec_batcher.stats()
                    if self.exec_batcher is not None
                    else {"enabled": False}
                ),
                # Packed multi-tenant execution: launch/lane counters,
                # plane residency, tenants-per-launch occupancy.
                "packed": (
                    self.packed_exec.stats()
                    if self.packed_exec is not None
                    else {"enabled": False}
                ),
                # Per-tenant QoS lanes: weights, inflight, windowed cost
                # and shed counts per lane (estpu_qos_* views).
                "qos": self.qos.stats(),
                # Async-search store: stored/running entries, partials
                # served, keep_alive expiries (estpu_async_* views).
                "async_search": self.async_search.stats(),
            },
            # Fault-injection registry (POST /_fault) and degraded-mode
            # serving counters: partial responses, absorbed shard
            # failures, batcher failure-isolation activity.
            "faults": FAULTS.stats(),
            "search_resilience": {
                **{
                    k: v
                    for k, v in sorted(self.search_resilience.items())
                },
                "batcher": self._batcher_resilience_stats(),
            },
            # Device-level launch instruments (obs/metrics.py): XLA
            # compile count/ms per plan class, H2D bytes, padding waste,
            # the retrace census (device.compile), and the HBM ledger
            # (device.hbm). Present-but-inert under ESTPU_DEVICE_OBS=0.
            "device": {
                **(
                    self.device.snapshot()
                    if self.device is not None
                    else {"enabled": False}
                ),
                "hbm": self.hbm_ledger.snapshot(),
            },
            # Tracing ring state (obs/tracing.py) + cluster-scope fan-in
            # accounting (estpu_nodes_stats_* / trace-fragment /
            # hot-threads views) + the query-insights ring.
            "obs": {
                "tracing": TRACER.stats(),
                "cluster": self._cluster_obs_stats(),
                "insights": self.insights.stats(),
            },
            # Health-report rounds + last-computed indicator statuses
            # (obs/health.py; estpu_health_* views).
            "health": self.health.stats(),
            # Flight recorder + incident ring (obs/incidents.py):
            # present-but-inert under ESTPU_INCIDENTS=0.
            "incidents": self.incidents.stats(),
        }
        if self.replication is not None:
            node_stats["replication"] = self.replication.stats()
        return node_stats

    def stats(self) -> dict:
        all_engines = [
            e for s in self.indices.values() for e in s.engines
        ]
        all_refresh, all_merges = self._refresh_merge_stats(all_engines)

        def _index_primaries(svc) -> dict:
            refresh, merges = self._refresh_merge_stats(svc.engines)
            return {
                "docs": {"count": svc.num_docs},
                "segments": {
                    "count": sum(len(e.segments) for e in svc.engines),
                    "device_memory_in_bytes": sum(
                        e.device_bytes for e in svc.engines
                    ),
                },
                # Reference-style refresh/merges blocks (_stats):
                # merges move docs by posting concatenation, never
                # through the analysis chain.
                "refresh": refresh,
                "merges": merges,
            }

        return {
            "_all": {
                "primaries": {
                    "docs": {
                        "count": sum(s.num_docs for s in self.indices.values())
                    },
                    "request_cache": self.request_cache.stats(),
                    "segments": {
                        "count": sum(
                            len(e.segments)
                            for s in self.indices.values()
                            for e in s.engines
                        ),
                        "device_memory_in_bytes": sum(
                            e.device_bytes
                            for s in self.indices.values()
                            for e in s.engines
                        ),
                    },
                    "refresh": all_refresh,
                    "merges": all_merges,
                }
            },
            "breakers": {"hbm": self.breaker.stats()},
            "indices": {
                name: {"primaries": _index_primaries(svc)}
                for name, svc in self.indices.items()
            },
        }
