"""HTTP/REST layer: Elasticsearch-compatible endpoints over a Node.

The analog of the reference's RestController dispatch (server/src/main/java/
org/elasticsearch/rest/RestController.java:57) + the per-API Rest*Action
handlers, on the stdlib threading HTTP server (the reference uses Netty4;
the serving hot path here is the device, not the socket layer).

Routes (subset mirroring rest-api-spec/):
    GET  /                                   — node banner
    GET  /_cluster/health                    — health
    GET  /_cat/indices[?format=json]         — cat API
    GET  /_stats                             — docs stats
    PUT  /{index}                            — create index
    DELETE /{index}                          — delete index
    GET  /{index}/_mapping | PUT             — mappings
    PUT|POST /{index}/_doc/{id} | POST /{index}/_doc — index document
    GET  /{index}/_doc/{id}                  — realtime get
    DELETE /{index}/_doc/{id}                — delete document
    POST /{index}/_update/{id}               — partial update
    POST /[{index}/]_bulk                    — NDJSON bulk
    GET|POST /{index}/_search                — search
    GET|POST /{index}/_count                 — count
    POST /{index}/_refresh                   — refresh
    GET|POST /{index}/_rank_eval             — relevance evaluation
    POST /{index}/_analyze                   — analysis debugging
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable
from urllib.parse import parse_qs, urlparse

from ..cluster import (
    ConnectTransportError,
    NoShardAvailableError,
    NotMasterError,
    ReplicationFailedError,
    ReplicationUnavailableError,
    StalePrimaryTermError,
)
from ..common.breaker import BreakerError
from ..faults import InjectedFaultError
from ..node import ApiError, Node
from ..obs.tracing import TRACER, format_traceparent
from ..search import rank_eval
from ..search.service import SearchPhaseFailedError

Handler = Callable[["RestServer", dict, dict, Any], Any]


class PlainText:
    """A non-JSON response body (the Prometheus exposition): the HTTP
    layer writes `text` verbatim with `content_type` instead of
    json.dumps-ing it."""

    __slots__ = ("text", "content_type")

    def __init__(
        self,
        text: str,
        content_type: str = "text/plain; version=0.0.4; charset=utf-8",
    ):
        self.text = text
        self.content_type = content_type


# Endpoints that observe the observer: tracing them would fill the ring
# buffer with scrapes instead of searches. `/_health_report` belongs
# here so a paced health poll (a 1/s liveness probe is normal ops)
# doesn't churn the trace ring; `/_incidents` for the same reason — a
# paced incident poll must not evict the very exemplar traces its
# capsules splice in.
_UNTRACED_PATHS = (
    "/_traces",
    "/_metrics",
    "/_health_report",
    "/_incidents",
)

# Cluster-topology failures that may escape the Node's own retry mapping
# (e.g. raised from a code path that predates replication): the router
# retries them once after a control-plane round, then answers 503 — the
# reference's unavailable-shards status — never a raw 500.
_TOPOLOGY_ERRORS = (
    ConnectTransportError,
    NoShardAvailableError,
    NotMasterError,
    ReplicationFailedError,
    StalePrimaryTermError,
    ReplicationUnavailableError,
)


def _json(body: str) -> dict:
    if not body or not body.strip():
        return {}
    return json.loads(body)


def _knn_search_body(body: dict) -> dict:
    """`_knn_search` request body → the equivalent `_search` body with a
    top-level `knn` section. The endpoint's own keys are the knn object,
    an optional top-level filter (folded into the section), and the
    ordinary fetch/paging keys, which pass through."""
    if "knn" not in body:
        raise ApiError(
            400, "parsing_exception", "[_knn_search] requires a [knn] body"
        )
    knn = dict(body["knn"]) if isinstance(body["knn"], dict) else body["knn"]
    out: dict = {}
    for key, value in body.items():
        if key == "knn":
            continue
        if key == "filter":
            if isinstance(knn, dict):
                knn = {**knn, "filter": value}
            continue
        out[key] = value
    out["knn"] = knn
    return out


def _verbose_param(q: dict) -> bool:
    """?verbose= on /_health_report: default true; false is the cheap
    liveness-probe mode (no cluster fan, no detail blocks)."""
    raw = q.get("verbose", "true").strip().lower()
    if raw in ("true", ""):
        return True
    if raw == "false":
        return False
    raise ApiError(
        400,
        "illegal_argument_exception",
        f"Failed to parse value [{q['verbose']}] for [verbose]: only "
        f"[true] or [false] are allowed.",
    )


# Bounded endpoint classes for the per-endpoint rolling latency window
# (`estpu_rest_latency_recent_ms{endpoint=...}`): route families, never
# raw paths (unbounded cardinality). Document-API paths split by method:
# GET/HEAD /{index}/_doc/{id} is a realtime read, not a write.
def _endpoint_class(path: str, method: str = "GET") -> str:
    if path.endswith(
        ("/_search", "/_msearch", "/_count", "/_knn_search")
    ) or "/_search/" in path:
        return "search"
    if "/_mget" in path or path == "/_mget":
        return "read"
    if (
        "/_doc" in path
        or "/_update" in path
        or "/_create" in path
        or path.endswith("/_bulk")
        or path == "/_bulk"
        or path.endswith(("/_delete_by_query", "/_update_by_query"))
    ):
        return "read" if method in ("GET", "HEAD") else "write"
    if path.startswith("/_") or "/_" in path:
        return "admin"
    return "other"


def _timeout_param(q: dict) -> float | None:
    """?timeout=30s on write APIs: per-request replication retry budget."""
    if "timeout" not in q:
        return None
    from ..common.units import parse_duration_s

    try:
        return parse_duration_s(q["timeout"])
    except ValueError:
        raise ApiError(
            400,
            "illegal_argument_exception",
            f"failed to parse [timeout]: [{q['timeout']}]",
        ) from None


def _interval_param(q: dict) -> float:
    """?interval=500ms on hot_threads (the reference's sample interval)."""
    if "interval" not in q:
        return 0.5
    from ..common.units import parse_duration_s

    try:
        return parse_duration_s(q["interval"])
    except ValueError:
        raise ApiError(
            400,
            "illegal_argument_exception",
            f"failed to parse [interval]: [{q['interval']}]",
        ) from None


def _partial_param(q: dict) -> bool | None:
    """?allow_partial_search_results= (the reference's URL param): None
    when absent (body/default wins), else the boolean. Anything but
    true/false is a 400 — a misspelled "False" must never silently
    invert the caller's no-partials demand."""
    if "allow_partial_search_results" not in q:
        return None
    raw = q["allow_partial_search_results"].strip().lower()
    if raw in ("true", ""):
        return True
    if raw == "false":
        return False
    raise ApiError(
        400,
        "illegal_argument_exception",
        f"Failed to parse value [{q['allow_partial_search_results']}] as "
        f"only [true] or [false] are allowed.",
    )


def _cas_params(q: dict) -> dict:
    """Extract if_seq_no/if_primary_term CAS query params (ES doc APIs)."""
    out: dict = {}
    for name in ("if_seq_no", "if_primary_term"):
        if name in q:
            try:
                out[name] = int(q[name])
            except ValueError:
                raise ApiError(
                    400,
                    "illegal_argument_exception",
                    f"[{name}] must be an integer, got [{q[name]}]",
                ) from None
    return out


class RestServer:
    # http.max_content_length (the reference's 100mb default).
    max_content_length = 100 * 1024 * 1024

    def __init__(
        self,
        node: Node | None = None,
        data_path: str | None = None,
        replication_nodes: int = 0,
        cluster_data_path: str | None = None,
        cluster_transport: str | None = None,
        proc_nodes: int = 0,
        transport_key: str | None = None,
    ):
        """A REST front. With `replication_nodes >= 2` (or the
        ESTPU_REPLICATION_NODES env var) the server boots an in-process
        replication cluster and serves the document APIs through it:
        acknowledged writes reach every in-sync copy before the 200, and
        reads/searches fail over across copies when nodes die. The
        background stepper keeps failure detection and promotion live
        under traffic. `cluster_transport` picks the node-to-node wire:
        "hub" (in-memory, default) or "tcp" (real loopback sockets);
        defaults from ESTPU_CLUSTER_TRANSPORT.

        With `proc_nodes >= 2` (or ESTPU_PROC_NODES) the server instead
        boots the SOCKETED topology: this process is the HTTP front +
        voting-only tiebreaker, and every data node is a separate OS
        process reached over cluster/tcp_transport.py — the one-machine
        rehearsal of the production layout. Document APIs route through
        ProcGateway (the replication gateway's retry/backoff/failover
        semantics over real sockets, per-send deadlines: a dead peer is
        a timed 503, never a hang); observability endpoints fan over the
        never-intercepted `_ctl` control path. `transport_key` (or
        ESTPU_TRANSPORT_KEY) arms shared-key HMAC handshake authn on
        every node-to-node connection."""
        if node is None and replication_nodes == 0:
            replication_nodes = int(
                os.environ.get("ESTPU_REPLICATION_NODES", "0") or 0
            )
        if node is None and proc_nodes == 0:
            proc_nodes = int(
                os.environ.get("ESTPU_PROC_NODES", "0") or 0
            )
        if node is not None and (replication_nodes or proc_nodes):
            raise ValueError(
                "replication_nodes/proc_nodes cannot be combined with an "
                "existing node; construct the Node with replication= "
                "instead"
            )
        if replication_nodes and proc_nodes:
            raise ValueError(
                "replication_nodes (in-process) and proc_nodes (socketed"
                " multi-process) are mutually exclusive topologies"
            )
        if replication_nodes == 1 or proc_nodes == 1:
            raise ValueError(
                "replication requires at least 2 nodes "
                f"(replication_nodes={replication_nodes} proc_nodes="
                f"{proc_nodes} would serve unreplicated)"
            )
        self.cluster = None
        if node is None and proc_nodes >= 2:
            from ..cluster import ProcCluster, ProcGateway

            self.cluster = ProcCluster(
                proc_nodes,
                data_path=cluster_data_path,
                auth_key=transport_key,
            )
            # The front's name must NOT collide with a data node's
            # ("node-0"): the nodes_stats/health merge rules would graft
            # front-local sections onto a worker's entry.
            node = Node(
                node_name="front",
                cluster_name=self.cluster.cluster_name,
                data_path=data_path,
                replication=ProcGateway(self.cluster),
            )
        elif node is None and replication_nodes >= 2:
            from ..cluster import LocalCluster, ReplicationGateway

            self.cluster = LocalCluster(
                replication_nodes,
                data_path=cluster_data_path,
                transport=cluster_transport,
            )
            self.cluster.start_stepper()
            node = Node(
                data_path=data_path,
                replication=ReplicationGateway(self.cluster),
            )
        self.node = node or Node(data_path=data_path)
        if self.cluster is None and self.node.replication is not None:
            self.cluster = self.node.replication.cluster
        # Wire byte length of the current request's body, per handler
        # thread (the Content-Length the socket actually carried).
        self._tl = threading.local()
        # Per-tenant QoS lane key: the request header (X-Opaque-Id by
        # default, ESTPU_QOS_HEADER overrides) rides thread-locally from
        # dispatch into the search handlers; absent → the _default lane.
        self._qos_header = os.environ.get("ESTPU_QOS_HEADER") or "X-Opaque-Id"
        self.routes: list[tuple[str, re.Pattern, Handler]] = []
        self._register_routes()

    def _tenant(self) -> str | None:
        return getattr(self._tl, "tenant", None)

    def close(self) -> None:
        """Stop the replication cluster (if any) and local engines."""
        if self.cluster is not None:
            self.cluster.close()
        self.node.close()

    def route(self, method: str, pattern: str, handler: Handler) -> None:
        # {name} → named group; index names can't start with _ so the
        # literal _-prefixed routes must be registered first.
        regex = re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern)
        self.routes.append((method, re.compile(f"^{regex}$"), handler))

    def _register_routes(self) -> None:
        n = self.node
        r = self.route
        r("GET", "/", lambda s, p, q, b: {
            "name": n.node_name,
            "cluster_name": n.cluster_name,
            "version": {"number": "8.0.0-tpu", "distribution": "elasticsearch-tpu"},
            "tagline": "You Know, for (TPU) Search",
        })
        r("GET", "/_cluster/health", lambda s, p, q, b: n.cluster_health(
            wait_for_status=q.get("wait_for_status"),
            timeout_s=(
                30.0 if "timeout" not in q else (_timeout_param(q) or 0.0)
            ),
        ))
        r("GET", "/_cluster/stats", lambda s, p, q, b: n.cluster_stats())
        # Health report (obs/health.py): rule-based indicators over the
        # rolling windows — the reference's GET /_health_report.
        # ?verbose=false skips the cluster fan and detail blocks (cheap
        # liveness probe); untraced (see _UNTRACED_PATHS).
        r("GET", "/_health_report", lambda s, p, q, b: n.health_report(
            verbose=_verbose_param(q)
        ))
        r("GET", "/_health_report/{indicator}", lambda s, p, q, b:
          n.health_report(
              verbose=_verbose_param(q), indicator=p["indicator"]
          ))
        # Query insights: the bounded top-N slowest-searches sample
        # (structured slowlog sibling, obs/insights.py).
        r("GET", "/_insights/queries", lambda s, p, q, b: n.query_insights(
            size=int(q["size"]) if "size" in q else None
        ))
        r("GET", "/_nodes", lambda s, p, q, b: n.nodes_info())
        r("GET", "/_nodes/stats", lambda s, p, q, b: n.nodes_stats())
        # Per-node thread-stack sampling, fanned over cluster members
        # (the reference's RestNodesHotThreadsAction; text response).
        r("GET", "/_nodes/hot_threads", lambda s, p, q, b: PlainText(
            n.hot_threads(
                threads=int(q.get("threads", 3)),
                interval_s=_interval_param(q),
                snapshots=int(q.get("snapshots", 10)),
            ),
            content_type="text/plain; charset=utf-8",
        ))
        r("GET", "/_cat/nodes", lambda s, p, q, b: n.cat_nodes())
        r("GET", "/_cat/plugins", lambda s, p, q, b: [
            {"name": n.node_name, "component": name}
            for name in n.plugin_names
        ])
        r("GET", "/_cat/health", lambda s, p, q, b: n.cat_health())
        r("GET", "/_cat/count", lambda s, p, q, b: n.cat_count())
        r("GET", "/_cat/count/{index}", lambda s, p, q, b: n.cat_count(
            p["index"]
        ))
        r("GET", "/_cat/shards", lambda s, p, q, b: n.cat_shards())
        r("GET", "/_cat/segments", lambda s, p, q, b: n.cat_segments())
        r("POST", "/_aliases", lambda s, p, q, b: n.update_aliases(_json(b)))
        r("PUT", "/_index_template/{name}", lambda s, p, q, b:
          n.put_index_template(p["name"], _json(b)))
        r("POST", "/_index_template/{name}", lambda s, p, q, b:
          n.put_index_template(p["name"], _json(b)))
        r("GET", "/_index_template", lambda s, p, q, b:
          n.get_index_template())
        r("GET", "/_index_template/{name}", lambda s, p, q, b:
          n.get_index_template(p["name"]))
        r("DELETE", "/_index_template/{name}", lambda s, p, q, b:
          n.delete_index_template(p["name"]))
        for method in ("PUT", "POST"):
            r(method, "/_scripts/{id}", lambda s, p, q, b: n.put_script(
                p["id"], _json(b)
            ))
        r("GET", "/_scripts/{id}", lambda s, p, q, b: n.get_script(p["id"]))
        r("DELETE", "/_scripts/{id}", lambda s, p, q, b: n.delete_script(
            p["id"]
        ))
        for method in ("GET", "POST"):
            r(method, "/_render/template", lambda s, p, q, b:
              n.render_template(_json(b)))
            r(method, "/_render/template/{id}", lambda s, p, q, b:
              n.render_template(dict(_json(b), id=p["id"])))
            r(method, "/{index}/_search/template", lambda s, p, q, b:
              n.search_template(p["index"], _json(b)))
        r("GET", "/_alias", lambda s, p, q, b: n.get_aliases())
        r("GET", "/{index}/_alias", lambda s, p, q, b: n.get_aliases(
            p["index"]
        ))
        r("PUT", "/{index}/_alias/{name}", lambda s, p, q, b: n.update_aliases(
            {"actions": [{"add": {"index": p["index"], "alias": p["name"]}}]}
        ))
        r("DELETE", "/{index}/_alias/{name}",
          lambda s, p, q, b: n.delete_alias(p["index"], p["name"]))
        r("GET", "/{index}/_settings", lambda s, p, q, b: n.get_settings(
            p["index"]
        ))
        r("PUT", "/{index}/_settings", lambda s, p, q, b: n.put_settings(
            p["index"], _json(b)
        ))
        # Fault-injection admin API (faults/registry.py): arm/inspect/
        # disarm deterministic fault specs at named serving sites.
        r("GET", "/_fault", lambda s, p, q, b: n.get_faults())
        r("POST", "/_fault", lambda s, p, q, b: n.put_fault(_json(b)))
        r("DELETE", "/_fault", lambda s, p, q, b: n.clear_faults())
        r("DELETE", "/_fault/{site}", lambda s, p, q, b: n.clear_faults(
            p["site"]
        ))
        # Self-driving remediation (cluster/remediation.py): planned-vs-
        # executed history + runtime dry_run/enabled toggles and forced
        # planning ticks.
        r("GET", "/_remediation", lambda s, p, q, b: n.get_remediation())
        r("POST", "/_remediation", lambda s, p, q, b: n.post_remediation(
            _json(b)
        ))
        # Flight recorder + incident autopsy (obs/incidents.py): the
        # bounded capsule ring. ?verbose=false returns statuses/trigger
        # lines only (no capsule bodies, no cluster fan); untraced (see
        # _UNTRACED_PATHS). `_capture` registers before `{id}` — route
        # registration order is match order.
        r("GET", "/_incidents", lambda s, p, q, b: n.get_incidents(
            verbose=_verbose_param(q)
        ))
        r("POST", "/_incidents/_capture", lambda s, p, q, b:
          n.capture_incident(_json(b)))
        r("GET", "/_incidents/{id}", lambda s, p, q, b: n.get_incident(
            p["id"]
        ))
        r("GET", "/_cat/incidents", lambda s, p, q, b: n.cat_incidents())
        # Observability: trace ring + Prometheus exposition.
        r("GET", "/_traces", lambda s, p, q, b: n.get_traces(
            limit=int(q.get("limit", 50))
        ))
        r("GET", "/_traces/{trace_id}", lambda s, p, q, b: n.get_trace(
            p["trace_id"], fmt=q.get("format")
        ))
        r("GET", "/_metrics", lambda s, p, q, b: PlainText(n.metrics_text()))
        # On-demand device profiler capture (obs/device.ProfilerCapture):
        # jax.profiler trace windows — single-flight, bounded duration,
        # 409 on double-start; stop returns the Perfetto trace directory.
        r("GET", "/_profiler", lambda s, p, q, b: n.profiler_status())
        r("POST", "/_profiler/start", lambda s, p, q, b: n.profiler_start(
            _json(b)
        ))
        r("POST", "/_profiler/stop", lambda s, p, q, b: n.profiler_stop())
        # HBM ledger cat view: per-(node, label, index) resident device
        # bytes read from the fanned `device.hbm` stats sections.
        r("GET", "/_cat/hbm", lambda s, p, q, b: n.cat_hbm())
        r("GET", "/_cat/tasks", lambda s, p, q, b: n.cat_tasks())
        r("GET", "/_tasks", lambda s, p, q, b: n.list_tasks(
            q.get("actions"),
            detailed=q.get("detailed") in ("true", ""),
        ))
        r("GET", "/_tasks/{task_id}", lambda s, p, q, b: n.get_task(
            p["task_id"]
        ))
        r("POST", "/_tasks/{task_id}/_cancel", lambda s, p, q, b: n.cancel_task(
            p["task_id"]
        ))
        r("PUT", "/_snapshot/{repo}", lambda s, p, q, b: n.put_repository(
            p["repo"], _json(b)
        ))
        r("GET", "/_snapshot/{repo}", lambda s, p, q, b: n.get_repository(
            p["repo"]
        ))
        r("PUT", "/_snapshot/{repo}/{snap}", lambda s, p, q, b: n.create_snapshot(
            p["repo"], p["snap"], _json(b)
        ))
        r("GET", "/_snapshot/{repo}/{snap}", lambda s, p, q, b: n.get_snapshot(
            p["repo"], p["snap"]
        ))
        r("DELETE", "/_snapshot/{repo}/{snap}", lambda s, p, q, b: n.delete_snapshot(
            p["repo"], p["snap"]
        ))
        r("POST", "/_snapshot/{repo}/{snap}/_restore",
          lambda s, p, q, b: n.restore_snapshot(p["repo"], p["snap"], _json(b)))
        r("GET", "/_cat/indices", lambda s, p, q, b: n.cat_indices())
        r("GET", "/_stats", lambda s, p, q, b: n.stats())
        r("POST", "/_bulk", lambda s, p, q, b: n.bulk(
            b, refresh=q.get("refresh") in ("true", ""),
            pipeline=q.get("pipeline"),
            nbytes=getattr(s._tl, "body_nbytes", None),
        ))
        r("POST", "/{index}/_bulk", lambda s, p, q, b: n.bulk(
            b, default_index=p["index"],
            refresh=q.get("refresh") in ("true", ""),
            pipeline=q.get("pipeline"),
            nbytes=getattr(s._tl, "body_nbytes", None),
        ))
        r("PUT", "/_ingest/pipeline/{id}", lambda s, p, q, b: n.put_pipeline(
            p["id"], _json(b)
        ))
        r("GET", "/_ingest/pipeline", lambda s, p, q, b: n.get_pipeline())
        r("GET", "/_ingest/pipeline/{id}", lambda s, p, q, b: n.get_pipeline(
            p["id"]
        ))
        r("DELETE", "/_ingest/pipeline/{id}",
          lambda s, p, q, b: n.delete_pipeline(p["id"]))
        r("POST", "/_ingest/pipeline/{id}/_simulate",
          lambda s, p, q, b: n.simulate_pipeline(p["id"], _json(b)))
        r("POST", "/_ingest/pipeline/_simulate",
          lambda s, p, q, b: n.simulate_pipeline(None, _json(b)))
        r("GET", "/{index}/_mapping", lambda s, p, q, b: n.get_mapping(p["index"]))
        r("PUT", "/{index}/_mapping", lambda s, p, q, b: n.put_mapping(
            p["index"], _json(b)
        ))
        for method in ("GET", "POST"):
            r(method, "/_search/scroll", lambda s, p, q, b: n.scroll(_json(b)))
            r(method, "/_search", lambda s, p, q, b: n.search(
                "_all", _json(b), scroll=q.get("scroll"),
                timeout_s=_timeout_param(q),
                allow_partial=_partial_param(q),
                tenant=s._tenant(),
            ))
            r(method, "/_count", lambda s, p, q, b: n.count(
                n.default_index(), _json(b)
            ))
            r(method, "/_refresh", lambda s, p, q, b: n.refresh_all())
            r(method, "/_flush", lambda s, p, q, b: n.flush_all())
        # Cache administration (the reference's clear-cache API,
        # RestClearIndicesCacheAction): drops filter-cache mask planes
        # and request-cache entries; per-cache cleared counts returned.
        r("POST", "/_cache/clear", lambda s, p, q, b: n.clear_cache())
        r("POST", "/{index}/_cache/clear", lambda s, p, q, b: n.clear_cache(
            p["index"]
        ))
        r("POST", "/_forcemerge", lambda s, p, q, b: [
            n.force_merge(name, int(q.get("max_num_segments", 1)))
            for name in list(n.indices)
        ] and {"_shards": {"failed": 0}} or {"_shards": {"failed": 0}})
        r("GET", "/_mapping", lambda s, p, q, b: n.get_mapping_all())
        for method in ("GET", "POST"):
            r(method, "/_mget", lambda s, p, q, b: n.mget(_json(b)))
            r(method, "/{index}/_search", lambda s, p, q, b: n.search(
                p["index"], _json(b), scroll=q.get("scroll"),
                request_cache=(
                    None if "request_cache" not in q
                    else q["request_cache"] in ("true", "")
                ),
                # ?timeout= is honored even while the search waits in the
                # exec micro-batcher's queue (deadline-aware launch).
                timeout_s=_timeout_param(q),
                allow_partial=_partial_param(q),
                tenant=s._tenant(),
            ))
            # Async search (the reference's RestSubmitAsyncSearchAction):
            # registers a stored progressive search; wait_for_completion_
            # timeout / keep_alive / keep_on_completion ride as params.
            r(method, "/{index}/_async_search", lambda s, p, q, b:
                n.async_search_submit(
                    p["index"], _json(b), params=q, tenant=s._tenant()
                ))
            r(method, "/{index}/_count", lambda s, p, q, b: n.count(
                p["index"], _json(b)
            ))
            # The reference's 8.0 dedicated kNN endpoint (RestKnnSearch-
            # Action, deprecated there in favor of the `knn` search
            # section both endpoints share here): {"knn": {...}} plus the
            # ordinary fetch keys; a top-level "filter" folds into the
            # knn section (its 8.1+ home).
            r(method, "/{index}/_knn_search", lambda s, p, q, b:
                n.search(p["index"], _knn_search_body(_json(b))))
            r(method, "/{index}/_rank_eval", lambda s, p, q, b: rank_eval.evaluate(
                n, p["index"], _json(b)
            ))
            r(method, "/{index}/_mget", lambda s, p, q, b: n.mget(
                _json(b), default_index=p["index"]
            ))
            r(method, "/{index}/_explain/{id}", lambda s, p, q, b: n.explain(
                p["index"], p["id"], _json(b)
            ))
        r("GET", "/_async_search/{id}", lambda s, p, q, b:
            n.async_search_get(p["id"], params=q))
        r("DELETE", "/_async_search/{id}", lambda s, p, q, b:
            n.async_search_delete(p["id"]))
        r("DELETE", "/_search/scroll", lambda s, p, q, b: n.clear_scroll(
            _json(b)
        ))
        r("POST", "/_msearch", lambda s, p, q, b: n.msearch(
            b, allow_partial=_partial_param(q)
        ))
        r("POST", "/{index}/_msearch", lambda s, p, q, b: n.msearch(
            b, default_index=p["index"], allow_partial=_partial_param(q)
        ))
        def _refresh_multi(s, p, q, b):
            names = n.expand_index_patterns(p["index"])
            if not names:
                return n.refresh(p["index"])  # 404 with ES shape
            out = None
            for name in names:
                out = n.refresh(name)
            return out

        r("POST", "/{index}/_refresh", _refresh_multi)
        r("GET", "/{index}/_refresh", _refresh_multi)
        r("POST", "/{index}/_flush", lambda s, p, q, b: n.flush(p["index"]))
        r("POST", "/{index}/_forcemerge", lambda s, p, q, b: n.force_merge(
            p["index"], int(q.get("max_num_segments", 1))
        ))
        r("POST", "/{index}/_delete_by_query",
          lambda s, p, q, b: n.delete_by_query(
              p["index"], _json(b), refresh=q.get("refresh") in ("true", "")
          ))
        r("POST", "/{index}/_update_by_query",
          lambda s, p, q, b: n.update_by_query(
              p["index"], _json(b), refresh=q.get("refresh") in ("true", ""),
              pipeline=q.get("pipeline"),
          ))
        r("POST", "/_reindex", lambda s, p, q, b: n.reindex(
            _json(b), refresh=q.get("refresh") in ("true", "")
        ))
        r("POST", "/{index}/_analyze", self._analyze)
        r("POST", "/_analyze", lambda s, p, q, b: s._analyze(
            s, {"index": None}, q, b
        ))
        r("GET", "/_analyze", lambda s, p, q, b: s._analyze(
            s, {"index": None}, q, b
        ))
        r("POST", "/{index}/_doc", lambda s, p, q, b: n.index_doc(
            p["index"], _json(b), None,
            refresh=q.get("refresh") in ("true", ""),
            pipeline=q.get("pipeline"),
            timeout_s=_timeout_param(q),
        ))
        for method in ("PUT", "POST"):
            r(method, "/{index}/_doc/{id}", lambda s, p, q, b: n.index_doc(
                p["index"], _json(b), p["id"],
                refresh=q.get("refresh") in ("true", ""),
                pipeline=q.get("pipeline"),
                timeout_s=_timeout_param(q),
                **_cas_params(q),
            ))
            r(method, "/{index}/_create/{id}", self._create_doc)
        r("GET", "/{index}/_doc/{id}", lambda s, p, q, b: n.get_doc(
            p["index"], p["id"]
        ))
        r("DELETE", "/{index}/_doc/{id}", lambda s, p, q, b: n.delete_doc(
            p["index"], p["id"], refresh=q.get("refresh") in ("true", ""),
            timeout_s=_timeout_param(q),
            **_cas_params(q),
        ))
        r("POST", "/{index}/_update/{id}", lambda s, p, q, b: n.update_doc(
            p["index"], p["id"], _json(b),
            refresh=q.get("refresh") in ("true", ""),
            **_cas_params(q),
        ))
        r("PUT", "/{index}", lambda s, p, q, b: n.create_index(
            p["index"], _json(b)
        ))
        r("GET", "/{index}", lambda s, p, q, b: n.get_index_info(p["index"]))
        r("DELETE", "/{index}", lambda s, p, q, b: n.delete_index(p["index"]))

    def _create_doc(self, s, p, q, b):
        # put-if-absent enforced atomically inside the engine lock
        # (IndexRequest.opType CREATE semantics).
        return self.node.index_doc(
            p["index"], _json(b), p["id"],
            refresh=q.get("refresh") in ("true", ""),
            op_type="create",
            pipeline=q.get("pipeline"),
        )

    def _analyze(self, s, p, q, b):
        body = _json(b) or {}
        if p.get("index"):
            registry = self.node.get_index(p["index"]).mappings
        else:  # index-less /_analyze: builtin analyzers only
            from ..index.mapping import Mappings as _Mappings

            registry = _Mappings()
        analyzer_name = body.get("analyzer")
        if analyzer_name:
            analyzer = registry.analysis.get(analyzer_name)
        elif "field" in body and p.get("index"):
            analyzer = registry.analyzer_for(body["field"])
        else:
            analyzer = registry.analysis.get("standard")
        text = body.get("text", "")
        if isinstance(text, list):
            text = " ".join(text)
        tokens = analyzer.analyze(text)
        return {
            "tokens": [
                {"token": t, "position": i} for i, t in enumerate(tokens)
            ]
        }

    # ------------------------------------------------------------- dispatch

    def _record_latency(
        self, method: str, path: str, elapsed_s: float
    ) -> None:
        self.node.metrics.windowed_histogram(
            "estpu_rest_latency_recent_ms",
            "Per-endpoint-class REST latency over the trailing window, ms",
            endpoint=_endpoint_class(path, method),
        ).record(elapsed_s * 1e3)

    def _invoke(self, handler: Handler, params: dict, query: dict, body: str):
        """Run one route handler with topology-failover: a cluster error
        that escapes the gateway's own retries gets ONE more attempt after
        a control-plane round (failure detection → promotion), so a
        request that raced a node death is served by the promoted primary
        (or a surviving replica) instead of erroring."""
        try:
            return handler(self, params, query, body)
        except _TOPOLOGY_ERRORS:
            if self.cluster is None:
                raise
            try:
                self.cluster.step()
            # staticcheck: ignore[broad-except] best-effort control-plane round before the single failover retry; a step failure only forfeits the retry's improved odds
            except Exception:
                pass
            return handler(self, params, query, body)

    def dispatch(
        self,
        method: str,
        path: str,
        query: dict,
        body: str,
        headers: dict | None = None,
    ):
        """Returns (status, payload). ES-style error payloads on failure.
        Extra response headers (e.g. Retry-After on shed 429s) land in
        `self._tl.response_headers` for the HTTP layer to emit.

        Every dispatched request runs inside a ROOT trace span: an inbound
        `traceparent` header continues the caller's W3C trace, and
        `X-Opaque-Id` tags the root (the reference threads it to tasks and
        slowlogs the same way). The trace id returns as `X-Trace-Id` +
        `traceparent` response headers."""
        headers = headers or {}
        # QoS lane key for this request, whatever dispatch path follows.
        self._tl.tenant = (
            headers.get(self._qos_header)
            or headers.get(self._qos_header.lower())
        )
        if any(path == p or path.startswith(p + "/") for p in _UNTRACED_PATHS):
            # Untraced, but still timed: the rolling per-endpoint window
            # is a few counter words, not a trace-ring slot.
            t0 = time.monotonic()
            try:
                return self._dispatch_inner(method, path, query, body)
            finally:
                self._record_latency(method, path, time.monotonic() - t0)
        tags = {"method": method, "path": path}
        opaque = headers.get("X-Opaque-Id") or headers.get("x-opaque-id")
        if opaque:
            tags["opaque_id"] = opaque
        with TRACER.start_trace(
            "rest.request",
            traceparent=(
                headers.get("traceparent") or headers.get("Traceparent")
            ),
            **tags,
        ) as root:
            t0 = time.monotonic()
            try:
                status, payload = self._dispatch_inner(
                    method, path, query, body
                )
            finally:
                # Per-endpoint-class rolling latency window — the
                # health report's serving-latency input
                # (estpu_rest_latency_recent_ms{endpoint=...}).
                self._record_latency(method, path, time.monotonic() - t0)
            root.tags["status"] = status
            if status >= 500:
                root.status = "error"
            self._tl.response_headers = {
                **getattr(self._tl, "response_headers", {}),
                "X-Trace-Id": root.trace_id,
                "traceparent": format_traceparent(
                    root.trace_id, root.span_id
                ),
            }
            return status, payload

    def _dispatch_inner(
        self, method: str, path: str, query: dict, body: str
    ):
        self._tl.response_headers = {}
        try:
            # HEAD is served by the matching GET handler (the HTTP layer
            # suppresses the body), like the reference's RestController
            # HEAD-from-GET dispatch.
            lookup = "GET" if method == "HEAD" else method
            path_matched = False
            for m, regex, handler in self.routes:
                match = regex.match(path)
                if not match:
                    continue
                if m != lookup:
                    path_matched = True
                    continue
                result = self._invoke(handler, match.groupdict(), query, body)
                return 200, result
            if path_matched:
                raise ApiError(
                    405,
                    "method_not_allowed_exception",
                    f"Incorrect HTTP method for uri [{path}] and method "
                    f"[{method}]",
                )
            raise ApiError(
                400, "invalid_request", f"no handler found for uri [{path}]"
            )
        except ApiError as e:
            if e.headers:
                self._tl.response_headers = dict(e.headers)
            return e.status, {
                "error": {
                    "type": e.err_type,
                    "reason": e.reason,
                    "root_cause": [{"type": e.err_type, "reason": e.reason}],
                },
                "status": e.status,
            }
        except BreakerError as e:
            return 429, {
                "error": {
                    "type": "circuit_breaking_exception",
                    "reason": str(e),
                },
                "status": 429,
            }
        except _TOPOLOGY_ERRORS as e:
            # Retries exhausted: the honest status is 503 (retryable),
            # mirroring the reference's unavailable-shards responses.
            return 503, {
                "error": {
                    "type": "unavailable_shards_exception",
                    "reason": str(e),
                },
                "status": 503,
            }
        except (SearchPhaseFailedError, InjectedFaultError) as e:
            # Shard failures that escaped a handler further down (e.g. an
            # internal by-query scan refusing a partial match set): 503,
            # never a stack trace out of the socket.
            return 503, {
                "error": {
                    "type": "search_phase_execution_exception",
                    "reason": str(e),
                },
                "status": 503,
            }
        except json.JSONDecodeError as e:
            return 400, {
                "error": {"type": "parsing_exception", "reason": str(e)},
                "status": 400,
            }
        except ValueError as e:
            return 400, {
                "error": {"type": "illegal_argument_exception", "reason": str(e)},
                "status": 400,
            }

    def serve(self, host: str = "127.0.0.1", port: int = 9200):
        """Run a threading HTTP server (blocking). Returns the server."""
        rest = self

        class RequestHandler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _handle(self):
                parsed = urlparse(self.path)
                query = {
                    key: vals[0] for key, vals in parse_qs(
                        parsed.query, keep_blank_values=True
                    ).items()
                }
                length = int(self.headers.get("Content-Length") or 0)
                if length > rest.max_content_length:
                    # http.max_content_length: reject BEFORE buffering the
                    # payload (the reference closes oversized requests with
                    # 413 in the netty pipeline).
                    data = json.dumps({
                        "error": {
                            "type": "content_too_long_exception",
                            "reason": (
                                f"entity content is too long [{length}] "
                                f"for the configured buffer limit "
                                f"[{rest.max_content_length}]"
                            ),
                        },
                        "status": 413,
                    }).encode("utf-8")
                    self.send_response(413)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(data)))
                    self.send_header("X-elastic-product", "Elasticsearch")
                    self.end_headers()
                    self.wfile.write(data)
                    self.close_connection = True
                    return
                rest._tl.body_nbytes = length
                body = self.rfile.read(length).decode("utf-8") if length else ""
                status, payload = rest.dispatch(
                    self.command, parsed.path.rstrip("/") or "/", query, body,
                    headers=dict(self.headers.items()),
                )
                if isinstance(payload, PlainText):
                    data = payload.text.encode("utf-8")
                    content_type = payload.content_type
                else:
                    data = json.dumps(payload).encode("utf-8")
                    content_type = "application/json"
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.send_header("X-elastic-product", "Elasticsearch")
                for name, value in getattr(
                    rest._tl, "response_headers", {}
                ).items():
                    self.send_header(name, value)
                self.end_headers()
                if self.command != "HEAD":  # HEAD: headers only, no body
                    self.wfile.write(data)

            do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _handle

            def log_message(self, *args):  # quiet
                pass

        server = ThreadingHTTPServer((host, port), RequestHandler)
        return server


def create_server(
    host: str = "127.0.0.1",
    port: int = 9200,
    data_path: str | None = None,
    replication_nodes: int = 0,
    proc_nodes: int = 0,
    transport_key: str | None = None,
):
    """(http_server, rest) pair; call http_server.serve_forever() to run."""
    rest = RestServer(
        data_path=data_path,
        replication_nodes=replication_nodes,
        proc_nodes=proc_nodes,
        transport_key=transport_key,
    )
    return rest.serve(host, port), rest


def main():
    import argparse

    parser = argparse.ArgumentParser(description="elasticsearch-tpu node")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=9200)
    parser.add_argument(
        "--data-path",
        default=None,
        help="enable durability: per-index translog + segment persistence",
    )
    parser.add_argument(
        "--replication-nodes",
        type=int,
        default=0,
        help="serve through an in-process replication cluster of N nodes "
        "(acknowledged writes reach every in-sync copy; reads fail over)",
    )
    parser.add_argument(
        "--proc-nodes",
        type=int,
        default=0,
        help="serve through a SOCKETED multi-process cluster of N data "
        "node processes (this process is the HTTP front + voting-only "
        "tiebreaker; every hop crosses a real TCP connection)",
    )
    parser.add_argument(
        "--transport-key",
        default=None,
        help="shared-key HMAC handshake authn for node-to-node transport "
        "connections (defaults to ESTPU_TRANSPORT_KEY)",
    )
    args = parser.parse_args()
    from ..utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    server, rest = create_server(
        args.host, args.port, args.data_path,
        replication_nodes=args.replication_nodes,
        proc_nodes=args.proc_nodes,
        transport_key=args.transport_key,
    )
    print(
        json.dumps(
            {
                "message": "started",
                "host": args.host,
                "port": args.port,
                "node": rest.node.node_name,
            }
        ),
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.shutdown()


if __name__ == "__main__":
    main()
