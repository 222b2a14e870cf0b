"""Shard-local search service: query phase + fetch phase.

The analog of the reference's SearchService.executeQueryPhase →
QueryPhase.execute → FetchPhase.execute pipeline (server/src/main/java/org/
elasticsearch/search/SearchService.java:403, search/query/QueryPhase.java:122,
search/fetch/FetchPhase.java:70), restructured for the TPU:

- QUERY phase: each refreshed segment executes the compiled plan on device
  (ops/bm25_device.execute); per-segment top-k + total hits come back as
  small arrays. Segments share shard-level statistics (engine.field_stats)
  so scoring is independent of segmentation, like Lucene's reader-level
  term statistics.
- REDUCE: per-segment top-k merge by (score desc, global doc id asc) —
  the same ordering contract as the reference's coordinator mergeTopDocs
  (action/search/SearchPhaseController.java:186).
- FETCH phase: _source loading happens on host from the segment's stored
  documents, exactly mirroring the query-then-fetch split (scores on
  device, documents on host).

Sorting by a field lowers to a device top-k over the doc-values column with
missing-last semantics (search/sort/FieldSortBuilder in the reference).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ..faults import fault_point
from ..index.engine import Engine, SegmentHandle
from ..obs.metrics import timed_launch
from ..obs.tracing import TRACER
from ..ops import bm25_device
from ..query.compile import FieldStats
from ..query.dsl import MatchAllQuery, Query, parse_query


class SearchPhaseFailedError(Exception):
    """Shard failures that must fail the whole request (HTTP 503): every
    shard failed, or allow_partial_search_results=false and any did.
    Carries the per-shard `failures[]` entries."""

    def __init__(self, message: str, failures: list | None = None):
        super().__init__(message)
        self.failures = failures or []


@dataclass
class SearchHit:
    doc_id: str
    score: float | None
    source: dict[str, Any] | None
    sort: list[Any] | None = None
    global_doc: int = -1
    highlight: dict[str, list[str]] | None = None
    fields: dict[str, list[Any]] | None = None
    # Internal addressing for coordinator-side fetch subphases (not
    # serialized): the owning segment handle + local doc id.
    handle: Any = None
    local: int = -1

    def to_json(self, index_name: str = "index") -> dict[str, Any]:
        out: dict[str, Any] = {
            "_index": index_name,
            "_id": self.doc_id,
            "_score": self.score,
        }
        if self.source is not None:
            out["_source"] = self.source
        if self.fields is not None:
            out["fields"] = self.fields
        if self.highlight is not None:
            out["highlight"] = self.highlight
        if self.sort is not None:
            out["sort"] = self.sort
        return out


@dataclass
class SearchResponse:
    took_ms: int
    total: int | None  # None = untracked (track_total_hits: false)
    total_relation: str
    max_score: float | None
    hits: list[SearchHit]
    aggregations: dict[str, Any] | None = None
    shards: int = 1
    scroll_id: str | None = None
    timed_out: bool = False
    profile: dict[str, Any] | None = None
    skipped: int = 0  # can_match pre-filtered shards
    # Degraded-mode accounting: shards whose every attempt failed, served
    # partial under allow_partial_search_results, with one failures[]
    # entry per failed shard ({shard, index, node, reason}).
    failed: int = 0
    failures: list = field(default_factory=list)
    # took breakdown (plan/queue/execute/reduce ms), populated when
    # profile: true. Profiled searches execute unbatched, so queue_ms is
    # honestly 0 here; batch queue waits surface as p50/p99 percentiles
    # in `GET /_nodes/stats` under exec.batcher.
    breakdown: dict[str, Any] | None = None
    # The same per-phase timings, collected on EVERY search (never
    # serialized into the response): the slowlog reads them so slow-query
    # lines carry a breakdown without the profile flag.
    phases: dict[str, Any] | None = None

    def to_json(self, index_name: str = "index") -> dict[str, Any]:
        hits_obj: dict[str, Any] = {
            "max_score": self.max_score,
            "hits": [h.to_json(index_name) for h in self.hits],
        }
        if self.total is not None:
            hits_obj = {
                "total": {"value": self.total, "relation": self.total_relation},
                **hits_obj,
            }
        shards_obj: dict[str, Any] = {
            "total": self.shards,
            # Honest accounting: successful + skipped + failed == total on
            # every response shape (the chaos suite's core invariant).
            "successful": max(0, self.shards - self.skipped - self.failed),
            "skipped": self.skipped,
            "failed": self.failed,
        }
        if self.failures:
            shards_obj["failures"] = list(self.failures)
        out = {
            "took": self.took_ms,
            "timed_out": self.timed_out,
            "_shards": shards_obj,
            "hits": hits_obj,
        }
        if self.scroll_id is not None:
            out["_scroll_id"] = self.scroll_id
        if self.aggregations is not None:
            out["aggregations"] = self.aggregations
        if self.profile is not None:
            out["profile"] = self.profile
        if self.breakdown is not None:
            out["took_breakdown"] = self.breakdown
        return out


def parse_lenient_bool(value, name: str) -> bool:
    """true/false (bool or string, any case) — anything else raises: a
    misspelled boolean must never silently pick a default."""
    if isinstance(value, bool):
        return value
    if isinstance(value, str):
        low = value.strip().lower()
        if low in ("true", ""):
            return True
        if low == "false":
            return False
    raise ValueError(
        f"Failed to parse value [{value!r}] for [{name}]: only [true] "
        f"or [false] are allowed"
    )


def clamp_total(total: int, track_total_hits) -> tuple[int | None, str]:
    """(reported total, relation) under the track_total_hits contract."""
    if track_total_hits is False:
        return None, "eq"
    if track_total_hits is True:
        return total, "eq"
    threshold = int(track_total_hits)
    if total > threshold:
        return threshold, "gte"
    return total, "eq"


@dataclass
class Rescore:
    """One rescore stage: re-rank the top-`window_size` docs per shard.

    Mirrors the reference's QueryRescorer (search/rescore/): combined score
    per `score_mode`, with query_weight/rescore_query_weight factors; docs
    in the window that don't match the rescore query keep
    query_weight * original.
    """

    query: Query
    window_size: int = 10
    query_weight: float = 1.0
    rescore_query_weight: float = 1.0
    score_mode: str = "total"  # total | multiply | avg | max | min

    def combine(self, orig: np.ndarray, resc: np.ndarray, matched: np.ndarray):
        qw = np.float32(self.query_weight)
        rw = np.float32(self.rescore_query_weight)
        a, b = qw * orig, rw * resc
        if self.score_mode == "total":
            combined = a + b
        elif self.score_mode == "multiply":
            combined = a * b
        elif self.score_mode == "avg":
            combined = (a + b) / np.float32(2.0)
        elif self.score_mode == "max":
            combined = np.maximum(a, b)
        elif self.score_mode == "min":
            combined = np.minimum(a, b)
        else:
            raise ValueError(f"unknown rescore score_mode [{self.score_mode}]")
        return np.where(matched, combined, a).astype(np.float32)


@dataclass
class KnnSpec:
    """The top-level `knn` search section (the reference's ES 8.0 `knn`
    option / `_knn_search` endpoint, SearchSourceBuilder.knnSearch).

    Approximate BY CONTRACT: the engine may serve it from the IVF
    partition planes (index/ann.py — only the `nprobe` probed partitions'
    vectors are examined), so the hit SET may miss true neighbors the
    probe never reached. Scoring is never approximate: every returned
    candidate's score is bit-exact fp32 against the exact brute-force
    scorer (the parity law ops/ann_device.py documents). Exact kNN stays
    available through `script_score` — that path is byte-identical to its
    pre-ANN behavior and keeps the routing-never-changes-top-k invariant.
    """

    field: str
    query_vector: np.ndarray  # f32[d]
    k: int = 10
    num_candidates: int = 100
    # IVF probe width (ours — the reference exposes num_candidates only).
    # None = the index-side default (index/ann.default_nprobe), raised if
    # needed so probed slots cover num_candidates.
    nprobe: int | None = None
    filter: Query | None = None

    KNOWN_KEYS = frozenset(
        {"field", "query_vector", "k", "num_candidates", "nprobe", "filter"}
    )

    @classmethod
    def from_json(cls, body) -> "KnnSpec":
        if not isinstance(body, dict):
            raise ValueError("[knn] must be an object")
        unknown = set(body) - cls.KNOWN_KEYS
        if unknown:
            raise ValueError(
                f"unknown key [{sorted(unknown)[0]}] in the [knn] section"
            )
        if "field" not in body:
            raise ValueError("[knn] requires a [field]")
        if "query_vector" not in body:
            raise ValueError("[knn] requires a [query_vector]")
        raw = body["query_vector"]
        if not isinstance(raw, list) or not raw or not all(
            isinstance(v, (int, float)) and not isinstance(v, bool)
            for v in raw
        ):
            raise ValueError(
                "[knn] [query_vector] must be a non-empty array of numbers"
            )
        k = int(body.get("k", 10))
        if k < 1:
            raise ValueError(f"[knn] [k] must be greater than 0, got [{k}]")
        num_candidates = int(body.get("num_candidates", max(100, k)))
        if num_candidates < k:
            raise ValueError(
                f"[knn] [num_candidates] cannot be less than [k] "
                f"([{num_candidates}] < [{k}])"
            )
        if num_candidates > 10_000:
            raise ValueError(
                "[knn] [num_candidates] cannot exceed [10000]"
            )
        nprobe = body.get("nprobe")
        if nprobe is not None:
            nprobe = int(nprobe)
            if nprobe < 1:
                raise ValueError(
                    f"[knn] [nprobe] must be greater than 0, got [{nprobe}]"
                )
        filter_q = None
        if body.get("filter") is not None:
            filter_q = parse_query(body["filter"])
        return cls(
            field=str(body["field"]),
            query_vector=np.asarray(raw, dtype=np.float32),
            k=k,
            num_candidates=num_candidates,
            nprobe=nprobe,
            filter=filter_q,
        )


@dataclass
class SearchRequest:
    query: Query = field(default_factory=MatchAllQuery)
    size: int = 10
    from_: int = 0
    source_includes: bool | list[str] = True
    sort: list[dict[str, str]] | None = None  # [{"field": "asc"|"desc"}]
    # Per-sort-key missing-value placement ("_first" | "_last"), aligned
    # with `sort` (FieldSortBuilder's missing parameter; default _last).
    sort_missing: list[str] | None = None
    rescore: list[Rescore] = field(default_factory=list)
    aggs: list[Any] | None = None  # list[aggs.AggNode]
    # Pagination cursor (search_after / scroll): the sort-key value of the
    # last consumed hit, plus an optional doc-id tiebreak (engine-global
    # doc id; -1 = key-only cursor, the public search_after form).
    search_after: list[Any] | None = None
    after_doc: int = -1
    # hits.total accounting: True = exact, False = untracked (omitted),
    # int = exact up to the threshold then ("gte", threshold). ES default
    # is 10_000 (search/internal/SearchContext TRACK_TOTAL_HITS_UP_TO).
    track_total_hits: bool | int = 10_000
    # Wall-clock budget in seconds (body "timeout"); polled at segment
    # boundaries — partial results with timed_out: true past it.
    timeout_s: float | None = None
    highlight: Any = None  # highlight.HighlightSpec
    docvalue_fields: list[str] | None = None
    fields: list[str] | None = None  # retrieved from _source
    profile: bool = False  # per-segment timing in the response
    # Degraded-mode contract (the reference's allow_partial_search_results,
    # default true): failed shards degrade to a partial 200 with honest
    # `_shards.failed`/`failures[]`; false turns ANY shard failure into a
    # 503. Overridable per request via body key or URL param.
    allow_partial_search_results: bool = True
    # Top-level `knn` section (approximate vector search; see KnnSpec).
    knn: KnnSpec | None = None

    # The search-body keys this node understands; anything else is a
    # parsing error, like the reference's strict SearchSourceBuilder
    # x-content parsing (unknown keys 400, never silently ignore).
    KNOWN_KEYS = frozenset(
        {
            "query", "aggs", "aggregations", "rescore", "sort", "from",
            "size", "search_after", "track_total_hits", "highlight",
            "docvalue_fields", "fields", "_source", "stored_fields",
            "timeout", "profile", "suggest", "min_score", "version",
            "seq_no_primary_term", "explain", "pit", "track_scores",
            "terminate_after", "indices_boost", "script_fields",
            "rest_total_hits_as_int", "scroll_id", "scroll",
            "allow_partial_search_results", "knn",
        }
    )

    # Search-body keys the `knn` section cannot ride with (each would
    # need a score-combination or re-sort contract the ANN path doesn't
    # define yet; the reference's 8.0 `_knn_search` was similarly pure).
    KNN_EXCLUSIVE = (
        "query", "aggs", "aggregations", "sort", "rescore",
        "search_after", "suggest", "min_score",
    )

    @classmethod
    def from_json(cls, body: dict[str, Any] | None) -> "SearchRequest":
        body = body or {}
        unknown = set(body) - cls.KNOWN_KEYS
        if unknown:
            raise ValueError(
                f"unknown key [{sorted(unknown)[0]}] in the search request"
            )
        knn = None
        if body.get("knn") is not None:
            for key in cls.KNN_EXCLUSIVE:
                if body.get(key) is not None:
                    raise ValueError(
                        f"[knn] cannot be combined with [{key}] yet; the "
                        f"knn section serves pure vector queries "
                        f"(script_score remains the exact hybrid path)"
                    )
            knn = KnnSpec.from_json(body["knn"])
        query = (
            parse_query(body["query"]) if "query" in body else MatchAllQuery()
        )
        aggs = None
        raw_aggs = body.get("aggs") or body.get("aggregations")
        if raw_aggs:
            from .aggs import parse_aggs

            aggs = parse_aggs(raw_aggs)
        rescore = []
        raw_rescore = body.get("rescore", [])
        if isinstance(raw_rescore, dict):
            raw_rescore = [raw_rescore]
        for entry in raw_rescore:
            rq = entry.get("query", {})
            rescore.append(
                Rescore(
                    query=parse_query(rq["rescore_query"]),
                    window_size=int(entry.get("window_size", 10)),
                    query_weight=float(rq.get("query_weight", 1.0)),
                    rescore_query_weight=float(
                        rq.get("rescore_query_weight", 1.0)
                    ),
                    score_mode=str(rq.get("score_mode", "total")),
                )
            )
        sort = None
        sort_missing = None
        if "sort" in body:
            sort = []
            sort_missing = []
            raw = body["sort"]
            if not isinstance(raw, list):
                raw = [raw]
            for entry in raw:
                missing = "_last"
                if isinstance(entry, str):
                    fname = entry
                    order = "asc" if entry != "_score" else "desc"
                else:
                    ((fname, spec),) = entry.items()
                    if isinstance(spec, dict):
                        order = spec.get("order", "asc")
                        missing = str(spec.get("missing", "_last"))
                    else:
                        order = str(spec)
                if missing not in ("_first", "_last"):
                    raise ValueError(
                        f"sort [missing] must be [_first] or [_last], got "
                        f"[{missing}] (custom missing values are not "
                        f"supported yet)"
                    )
                sort.append({fname: order})
                sort_missing.append(missing)
        if rescore and sort is not None:
            # Reference behavior (SearchService.parseSource): rescore
            # re-ranks the score-ordered top window; combined with an
            # explicit sort — including [{"_score": "asc"}] — it has no
            # defined semantics. This used to be silently IGNORED on the
            # ascending-score host path; a clear 400 is the contract.
            raise ValueError(
                "Cannot use [sort] option in conjunction with [rescore]"
            )
        source = body.get("_source", True)
        if isinstance(source, str):  # ES accepts a single field name/pattern
            source = [source]
        search_after = body.get("search_after")
        if search_after is not None:
            if not isinstance(search_after, list) or len(search_after) != 1:
                raise ValueError(
                    "search_after must be a one-element array matching the "
                    "primary sort key (multi-key cursors are not supported "
                    "yet)"
                )
            if sort is None:
                raise ValueError(
                    "search_after requires a sort to be specified"
                )
            if rescore:
                raise ValueError("cannot use [rescore] with [search_after]")
            if int(body.get("from", 0)) > 0:
                raise ValueError(
                    "[from] parameter must be set to 0 when [search_after] "
                    "is used"
                )
            ((sa_field, _),) = sort[0].items()
            if sa_field == "_score" and not isinstance(
                search_after[0], (int, float)
            ):
                raise ValueError(
                    "search_after value for a [_score] sort must be a number"
                )
        tth = body.get("track_total_hits", 10_000)
        if not isinstance(tth, bool):
            tth = int(tth)
        timeout_s = None
        if "timeout" in body:
            timeout_s = _parse_timeout(body["timeout"])
        highlight = None
        if "highlight" in body:
            from .highlight import parse_highlight

            highlight = parse_highlight(body["highlight"])
        docvalue_fields = None
        if "docvalue_fields" in body:
            docvalue_fields = [
                f if isinstance(f, str) else f["field"]
                for f in body["docvalue_fields"]
            ]
        fields = None
        if "fields" in body:
            fields = [
                f if isinstance(f, str) else f["field"]
                for f in body["fields"]
            ]
        allow_partial = parse_lenient_bool(
            body.get("allow_partial_search_results", True),
            "allow_partial_search_results",
        )
        return cls(
            query=query,
            size=int(body.get("size", 10)),
            from_=int(body.get("from", 0)),
            source_includes=source,
            sort=sort,
            sort_missing=sort_missing,
            rescore=rescore,
            aggs=aggs,
            search_after=search_after,
            track_total_hits=tth,
            timeout_s=timeout_s,
            highlight=highlight,
            docvalue_fields=docvalue_fields,
            fields=fields,
            profile=bool(body.get("profile", False)),
            allow_partial_search_results=bool(allow_partial),
            knn=knn,
        )


_NO_SORT = object()  # sentinel: hit carries no sort values (default score sort)

F32_MAX = float(np.finfo(np.float32).max)


def normalized_sort(request: "SearchRequest") -> list[tuple[str, bool, bool]]:
    """The request's sort as [(field, descending, missing_first)], with a
    trailing "_doc" key dropped: the merge contract is ALWAYS doc-id
    tiebroken, so an explicit trailing _doc only makes the implicit
    tiebreak visible (it contributes no sort value). "_score" keys pass
    through as the pseudo-field "_score"."""
    if request.sort is None:
        return []
    missing = request.sort_missing or ["_last"] * len(request.sort)
    out: list[tuple[str, bool, bool]] = []
    for i, entry in enumerate(request.sort):
        ((fname, order),) = entry.items()
        if fname == "_doc" and i == len(request.sort) - 1 and i > 0:
            continue
        out.append((fname, str(order) == "desc", missing[i] == "_first"))
    return out


def sort_merge_key(request: "SearchRequest", score, sort_values):
    """Cross-shard merge key for one hit under the request's sort: a
    scalar for single-key sorts (back-compat with scroll cursors), a
    tuple for multi-key. Ascending key space; missing values map to
    +/-inf per the key's missing directive — the single definition the
    host-loop coordinator AND the replicated cluster coordinator merge
    with (FieldSortBuilder missing-value semantics)."""
    if request.sort is None:
        return -score if score is not None else np.inf
    keys = normalized_sort(request)
    if keys and keys[0][0] == "_score":
        s = score if score is not None else 0.0
        return s if not keys[0][1] else -s
    vals = sort_values or []
    out = []
    for i, (_f, desc, mfirst) in enumerate(keys):
        v = vals[i] if i < len(vals) else None
        if v is None:
            out.append(-np.inf if mfirst else np.inf)
        else:
            out.append(-v if desc else v)
    if not out:
        return np.inf
    return tuple(out) if len(out) > 1 else out[0]


def sparse_family_key(spec) -> tuple | None:
    """Coalescing family of a compiled sparse spec: same kind/field/
    trailing shape, differing only in the nt bucket (spec[2]). Groups in
    one family re-bucket to a common nt and share ONE padded launch
    (_merge_term_groups); None for non-coalescible specs. bench.py uses
    the same key so its padding_waste_pct mirrors what serving would pad.
    """
    if (
        isinstance(spec, tuple)
        and spec
        and spec[0] in ("terms", "terms_gather")
        and len(spec) == 4
    ):
        return (spec[0], spec[1], spec[3])
    return None


def family_padding_tiles(spec_rows) -> tuple[int, int]:
    """(actual, padded) worklist tiles if the same-family groups in
    `spec_rows` ([(spec, n_rows), ...]) coalesce to one nt_max launch."""
    nt_max = max(s[2] for s, _ in spec_rows)
    n_rows = sum(r for _, r in spec_rows)
    actual = sum(s[2] * r for s, r in spec_rows)
    return actual, nt_max * n_rows

def _iso_millis(ms: float) -> str:
    """Epoch millis → the reference's strict_date_optional_time rendering."""
    from datetime import datetime, timezone

    dt = datetime.fromtimestamp(ms / 1000.0, tz=timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{dt.microsecond // 1000:03d}Z"


def _parse_timeout(value) -> float | None:
    """ES search timeout → seconds; None disables (the -1 sentinel)."""
    from ..common.units import parse_duration_s

    if isinstance(value, bool):
        raise ValueError(f"failed to parse timeout value [{value}]")
    if isinstance(value, (int, float)):
        # Bare numbers are milliseconds; negative = no timeout (ES -1).
        return None if value < 0 else float(value) / 1000.0
    return parse_duration_s(value)


class SearchService:
    """Executes SearchRequests against one Engine (one shard)."""

    def __init__(
        self,
        engine: Engine,
        index_name: str = "index",
        planner=None,
        device=None,
        filter_cache=None,
        ann_cache=None,
    ):
        self.engine = engine
        self.index_name = index_name
        # exec.ExecPlanner: cost-based backend routing for the query
        # phase. None (the default) preserves the pure device path.
        self.planner = planner
        # obs.DeviceInstruments: launch-site metrics (compile count/ms,
        # H2D bytes, padding waste). None = uninstrumented.
        self.device = device
        # index.filter_cache.FilterCache: device-resident mask planes for
        # repeated filter-context subtrees. None (default, and the
        # ESTPU_FILTER_CACHE=0 opt-out) recomputes every filter.
        self.filter_cache = filter_cache
        # index.ann.AnnCache: IVF partition planes for the `knn` section.
        # None (the ESTPU_ANN=0 opt-out) serves every knn exactly via the
        # brute-force kernel.
        self.ann_cache = ann_cache

    # --------------------------------------------------------- filter cache

    def _collect_filter_entries(self, query, record: bool) -> list:
        """The request's cacheable-filter entries, with one admission
        sighting recorded when `record` (the frequency signal is counted
        once per USER request — never per segment, and never per shard
        when a coordinator drives this service with record=False).
        Collected ONCE here and threaded through every per-segment apply
        so the query AST is not re-walked on the hot path."""
        from ..index.filter_cache import record_filter_usage

        return record_filter_usage(self.filter_cache, query, record=record)

    def _apply_filter_cache(
        self, handle, query, compiled, seg_tree, entries=None
    ):
        """Substitute cached mask planes into one segment's compiled plan.
        Returns (compiled', masks) — masks empty when nothing applied."""
        if self.filter_cache is None:
            return compiled, {}
        from ..index.filter_cache import apply_cached_masks

        def build(child_spec, child_arrays, _norm):
            plane = bm25_device.compute_filter_mask(
                seg_tree, child_spec, child_arrays
            )
            return plane, int(plane.nbytes)

        # Keyed per segment handle, NOT per engine generation: postings
        # are immutable and planes exclude the live mask, so a plane
        # stays servable across refreshes that only add/merge OTHER
        # segments — the whole point of a filter cache under live write
        # traffic. live_uids lets the store prune planes of merged-away
        # segments eagerly.
        prefix = (self.engine.uid, 0, handle.uid)
        compiled, masks, reused = apply_cached_masks(
            self.filter_cache, prefix, query, compiled, build,
            entries=entries,
            live_uids=frozenset(h.uid for h in self.engine.segments),
        )
        if reused:
            # The span-level signal the tracing satellite asks for: this
            # segment pass served at least one filter from a cached plane.
            TRACER.tag(filter_cache_hit=True)
        return compiled, masks

    def search(
        self,
        request: SearchRequest,
        stats: dict[str, FieldStats] | None = None,
        segments: list | None = None,
        task=None,  # common.tasks.Task: cancellation + timeout polling
        record_filter_usage: bool = True,
        fc_entries: list | None = None,
    ) -> SearchResponse:
        """Execute one request against this shard.

        `stats` overrides the statistics scope: the sharded coordinator
        passes index-global statistics (the reference's DFS phase /
        AggregatedDfs, search/dfs/DfsPhase.java:31) so scores are routing-
        independent; default is shard-local, ES query_then_fetch parity.
        `segments` pins an explicit segment snapshot (the coordinator
        shares one snapshot between its agg pass and every shard's hits
        pass). `record_filter_usage=False` suppresses the filter-cache
        admission sighting: the sharded coordinator records ONCE per user
        request and passes False to its per-shard calls — otherwise an
        n-shard scatter would count n sightings and one-off filters would
        self-admit past min_freq on their very first request. `fc_entries`
        passes the coordinator's already-collected cacheable-filter
        entries so the scatter doesn't re-walk the query AST per shard.
        """
        start = time.monotonic()
        k = max(0, request.from_) + max(0, request.size)
        if stats is None:
            stats = self.engine.field_stats()
        self._validate_sort(request)
        self._validate_knn(request)
        if fc_entries is None:
            fc_entries = self._collect_filter_entries(
                request.query, record_filter_usage
            )
        if request.knn is not None:
            # One admission sighting for the knn filter per USER request —
            # the same counting contract as bool filter clauses (the
            # coordinator records once and passes record_filter_usage=
            # False to its per-shard scatter).
            from ..index.filter_cache import record_knn_filter_usage

            record_knn_filter_usage(
                self.filter_cache, request.knn, record=record_filter_usage
            )

        # One segment snapshot shared by the agg pass and the hits pass —
        # a concurrent refresh must not desynchronize totals from hits
        # (the reference pins one IndexReader per request the same way).
        if segments is None:
            segments = list(self.engine.segments)

        aggregations = None
        agg_total = None
        if request.aggs is not None:
            from .aggs import Aggregator

            agg_total, aggregations = Aggregator(
                self.engine,
                request.aggs,
                handles=segments,
                index_name=self.index_name,
            ).run(request.query, stats=stats, task=task)

        # Candidate tuples: (merge_key, global_doc, handle, local, score,
        # sort_value). merge_key ascending + global doc id ascending gives
        # Lucene's ordering for both score sort (key = -score) and field sort.
        candidates: list[tuple] = []
        total = 0
        timed_out = task is not None and task.timed_out  # agg pass may trip
        profile_segments: list[dict] = []
        # Per-backend segment tally on EVERY search (bounded: backend
        # names) — the insights ring's "which backend served this slow
        # query" attribution, riding the phases hook like the slowlog.
        backend_tally: dict[str, int] = {}
        timings = {"plan_s": 0.0, "exec_s": 0.0}
        if k > 0 or agg_total is None:
            for seg_i, handle in enumerate(segments):
                if handle.segment.num_docs == 0:
                    continue
                if task is not None:
                    # Kernel-launch-boundary polling: the analog of the
                    # reference's per-segment cancellation check
                    # (ContextIndexSearcher.java:91) — an XLA program is
                    # not interruptible, so granularity is one segment.
                    task.raise_if_cancelled()
                    if task.check_deadline():
                        timed_out = True
                        break
                seg_t0 = time.monotonic_ns() if request.profile else 0
                # Per-segment device block (profile: true): launch ms,
                # compile hit/miss, H2D bytes this request staged.
                seg_device: dict | None = (
                    {} if request.profile and self.device is not None
                    else None
                )
                # One leaf span per segment launch — the kernel-launch
                # granularity the whole trace tree bottoms out at.
                with TRACER.span(
                    "search.segment",
                    task=task,
                    segment=seg_i,
                    index=self.index_name,
                    docs=handle.segment.num_docs,
                ) as seg_span:
                    seg_total, backend = self._query_segment(
                        handle, request, k, stats, candidates,
                        timings=timings, fc_entries=fc_entries,
                        device_info=seg_device,
                    )
                    if seg_span is not None:
                        seg_span.tags["backend"] = backend
                backend_tally[backend] = backend_tally.get(backend, 0) + 1
                total += seg_total
                if request.profile:
                    entry = {
                        "segment": seg_i,
                        "docs": handle.segment.num_docs,
                        "time_in_nanos": time.monotonic_ns() - seg_t0,
                        # The planner-chosen execution backend for this
                        # segment's scoring pass.
                        "backend": backend,
                    }
                    if seg_device:
                        entry["device"] = seg_device
                    profile_segments.append(entry)
        if agg_total is not None:
            # The agg program already counted matched ∧ live docs; trust one
            # source for totals (they are the same mask by construction).
            total = agg_total

        reduce_t0 = time.monotonic()
        with TRACER.span("search.reduce", task=task, candidates=len(candidates)):
            candidates.sort(key=lambda c: (c[0], c[1]))
            if request.knn is not None:
                # The knn contract returns the GLOBAL top k: segments
                # each contribute up to k candidates, the merge keeps k
                # (the reference's kNN coordinator reduce), and from/size
                # page within those.
                candidates = candidates[: request.knn.k]
            page = candidates[request.from_ : request.from_ + request.size]

            hits = []
            max_score = None
            if request.sort is None and candidates:
                max_score = -candidates[0][0]
            with TRACER.span("search.assemble"):
                hl_ctx = self._highlight_context(request)
                for row in page:
                    _key, global_doc, handle, local, score, sort_value = row
                    hits.append(
                        SearchHit(
                            doc_id=handle.segment.ids[local],
                            score=score,
                            source=self._fetch_source(handle, local, request),
                            sort=(
                                None
                                if sort_value is _NO_SORT
                                else sort_value
                                if isinstance(sort_value, list)
                                else [sort_value]
                            ),
                            global_doc=global_doc,
                            highlight=self._fetch_highlight(
                                handle, local, hl_ctx
                            ),
                            fields=self._fetch_fields(handle, local, request),
                            handle=handle,
                            local=local,
                        )
                    )
        took = int((time.monotonic() - start) * 1000)
        total_out, relation = clamp_total(total, request.track_total_hits)
        profile = None
        breakdown = None
        # Per-phase timings on EVERY search (the slowlog's breakdown);
        # only profile: true serializes them into the response.
        phases = {
            "plan_ms": round(timings["plan_s"] * 1e3, 3),
            "queue_ms": 0.0,
            "execute_ms": round(timings["exec_s"] * 1e3, 3),
            "reduce_ms": round((time.monotonic() - reduce_t0) * 1e3, 3),
        }
        if backend_tally:
            phases["backends"] = backend_tally
        if request.profile:
            backends: dict[str, int] = {}
            for s in profile_segments:
                backends[s["backend"]] = backends.get(s["backend"], 0) + 1
            # Per-segment kernel-launch timing — the honest TPU shape of
            # the reference's profile API (search/profile/): inside one
            # XLA program there are no per-operator boundaries to time.
            profile = {
                "shards": [
                    {
                        "id": f"[{self.index_name}][0]",
                        # Planner routing per shard: which execution
                        # backend(s) served this shard's scoring pass.
                        "backends": backends,
                        "searches": [
                            {
                                "query": [
                                    {
                                        "type": type(request.query).__name__,
                                        "description": repr(request.query),
                                        "time_in_nanos": sum(
                                            s["time_in_nanos"]
                                            for s in profile_segments
                                        ),
                                        "breakdown": {
                                            "segments": profile_segments
                                        },
                                    }
                                ]
                            }
                        ],
                    }
                ]
            }
            # Profiled searches run unbatched (never queued), so queue_ms
            # is honestly 0; batch queue waits are in _nodes/stats
            # exec.batcher p50/p99. The backends tally stays internal
            # (the profile's own `backends` block already reports it).
            breakdown = {
                k: v for k, v in phases.items() if k != "backends"
            }
        return SearchResponse(
            took_ms=took,
            total=total_out,
            total_relation=relation,
            max_score=max_score,
            hits=hits,
            aggregations=aggregations,
            timed_out=timed_out,
            profile=profile,
            breakdown=breakdown,
            phases=phases,
        )

    # ------------------------------------------------- batched query phase

    def search_many(self, requests: list, tasks: list | None = None) -> list:
        """Serve several PLAIN searches with coalesced device launches.

        The exec micro-batcher's group executor: one padded launch per
        (segment, spec group) scores every request's lane at once instead
        of one launch per request. Every request must be a plain
        score-sorted query (no sort/aggs/rescore/search_after/suggest —
        the batcher's eligibility gate guarantees it). Returns one
        SearchResponse (or Exception) per request, result-identical to
        running each request through search() alone.
        """
        start = time.monotonic()
        if tasks is None:
            tasks = [None] * len(requests)
        if any(r.knn is not None for r in requests):
            # Coalesced kNN group (the batcher's ("_knn", ...) group key):
            # query vectors stack into one batched ANN/exact launch.
            return self._knn_search_many(requests, tasks)
        stats = self.engine.field_stats()
        segments = list(self.engine.segments)
        ks = [max(0, r.from_) + max(0, r.size) for r in requests]
        cands, totals, timed, errors = self._batched_query_phase(
            requests, ks, stats, segments, tasks
        )
        out: list = []
        for i, request in enumerate(requests):
            if errors[i] is not None:
                out.append(errors[i])
                continue
            out.append(
                self.assemble_plain(
                    request, cands[i], totals[i], timed[i], start
                )
            )
        return out

    def assemble_plain(
        self,
        request: SearchRequest,
        rows: list,
        total: int,
        timed_out: bool,
        start: float,
    ) -> SearchResponse:
        """Assemble one plain score-sorted SearchResponse from candidate
        tuples (the shared fetch/pagination step behind the coalesced
        batch path AND the packed multi-tenant executor, exec/packed.py —
        both score elsewhere and fetch here, so hits/highlights/fields
        render identically to a solo search)."""
        with TRACER.span("search.assemble"):
            rows = sorted(rows, key=lambda c: (c[0], c[1]))
            page = rows[request.from_ : request.from_ + request.size]
            max_score = -rows[0][0] if rows else None
            hl_ctx = self._highlight_context(request)
            hits = []
            for _key, global_doc, handle, local, score, _sv in page:
                hits.append(
                    SearchHit(
                        doc_id=handle.segment.ids[local],
                        score=score,
                        source=self._fetch_source(handle, local, request),
                        sort=None,
                        global_doc=global_doc,
                        highlight=self._fetch_highlight(
                            handle, local, hl_ctx
                        ),
                        fields=self._fetch_fields(handle, local, request),
                        handle=handle,
                        local=local,
                    )
                )
        total_out, relation = clamp_total(total, request.track_total_hits)
        return SearchResponse(
            took_ms=int((time.monotonic() - start) * 1000),
            total=total_out,
            total_relation=relation,
            max_score=max_score,
            hits=hits,
            timed_out=timed_out,
        )

    def _batched_query_phase(
        self,
        requests: list,
        ks: list[int],
        stats: dict[str, FieldStats],
        segments: list,
        tasks: list,
        record_filter_usage: bool = True,
        fc_entries: list | None = None,
    ):
        """One coalesced scoring pass over this shard for N plain requests.

        Per segment, requests compile and group by spec (sparse term
        groups are re-bucketed to a common nt via nt_floor so they share
        ONE padded launch); each group executes as a single batched
        kernel call — or through the CPU oracle when the planner's cost
        model says the host wins for this plan class. Returns
        (candidates per request, totals, timed_out flags, errors).
        """
        n = len(requests)
        cands: list[list] = [[] for _ in range(n)]
        totals = [0] * n
        timed = [False] * n
        errors: list[Exception | None] = [None] * n
        alive = set(range(n))
        # One admission sighting per rider, collected once for the whole
        # batch (the sharded coordinator records per user request itself,
        # passes record_filter_usage=False, and hands its precollected
        # per-rider entries in); the entries thread into every per-segment
        # apply so the ASTs aren't re-walked.
        if fc_entries is None:
            fc_entries = [
                self._collect_filter_entries(r.query, record_filter_usage)
                for r in requests
            ]
        for handle in segments:
            if handle.segment.num_docs == 0 or not alive:
                continue
            for i in sorted(alive):
                task = tasks[i]
                if task is None:
                    continue
                if task.cancelled:
                    from ..common.tasks import TaskCancelledError

                    reason = task.cancel_reason or "cancelled"
                    errors[i] = TaskCancelledError(
                        f"task cancelled [{reason}]"
                    )
                    alive.discard(i)
                elif task.check_deadline():
                    timed[i] = True
                    alive.discard(i)
            if not alive:
                break
            with TRACER.span("device.stage"):
                seg_tree = bm25_device.segment_tree(handle.device)
            compiled: dict[int, Any] = {}
            req_masks: dict[int, dict] = {}
            for i in sorted(alive):
                with TRACER.span("query.compile"):
                    try:
                        compiled[i] = self._lower(handle, stats, requests[i])
                    except ValueError as e:
                        errors[i] = e
                        alive.discard(i)
                        continue
                    # Coalesced batchmates sharing a filter share ONE
                    # plane: substitution happens before grouping, so
                    # identical (spec, plane set) lanes land in the same
                    # launch with the plane passed ONCE via seg["masks"]
                    # — never stacked per lane.
                    compiled[i], req_masks[i] = self._apply_filter_cache(
                        handle, requests[i].query, compiled[i], seg_tree,
                        entries=fc_entries[i],
                    )
            from ..index.filter_cache import mask_group_token

            groups: dict[tuple, list[int]] = {}
            for i, c in compiled.items():
                if i in alive:
                    token = mask_group_token(req_masks.get(i, {}))
                    groups.setdefault((c.spec, token), []).append(i)
            groups = self._merge_term_groups(
                handle, stats, groups, compiled, requests
            )
            for (spec, _token), rows in groups.items():
                try:
                    fault_point("search.kernel", index=self.index_name)
                    self._execute_group(
                        handle, spec, rows, compiled, requests, ks, stats,
                        cands, totals, seg_tree=seg_tree,
                        masks=req_masks.get(rows[0], {}),
                    )
                except (ValueError, TypeError):
                    raise  # request-shaped: the compile path 400s
                # staticcheck: ignore[broad-except] launch-failure isolation: only this group's riders fail (then retry individually); a re-raise here would fail batchmates on one rider's error
                except Exception as e:
                    # Launch failure isolation: only the riders of THIS
                    # group fail (and get retried individually by the
                    # micro-batcher); batchmates in other groups and
                    # segments are untouched.
                    for i in rows:
                        errors[i] = e
                        alive.discard(i)
        return cands, totals, timed, errors

    def _merge_term_groups(self, handle, stats, groups, compiled, requests):
        """Coalesce same-family sparse term groups that differ only in
        their nt bucket — ADAPTIVELY. The old policy padded the whole
        family to its max bucket unconditionally, so one fat-worklist
        query taxed every batchmate (the BENCH_r05 cfg3 batched-worse-
        than-sequential inversion). Now exec/batcher.plan_spec_buckets
        splits the family into pow-2 sub-buckets: a smaller group joins a
        larger bucket only when the padding it would pay costs less than
        the launch it saves; everything else keeps its own bucket and
        launch. Joined groups PAD their compiled arrays to the bucket
        spec (bit-identical results, no recompile); the device padding
        instrument records exactly the waste each accepted merge pays."""
        from ..exec.batcher import plan_spec_buckets
        from ..query.compile import CompiledQuery, pad_arrays_to_spec, unify_specs

        # Group keys are (spec, mask token); sparse term families never
        # carry masks (mask substitution only rewrites bool filter
        # clauses), so family merging operates on the empty-token keys.
        families: dict[tuple, list[tuple]] = {}
        for spec, token in list(groups):
            fam = sparse_family_key(spec)
            if fam is not None and token == ():
                families.setdefault(fam, []).append(spec)
        for specs in families.values():
            if len(specs) < 2:
                continue
            for bucket in plan_spec_buckets(
                [(s, len(groups[(s, ())])) for s in specs]
            ):
                if len(bucket) < 2:
                    continue
                target = unify_specs(list(bucket))
                if self.device is not None:
                    # Padding waste of this coalesced bucket: every lane
                    # launches at the bucket's nt regardless of need.
                    self.device.padding(
                        *family_padding_tiles(
                            [(s, len(groups[(s, ())])) for s in bucket]
                        )
                    )
                merged_rows: list[int] = []
                for s in bucket:
                    rows = groups.pop((s, ()))
                    for i in rows:
                        compiled[i] = CompiledQuery(
                            spec=target,
                            arrays=pad_arrays_to_spec(
                                compiled[i].spec, target, compiled[i].arrays
                            ),
                        )
                    merged_rows.extend(rows)
                groups.setdefault((target, ()), []).extend(merged_rows)
        return groups

    # Penalty latency recorded for a backend that RAISED instead of
    # answering: completes its exploration quota with an estimate no
    # healthy backend will ever lose to, so the planner stops retrying it
    # for the class instead of paying a doomed attempt per request.
    FAILED_BACKEND_PENALTY_S = 60.0

    def _execute_group(
        self, handle, spec, rows, compiled, requests, ks, stats, cands,
        totals, seg_tree=None, masks=None,
    ) -> None:
        """Execute one same-spec group — one padded device launch (or the
        oracle per lane when routed there) — and append candidates.
        `masks` holds the group's shared filter-cache planes (every rider
        in the group references the same planes, by group-key
        construction), injected once into the launch's seg tree."""
        k_max = max(ks[i] for i in rows)
        backend = "device_batched"
        plan_class = None
        if self.planner is not None:
            from ..exec.cost import PlanFeatures
            from ..exec.planner import oracle_eligible, spec_work_tiles

            with TRACER.span("exec.plan"):
                if all(oracle_eligible(requests[i].query) for i in rows):
                    plan_class = ("batched", spec, k_max)
                    feats = PlanFeatures(
                        n_docs=handle.segment.num_docs,
                        work_tiles=(
                            spec_work_tiles(spec)
                            if bm25_device.supports_sparse(spec)
                            else 0
                        ),
                    )
                    backend = self.planner.decide(
                        plan_class, ["device_batched", "oracle"], feats
                    )
        if backend == "oracle":
            # One span for the whole branch: the oracle is built once per
            # group, then serves its lanes one by one.
            with TRACER.span("search.oracle"):
                from .oracle import OracleSearcher

                oracle = OracleSearcher(
                    handle.segment,
                    self.engine.mappings,
                    self.engine.params,
                    # the compiler's pushed-down scope, verbatim
                    stats=stats,
                    live=self._host_live(handle),
                )
                remaining = list(rows)
                while remaining:
                    i = remaining[0]
                    lane_t0 = time.monotonic()
                    try:
                        scores, ids, tot = oracle.search(
                            requests[i].query, ks[i]
                        )
                    # staticcheck: ignore[broad-except] oracle gap falls back to the device; the numpy oracle polls no tasks and hosts no fault sites
                    except Exception:
                        # Same contract as the single-request path: an
                        # oracle gap falls back to the device (for every
                        # lane not yet served, below) instead of failing
                        # the whole batch, and the penalty observation
                        # stops the planner from retrying the oracle for
                        # this class.
                        if plan_class is not None:
                            # observe (not record): a failed attempt is a
                            # cost sample, not a served decision.
                            self.planner.cost.observe(
                                plan_class, "oracle",
                                self.FAILED_BACKEND_PENALTY_S,
                            )
                        break
                    remaining.pop(0)
                    self._append_plain(
                        cands[i], handle, scores, ids,
                        min(ks[i], len(ids)),
                    )
                    totals[i] += int(tot)
                    if bm25_device.supports_maxscore(spec):
                        self._count_maxscore("oracle")
                    if plan_class is not None:
                        self.planner.record(
                            plan_class, "oracle",
                            time.monotonic() - lane_t0,
                        )
            rows = remaining
        if rows:
            redo = self._device_batch(
                handle, spec, rows, compiled, ks, k_max, plan_class, cands,
                totals, seg_tree=seg_tree, masks=masks,
            )
            self._maxscore_fallback(
                handle, stats, requests, redo, compiled, ks, cands, totals,
                seg_tree,
            )

    def _device_batch(
        self, handle, spec, rows, compiled, ks, k_max, plan_class, cands,
        totals, seg_tree=None, masks=None,
    ) -> list[int]:
        """One padded device launch for a same-spec row group. Filter-
        cache planes (`masks`) ride the seg tree — one shared plane per
        launch, never stacked per lane. Returns the MaxScore lanes whose
        threshold check failed, unanswered (`_maxscore_fallback`)."""
        import jax

        t0 = time.monotonic()
        if seg_tree is None:
            seg_tree = bm25_device.segment_tree(handle.device)
        if masks:
            seg_tree = {**seg_tree, "masks": masks}
        if not jax.tree.leaves(compiled[rows[0]].arrays):
            # Plans with no array leaves (match_none compiles to an
            # empty pytree) give vmap nothing to infer the batch axis
            # from; execute the rows directly (they are trivial).
            for i in rows:
                s, idx, t = jax.device_get(
                    bm25_device.execute_auto(
                        seg_tree, spec, compiled[i].arrays, ks[i]
                    )
                )
                tot = int(t)
                self._append_plain(
                    cands[i], handle, s, idx, min(ks[i], tot, len(idx))
                )
                totals[i] += tot
            return []
        with TRACER.span("device.stage"):
            arrays_b = jax.tree.map(
                lambda *xs: np.stack(xs), *[compiled[i].arrays for i in rows]
            )
            if self.device is not None:
                self.device.h2d(arrays_b)
        maxscore = bm25_device.supports_maxscore(spec)
        if maxscore:
            kernel = bm25_device.execute_batch_maxscore
        elif bm25_device.supports_sparse(spec):
            kernel = bm25_device.execute_batch_sparse
        else:
            kernel = bm25_device.execute_batch
        kind = str(spec[0]) if isinstance(spec, tuple) and spec else "dense"
        # Per-launch timing wrapper (obs/metrics.DeviceInstruments.timed):
        # queue/execute split around block_until_ready + retrace-census
        # attribution for any XLA compile this dispatch provokes.
        with timed_launch(
            self.device,
            f"{kind}_batched",
            (spec, k_max, "device_batched"),
            "device_batched",
        ) as tl:
            # Argument transfer, jit cache lookup and enqueue; the wait on
            # the device is `device.wait`, inside dispatched().
            with TRACER.span("device.dispatch"):
                out = kernel(seg_tree, spec, arrays_b, k_max)
            out = tl.dispatched(out)
        redo: list[int] = []
        with TRACER.span("device.fetch"):
            s_b, i_b, t_b, *ok_b = jax.device_get(out)
            elapsed = time.monotonic() - t0
            for row, i in enumerate(rows):
                if plan_class is not None and (tl.first or tl.compiles):
                    # A launch that traced and compiled (or loaded) its
                    # program is no sample of the class's cost: priced by
                    # it, the class would stay on the oracle, never to be
                    # observed on the device again. Counted, not folded.
                    self.planner.note("device_batched")
                elif plan_class is not None:
                    # Amortized per-lane cost: what this class actually
                    # pays per query when batched.
                    self.planner.record(
                        plan_class, "device_batched", elapsed / len(rows)
                    )
                if ok_b and not ok_b[0][row]:
                    redo.append(i)
                    continue
                tot = int(t_b[row])
                nn = min(ks[i], tot, s_b.shape[1])
                self._append_plain(cands[i], handle, s_b[row], i_b[row], nn)
                totals[i] += tot
        if maxscore:
            self._count_maxscore("served", len(rows) - len(redo))
            self._count_maxscore("fallback", len(redo))
        return redo

    def _maxscore_fallback(
        self, handle, stats, requests, rows, compiled, ks, cands, totals,
        seg_tree,
    ) -> None:
        """Answer MaxScore lanes whose threshold check failed from their
        exhaustive plans (`_exhaustive_plan`), one launch per spec."""
        groups: dict[tuple, list[int]] = {}
        for i in rows:
            compiled[i] = self._exhaustive_plan(handle, stats, requests[i])
            groups.setdefault(compiled[i].spec, []).append(i)
        for spec, group in groups.items():
            self._device_batch(
                handle, spec, group, compiled, ks,
                max(ks[i] for i in group), None, cands, totals,
                seg_tree=seg_tree,
            )

    @staticmethod
    def _append_plain(bucket, handle, scores, ids, n) -> None:
        for rank in range(n):
            score = float(scores[rank])
            local = int(ids[rank])
            bucket.append(
                (
                    -score,
                    handle.base + local,
                    handle,
                    local,
                    score,
                    _NO_SORT,
                )
            )

    def _validate_sort(self, request: SearchRequest) -> None:
        """Validate the sort spec against the mappings up front, so request
        validity doesn't depend on whether the hits pass runs (an agg-only
        size=0 request must still 400 on a bad sort).

        Accepted shapes: one or more numeric doc-values fields (multi-key
        sorts lexsort on the host path), an optional trailing "_doc"
        tiebreak (which only makes the implicit doc tiebreak explicit),
        or a lone "_score" key."""
        if request.sort is None:
            return
        fields = [next(iter(e)) for e in request.sort]
        for i, f in enumerate(fields):
            if f == "_doc":
                if i != len(fields) - 1 or i == 0:
                    raise ValueError(
                        "[_doc] is only supported as a trailing tiebreak "
                        "after a field sort key"
                    )
                continue
            if f == "_score":
                if len(fields) > 1:
                    raise ValueError(
                        "[_score] cannot be combined with other sort keys"
                    )
                continue
            fm = self.engine.mappings.get(f)
            if fm is None or not fm.is_numeric:
                raise ValueError(
                    f"No mapping found for [{f}] in order to sort on"
                )
        real = [f for f in fields if f not in ("_doc", "_score")]
        if request.search_after is not None and len(real) > 1:
            raise ValueError(
                "search_after with a multi-key sort is not supported yet"
            )

    def _validate_knn(self, request: SearchRequest) -> None:
        """Validate the knn section against the mappings up front (field
        mapped as dense_vector, query_vector dims agree) so a malformed
        request 400s before any segment pass runs."""
        if request.knn is None:
            return
        knn = request.knn
        fm = self.engine.mappings.get(knn.field)
        if fm is None:
            raise ValueError(
                f"failed to find knn vector field [{knn.field}] in mapping"
            )
        if fm.type != "dense_vector":
            raise ValueError(
                f"[knn] field [{knn.field}] must be of type [dense_vector] "
                f"but is [{fm.type}]"
            )
        if len(knn.query_vector) != fm.dims:
            raise ValueError(
                f"the query vector has a different number of dimensions "
                f"[{len(knn.query_vector)}] than the document vectors "
                f"[{fm.dims}]"
            )

    # ------------------------------------------------------------------ query

    def _host_live(self, handle: SegmentHandle):
        """The live mask the DEVICE currently serves, as a host array (or
        None when every doc is live). When deletions are pending upload
        (live_dirty), live_host is AHEAD of the device — parity with the
        device backends requires the device's own mask."""
        if getattr(handle, "live_dirty", False):
            live = np.asarray(handle.device.live)[: handle.segment.num_docs]
        else:
            live = handle.live_host
        return None if live.all() else live

    def _maxscore_threshold(self, request, handle) -> int | None:
        """The track_total_hits threshold under which this request's query
        may take the MaxScore lowering on this segment, or None.

        Only a plain score-sorted top-k (no sort, cursor, rescore, aggs or
        knn, k > 0) with an integer threshold qualifies: its answer is the
        top k plus a total clamped at the threshold, which the MaxScore
        kernel gives exactly (ops/bm25_device.execute_maxscore). A segment
        with deleted docs does not: the kernel's total floor counts a
        term's whole span as live matches."""
        tth = request.track_total_hits
        if (
            isinstance(tth, bool)
            or request.sort is not None
            or request.search_after is not None
            or request.rescore
            or request.aggs is not None
            or request.knn is not None
            or max(0, request.from_) + max(0, request.size) <= 0
            or self._host_live(handle) is not None
        ):
            return None
        return int(tth)

    def _lower(self, handle, stats, request):
        """The request's query compiled for one segment: through
        Compiler.compile_maxscore where `_maxscore_threshold` allows it,
        else as always. A scored disjunction of two or more terms whose
        plan is not the MaxScore one counts as the MaxScore counter's
        `bypass`."""
        compiler = self.engine.compiler_for(handle, stats)
        threshold = self._maxscore_threshold(request, handle)
        compiled = None
        if threshold is not None:
            compiled = compiler.compile_maxscore(request.query, threshold)
        if compiled is None:
            compiled = compiler.compile(request.query)
        spec = compiled.spec
        if spec[0] in ("terms", "terms_gather") and spec[3] >= 2:
            self._count_maxscore("bypass")
        return compiled

    def _exhaustive_plan(self, handle, stats, request):
        """The exhaustive plan of a MaxScore-lowered request whose
        threshold check failed (Compiler.compile_exhaustive)."""
        with TRACER.span("query.compile"):
            return self.engine.compiler_for(handle, stats).compile_exhaustive(
                request.query
            )

    def _count_maxscore(self, outcome: str, n: int = 1) -> None:
        if self.device is not None and n:
            self.device.maxscore(outcome, n)

    def _decide_backend(
        self,
        handle: SegmentHandle,
        request: SearchRequest,
        compiled,
        k: int,
        masked: bool = False,
    ) -> tuple[str, tuple | None]:
        """(backend, plan_class) for one plain score-sorted segment pass.

        Candidate backends are restricted to those that CANNOT change the
        top-k result (the planner's hard invariant): block-max only when
        exact totals aren't tracked (its totals are "gte"), the oracle
        only for statistics-faithful query shapes. A mask-substituted plan
        runs the same device kernels but is priced (and counted) as the
        `cached_mask` backend: its work_tiles exclude the cached clauses'
        worklists, so the planner prices mask reuse against the oracle's
        full recompute honestly."""
        base = "cached_mask" if masked else "device"
        if self.planner is None:
            return base, None
        from ..exec.cost import PlanFeatures
        from ..exec.planner import oracle_eligible, spec_work_tiles

        spec = compiled.spec
        candidates = [base]
        if request.track_total_hits is False:
            # Two-phase tile-pruned paths report "gte" totals, so they are
            # only eligible when exact totals aren't tracked.
            if spec[0] == "terms":
                candidates.append("blockmax")
            elif bm25_device.supports_blockmax_conj(spec):
                candidates.append("blockmax_conj")
        if oracle_eligible(request.query):
            candidates.append("oracle")
        plan_class = self.planner.classify(spec, k)
        if len(candidates) == 1:
            return base, plan_class
        feats = PlanFeatures(
            n_docs=handle.segment.num_docs,
            work_tiles=(
                spec_work_tiles(spec)
                if bm25_device.supports_sparse(spec)
                else 0
            ),
            n_clauses=spec[3] if spec[0] == "terms" else 1,
        )
        return self.planner.decide(plan_class, candidates, feats), plan_class

    # ------------------------------------------------------------------ knn

    def _knn_filter_mask(self, handle, seg_tree, filter_query, stats):
        """The knn filter as a device mask plane (bool[N]) — applied
        PRE-rank inside the kernel, so filtered-out docs never consume a
        candidate slot. Reuses the PR-9 filter cache when the filter is a
        cacheable shape and has earned admission; otherwise computed
        fresh (one dense filter pass, same as an uncached bool filter)."""
        compiled = self.engine.compiler_for(handle, stats).compile(
            filter_query
        )

        def build():
            return bm25_device.compute_filter_mask(
                seg_tree, compiled.spec, compiled.arrays
            )

        if self.filter_cache is None:
            return build()
        from ..query.compile import cacheable_filter_key

        norm = cacheable_filter_key(filter_query)
        if norm is None:
            return build()
        key = (self.engine.uid, 0, handle.uid, norm)
        plane = self.filter_cache.get(key)
        if plane is not None:
            self.filter_cache.note_reuse(1)
            TRACER.tag(filter_cache_hit=True)
            return plane
        plane = build()
        if self.filter_cache.should_admit(norm):
            self.filter_cache.put(
                key, plane, int(plane.nbytes),
                live_uids=frozenset(h.uid for h in self.engine.segments),
            )
        return plane

    def _knn_plan(self, handle, knn):
        """(partitions-or-None, nprobe, metric, plan_class, backend) for
        one segment's knn pass. A segment with no partitions (too small,
        cache disabled, or residency declined) serves the exact
        brute-force kernel — the planner's `ann_ivf` backend exists only
        where the IVF planes do. Routing between `ann_ivf` and the exact
        `device` kernel is admissible here BECAUSE the knn section is
        approximate by contract (exact answers satisfy it trivially);
        `script_score` kNN never enters this path."""
        metric = self.engine.mappings.get(knn.field).similarity
        parts = None
        if self.ann_cache is not None:
            parts = self.ann_cache.get_or_build(
                self.engine, handle, knn.field, metric
            )
        if parts is None:
            return None, 0, metric, None, "device"
        from ..index.ann import default_nprobe

        nprobe = knn.nprobe or default_nprobe(parts.n_partitions)
        # num_candidates is a floor on the candidates examined: widen the
        # probe until the expected REAL vectors covered reach it
        # (average partition fill = n_vectors / n_partitions; counting
        # padded slots instead would under-probe small or skewed
        # segments). num_candidates >= the corpus degenerates to a full
        # probe.
        nprobe = max(
            nprobe,
            -(-knn.num_candidates * parts.n_partitions // max(
                1, parts.n_vectors
            )),
        )
        nprobe = min(nprobe, parts.n_partitions)
        backend = "ann_ivf"
        plan_class = None
        if self.planner is not None:
            from ..exec.cost import PlanFeatures

            spec = ("knn", knn.field, metric, parts.n_partitions, nprobe)
            plan_class = self.planner.classify(spec, knn.k)
            feats = PlanFeatures(
                n_docs=handle.segment.num_docs,
                n_candidates=parts.n_partitions + nprobe * parts.pmax,
            )
            backend = self.planner.decide(
                plan_class, ["ann_ivf", "device"], feats
            )
        return parts, nprobe, metric, plan_class, backend

    def _query_segment_knn(
        self,
        handle: SegmentHandle,
        request: SearchRequest,
        stats: dict[str, FieldStats],
        candidates: list,
        timings: dict | None = None,
        device_info: dict | None = None,
    ) -> tuple[int, str]:
        """One segment's knn pass: IVF probe + exact re-rank when the
        segment has partition planes, exact brute force otherwise.
        Appends up to knn.k candidates (the per-segment candidate count
        the reference's per-shard kNN contract uses); pagination happens
        at the shared reduce."""
        from ..ops import ann_device

        fault_point("search.kernel", index=self.index_name)
        knn = request.knn
        plan_t0 = time.monotonic()
        dev = handle.device
        vectors = dev.vectors.get(knn.field)
        if vectors is None:
            return 0, "device"  # mapped field, no vectors in this segment
        seg_tree = bm25_device.segment_tree(dev)
        fmask = None
        if knn.filter is not None:
            fmask = self._knn_filter_mask(
                handle, seg_tree, knn.filter, stats
            )
        parts, nprobe, metric, plan_class, backend = self._knn_plan(
            handle, knn
        )
        now = time.monotonic()
        if timings is not None:
            timings["plan_s"] += now - plan_t0
        exec_t0 = now
        h2d_bytes = 0
        if self.device is not None:
            h2d_bytes = self.device.h2d(knn.query_vector)
        with timed_launch(
            self.device, "knn", (knn.field, metric, knn.k, backend), backend
        ) as tl:
            if backend == "ann_ivf":
                out = tl.dispatched(
                    ann_device.ann_ivf_search(
                        parts.tree(), dev.live, knn.query_vector, knn.k,
                        nprobe, metric, filter_mask=fmask,
                    )
                )
                scores, ids, tot, n_cand = out
            else:
                out = tl.dispatched(
                    ann_device.knn_exact(
                        vectors, dev.live, knn.query_vector, knn.k, metric,
                        filter_mask=fmask,
                    )
                )
                scores, ids, tot = out
                n_cand = tot
        scores, ids = np.asarray(scores), np.asarray(ids)
        tot, n_cand = int(tot), int(n_cand)
        # Trim to REAL hits: totals count the eligible doc space, but
        # vector-less docs can't be scored, so the hit count is the
        # finite-score prefix (both kernels fill unserved slots -inf).
        n_cand = min(n_cand, int(np.sum(scores > np.float32(bm25_device.NEG_INF))))
        elapsed = time.monotonic() - exec_t0
        if timings is not None:
            timings["exec_s"] += elapsed
        if device_info is not None:
            device_info.update(
                launch_ms=round(elapsed * 1e3, 3),
                queue_ms=tl.queue_ms,
                execute_ms=tl.execute_ms,
                compile=bool(tl.first),
                h2d_bytes=h2d_bytes,
            )
        if self.planner is not None:
            if plan_class is not None:
                self.planner.record(plan_class, backend, elapsed)
            else:
                self.planner.note(backend)
        if self.ann_cache is not None:
            self.ann_cache.note_search(
                backend,
                nprobe=nprobe if backend == "ann_ivf" else 0,
                candidate_fraction=(
                    n_cand / max(1, handle.segment.num_docs)
                ),
            )
        n = min(knn.k, n_cand, len(ids))
        self._append_plain(candidates, handle, scores, ids, n)
        return tot, backend

    def _knn_search_many(self, requests: list, tasks: list) -> list:
        """Coalesced knn serving: the micro-batcher groups knn requests
        by (field, k, num_candidates, nprobe, no filter), so every rider
        here shares one kernel shape — their query vectors stack into ONE
        batched launch per segment. Results are identical to solo
        execution (the batched kernel vmaps the same program)."""
        from ..common.tasks import TaskCancelledError
        from ..ops import ann_device

        start = time.monotonic()
        n = len(requests)
        stats = self.engine.field_stats()
        segments = list(self.engine.segments)
        cands: list[list] = [[] for _ in range(n)]
        totals = [0] * n
        timed = [False] * n
        errors: list[Exception | None] = [None] * n
        for i, r in enumerate(requests):
            try:
                self._validate_knn(r)
            except ValueError as e:
                errors[i] = e
        knn0 = next(
            (requests[i].knn for i in range(n) if errors[i] is None), None
        )
        uniform = all(
            errors[i] is not None
            or (
                (kn := requests[i].knn) is not None
                and kn.filter is None
                and (kn.field, kn.k, kn.num_candidates, kn.nprobe)
                == (knn0.field, knn0.k, knn0.num_candidates, knn0.nprobe)
            )
            for i in range(n)
        )
        if knn0 is None or not uniform:
            # Defensive: a mixed group (the batcher's group key should
            # prevent it) serves each rider solo, result-identical.
            return [
                errors[i]
                if errors[i] is not None
                else self.search(requests[i], task=tasks[i])
                for i in range(n)
            ]
        for handle in segments:
            alive = [i for i in range(n) if errors[i] is None]
            for i in list(alive):
                task = tasks[i]
                if task is None:
                    continue
                if task.cancelled:
                    reason = task.cancel_reason or "cancelled"
                    errors[i] = TaskCancelledError(
                        f"task cancelled [{reason}]"
                    )
                    alive.remove(i)
                elif task.check_deadline():
                    timed[i] = True
                    alive.remove(i)
            if not alive:
                break
            dev = handle.device
            vectors = dev.vectors.get(knn0.field)
            if vectors is None or handle.segment.num_docs == 0:
                continue
            fault_point("search.kernel", index=self.index_name)
            parts, nprobe, metric, plan_class, backend = self._knn_plan(
                handle, knn0
            )
            qs = np.stack(
                [requests[i].knn.query_vector for i in alive]
            )
            t0 = time.monotonic()
            with timed_launch(
                self.device,
                "knn_batched",
                (knn0.field, metric, knn0.k, backend, len(alive)),
                backend,
            ) as tl:
                if backend == "ann_ivf":
                    s_b, i_b, t_b, nc_b = tl.dispatched(
                        ann_device.ann_ivf_search_batch(
                            parts.tree(), dev.live, qs, knn0.k, nprobe,
                            metric,
                        )
                    )
                else:
                    s_b, i_b, t_b = tl.dispatched(
                        ann_device.knn_exact_batch(
                            vectors, dev.live, qs, knn0.k, metric
                        )
                    )
                    nc_b = t_b
            s_b, i_b = np.asarray(s_b), np.asarray(i_b)
            t_b, nc_b = np.asarray(t_b), np.asarray(nc_b)
            # Real hits per lane = the finite-score prefix (totals count
            # the eligible doc space; vector-less docs can't be scored).
            finite_b = np.sum(
                s_b > np.float32(bm25_device.NEG_INF), axis=1
            )
            elapsed = time.monotonic() - t0
            for row, i in enumerate(alive):
                tot = int(t_b[row])
                nn = min(
                    knn0.k, int(nc_b[row]), int(finite_b[row]),
                    i_b.shape[1],
                )
                self._append_plain(cands[i], handle, s_b[row], i_b[row], nn)
                totals[i] += tot
                if self.planner is not None and plan_class is not None:
                    self.planner.record(
                        plan_class, backend, elapsed / len(alive)
                    )
                if self.ann_cache is not None:
                    self.ann_cache.note_search(
                        backend,
                        nprobe=nprobe if backend == "ann_ivf" else 0,
                        candidate_fraction=(
                            int(nc_b[row])
                            / max(1, handle.segment.num_docs)
                        ),
                    )
        out: list = []
        for i, request in enumerate(requests):
            if errors[i] is not None:
                out.append(errors[i])
                continue
            rows = sorted(cands[i], key=lambda c: (c[0], c[1]))
            out.append(
                self.assemble_plain(
                    request,
                    rows[: request.knn.k],  # global top-k, then page
                    totals[i],
                    timed[i],
                    start,
                )
            )
        return out

    def _query_segment(
        self,
        handle: SegmentHandle,
        request: SearchRequest,
        k: int,
        stats: dict[str, FieldStats],
        candidates: list,
        timings: dict | None = None,
        fc_entries: list | None = None,
        device_info: dict | None = None,
    ) -> tuple[int, str]:
        """Score one segment, appending candidate tuples. Returns
        (total hits, execution backend used). `device_info` (profile:
        true) is filled with this segment's device block: launch ms,
        compile hit/miss, H2D bytes staged for this request."""
        if request.knn is not None:
            return self._query_segment_knn(
                handle, request, stats, candidates, timings=timings,
                device_info=device_info,
            )
        # Injectable device-launch failure / slow-segment delay
        # (faults/registry.py `search.kernel`).
        fault_point("search.kernel", index=self.index_name)
        plan_t0 = time.monotonic()
        with TRACER.span("device.stage"):
            seg_tree = bm25_device.segment_tree(handle.device)
        with TRACER.span("query.compile"):
            compiled = self._lower(handle, stats, request)
            # Filter cache: swap cacheable filter-context clauses for
            # their cached (or freshly admitted) mask planes —
            # bit-identical by construction, the plane IS the clause's
            # own evaluation.
            compiled, fc_masks = self._apply_filter_cache(
                handle, request.query, compiled, seg_tree, entries=fc_entries
            )
        if fc_masks:
            seg_tree = {**seg_tree, "masks": fc_masks}
        now = time.monotonic()
        if timings is not None:
            timings["plan_s"] += now - plan_t0
        exec_t0 = now
        spec_kind = (
            str(compiled.spec[0])
            if isinstance(compiled.spec, tuple) and compiled.spec
            else type(request.query).__name__
        )
        h2d_bytes = 0
        if self.device is not None:
            # Host→device plan-array bytes this launch stages.
            with TRACER.span("device.stage"):
                h2d_bytes = self.device.h2d(compiled.arrays)

        def done(total: int, backend: str = "device") -> tuple[int, str]:
            elapsed = time.monotonic() - exec_t0
            if timings is not None:
                timings["exec_s"] += elapsed
            first = False
            if self.device is not None and backend != "oracle":
                # First launch of a new (spec, k, backend) shape is the
                # XLA compile for its plan class.
                first = self.device.launch(
                    spec_kind, (compiled.spec, k, backend), elapsed,
                    backend=backend,
                )
            if device_info is not None:
                device_info.update(
                    launch_ms=round(elapsed * 1e3, 3),
                    compile=bool(first),
                    h2d_bytes=h2d_bytes,
                )
            return total, backend

        # Sort spec validity is enforced up front by _validate_sort.
        sort_field = None
        descending = False
        missing_first = False
        if request.sort is not None:
            keys = normalized_sort(request)
            if keys[0][0] == "_score":
                sort_field = "_score"
                descending = keys[0][1]
            elif len(keys) == 1:
                sort_field, descending, missing_first = keys[0]
            else:
                # Multi-key field sort: dense matched mask + host lexsort
                # (a per-segment top-k by the primary key alone could drop
                # docs that tie on it but win on a secondary key).
                total, backend = self._query_segment_multisort(
                    handle, request, k, keys, compiled, seg_tree, candidates
                )
                return done(total, backend)

        cursor = request.search_after
        if sort_field is None or sort_field == "_score":
            ascending_score = sort_field == "_score" and not descending
            backend = "device"
            fetch_k = k
            if request.rescore and not ascending_score:
                fetch_k = max(k, max(r.window_size for r in request.rescore))
            if cursor is not None:
                # Cursor pagination: mask docs at or before the (score, doc)
                # cursor BEFORE the device top-k — the next page may lie
                # beyond this segment's uncursored top-k.
                a_doc = (
                    request.after_doc - handle.base
                    if request.after_doc >= 0
                    else handle.device.num_docs  # key-only: no tie clause
                )
                scores, ids, tot, n_after = bm25_device.execute_score_after(
                    seg_tree,
                    compiled.spec,
                    compiled.arrays,
                    k,
                    np.float32(cursor[0]),
                    np.int32(a_doc),
                    ascending=ascending_score,
                )
                scores, ids = np.asarray(scores), np.asarray(ids)
                n = min(k, int(n_after), len(ids))
                tot = int(tot)
            elif ascending_score:
                # Bottom-k needs its own device reduction — the default
                # top-k collector would never see the lowest-scoring hits.
                scores, ids, tot = bm25_device.execute_score_asc(
                    seg_tree, compiled.spec, compiled.arrays, k
                )
                scores, ids = np.asarray(scores), np.asarray(ids)
                n = min(k, int(tot), len(ids))
            else:
                # The hot plain-score path: the planner routes this
                # (shard, query) to whichever backend its cost model
                # predicts wins — the invariant (enforced by eligibility
                # and fuzzed in tests/test_exec_parity.py) is that every
                # candidate backend returns identical top-k/totals.
                plan_class = None
                if self.planner is not None and not request.rescore:
                    with TRACER.span("exec.plan"):
                        backend, plan_class = self._decide_backend(
                            handle, request, compiled, k,
                            masked=bool(fc_masks),
                        )
                    # The routing decision, as a tagged event on the
                    # enclosing segment span.
                    TRACER.event(
                        "planner.decision",
                        backend=backend,
                        plan_class=spec_kind,
                        k=k,
                    )
                kern_t0 = time.monotonic()
                if backend == "oracle":
                    from .oracle import OracleSearcher

                    try:
                        with TRACER.span("search.oracle"):
                            scores, ids, tot = OracleSearcher(
                                handle.segment,
                                self.engine.mappings,
                                self.engine.params,
                                stats=stats,
                                live=self._host_live(handle),
                            ).search(request.query, k)
                    # staticcheck: ignore[broad-except] oracle gap falls back to the device; the numpy oracle polls no tasks and hosts no fault sites
                    except Exception:
                        # Defensive: an oracle gap falls back to the
                        # device rather than failing the request; the
                        # penalty observation completes the oracle's
                        # exploration quota so the planner stops paying a
                        # doomed attempt on every request of this class.
                        backend = "device"
                        if plan_class is not None:
                            # observe (not record): a failed attempt is a
                            # cost sample, not a served decision.
                            self.planner.cost.observe(
                                plan_class, "oracle",
                                self.FAILED_BACKEND_PENALTY_S,
                            )
                if backend == "oracle" and bm25_device.supports_maxscore(
                    compiled.spec
                ):
                    self._count_maxscore("oracle")
                if backend == "blockmax":
                    s, i, t, _rel = bm25_device.execute_batch_blockmax(
                        seg_tree, compiled.spec, [compiled.arrays], k,
                        instruments=self.device,
                    )
                    scores, ids, tot = s[0], i[0], int(t[0])
                elif backend == "blockmax_conj":
                    s, i, t, _rel = bm25_device.execute_batch_blockmax_conj(
                        seg_tree, compiled.spec, [compiled.arrays], k,
                        instruments=self.device,
                    )
                    scores, ids, tot = s[0], i[0], int(t[0])
                elif backend == "device" and bm25_device.supports_maxscore(
                    compiled.spec
                ):
                    with TRACER.span("device.dispatch"):
                        scores, ids, tot, ok = bm25_device.execute_maxscore(
                            seg_tree, compiled.spec, compiled.arrays, k
                        )
                    with TRACER.span("device.fetch"):
                        scores, ids = np.asarray(scores), np.asarray(ids)
                        ok = bool(ok)
                    self._count_maxscore("served" if ok else "fallback")
                    if not ok:
                        plan = self._exhaustive_plan(handle, stats, request)
                        with TRACER.span("device.dispatch"):
                            scores, ids, tot = bm25_device.execute_auto(
                                seg_tree, plan.spec, plan.arrays, k
                            )
                        with TRACER.span("device.fetch"):
                            scores, ids = np.asarray(scores), np.asarray(ids)
                elif backend in ("device", "cached_mask"):
                    with TRACER.span("device.dispatch"):
                        scores, ids, tot = bm25_device.execute_auto(
                            seg_tree, compiled.spec, compiled.arrays, fetch_k
                        )
                    # Unbatched, the wait on the device is in the fetch.
                    with TRACER.span("device.fetch"):
                        scores, ids = np.asarray(scores), np.asarray(ids)
                    if request.rescore:
                        scores, ids = self._apply_rescore(
                            handle, seg_tree, request, scores, ids, int(tot),
                            stats,
                        )
                if plan_class is not None:
                    self.planner.record(
                        plan_class, backend, time.monotonic() - kern_t0
                    )
                n = min(k, int(tot), len(ids))
            for rank in range(n):
                score = float(scores[rank])
                local = int(ids[rank])
                if sort_field is None:
                    key, sort_value = -score, _NO_SORT
                else:
                    key, sort_value = (score if ascending_score else -score), score
                candidates.append(
                    (key, handle.base + local, handle, local, score, sort_value)
                )
            return done(int(tot), backend)

        missing_key = -np.inf if missing_first else np.inf
        if sort_field not in handle.device.doc_values:
            # Mapped numeric field with no values in this segment: every
            # matched doc is "missing" — placed per the missing directive,
            # ordered by doc id (the same contract as NaN values in
            # execute_sorted).
            _, eligible = bm25_device.execute_dense(
                seg_tree, compiled.spec, compiled.arrays
            )
            mask = np.asarray(eligible)
            locs = np.flatnonzero(mask)
            if cursor is not None:
                if cursor[0] is None:
                    # Cursor inside the missing region: resume by doc id
                    # (key-only null cursor skips the whole region).
                    if request.after_doc >= 0:
                        locs = locs[locs > request.after_doc - handle.base]
                    else:
                        locs = locs[:0]
                elif missing_first:
                    # Missing-first: a real-valued cursor is PAST the
                    # whole missing region.
                    locs = locs[:0]
                # Missing-last: a real cursor precedes every missing doc.
            for local in locs[:k]:
                candidates.append(
                    (missing_key, handle.base + int(local), handle,
                     int(local), None, None)
                )
            return done(int(mask.sum()))
        if cursor is not None:
            raw_after = cursor[0]
            fmax = np.float32(np.finfo(np.float32).max)
            if raw_after is None:
                # Missing-region cursor, in the transformed ascending key
                # space (missing = +fmax last / -fmax first).
                a_key = -fmax if missing_first else fmax
            else:
                a_key = np.float32(raw_after)
                if descending:
                    a_key = np.float32(-a_key)
            a_doc = (
                request.after_doc - handle.base
                if request.after_doc >= 0
                else handle.device.num_docs
            )
            values, ids, tot, n_after = bm25_device.execute_sorted_after(
                seg_tree,
                compiled.spec,
                compiled.arrays,
                sort_field,
                descending,
                k,
                a_key,
                np.int32(a_doc),
                missing_first=missing_first,
            )
            values, ids = np.asarray(values), np.asarray(ids)
            n = min(k, int(n_after))
        else:
            values, ids, tot = bm25_device.execute_sorted(
                seg_tree, compiled.spec, compiled.arrays, sort_field,
                descending, k, missing_first=missing_first,
            )
            values, ids = np.asarray(values), np.asarray(ids)
            n = min(k, int(tot))
        for rank in range(n):
            local = int(ids[rank])
            raw = float(values[rank])
            missing = np.isnan(values[rank])
            key = missing_key if missing else (-raw if descending else raw)
            candidates.append(
                (
                    key,
                    handle.base + local,
                    handle,
                    local,
                    None,  # ES omits _score for field sorts by default
                    None if missing else raw,
                )
            )
        return done(int(tot))

    def _query_segment_multisort(
        self,
        handle: SegmentHandle,
        request: SearchRequest,
        k: int,
        keys: list[tuple[str, bool, bool]],
        compiled,
        seg_tree,
        candidates: list,
    ) -> tuple[int, str]:
        """Multi-key field sort over one segment: ONE dense device launch
        for the matched mask, then a host lexsort over the f32-quantized
        doc-values columns (FieldSortBuilder semantics per key: asc/desc,
        missing first/last, final doc-id tiebreak). A per-key device top-k
        cannot serve this shape — docs tying on the primary key may win on
        a secondary key from beyond the primary top-k."""
        _, eligible = bm25_device.execute_dense(
            seg_tree, compiled.spec, compiled.arrays
        )
        n_docs = handle.segment.num_docs
        mask = np.asarray(eligible)[:n_docs]
        locs = np.flatnonzero(mask)
        total = int(len(locs))
        if total == 0 or k <= 0:
            return total, "device"
        vals32 = []  # f32 stored-value semantics, like the device column
        sortkeys = []  # transformed ascending f64 key per sort position
        for f, desc, mfirst in keys:
            col = handle.segment.doc_values.get(f)
            if col is None:
                v = np.full(len(locs), np.nan, dtype=np.float32)
            else:
                v = col[locs].astype(np.float32)
            miss = np.float32(-F32_MAX if mfirst else F32_MAX)
            key = np.where(
                np.isnan(v), miss, (-v if desc else v)
            ).astype(np.float64)
            vals32.append(v)
            sortkeys.append(key)
        order = np.lexsort((locs,) + tuple(reversed(sortkeys)))[:k]
        for pos in order:
            local = int(locs[pos])
            sort_vals = []
            merge_key = []
            for ki, (f, desc, mfirst) in enumerate(keys):
                v = vals32[ki][pos]
                if np.isnan(v):
                    sort_vals.append(None)
                    merge_key.append(-np.inf if mfirst else np.inf)
                else:
                    sort_vals.append(float(v))
                    merge_key.append(-float(v) if desc else float(v))
            candidates.append(
                (
                    tuple(merge_key),
                    handle.base + local,
                    handle,
                    local,
                    None,  # no _score for field sorts
                    sort_vals,
                )
            )
        return total, "device"

    def _apply_rescore(
        self,
        handle: SegmentHandle,
        seg_tree,
        request: SearchRequest,
        scores: np.ndarray,
        ids: np.ndarray,
        total: int,
        stats: dict[str, FieldStats],
    ) -> tuple[np.ndarray, np.ndarray]:
        """Run rescore stages over the shard-local top window.

        Window docs are re-sorted by combined score; hits past the window
        keep their original order BELOW the window, exactly like Lucene's
        QueryRescorer contract."""
        n = min(len(ids), total)
        scores, ids = scores[:n].copy(), ids[:n].copy()
        compiler = self.engine.compiler_for(handle, stats)
        for stage in request.rescore:
            w = min(stage.window_size, len(ids))
            if w == 0:
                continue
            compiled = compiler.compile(stage.query)
            # Pad the window to a pow-2 bucket to bound jit recompiles.
            w_pad = 1 << (w - 1).bit_length()
            padded = np.zeros(w_pad, dtype=np.int32)
            padded[:w] = ids[:w]
            r_scores, r_matched = bm25_device.scores_at(
                seg_tree, compiled.spec, compiled.arrays, padded
            )
            r_scores = np.asarray(r_scores)[:w]
            r_matched = np.asarray(r_matched)[:w]
            combined = stage.combine(scores[:w], r_scores, r_matched)
            order = np.lexsort((ids[:w], -combined.astype(np.float64)))
            scores[:w] = combined[order]
            ids[:w] = ids[:w][order]
        return scores, ids

    # ------------------------------------------------------------------ fetch

    def _highlight_context(self, request: SearchRequest):
        """Per-request highlight state: query terms/predicates + analyzer
        per highlighted field (computed once, applied per page hit)."""
        if request.highlight is None or not request.highlight.fields:
            return None
        from .highlight import collect_query_terms

        ctx = []
        for hf in request.highlight.fields:
            terms, preds = collect_query_terms(
                request.query,
                hf.name,
                self.engine.mappings,
                match_any_field=not hf.require_field_match,
            )
            analyzer = self.engine.mappings.analyzer_for(hf.name)
            ctx.append((hf, terms, preds, analyzer))
        return ctx

    def _fetch_highlight(
        self, handle: SegmentHandle, local: int, hl_ctx
    ) -> dict[str, list[str]] | None:
        if hl_ctx is None:
            return None
        from .highlight import highlight_value

        src = handle.segment.sources[local]
        out: dict[str, list[str]] = {}
        for hf, terms, preds, analyzer in hl_ctx:
            value = src.get(hf.name)
            if value is None:
                continue
            frags: list[str] = []
            for v in value if isinstance(value, list) else [value]:
                frags.extend(
                    highlight_value(str(v), analyzer, terms, preds, hf)
                )
            if hf.number_of_fragments:
                frags = frags[: hf.number_of_fragments]
            if frags:
                out[hf.name] = frags
        return out or None

    def _fetch_fields(
        self, handle: SegmentHandle, local: int, request: SearchRequest
    ) -> dict[str, list[Any]] | None:
        """docvalue_fields (from the columnar store) + fields (from
        _source), both rendered as ES value arrays."""
        if not request.docvalue_fields and not request.fields:
            return None
        out: dict[str, list[Any]] = {}
        for f in request.docvalue_fields or []:
            fm = self.engine.mappings.get(f)
            if fm is not None and fm.type in ("keyword", "text"):
                # Keyword "doc values" render from the stored source (the
                # columnar store is numeric-only); text has no doc values.
                if fm.type == "keyword":
                    src = handle.segment.sources[local]
                    if f in src and src[f] is not None:
                        v = src[f]
                        out[f] = (
                            [str(x) for x in v]
                            if isinstance(v, list)
                            else [str(v)]
                        )
                continue
            col = handle.segment.doc_values.get(f)
            if col is None or np.isnan(col[local]):
                continue
            v = col[local]
            if fm is None:
                out[f] = [float(v)]
            elif fm.type == "boolean":
                out[f] = [bool(v)]
            elif fm.type == "date":
                out[f] = [_iso_millis(float(v))]
            elif fm.type in ("long", "integer", "short", "byte"):
                out[f] = [int(v)]
            else:
                out[f] = [float(v)]
        for f in request.fields or []:
            src = handle.segment.sources[local]
            if f in src and src[f] is not None:
                v = src[f]
                out[f] = v if isinstance(v, list) else [v]
        return out or None

    def _fetch_source(
        self, handle: SegmentHandle, local: int, request: SearchRequest
    ) -> dict[str, Any] | None:
        if request.source_includes is False:
            return None
        src = handle.segment.sources[local]
        if request.source_includes is True:
            return src
        return {k: v for k, v in src.items() if k in set(request.source_includes)}
