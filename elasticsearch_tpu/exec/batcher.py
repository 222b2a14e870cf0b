"""Continuous micro-batching scheduler for the search serving path.

Concurrent searches that share a plan class (same index searcher, same
query-AST shape — see planner.ast_signature) coalesce into ONE padded
device launch instead of N serialized launches. Per-query device work for
the hot shapes is launch-dominated (~1 ms dispatch vs ~0.2 ms compute,
BENCH_r05), so coalescing multiplies throughput under concurrency without
touching single-request latency:

- an arrival into an idle group launches immediately (no idle tax —
  sequential traffic behaves exactly as before);
- arrivals while a batch is in flight (or queued behind one) wait up to
  ``max_wait`` for companions — the continuous-batching window;
- the wait is deadline-aware: a request with ``?timeout=``/body timeout
  never waits past its own deadline (it launches early and the normal
  partial-results machinery applies);
- ``POST /_tasks/{id}/_cancel`` on a search still waiting in the queue
  removes it immediately (tasks.Task cancel listeners) — it never rides
  the launch;
- when the queue backs up past ``queue_limit`` the batcher sheds load
  through the indexing-pressure rejection machinery (HTTP 429
  ``es_rejected_execution_exception``), carrying a ``Retry-After`` hint
  derived from the observed queue-wait p50 so shed clients back off
  sanely instead of hot-looping;
- failure isolation: a sub-request that fails inside a coalesced launch
  (injected ``batcher.launch`` fault, device-launch error, shard blowup)
  is RETRIED INDIVIDUALLY through the plain per-request path instead of
  poisoning its batchmates, and a group key that keeps failing while
  coalesced is QUARANTINED to the per-request path for a cooldown.

Counters for `GET /_nodes/stats`: batches launched, batch-occupancy
histogram, queue-wait p50/p99, queue-cancellations, sheds, individual
retries and quarantine activity.
"""

from __future__ import annotations

import math
import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import numpy as np

from ..common.indexing_pressure import IndexingPressureRejected
from ..common.tasks import TaskCancelledError
from ..faults import fault_point
from ..obs.metrics import OCCUPANCY_BUCKETS, QUEUE_WAIT_MS_BUCKETS
from ..obs.tracing import TRACER
from .qos import DEFAULT_LANE

# Errors that must surface verbatim, never trigger an individual retry:
# cancellations honor the cancel contract; ValueError/TypeError are
# request-shaped (the same request would fail solo too).
_NO_RETRY_ERRORS = (TaskCancelledError, ValueError, TypeError)


def plan_spec_buckets(spec_rows, n_shards: int = 1) -> list[tuple]:
    """Adaptive worklist sub-bucketing for coalesced launches.

    `spec_rows`: [(compiled spec, row count or row list)] — the same-spec
    groups of a batch. Returns a list of buckets (tuples of specs); each
    bucket shares ONE padded launch at its per-position-max bucket, the
    rest launch separately. Greedy largest-first: a smaller group joins a
    bucket only when (a) its spec unifies with the bucket's (structural
    compatibility) and (b) the padding tiles it would pay cost less than
    the launch it saves (exec/cost.coalesce_wins seeds). This replaces
    the unconditional pad-everything-to-the-group-max policy whose
    padding made batched execution slower than sequential for skewed
    worklists (BENCH_r05 cfg3's 7x inversion).
    """
    from ..query.compile import SpecUnifyError, unify_specs
    from .cost import coalesce_wins
    from .planner import spec_work_tiles

    items = []
    for spec, rows in spec_rows:
        n = rows if isinstance(rows, int) else len(rows)
        items.append((spec_work_tiles(spec), spec, max(1, n)))
    items.sort(key=lambda it: -it[0])
    # Each bucket: [target_spec, target_tiles, total_rows, [member specs]]
    buckets: list[list] = []
    for tiles, spec, n in items:
        placed = False
        for b in buckets:
            try:
                target = unify_specs([b[0], spec])
            except SpecUnifyError:
                continue
            # Price the merge against the UNIFIED target: per-position
            # maxima can exceed both inputs' totals, and existing bucket
            # members pay any growth too — all of that padding must beat
            # the one launch the merge saves.
            t_tiles = spec_work_tiles(target)
            extra = ((t_tiles - b[1]) * b[2] + (t_tiles - tiles) * n) * max(
                1, n_shards
            )
            if not coalesce_wins(extra):
                continue
            b[0] = target
            b[1] = t_tiles
            b[2] += n
            b[3].append(spec)
            placed = True
            break
        if not placed:
            buckets.append([spec, tiles, n, [spec]])
    return [tuple(b[3]) for b in buckets]


@dataclass
class _Pending:
    searcher: object
    request: object
    task: object
    group: tuple
    enqueued_at: float
    launch_at: float
    event: threading.Event = field(default_factory=threading.Event)
    claimed: bool = False  # popped for execution (or cancelled/shed)
    result: object = None
    error: Exception | None = None
    queue_wait_s: float = 0.0
    # Tenant lane this rider is attributed to (QoS accounting, weighted
    # shedding and DRR drain all key on it).
    lane: str = DEFAULT_LANE
    # Failed while riding a coalesced launch: the CALLER thread runs one
    # individual retry on the per-request path (keeping the scheduler
    # thread free for other groups).
    retry_solo: bool = False
    # Caller's (trace_id, span_id) captured at enqueue: the scheduler
    # thread has no contextvar continuity, so queue-wait and coalesced-
    # launch spans are recorded retrospectively under this context.
    trace_ctx: tuple | None = None


class MicroBatcher:
    """One node's continuous micro-batching scheduler."""

    # A group key whose coalesced launches failed this many times in a
    # row is quarantined to the per-request path for QUARANTINE_TTL_S
    # (then paroled and allowed to coalesce again).
    QUARANTINE_FAILURES = 3
    QUARANTINE_TTL_S = 30.0

    def __init__(
        self,
        max_wait_s: float | None = None,
        max_batch: int = 64,
        queue_limit: int = 256,
        metrics=None,
        qos=None,
    ):
        # Optional per-tenant QoS controller (exec/qos.QosController).
        # When present: ready groups drain by weighted deficit-round-
        # robin instead of strict earliest-due, a full queue sheds the
        # most over-quota lane's newest rider first, Retry-After comes
        # from the shed lane's own windowed wait p50, and each rider's
        # share of the observed launch wall is charged to its lane.
        self.qos = qos
        if max_wait_s is None:
            max_wait_s = (
                float(os.environ.get("ESTPU_EXEC_BATCH_WAIT_MS", 4.0)) / 1e3
            )
        self.max_wait_s = max_wait_s
        self.max_batch = max(1, max_batch)
        self.queue_limit = max(1, queue_limit)
        self._cv = threading.Condition()
        self._queues: dict[tuple, deque[_Pending]] = {}
        self._in_flight: set[tuple] = set()
        self._thread: threading.Thread | None = None
        self._closed = False
        # Telemetry: one write path, the node's metrics registry
        # (obs/metrics.py) — `_nodes/stats` and `GET /_metrics` are both
        # views over these instruments. A standalone batcher gets a
        # private registry.
        if metrics is None:
            from ..obs.metrics import MetricsRegistry

            metrics = MetricsRegistry()
        self.metrics = metrics

        # Full literal instrument names (not prefix-built): the metrics
        # CATALOG contract is checked by grep-able literals.
        self._batches = metrics.counter(
            "estpu_exec_batcher_batches_total", "Coalesced launches executed"
        )
        self._requests = metrics.counter(
            "estpu_exec_batcher_requests_total", "Requests through the queue"
        )
        self._coalesced = metrics.counter(
            "estpu_exec_batcher_coalesced_requests_total",
            "Requests served in a batch of >= 2",
        )
        self._cancelled = metrics.counter(
            "estpu_exec_batcher_queue_cancellations_total",
            "Searches cancelled while queued",
        )
        self._shed = metrics.counter(
            "estpu_exec_batcher_shed_total",
            "Requests shed with 429 (queue full)",
        )
        # Rolling-window twins (ISSUE 15): the health report's
        # exec_saturation indicator needs RECENT queue waits and shed
        # rate, not the since-boot cumulatives.
        self._shed_recent = metrics.windowed_counter(
            "estpu_exec_batcher_shed_recent",
            "Requests shed with 429 over the trailing window",
        )
        self._queue_wait_recent = metrics.windowed_histogram(
            "estpu_exec_batcher_queue_wait_recent_ms",
            "Queue wait before launch over the trailing window, ms",
        )
        self._retried = metrics.counter(
            "estpu_exec_batcher_retried_individually_total",
            "Riders retried solo after a coalesced-launch failure",
        )
        self._quarantined_total = metrics.counter(
            "estpu_exec_batcher_groups_quarantined_total",
            "Group keys quarantined to the per-request path",
        )
        self._quarantine_hits_c = metrics.counter(
            "estpu_exec_batcher_quarantine_hits_total",
            "Requests served while group quarantined",
        )
        self._occupancy = metrics.histogram(
            "estpu_exec_batcher_occupancy",
            (0.0,) + OCCUPANCY_BUCKETS,
            "Batch occupancy (pow-2 bucketed riders per launch)",
        )
        self._queue_wait_hist = metrics.histogram(
            "estpu_exec_batcher_queue_wait_ms",
            QUEUE_WAIT_MS_BUCKETS,
            "Queue wait before launch, milliseconds",
        )

        def _queued_depth() -> int:
            # Scrapes race queue mutation: snapshot under the condition
            # lock (a lock-free sum can die mid-iteration and silently
            # report 0 exactly when depth is the signal that matters).
            with self._cv:
                return sum(len(q) for q in self._queues.values())

        metrics.gauge(
            "estpu_exec_batcher_queued",
            "Searches currently waiting in the batch queue",
            fn=_queued_depth,
        )
        self._wait_samples: deque[float] = deque(maxlen=512)
        # Per-group coalescing effectiveness (under _cv): group label ->
        # {launches, riders, tenants_last, tenants_max}. Labeled by the
        # group key's leading element (index name, or "_packed" for the
        # cross-index packed group) so cardinality stays bounded; riders
        # carrying a `tenant_key` attribute (exec/packed.TenantSearch)
        # count distinct tenants per launch — the observable that says
        # whether multi-tenant packing is actually coalescing.
        self._group_stats: "OrderedDict[str, dict]" = OrderedDict()
        # Failure isolation / quarantine state (under _cv).
        self._group_failures: dict[tuple, int] = {}
        # group -> (parole time, weakref to the offending searcher). The
        # weakref pins identity: id() reuse by a NEW searcher at the same
        # address must not inherit a dead group's quarantine.
        self._quarantine: dict[tuple, tuple[float, object]] = {}

    # ------------------------------------------------------------- public

    def execute(
        self, searcher, request, task=None, group_key=(), tenant_key=None
    ) -> object:
        """Run one search through the batching queue (blocking).

        `tenant_key` attributes the request to a QoS lane (the REST
        layer threads the `X-Opaque-Id` header here); riders without
        one fall back to the request's own `lane_key` (packed wrappers
        carry it) and then to the shared `_default` lane.

        Returns the SearchResponse; raises the search's own error
        (including TaskCancelledError for a queue-cancelled task and
        IndexingPressureRejected when load is shed)."""
        self._ensure_thread()
        lane_key = (
            tenant_key
            or getattr(request, "lane_key", None)
            or DEFAULT_LANE
        )
        group = (id(searcher), group_key)
        now = time.monotonic()
        with self._cv:
            # Opportunistic pruning: expired quarantines (and ones whose
            # searcher died — dropped/recreated indices) must not
            # accumulate or leak onto unrelated work.
            for g, (t, ref) in list(self._quarantine.items()):
                if now >= t or ref() is None:
                    self._quarantine.pop(g, None)
                    self._group_failures.pop(g, None)
            entry = self._quarantine.get(group)
            quarantined = entry is not None and entry[1]() is searcher
            if quarantined:
                # Repeat offender: this spec keeps failing coalesced
                # launches — serve it on the plain per-request path so
                # it cannot take batchmates down with it.
                self._quarantine_hits_c.inc()
        if quarantined:
            return searcher.search(request, task=task)
        victim: _Pending | None = None
        with self._cv:
            depth = sum(len(q) for q in self._queues.values())
            if depth >= self.queue_limit:
                # Weighted shedding: before 429ing the arrival, see if a
                # strictly more over-quota lane has a queued rider — the
                # flooding tenant absorbs its own backpressure first.
                victim = self._pick_shed_victim_locked(lane_key)
                if victim is None:
                    self._shed.inc()
                    self._shed_recent.inc()
                    retry_after = self._retry_after_locked(depth, lane_key)
                    message = (
                        f"rejected execution of search: exec batch queue is "
                        f"full [queued={depth}, limit={self.queue_limit}]"
                    )
                    if self.qos is not None:
                        raise self.qos.shed(lane_key, message, retry_after)
                    err = IndexingPressureRejected(message)
                    # Back-off hint for the REST layer's Retry-After
                    # header.
                    err.retry_after_s = retry_after
                    raise err
            queue = self._queues.setdefault(group, deque())
            # Idle groups launch immediately; a group with work in flight
            # (or already queued) opens the continuous-batching window so
            # companions coalesce while the current batch executes.
            busy = bool(queue) or group in self._in_flight
            launch_at = now + (self.max_wait_s if busy else 0.0)
            if task is not None and task.deadline is not None:
                # Deadline-aware: never sit in the queue past the
                # request's own timeout.
                launch_at = min(launch_at, max(now, task.deadline))
            item = _Pending(
                searcher=searcher,
                request=request,
                task=task,
                group=group,
                enqueued_at=now,
                launch_at=launch_at,
                lane=lane_key,
                trace_ctx=TRACER.context(),
            )
            if task is not None:
                task.span_name = "batcher.queue"
            queue.append(item)
            self._cv.notify_all()
        if victim is not None:
            # Wake the evicted rider outside the lock; its execute()
            # raises the 429 built by _pick_shed_victim_locked.
            TRACER.record(
                victim.trace_ctx,
                "batcher.queue",
                victim.enqueued_at,
                time.monotonic(),
                status="error",
                shed=True,
                lane=victim.lane,
            )
            victim.event.set()
        if task is not None:
            task.add_cancel_listener(lambda: self._cancel_queued(item))
        with TRACER.annotate("batcher.await"):
            self._await(item)
        if item.retry_solo:
            # Failure isolation: this rider failed inside the coalesced
            # launch — one individual retry on the plain per-request
            # path, run HERE so a batch of failures never serializes on
            # the scheduler thread. record_filter_usage=False: the
            # coalesced attempt's search_many already counted this
            # request's filter-cache sighting; counting the retry too
            # would let a one-off filter self-admit past min_freq within
            # a single user request.
            return searcher.search(
                request, task=task, record_filter_usage=False
            )
        if item.error is not None:
            raise item.error
        return item.result

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    _GROUP_STATS_MAX = 64  # LRU bound on tracked group labels

    def _note_group_locked(self, group: tuple, live: list) -> None:
        """Record one launch's coalescing stats for its group label.
        Caller holds _cv."""
        gkey = group[1]
        label = str(
            gkey[0] if isinstance(gkey, tuple) and gkey else gkey
        )
        tenants = set()
        for it in live:
            t = getattr(it.request, "tenant_key", None)
            tenants.add(label if t is None else t)
        entry = self._group_stats.get(label)
        if entry is None:
            entry = {
                "launches": 0,
                "riders": 0,
                "coalesced_tenants_last": 0,
                "coalesced_tenants_max": 0,
            }
            self._group_stats[label] = entry
        entry["launches"] += 1
        entry["riders"] += len(live)
        entry["coalesced_tenants_last"] = len(tenants)
        entry["coalesced_tenants_max"] = max(
            entry["coalesced_tenants_max"], len(tenants)
        )
        self._group_stats.move_to_end(label)
        while len(self._group_stats) > self._GROUP_STATS_MAX:
            self._group_stats.popitem(last=False)

    def _retry_after_locked(
        self, depth: int, lane_key: str | None = None
    ) -> int:
        """Retry-After seconds for a shed request: the observed queue-wait
        p50 scaled by how many batches deep the queue is — an honest
        drain-time estimate, clamped to [1, 30]s. Caller holds _cv.

        With QoS attached the p50 comes from the SHED LANE's own windowed
        waits (global p50 only as the cold-lane fallback): a throttled
        heavy tenant's long waits must not inflate the backoff advertised
        to everyone else."""
        if self._wait_samples:
            p50_s = float(
                np.percentile(
                    np.asarray(self._wait_samples, dtype=np.float64), 50
                )
            )
        else:
            p50_s = self.max_wait_s
        if self.qos is not None and lane_key is not None:
            return self.qos.retry_after_s(
                lane_key,
                depth=depth,
                max_batch=self.max_batch,
                fallback_p50_s=p50_s,
            )
        estimate = p50_s * (1.0 + depth / self.max_batch)
        return int(min(30, max(1, math.ceil(estimate))))

    def _pick_shed_victim_locked(self, arriving_lane: str):
        """Weighted shedding: when the queue is full, evict the NEWEST
        queued rider of the most over-quota lane — but only a lane
        STRICTLY more over-quota than the arrival's (otherwise the
        arrival itself is the right victim and the caller sheds it).
        Caller holds _cv; returns the claimed/errored victim (caller
        fires its event outside the lock) or None."""
        if self.qos is None:
            return None
        lanes = set()
        for q in self._queues.values():
            for it in q:
                if not it.claimed:
                    lanes.add(it.lane)
        if not lanes:
            return None
        victim_lane = self.qos.pick_shed_lane(
            sorted(lanes), arriving=arriving_lane
        )
        if victim_lane is None:
            return None
        victim = None
        for q in self._queues.values():
            for it in reversed(q):
                if not it.claimed and it.lane == victim_lane:
                    if victim is None or it.enqueued_at > victim.enqueued_at:
                        victim = it
                    break
        if victim is None:
            return None
        victim.claimed = True
        queue = self._queues.get(victim.group)
        if queue is not None:
            try:
                queue.remove(victim)
            except ValueError:
                pass
            if not queue:
                self._queues.pop(victim.group, None)
        depth = sum(len(q) for q in self._queues.values())
        self._shed.inc()
        self._shed_recent.inc()
        victim.error = self.qos.shed(
            victim_lane,
            f"rejected execution of search: exec batch queue is full "
            f"[queued={depth}, limit={self.queue_limit}] (weighted shed: "
            f"lane [{victim_lane}] over quota)",
            self._retry_after_locked(depth, victim_lane),
        )
        return victim

    def stats(self) -> dict:
        with self._cv:
            samples = np.asarray(self._wait_samples, dtype=np.float64)
            occupancy = self._occupancy.snapshot()
            out = {
                "max_wait_ms": round(self.max_wait_s * 1e3, 3),
                "batches": int(self._batches.value),
                "requests": int(self._requests.value),
                "coalesced_requests": int(self._coalesced.value),
                "occupancy_histogram": {
                    k: int(v)
                    for k, v in occupancy["buckets"].items()
                    if v  # seed shape: only observed buckets appear
                },
                "queue_cancellations": int(self._cancelled.value),
                "rejected": int(self._shed.value),
                "queued": sum(len(q) for q in self._queues.values()),
                # Failure-isolation telemetry: sub-requests retried solo
                # after failing a coalesced launch, and quarantine state.
                "retried_individually": int(self._retried.value),
                "groups_quarantined": int(self._quarantined_total.value),
                "quarantine_hits": int(self._quarantine_hits_c.value),
                "quarantined_now": len(self._quarantine),
                # Per-group coalescing effectiveness: launches/riders and
                # distinct coalesced tenants per launch (packing shows up
                # here as coalesced_tenants_* > 1 under "_packed").
                "groups": {
                    label: dict(entry)
                    for label, entry in self._group_stats.items()
                },
            }
        if samples.size:
            out["queue_wait_p50_ms"] = round(
                float(np.percentile(samples, 50)) * 1e3, 3
            )
            out["queue_wait_p99_ms"] = round(
                float(np.percentile(samples, 99)) * 1e3, 3
            )
        else:
            out["queue_wait_p50_ms"] = 0.0
            out["queue_wait_p99_ms"] = 0.0
        return out

    # ----------------------------------------------------------- internal

    def _ensure_thread(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        with self._cv:
            if self._thread is not None and self._thread.is_alive():
                return
            self._closed = False
            self._thread = threading.Thread(
                target=self._loop, name="exec-batcher", daemon=True
            )
            self._thread.start()

    def _cancel_queued(self, item: _Pending) -> None:
        """Cancel-listener hook: drop a still-queued item immediately."""
        with self._cv:
            if item.claimed or item.event.is_set():
                return  # already launching/done; the task poll handles it
            item.claimed = True
            queue = self._queues.get(item.group)
            if queue is not None:
                try:
                    queue.remove(item)
                except ValueError:
                    pass
                if not queue:
                    self._queues.pop(item.group, None)
            reason = getattr(item.task, "cancel_reason", None) or "cancelled"
            item.error = TaskCancelledError(f"task cancelled [{reason}]")
            self._cancelled.inc()
        TRACER.record(
            item.trace_ctx,
            "batcher.queue",
            item.enqueued_at,
            time.monotonic(),
            status="error",
            cancelled=True,
        )
        item.event.set()

    def _await(self, item: _Pending) -> None:
        """Wait for the scheduler to serve `item`, with a self-healing
        fallback: if the scheduler thread ever dies (or wedges past the
        item's launch window), the caller claims its own item and runs it
        solo — a request can never hang on scheduler health."""
        while not item.event.wait(timeout=0.25):
            with self._cv:
                if item.claimed or item.event.is_set():
                    continue  # executing now; keep waiting
                overdue = time.monotonic() > item.launch_at + 2.0
                dead = self._thread is None or not self._thread.is_alive()
                if not (overdue or dead):
                    continue
                item.claimed = True
                queue = self._queues.get(item.group)
                if queue is not None:
                    try:
                        queue.remove(item)
                    except ValueError:
                        pass
            self._run_batch([item])
            return

    def _loop(self) -> None:
        while True:
            batch: list[_Pending] = []
            group = None
            with self._cv:
                while not self._closed and not any(self._queues.values()):
                    self._cv.wait()
                if self._closed:
                    return
                now = time.monotonic()
                ready_groups: list[tuple] = []  # (group, due, lane)
                for g, q in self._queues.items():
                    if not q:
                        continue
                    due = min(it.launch_at for it in q)
                    if len(q) >= self.max_batch or due <= now:
                        lane = next(
                            (it.lane for it in q if not it.claimed), None
                        )
                        ready_groups.append((g, due, lane))
                if len(ready_groups) == 1:
                    group = ready_groups[0][0]
                elif ready_groups:
                    if self.qos is not None:
                        # Weighted deficit-round-robin: the lane that
                        # spent the most observed launch ms waits while
                        # lighter lanes' groups drain first.
                        group = self.qos.drr_pick(ready_groups)
                    else:
                        best_due = None
                        for g, due, _lane in ready_groups:
                            if best_due is None or due < best_due:
                                best_due, group = due, g
                if group is None:
                    soonest = min(
                        min(it.launch_at for it in q)
                        for q in self._queues.values()
                        if q
                    )
                    self._cv.wait(timeout=max(1e-4, soonest - now))
                    continue
                queue = self._queues[group]
                while queue and len(batch) < self.max_batch:
                    it = queue.popleft()
                    if it.claimed:
                        continue
                    it.claimed = True
                    batch.append(it)
                if not queue:
                    self._queues.pop(group, None)
                if not batch:
                    continue
                self._in_flight.add(group)
            try:
                self._run_batch(batch)
            finally:
                with self._cv:
                    self._in_flight.discard(group)
                    self._cv.notify_all()

    def _run_batch(self, batch: list[_Pending]) -> None:
        now = time.monotonic()
        live: list[_Pending] = []
        faulted: list[tuple[_Pending, Exception]] = []
        # Retrospective spans (queue-wait + coalesced launch) accumulate
        # here and flush AFTER every rider's event fires: span recording
        # must never sit between the result and the caller's wake-up.
        deferred_spans: list[tuple] = []
        for item in batch:
            item.queue_wait_s = now - item.enqueued_at
            task = item.task
            if task is not None and task.cancelled:
                reason = getattr(task, "cancel_reason", None) or "cancelled"
                item.error = TaskCancelledError(f"task cancelled [{reason}]")
                item.event.set()
                continue
            # Queue-wait span: recorded retrospectively under the caller's
            # captured context (the scheduler thread has none of its own).
            deferred_spans.append(
                (
                    item.trace_ctx,
                    "batcher.queue",
                    item.enqueued_at,
                    now,
                    "ok",
                    {"group": repr(item.group[1])},
                )
            )
            try:
                # Injectable per-sub-request launch fault
                # (faults/registry.py `batcher.launch`): evaluated per
                # rider so one injected failure cannot touch batchmates.
                fault_point("batcher.launch")
            # staticcheck: ignore[broad-except] per-rider fault isolation IS the tested feature: an injected launch fault must not touch batchmates
            except Exception as e:
                faulted.append((item, e))
                continue
            live.append(item)
        retry: list[tuple[_Pending, Exception]] = list(faulted)
        launch_id = f"launch-{id(batch):x}-{int(now * 1e6) & 0xFFFFFF:x}"
        for item, e in faulted:
            # The injected fault kept this rider off the launch entirely:
            # give its trace a zero-length launch span carrying the error.
            deferred_spans.append(
                (
                    item.trace_ctx,
                    "batcher.launch",
                    now,
                    now,
                    "error",
                    {
                        "launch_id": launch_id,
                        "error_type": type(e).__name__,
                        **(
                            {"injected_fault": True}
                            if getattr(e, "injected", False)
                            else {}
                        ),
                    },
                )
            )
        if live:
            for it in live:
                if it.task is not None:
                    it.task.span_name = "batcher.launch"
            launch_t0 = time.monotonic()
            try:
                # One launch serves every rider's trace: the profiler
                # sees it once here, each rider's ring gets a
                # `batcher.launch` span recorded below.
                with TRACER.annotate("batcher.launch"):
                    results = live[0].searcher.search_many(
                        [it.request for it in live],
                        tasks=[it.task for it in live],
                    )
            # staticcheck: ignore[broad-except] whole-launch failure fans out to per-rider individual retries; each rider's own error (incl. cancellation) re-raises on its thread
            except Exception as e:  # whole-launch failure
                results = [e] * len(live)
            launch_t1 = time.monotonic()
            if self.qos is not None:
                # Windowed cost accounting: each rider's lane pays an
                # equal share of the OBSERVED launch wall (the span
                # `batcher.launch` times) — the signal DRR deficits and
                # shed-victim choice run on.
                share_ms = (launch_t1 - launch_t0) * 1e3 / max(1, len(live))
                for it in live:
                    self.qos.charge(it.lane, share_ms)
            for item, result in zip(live, results):
                failed = isinstance(result, Exception)
                # The coalesced-launch span, shared across batchmates: the
                # same launch_id and timing land in every rider's trace.
                deferred_spans.append(
                    (
                        item.trace_ctx,
                        "batcher.launch",
                        launch_t0,
                        launch_t1,
                        "error" if failed else "ok",
                        {
                            "launch_id": launch_id,
                            "batch_size": len(live),
                            "coalesced": len(live) >= 2,
                        },
                    )
                )
                if failed:
                    if isinstance(result, _NO_RETRY_ERRORS):
                        item.error = result  # would fail solo too
                        item.event.set()
                    else:
                        retry.append((item, result))
                else:
                    item.result = result
                    item.event.set()
        # Failure isolation: anything that failed while riding the
        # coalesced launch gets ONE individual retry on the plain
        # per-request path — executed by ITS caller's thread (execute()),
        # so a batch of failures never stalls other groups behind the
        # scheduler thread.
        for item, _first_error in retry:
            item.retry_solo = True
            item.event.set()
        # Every rider is unblocked; NOW pay for span recording (a sealed
        # rider trace still accepts these — span_from appends to the
        # sealed span list the ring already holds).
        for ctx, name, t0, t1, status, tags in deferred_spans:
            TRACER.record(ctx, name, t0, t1, status=status, **tags)
        group = batch[0].group if batch else None
        self._batches.inc()
        self._requests.inc(len(batch))
        self._retried.inc(len(retry))
        with self._cv:
            if group is not None:
                if retry:
                    # Repeat-offender tracking: consecutive coalesced
                    # failures quarantine the group to the per-request
                    # path for a cooldown.
                    while len(self._group_failures) > 4096:
                        # Bound residue from groups that never return
                        # (dropped indices): evict oldest-first.
                        self._group_failures.pop(
                            next(iter(self._group_failures))
                        )
                    fails = self._group_failures.get(group, 0) + 1
                    self._group_failures[group] = fails
                    if (
                        fails >= self.QUARANTINE_FAILURES
                        and group not in self._quarantine
                    ):
                        self._quarantine[group] = (
                            time.monotonic() + self.QUARANTINE_TTL_S,
                            weakref.ref(batch[0].searcher),
                        )
                        self._quarantined_total.inc()
                elif live:
                    self._group_failures.pop(group, None)
            if len(live) >= 2:
                self._coalesced.inc(len(live))
            if group is not None and live:
                self._note_group_locked(group, live)
            bucket = 1 << max(0, len(live) - 1).bit_length() if live else 0
            self._occupancy.observe(float(bucket))
            # Two renderings of the same observations: the bounded deque
            # keeps exact recent-window p50/p99 for stats()/Retry-After;
            # the registry histogram is the cumulative Prometheus series
            # (scrapers compute quantiles from buckets).
            for item in batch:
                self._wait_samples.append(item.queue_wait_s)
                self._queue_wait_hist.observe(item.queue_wait_s * 1e3)
                self._queue_wait_recent.record(item.queue_wait_s * 1e3)
                if self.qos is not None:
                    # Per-lane windowed wait — the fairness arc's gate
                    # (and the lane's own Retry-After source).
                    self.qos.note_queue_wait(item.lane, item.queue_wait_s)
