"""End-to-end distributed tracing for the TPU query path.

One search produces ONE span tree: the REST root span (rest/server.py),
the gateway hop (cluster/gateway.py), per-copy transport sends
(cluster/transport.py — context rides the payload so spans from remote
ClusterNode shard executions parent correctly), per-shard scoring passes
(search/coordinator.py), planner decisions (exec/planner via tagged
events), micro-batcher queue-wait + coalesced-launch spans
(exec/batcher.py, shared across batchmates via a common launch_id), and
per-segment XLA launches (search/service.py). The granularity is the
kernel launch — an XLA program is not interruptible or observable inside,
so one segment's launch is one leaf span, the same boundary
common/tasks.py polls cancellation at.

The reference's shape for this triad is TaskManager.java (what is
running), `index.search.slowlog.*` (what was slow) and the search profile
API (where the time went); this module is the substrate all three read
from here.

Propagation is via ``contextvars`` inside a process (REST handler threads,
the in-process transport hub) plus explicit wire context: the REST edge
accepts/returns W3C ``traceparent`` (and tags ``X-Opaque-Id``), and
transport sends attach ``{"_trace": {trace_id, parent}}`` to the payload
so the receiving node re-activates the caller's context exactly as a
cross-host transport would.

Finished traces land in a bounded ring buffer (ESTPU_TRACE_BUFFER, default
256) served by `GET /_traces[/{trace_id}]`; ``?format=chrome`` renders
Chrome trace-event JSON loadable in Perfetto / chrome://tracing.

Every span a handle opens also reaches two more sinks, traced or not:

- the JAX profiler: a ``jax.profiler.TraceAnnotation("estpu/<name>")``
  around the span, so a capture (`POST /_profiler/start`, or any
  ``jax.profiler`` trace of the process) holds the program's host work on
  the same clock and thread lines as the device's ops. The micro-batcher's
  scheduler thread has no trace context; its steps reach the profiler this
  way, and ``Tracer.annotate`` names work that serves several traces at
  once (a coalesced launch);
- the span-time counters on `GET /_metrics`: per span name, how many
  closed (`estpu_spans_total`), their wall seconds
  (`estpu_span_seconds_total`) and their self seconds
  (`estpu_span_self_seconds_total`: wall time minus that of the spans
  nested inside on the same thread).

Retrospective spans (``Tracer.record``) reach the ring only.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any

from .metrics import MetricsRegistry

# (trace_id, span_id) of the active span on this thread/context.
_CURRENT: contextvars.ContextVar[tuple[str, str] | None] = (
    contextvars.ContextVar("estpu_trace_ctx", default=None)
)

# What every span's profiler annotation is named: PROFILER_PREFIX + name.
PROFILER_PREFIX = "estpu/"
_PROFILER_NAMES: dict[str, str] = {}
_TRACE_ME = None  # jax.profiler.TraceAnnotation, imported on first use

# Per thread, the stack of handles open on it (self-time accounting).
_OPEN = threading.local()


def _annotation(name: str):
    """A profiler annotation for span `name`, not yet entered. No tags:
    it is on the hot path, where an annotation costs ~1 us."""
    global _TRACE_ME
    if _TRACE_ME is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ME = TraceAnnotation
    label = _PROFILER_NAMES.get(name)
    if label is None:
        label = _PROFILER_NAMES.setdefault(name, PROFILER_PREFIX + name)
    return _TRACE_ME(label)


def _new_trace_id() -> str:
    return uuid.uuid4().hex  # 32 hex chars, the W3C traceparent width


# Span ids are HOT (several per search): a random per-process prefix + a
# GIL-atomic counter gives unique 16-hex ids at ~15x less cost than a
# uuid4 per span (measured ~5us each — a third of the whole span budget).
_SPAN_ID_PREFIX = uuid.uuid4().hex[:8]
_SPAN_ID_COUNTER = itertools.count(1)


def _new_span_id() -> str:
    return f"{_SPAN_ID_PREFIX}{next(_SPAN_ID_COUNTER) & 0xFFFFFFFF:08x}"


def parse_traceparent(value: str | None) -> tuple[str, str] | None:
    """W3C `traceparent: 00-<trace32hex>-<span16hex>-<flags>` →
    (trace_id, parent_span_id), or None on anything malformed (a broken
    header must start a fresh trace, never crash the request)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    _version, trace_id, span_id, _flags = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


@dataclass(slots=True)
class Span:
    """One timed node of a trace tree."""

    trace_id: str
    span_id: str
    parent_id: str | None
    name: str
    start_ms: float  # epoch millis (display)
    start_mono: float  # monotonic seconds (duration math)
    duration_ms: float | None = None  # None while open
    tags: dict[str, Any] = field(default_factory=dict)
    events: list[dict[str, Any]] = field(default_factory=list)
    status: str = "ok"  # ok | error

    def finish(self, end_mono: float | None = None) -> None:
        end = time.monotonic() if end_mono is None else end_mono
        self.duration_ms = max(0.0, (end - self.start_mono) * 1e3)

    def add_event(self, name: str, **attrs: Any) -> None:
        self.events.append(
            {
                "name": name,
                # staticcheck: ignore[wallclock-duration] user-facing event epoch timestamp in the trace export, not a duration
                "timestamp_ms": time.time() * 1e3,
                **attrs,
            }
        )

    def record_error(self, exc: BaseException) -> None:
        self.status = "error"
        self.tags["error_type"] = type(exc).__name__
        self.tags["error_reason"] = str(exc)[:200]
        # Fault-injected errors (faults/registry.py marks them) tag their
        # enclosing span so chaos runs produce readable traces.
        if getattr(exc, "injected", False):
            self.tags["injected_fault"] = True

    def to_json(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_time_in_millis": int(self.start_ms),
            # Sub-millisecond start for renderers (chrome_trace): spans
            # fanned within one millisecond must keep their real order.
            "start_ms": round(self.start_ms, 3),
            "duration_ms": (
                round(self.duration_ms, 3)
                if self.duration_ms is not None
                # Live export (`profile: true` inlines the still-open
                # request trace): honest elapsed-so-far, flagged.
                else round((time.monotonic() - self.start_mono) * 1e3, 3)
            ),
            "status": self.status,
        }
        if self.duration_ms is None:
            out["in_progress"] = True
        if self.tags:
            out["tags"] = dict(self.tags)
        if self.events:
            out["events"] = list(self.events)
        return out


class _SpanHandle:
    """Context manager for one span: activates it, finishes it, records
    errors without swallowing them, and (optionally) mirrors the span name
    onto a Task so `GET /_tasks` can show what a task is doing now.

    With no span (untraced, or `Tracer.annotate`) it still annotates the
    profiler and feeds the span-time counters."""

    __slots__ = (
        "tracer", "span", "name", "_token", "_task", "_prev_task_span",
        "_annotation", "_t0", "_nested_s",
    )

    def __init__(
        self,
        tracer: "Tracer",
        span: Span | None,
        task=None,
        name: str | None = None,
    ):
        self.tracer = tracer
        self.span = span
        self.name = span.name if span is not None else name
        self._token = None
        self._task = task
        self._prev_task_span = None

    def __enter__(self) -> Span | None:
        self._annotation = _annotation(self.name)
        self._annotation.__enter__()
        try:
            stack = _OPEN.stack
        except AttributeError:
            stack = _OPEN.stack = []
        stack.append(self)
        self._nested_s = 0.0
        if self.span is not None:
            self._token = _CURRENT.set(
                (self.span.trace_id, self.span.span_id)
            )
            if self._task is not None:
                self._prev_task_span = getattr(self._task, "span_name", None)
                self._task.span_name = self.span.name
        self._t0 = time.perf_counter()
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        wall_s = time.perf_counter() - self._t0
        stack = _OPEN.stack
        if stack[-1] is self:
            stack.pop()
        else:
            stack.remove(self)
        if stack:
            stack[-1]._nested_s += wall_s
        try:
            self.tracer._note_span(self.name, wall_s, wall_s - self._nested_s)
            if self.span is not None:
                if exc is not None:
                    self.span.record_error(exc)
                self.span.finish()
                if self._token is not None:
                    _CURRENT.reset(self._token)
                if self._task is not None:
                    self._task.span_name = self._prev_task_span
                self.tracer._on_span_closed(self.span)
        finally:
            self._annotation.__exit__(None, None, None)
        return False  # never swallow


class Tracer:
    """Thread-safe span collector with a bounded ring of finished traces.

    Spans are cheap no-ops when no trace is active on the calling context
    (``span()`` returns a dummy handle), so instrumented hot paths pay one
    ContextVar read when untraced."""

    def __init__(self, max_traces: int | None = None):
        if max_traces is None:
            max_traces = int(os.environ.get("ESTPU_TRACE_BUFFER", 256) or 256)
        self.max_traces = max(1, max_traces)
        self._lock = threading.Lock()
        # trace_id -> {span_id -> Span}: spans of traces still in flight.
        self._active: dict[str, dict[str, Span]] = {}
        # trace_id of each active trace's ROOT span (finishing it seals
        # the trace into the ring).
        self._roots: dict[str, str] = {}
        self._ring: deque[tuple[str, list[Span]]] = deque(
            maxlen=self.max_traces
        )
        self._index: dict[str, list[Span]] = {}
        # Span-time counters (module docstring), merged into the node's
        # `GET /_metrics`. span name -> (count, wall s, self s) counters.
        self.metrics = MetricsRegistry()
        self._span_series: dict[str, tuple] = {}

    # --------------------------------------------------------- span entry

    def context(self) -> tuple[str, str] | None:
        """(trace_id, span_id) of the active span, or None. This is the
        wire context transport sends attach to their payloads."""
        return _CURRENT.get()

    def current_trace_id(self) -> str | None:
        ctx = _CURRENT.get()
        return None if ctx is None else ctx[0]

    def start_trace(
        self,
        name: str,
        traceparent: str | None = None,
        task=None,
        **tags: Any,
    ) -> _SpanHandle:
        """Open a ROOT span (new trace, or continuing an inbound W3C
        traceparent). Finishing the root seals the trace into the ring."""
        parent = parse_traceparent(traceparent)
        trace_id = parent[0] if parent else _new_trace_id()
        span = Span(
            trace_id=trace_id,
            span_id=_new_span_id(),
            parent_id=parent[1] if parent else None,
            name=name,
            # staticcheck: ignore[wallclock-duration] user-facing span start epoch timestamp; durations come from start_mono
            start_ms=time.time() * 1e3,
            start_mono=time.monotonic(),
            tags=dict(tags),
        )
        with self._lock:
            self._active.setdefault(trace_id, {})[
                span.span_id
            ] = span
            self._roots.setdefault(trace_id, span.span_id)
        return _SpanHandle(self, span, task=task)

    def span(
        self, name: str, root: bool = False, task=None, **tags: Any
    ) -> _SpanHandle:
        """Open a child of the context's active span. With no active trace:
        a no-op handle, unless ``root=True`` which starts a new trace (the
        entry points — REST dispatch, Node.search — use root=True so every
        request is traced even off the HTTP path)."""
        ctx = _CURRENT.get()
        if ctx is None:
            if not root:
                return _SpanHandle(self, None, name=name)
            return self.start_trace(name, task=task, **tags)
        return self.span_from(ctx, name, task=task, **tags)

    def annotate(self, name: str) -> _SpanHandle:
        """A span for the profiler and the span-time counters only, never
        the ring: for work that serves several traces at once, such as a
        coalesced launch, whose per-rider ring spans `record()` writes
        after the fact."""
        return _SpanHandle(self, None, name=name)

    def span_from(
        self, ctx: tuple[str, str], name: str, task=None, **tags: Any
    ) -> _SpanHandle:
        """Open a child of an EXPLICIT (trace_id, parent_span_id) context —
        the receive side of wire propagation (cluster transport handlers,
        batcher scheduler threads)."""
        trace_id, parent_id = ctx
        span = Span(
            trace_id=trace_id,
            span_id=_new_span_id(),
            parent_id=parent_id,
            name=name,
            # staticcheck: ignore[wallclock-duration] user-facing span start epoch timestamp; durations come from start_mono
            start_ms=time.time() * 1e3,
            start_mono=time.monotonic(),
            tags=dict(tags),
        )
        with self._lock:
            # A trace that already sealed (root closed while an async
            # straggler reports) still accepts the span into the sealed
            # list so nothing is silently dropped.
            sealed = self._index.get(trace_id)
            if trace_id in self._active:
                self._active[trace_id][span.span_id] = span
            elif sealed is not None:
                sealed.append(span)
            else:
                self._active.setdefault(trace_id, {})[
                    span.span_id
                ] = span
                self._roots.setdefault(trace_id, span.span_id)
        return _SpanHandle(self, span, task=task)

    def record(
        self,
        ctx: tuple[str, str] | None,
        name: str,
        start_mono: float,
        end_mono: float,
        status: str = "ok",
        **tags: Any,
    ) -> None:
        """Record a RETROSPECTIVE span (already-elapsed interval) under an
        explicit context — the micro-batcher's queue-wait and coalesced-
        launch spans, measured on the scheduler thread after the fact."""
        if ctx is None:
            return
        handle = self.span_from(ctx, name, **tags)
        if handle.span is None:
            return
        handle.span.start_mono = start_mono
        # staticcheck: ignore[wallclock-duration] reconstructs the span's epoch start for the trace export; elapsed part stays monotonic
        handle.span.start_ms = time.time() * 1e3 - max(
            0.0, (time.monotonic() - start_mono) * 1e3
        )
        handle.span.status = status
        handle.span.finish(end_mono)
        self._on_span_closed(handle.span)

    def event(self, name: str, **attrs: Any) -> None:
        """Attach an event to the context's active span (e.g. the planner's
        backend decision). No-op when untraced."""
        ctx = _CURRENT.get()
        if ctx is None:
            return
        with self._lock:
            spans = self._active.get(ctx[0])
            span = None if spans is None else spans.get(ctx[1])
        if span is not None:
            span.add_event(name, **attrs)

    def tag(self, **tags: Any) -> None:
        """Merge tags into the context's active span. No-op untraced."""
        ctx = _CURRENT.get()
        if ctx is None:
            return
        with self._lock:
            spans = self._active.get(ctx[0])
            span = None if spans is None else spans.get(ctx[1])
        if span is not None:
            span.tags.update(tags)

    # ------------------------------------------------------------- sealing

    def _note_span(self, name: str, wall_s: float, self_s: float) -> None:
        series = self._span_series.get(name)
        if series is None:
            series = self._span_series.setdefault(
                name,
                (
                    self.metrics.counter(
                        "estpu_spans_total", "Spans closed, by name",
                        span=name,
                    ),
                    self.metrics.counter(
                        "estpu_span_seconds_total",
                        "Wall seconds of closed spans, by name",
                        span=name,
                    ),
                    self.metrics.counter(
                        "estpu_span_self_seconds_total",
                        "Wall seconds of closed spans minus the spans "
                        "nested in them on the same thread, by name",
                        span=name,
                    ),
                ),
            )
        count, wall, own = series
        count.inc()
        wall.inc(wall_s)
        own.inc(max(0.0, self_s))

    def _on_span_closed(self, span: Span) -> None:
        # Lock-free fast path: only the trace's ROOT span seals anything
        # (dict reads are GIL-atomic; the root close re-checks under the
        # lock before mutating).
        if self._roots.get(span.trace_id) != span.span_id:
            return
        with self._lock:
            root_id = self._roots.get(span.trace_id)
            if root_id != span.span_id:
                return
            spans = self._active.pop(span.trace_id, None)
            self._roots.pop(span.trace_id, None)
            if spans is None:
                return
            trace = list(spans.values())
            if len(self._ring) == self._ring.maxlen:
                # Capture the entry the full deque is about to evict and
                # drop its index in O(1) — scanning the ring per seal was
                # measured at ~15us/search once the buffer filled.
                evicted_tid, evicted = self._ring[0]
                if self._index.get(evicted_tid) is evicted:
                    self._index.pop(evicted_tid, None)
            self._ring.append((span.trace_id, trace))
            self._index[span.trace_id] = trace

    # -------------------------------------------------------------- export

    def traces(self, limit: int = 50) -> list[dict[str, Any]]:
        """Newest-first summaries of the buffered traces."""
        with self._lock:
            items = list(self._ring)[-limit:]
        out = []
        for trace_id, spans in reversed(items):
            root = next((s for s in spans if s.parent_id is None), spans[0])
            out.append(
                {
                    "trace_id": trace_id,
                    "root": root.name,
                    "status": (
                        "error"
                        if any(s.status == "error" for s in spans)
                        else "ok"
                    ),
                    "spans": len(spans),
                    "start_time_in_millis": int(root.start_ms),
                    "duration_ms": (
                        round(root.duration_ms, 3)
                        if root.duration_ms is not None
                        else None
                    ),
                }
            )
        return out

    def get(self, trace_id: str) -> list[Span] | None:
        """Spans of one trace: sealed first, else the live in-flight set
        (so `profile: true` can inline the request's own tree mid-flight)."""
        with self._lock:
            sealed = self._index.get(trace_id)
            if sealed is not None:
                return list(sealed)
            live = self._active.get(trace_id)
            return None if live is None else list(live.values())

    def export(self, trace_id: str) -> dict[str, Any] | None:
        spans = self.get(trace_id)
        if spans is None:
            return None
        return {
            "trace_id": trace_id,
            "spans": [s.to_json() for s in spans],
        }

    def to_chrome(self, trace_id: str) -> dict[str, Any] | None:
        """Chrome trace-event JSON (the `?format=chrome` shape): complete
        'X' events in microseconds, loadable in Perfetto."""
        spans = self.get(trace_id)
        if spans is None:
            return None
        return chrome_trace([s.to_json() for s in spans])

    def clear(self) -> None:
        """Drop buffered AND in-flight spans (test isolation)."""
        with self._lock:
            self._ring.clear()
            self._index.clear()
            self._active.clear()
            self._roots.clear()

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {
                "buffered_traces": len(self._ring),
                "in_flight_traces": len(self._active),
                "buffer_capacity": self.max_traces,
            }


def splice_spans(span_lists: list[list[dict]]) -> list[dict]:
    """Splice span-JSON fragments collected from several processes into
    ONE tree's span list — the assembly half of distributed tracing.
    Remote spans already parent into the caller's tree via the `_trace`
    wire context, so no id fixup is needed; splicing is dedup (by
    span_id — the same span can arrive both locally and via a fragment
    when cluster members share a process, and a finished version beats an
    in-progress one) plus a stable start-time ordering."""
    by_id: dict[str, dict] = {}
    for spans in span_lists:
        for span in spans or ():
            sid = str(span.get("span_id"))
            prev = by_id.get(sid)
            if prev is None or (
                prev.get("in_progress") and not span.get("in_progress")
            ):
                by_id[sid] = span
    return sorted(
        by_id.values(),
        key=lambda s: (
            s.get("start_ms", s.get("start_time_in_millis", 0)),
            str(s.get("span_id")),
        ),
    )


def collect_fragments(
    local_spans: list[Span] | None, fragment_results: dict
) -> tuple[list[dict], int]:
    """The coordinator half of trace assembly, shared by Node.get_trace
    and ProcCluster.trace: this process' own spans plus the
    `trace_fragment` fan results → (ONE spliced span-JSON list, count of
    remote spans collected)."""
    fragments: list[list[dict]] = []
    if local_spans is not None:
        fragments.append([s.to_json() for s in local_spans])
    collected = 0
    for node_id in sorted(fragment_results):
        spans = (fragment_results[node_id] or {}).get("spans")
        if spans:
            fragments.append(spans)
            collected += len(spans)
    return splice_spans(fragments), collected


def chrome_trace(spans: list[dict]) -> dict[str, Any]:
    """Chrome trace-event JSON from span JSON (`Span.to_json` shapes):
    complete 'X' events in microseconds. Spans are laned by their `node`
    tag — one tid per node — so a spliced cluster trace renders each
    worker process as its own track in Perfetto."""
    tids: dict[str, int] = {}
    events = []
    for span in spans:
        node = str((span.get("tags") or {}).get("node", ""))
        tid = tids.setdefault(node, len(tids) + 1)
        args: dict[str, Any] = {
            "span_id": span.get("span_id"),
            "parent_id": span.get("parent_id"),
            "status": span.get("status", "ok"),
        }
        args.update(span.get("tags") or {})
        if span.get("events"):
            args["events"] = span["events"]
        events.append(
            {
                "name": span.get("name", "?"),
                "ph": "X",
                # Chrome wants microseconds; the float start_ms keeps
                # sub-millisecond ordering of fanned spans.
                "ts": float(
                    span.get(
                        "start_ms", span.get("start_time_in_millis", 0)
                    )
                )
                * 1e3,
                "dur": max(1.0, float(span.get("duration_ms") or 0.0) * 1e3),
                "pid": 1,
                "tid": tid,
                "cat": "estpu",
                "args": args,
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# The process-wide tracer every instrumented site writes through, like
# faults.REGISTRY: in-process cluster nodes share one trace store, which
# is exactly what lets a remote shard execution land in its caller's tree.
TRACER = Tracer()
