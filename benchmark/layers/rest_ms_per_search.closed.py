"""Host milliseconds per search in REST dispatch: the self time of the
program's `rest.request` span (the HTTP handler's body read, routing,
JSON encoding and socket write) and of `search` (`node.search` around the
micro-batcher's queue), over the searches answered in the window. Read
from the program's span-time counters on `GET /_metrics`
(`estpu_span_self_seconds_total`, obs/tracing.py): self time is a span's
wall time less the spans nested in it on the same thread. Nothing to read
where the program has no such counters."""

UNIT = "ms"
LAYER = "REST dispatch"
MOVES = "search_qps"
SPANS = ("rest.request", "search")


def read(w):
    own = w.counters.by_label("estpu_span_self_seconds_total", "span")
    if not w.completed or not any(s in own for s in SPANS):
        return None
    return sum(own.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
