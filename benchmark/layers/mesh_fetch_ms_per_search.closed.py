"""Host milliseconds per search turning the SPMD mesh program's outputs
into the response: the wall time of the program's `mesh.fetch` spans
(`device_get` of the merged scores, ids and totals, the hits located in
their shards, fetch subphases, the response object), over the searches
answered in the window. Read from the program's span-time counters on
`GET /_metrics` (`estpu_span_seconds_total`, obs/tracing.py). Nothing to
read where the program has no such span."""

UNIT = "ms"
LAYER = "reduce + fetch"
MOVES = "search_qps"
SPANS = ("mesh.fetch",)


def read(w):
    wall = w.counters.by_label("estpu_span_seconds_total", "span")
    if not w.completed or not any(s in wall for s in SPANS):
        return None
    return sum(wall.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
