"""Host milliseconds per search fetching and assembling results: the wall
time of the program's `device.fetch` spans (scores, ids and totals copied
to the host, candidates appended) and `search.assemble` spans (sort,
page, hits), over the searches answered in the window. Read from the
program's span-time counters on `GET /_metrics`
(`estpu_span_seconds_total`, obs/tracing.py). Nothing to read where the
program has no such counters."""

UNIT = "ms"
LAYER = "reduce + fetch"
MOVES = "search_qps"
SPANS = ("device.fetch", "search.assemble")


def read(w):
    wall = w.counters.by_label("estpu_span_seconds_total", "span")
    if not w.completed or not any(s in wall for s in SPANS):
        return None
    return sum(wall.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
