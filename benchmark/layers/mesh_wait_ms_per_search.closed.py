"""Host milliseconds per search in the SPMD mesh launch: the wall time of
the program's `mesh.dispatch` spans (the jitted shard_map call until it
returns: argument transfer, jit cache lookup, enqueue on every chip) and
`mesh.wait` spans (the host blocked until the program's outputs are
ready: per-shard scoring and top-k, then the all-gather merge and psum
totals over ICI), over the searches answered in the window. Read from
the program's span-time counters on `GET /_metrics`
(`estpu_span_seconds_total`, obs/tracing.py). Nothing to read where the
program has no such spans."""

UNIT = "ms"
LAYER = "SPMD mesh launch"
MOVES = "search_qps"
SPANS = ("mesh.dispatch", "mesh.wait")


def read(w):
    wall = w.counters.by_label("estpu_span_seconds_total", "span")
    if not w.completed or not any(s in wall for s in SPANS):
        return None
    return sum(wall.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
