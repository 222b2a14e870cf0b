"""Host milliseconds per search planning the SPMD mesh launch: the wall
time of the program's `mesh.refresh` spans (bringing the mesh's stacked
shard planes up to the engines' generations) and `mesh.compile` spans
(the per-shard query plans lowered shard-uniform and stacked, with the
aggregation plans and filter-cache substitution), over the searches
answered in the window. Read from the program's span-time counters on
`GET /_metrics` (`estpu_span_seconds_total`, obs/tracing.py). Nothing to
read where the program has no such spans."""

UNIT = "ms"
LAYER = "plan + micro-batch queue"
MOVES = "search_qps"
SPANS = ("mesh.refresh", "mesh.compile")


def read(w):
    wall = w.counters.by_label("estpu_span_seconds_total", "span")
    if not w.completed or not any(s in wall for s in SPANS):
        return None
    return sum(wall.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
