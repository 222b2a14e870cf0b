"""Host milliseconds per search in query compile and planning: the wall
time of the program's `query.compile` spans (the query's plan arrays and
filter-cache substitution, per search per segment) and `exec.plan` spans
(the exec planner's backend decision), over the searches answered in the
window. Read from the program's span-time counters on `GET /_metrics`
(`estpu_span_seconds_total`, obs/tracing.py). Nothing to read where the
program has no such counters."""

UNIT = "ms"
LAYER = "plan + micro-batch queue"
MOVES = "search_qps"
SPANS = ("query.compile", "exec.plan")


def read(w):
    wall = w.counters.by_label("estpu_span_seconds_total", "span")
    if not w.completed or not any(s in wall for s in SPANS):
        return None
    return sum(wall.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
