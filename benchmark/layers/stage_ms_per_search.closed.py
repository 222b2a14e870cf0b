"""Host milliseconds per search staging work for the device: the wall
time of the program's `device.stage` spans (the segment's jit-input tree,
stacking the riders' plan arrays, host-to-device byte accounting) and
`device.dispatch` spans (the jitted kernel call until it returns:
argument transfer, jit cache lookup, enqueue), over the searches answered
in the window. Read from the program's span-time counters on
`GET /_metrics` (`estpu_span_seconds_total`, obs/tracing.py). Nothing to
read where the program has no such counters."""

UNIT = "ms"
LAYER = "host-to-device staging"
MOVES = "search_qps"
SPANS = ("device.stage", "device.dispatch")


def read(w):
    wall = w.counters.by_label("estpu_span_seconds_total", "span")
    if not w.completed or not any(s in wall for s in SPANS):
        return None
    return sum(wall.get(s, 0.0) for s in SPANS) * 1e3 / w.completed
