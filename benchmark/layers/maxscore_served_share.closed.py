"""Share of the window's scored disjunctions of two or more terms that
the MaxScore kernel answered: served / (served + fallback + oracle +
bypass) over the change of `estpu_sparse_maxscore_total` by outcome.
`fallback` counts searches re-run on the exhaustive kernel after the
MaxScore kernel's threshold check failed, `oracle` MaxScore plans the exec
planner routed to the CPU oracle, `bypass` disjunctions that were not
lowered for MaxScore. Nothing to read where the program has no such
counter."""

UNIT = "fraction"
LAYER = "device kernels"
MOVES = "search_qps"
OUTCOMES = ("served", "fallback", "oracle", "bypass")


def read(w):
    by = w.counters.by_label("estpu_sparse_maxscore_total", "outcome")
    total = sum(by.get(o, 0.0) for o in OUTCOMES)
    return by.get("served", 0.0) / total if total else None
