"""Share of the window's searches of a mesh-served index that the one
SPMD program answered: served / (served + fallback), over the change of
`estpu_mesh_served_total` (by shape) and `estpu_mesh_fallback_total` (by
reason, each a search the host-loop coordinator answered instead).
Nothing to read where the program has no such counters or served no
search on the mesh path."""

UNIT = "fraction"
LAYER = "plan (routing)"
MOVES = "search_qps"


def read(w):
    served = w.counters.sum("estpu_mesh_served_total")
    total = served + w.counters.sum("estpu_mesh_fallback_total")
    return served / total if total else None
