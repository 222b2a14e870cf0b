#!/usr/bin/env python
"""Profile the serving path through the ISSUE-14 capture API.

Replaces the three hand-rolled timer scripts (profile_device.py,
profile_sparse.py, profile_staging.py): instead of re-implementing
timeit loops around raw kernels, this drives the REAL serving stack —
Node.search over a zipf corpus — under an on-demand `jax.profiler`
capture window (the `POST /_profiler/start|stop` surface), then reports
what the always-on instruments measured:

- per-(plan class, backend, phase) launch-ms summaries from the
  `estpu_launch_ms` histograms (queue = dispatch return, execute =
  block_until_ready — the split is honest only on real devices; on
  XLA:CPU the work runs inside dispatch),
- the compile census: real XLA compiles, attributed per plan class, and
  retraces (a compile on an already-seen plan key — the
  shape-polymorphism alarm),
- the HBM ledger (`/_cat/hbm` rows), and
- the Perfetto trace directory (load the .trace.json.gz in
  https://ui.perfetto.dev or chrome://tracing).

Run on the real TPU for the ROADMAP residue rounds (packed win, refresh
p50, MXU matmul-vs-elementwise revisit):

    python scripts/profile_capture.py --docs 1000000 --queries 64 --reps 5
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _hist_summary(snap: dict) -> str:
    count = snap["count"]
    if not count:
        return "n=0"
    mean = snap["sum"] / count
    return f"n={count} mean={mean:.3f}ms sum={snap['sum']:.1f}ms"


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--docs", type=int, default=100_000)
    parser.add_argument("--queries", type=int, default=32)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument(
        "--knn", action="store_true",
        help="include a dense_vector field + knn queries in the mix",
    )
    parser.add_argument(
        "--trace-dir", default=None,
        help="capture directory (default: a fresh temp dir)",
    )
    args = parser.parse_args()

    from elasticsearch_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.obs import device as device_obs
    from elasticsearch_tpu.utils.corpus import (
        build_zipf_segment,
        pick_query_terms,
    )

    print("platform:", jax.devices()[0].platform, flush=True)
    rng = np.random.default_rng(11)
    t0 = time.monotonic()
    _, seg = build_zipf_segment(
        args.docs, vocab_size=20_000, seed=23, with_sources=True
    )
    seg.doc_values["rank"] = rng.random(args.docs).astype(np.float64)
    d = 16
    if args.knn:
        seg.vectors["vec"] = rng.standard_normal(
            (args.docs, d), dtype=np.float32
        )
    node = Node()
    props = {"body": {"type": "text"}, "rank": {"type": "float"}}
    if args.knn:
        props["vec"] = {
            "type": "dense_vector", "dims": d, "similarity": "l2_norm",
        }
    node.create_index("profile", {"mappings": {"properties": props}})
    engine = node.indices["profile"].engines[0]
    engine.restore_segments([(seg, np.ones(args.docs, dtype=bool))])
    node.refresh("profile")
    print(f"corpus+index build: {time.monotonic() - t0:.1f}s", flush=True)

    term_sets = pick_query_terms(seg, rng, args.queries)
    bodies = []
    for i, terms in enumerate(term_sets):
        lo = float(rng.random() * 0.4)
        bodies.append(
            {
                "query": {
                    "bool": {
                        "must": [{"match": {"body": " ".join(terms[:2])}}],
                        "filter": [
                            {"range": {"rank": {"gte": lo, "lte": lo + 0.5}}}
                        ],
                    }
                },
                "size": 10,
            }
        )
        if args.knn and i % 4 == 0:
            bodies.append(
                {
                    "knn": {
                        "field": "vec",
                        "query_vector": rng.standard_normal(d).tolist(),
                        "k": 10,
                        "num_candidates": 100,
                    }
                }
            )
    for body in bodies:  # warm: every shape compiles outside the capture
        node.search("profile", body)

    census0 = device_obs.process_census()
    start = node.profiler_start(
        {"duration_s": 120, "trace_dir": args.trace_dir}
    )
    t0 = time.monotonic()
    times = []
    for _ in range(args.reps):
        for body in bodies:
            t1 = time.monotonic()
            node.search("profile", body)
            times.append(time.monotonic() - t1)
    elapsed = time.monotonic() - t0
    stop = node.profiler_stop()
    census1 = device_obs.process_census()

    n = len(times)
    print(
        f"\nserved {n} searches in {elapsed:.2f}s "
        f"(p50 {np.median(times) * 1e3:.2f}ms, "
        f"p99 {np.percentile(times, 99) * 1e3:.2f}ms)",
        flush=True,
    )

    print("\n== estpu_launch_ms (plan class / backend / phase) ==")
    family = node.metrics.family("estpu_launch_ms")
    samples = family[2] if family is not None else {}
    for key, snap in sorted(samples.items()):
        labels = dict(key)
        print(
            f"  {labels.get('plan_class', '?'):<22} "
            f"{labels.get('backend', '?'):<16} "
            f"{labels.get('phase', '?'):<8} {_hist_summary(snap)}"
        )

    print("\n== compile census ==")
    compile_section = node.device.compile_census()
    for kind, entry in compile_section["attributed_xla_compiles"].items():
        print(
            f"  {kind:<22} compiles={entry['compiles']} "
            f"compile_ms={entry['compile_ms']} retraces={entry['retraces']}"
        )
    print(
        f"  window: compiles={census1['compiles'] - census0['compiles']} "
        f"retraces={census1['retraces'] - census0['retraces']} "
        f"(a nonzero capture-window retrace means a plan class recompiles "
        f"per query)"
    )

    print("\n== HBM ledger (/_cat/hbm) ==")
    for row in node.cat_hbm():
        print(
            f"  {row['node']:<10} {row['label']:<14} {row['index']:<12} "
            f"{row['bytes']}"
        )

    print(
        f"\nPerfetto trace dir: {stop['trace_dir']} "
        f"(capture {stop['duration_ms']:.0f}ms; load the .trace.json.gz "
        f"at ui.perfetto.dev)"
    )
    print(f"obs trace ring id: {stop['trace_id']} (GET /_traces/<id>)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
