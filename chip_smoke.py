#!/usr/bin/env python
"""Chip smoke: serve REST `_bulk`/`_search` over a seeded Zipf corpus on the
TPU, and check every answer against the CPU oracle.

One process drives the served path a user calls: an in-process REST server
(`rest/server.create_server`), `_bulk` ingest of `--docs` documents (default
1,000,000) in chunks, `_refresh`, then 32 `_search` requests (`size: 10`)
mixing 2-5 term `match` disjunctions and `bool` must(2-term match) +
filter(term on `tag`). Every answer must equal the CPU oracle
(`search/oracle.OracleSearcher` over a segment built independently by
`utils/corpus.build_zipf_segment`) under bench.py's `ranked_match`: same ids,
order modulo ties within 4 ulp, fp32 scores within 4 ulp, equal totals.

The exec planner is switched off (ESTPU_EXEC_PLANNER=0) so no request is
routed to the CPU oracle backend, and the `estpu_launch_ms` instruments must
show at least one device launch per request. With `--chips 4` the script
runs only the SPMD mesh path instead: a 4-shard index served by one
shard_map program across four chips, compared with the oracle's 4-shard
scatter/gather.

Exits non-zero, printing no result line, when JAX finds no TPU or any phase
fails. The last stdout line on success is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
Figures printed on the way are smoke figures, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

K = 10
N_QUERIES = 32
VOCAB = 30_000
TAGS = ("amber", "blue", "green", "red", "violet")
BULK_DOCS = 10_000  # docs per `_bulk` request (~2 MB of NDJSON)
INDEX = "corpus"
MAPPINGS = {
    "properties": {
        "body": {"type": "text"},
        "tag": {"type": "keyword"},
        "rank": {"type": "float"},
    }
}


class SmokeFailure(Exception):
    """A phase did not do what the chip run requires."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int) -> dict:
    """The device stamp; refuses any platform but the TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"JAX found no TPU (platform {dev.platform!r}); the chip smoke "
            "never falls back to another backend"
        )
    if len(devices) < n_chips:
        raise SmokeFailure(f"--chips {n_chips} but JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# --------------------------------------------------------------- corpus


class Corpus:
    """The seeded documents: Zipf bodies over `t<i>` terms (the draw
    build_zipf_segment makes for the same seed), a keyword `tag` and a
    float `rank` per doc. `oracle` is the CPU reference segment, built
    from the same draw without the analysis chain or the device code;
    `shard_of` is each doc's murmur3 `_id` routing over `n_shards`."""

    def __init__(self, n_docs: int, seed: int, n_shards: int):
        from elasticsearch_tpu.index.mapping import Mappings
        from elasticsearch_tpu.parallel.routing import shard_for_id
        from elasticsearch_tpu.utils.corpus import (
            build_zipf_segment,
            keyword_field,
            zipf_tokens,
        )

        self.n_docs = n_docs
        self.n_shards = n_shards
        lengths, self.tokens = zipf_tokens(n_docs, VOCAB, seed)
        self.offsets = np.concatenate([[0], np.cumsum(lengths)])
        rng = np.random.default_rng(seed + 1)
        self.tags = rng.integers(0, len(TAGS), n_docs)
        self.ranks = rng.random(n_docs, dtype=np.float32)
        self.mappings = Mappings(properties=MAPPINGS["properties"])
        _, self.oracle = build_zipf_segment(n_docs, VOCAB, seed)
        self.oracle.fields["tag"] = keyword_field("tag", self.tags, TAGS)
        self.oracle.doc_values["rank"] = self.ranks.astype(np.float64)
        self._names = np.array([f"t{i}" for i in range(VOCAB)])
        # floorMod(murmur3(_id), 1) is 0: one shard needs no hashing.
        self.shard_of = np.zeros(n_docs, dtype=np.int64) if n_shards == 1 else np.array(
            [shard_for_id(str(i), n_shards) for i in range(n_docs)]
        )

    def bulk_bodies(self):
        """NDJSON `_bulk` bodies of BULK_DOCS docs each, `_id` = doc number."""
        for lo in range(0, self.n_docs, BULK_DOCS):
            hi = min(self.n_docs, lo + BULK_DOCS)
            words = self._names[self.tokens[self.offsets[lo]:self.offsets[hi]]]
            lines = []
            for i in range(lo, hi):
                a, b = self.offsets[i] - self.offsets[lo], self.offsets[i + 1] - self.offsets[lo]
                lines.append('{"index":{"_id":"%d"}}' % i)
                lines.append(json.dumps({
                    "body": " ".join(words[a:b].tolist()),
                    "tag": TAGS[self.tags[i]],
                    "rank": float(self.ranks[i]),
                }))
            yield hi - lo, "\n".join(lines) + "\n"

    def queries(self, seed: int) -> list[dict]:
        """N_QUERIES `_search` bodies alternating the two shapes; terms are
        drawn like pick_query_terms (one head term + mid-df terms)."""
        from elasticsearch_tpu.utils.corpus import pick_query_terms

        rng = np.random.default_rng(seed + 2)
        out = []
        for i in range(N_QUERIES):
            if i % 2 == 0:
                terms = pick_query_terms(self.oracle, rng, 1, int(rng.integers(2, 6)))[0]
                query = {"match": {"body": " ".join(terms)}}
            else:
                terms = pick_query_terms(self.oracle, rng, 1, 2)[0]
                query = {"bool": {
                    "must": [{"match": {"body": " ".join(terms)}}],
                    "filter": [{"term": {"tag": TAGS[int(rng.integers(len(TAGS)))]}}],
                }}
            out.append({"query": query, "size": K, "track_total_hits": True})
        return out

    def expected(self, body: dict):
        """(oracle doc ids, fp32 scores, total) of one request: the
        scatter/gather — each shard's top-k under the index-wide statistics
        (the full segment's), merged by (score desc, shard, doc)."""
        from elasticsearch_tpu.query.dsl import parse_query
        from elasticsearch_tpu.search.oracle import OracleSearcher

        query = parse_query(body["query"])
        hits, total = [], 0
        for s in range(self.n_shards):
            scores, ids, t = OracleSearcher(
                self.oracle, self.mappings, live=self.shard_of == s
            ).search(query, K)
            total += t
            hits += [(-float(sc), s, int(d)) for sc, d in zip(scores, ids)]
        hits.sort()
        hits = hits[:K]
        return ([d for _, _, d in hits],
                np.array([-sc for sc, _, _ in hits], np.float32), total)


# --------------------------------------------------------------- server


class Server:
    """The REST server in this process, `serve_forever` on a daemon thread."""

    def __init__(self):
        from elasticsearch_tpu.rest.server import create_server

        self.http, self.rest = create_server(port=0)
        self.port = self.http.server_address[1]
        self._thread = threading.Thread(target=self.http.serve_forever, daemon=True)
        self._thread.start()

    def call(self, method: str, path: str, body=None):
        """JSON (or text for /_metrics) of one request; any HTTP error fails."""
        data = None
        headers = {}
        if body is not None:
            data = (body if isinstance(body, str) else json.dumps(body)).encode()
            headers["Content-Type"] = (
                "application/x-ndjson" if isinstance(body, str) else "application/json"
            )
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data, method=method,
            headers=headers,
        )
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                raw = resp.read().decode()
                ctype = resp.headers.get("Content-Type", "")
        except urllib.error.HTTPError as e:
            raise SmokeFailure(f"{method} {path} -> HTTP {e.code}: {e.read()[:2000]!r}")
        return json.loads(raw) if "json" in ctype else raw

    def close(self) -> None:
        self.http.shutdown()
        self.http.server_close()
        self._thread.join(timeout=30)
        self.rest.close()


def launch_counts(server: Server) -> dict[str, float]:
    """Completed kernel launches per backend from the `estpu_launch_ms`
    histograms: one launch is one `execute` or `total` sample. The
    micro-batcher's own `batcher` samples time the envelope around a
    group, which the CPU oracle may have served, so they do not count."""
    counts: dict[str, float] = {}
    for line in server.call("GET", "/_metrics").splitlines():
        if not line.startswith("estpu_launch_ms_count{"):
            continue
        labels = dict(
            kv.split("=", 1) for kv in line[line.index("{") + 1:line.index("}")].split(",")
        )
        labels = {k: v.strip('"') for k, v in labels.items()}
        if labels.get("phase") == "queue" or labels.get("backend") == "batcher":
            continue
        backend = labels.get("backend", "")
        counts[backend] = counts.get(backend, 0.0) + float(line.rsplit(" ", 1)[1])
    return counts


# --------------------------------------------------------------- phases


def ingest(server: Server, corpus: Corpus) -> float:
    """Create the index, `_bulk` every doc, `_refresh`; returns seconds."""
    server.call("PUT", f"/{INDEX}", {
        "settings": {"index": {"number_of_shards": corpus.n_shards, "number_of_replicas": 0}},
        "mappings": MAPPINGS,
    })
    t0 = time.monotonic()
    sent = 0
    for n, body in corpus.bulk_bodies():
        resp = server.call("POST", f"/{INDEX}/_bulk", body)
        if resp.get("errors"):
            bad = next(i for i in resp["items"] if "error" in next(iter(i.values())))
            raise SmokeFailure(f"_bulk item failed: {bad}")
        sent += n
    server.call("POST", f"/{INDEX}/_refresh")
    seconds = time.monotonic() - t0
    count = server.call("GET", f"/{INDEX}/_count")["count"]
    if count != sent or sent != corpus.n_docs:
        raise SmokeFailure(f"_count {count} after sending {sent} of {corpus.n_docs} docs")
    return seconds


def serve_and_check(server: Server, corpus: Corpus, bodies: list[dict]) -> list[float]:
    """Send every `_search`, check it against the oracle and that the device
    launched for it; returns the per-request wall seconds."""
    from bench import ranked_match

    latencies = []
    for i, body in enumerate(bodies):
        before = launch_counts(server)
        t0 = time.monotonic()
        resp = server.call("POST", f"/{INDEX}/_search", body)
        latencies.append(time.monotonic() - t0)
        after = launch_counts(server)
        launched = {b: after.get(b, 0.0) - before.get(b, 0.0) for b in after}
        if sum(launched.values()) < 1:
            raise SmokeFailure(f"request {i} launched nothing on the device: {launched}")
        if resp["_shards"]["failed"] or resp["timed_out"]:
            raise SmokeFailure(f"request {i} partial: {resp['_shards']} timed_out={resp['timed_out']}")
        ids = [int(h["_id"]) for h in resp["hits"]["hits"]]
        scores = np.array([h["_score"] for h in resp["hits"]["hits"]], np.float32)
        o_ids, o_scores, o_total = corpus.expected(body)
        total = resp["hits"]["total"]
        if total != {"value": o_total, "relation": "eq"}:
            raise SmokeFailure(f"request {i} total {total} != oracle {o_total}: {body}")
        if len(ids) != len(o_ids) or not ranked_match(ids, scores, o_ids, o_scores):
            raise SmokeFailure(
                f"request {i} parity mismatch: {body}\n  served {list(zip(ids, scores.tolist()))}"
                f"\n  oracle {list(zip(o_ids, o_scores.tolist()))}"
            )
    return latencies


def mesh_stats(server: Server) -> dict:
    stats = server.call("GET", "/_nodes/stats")
    return next(iter(stats["nodes"].values()))["mesh_serving"]


def check_mesh(server: Server, n_shards: int, served_before: int,
               n_requests: int) -> dict:
    """The mesh path served every request on n_shards distinct devices and
    its breaker never tripped."""
    import jax

    mesh = mesh_stats(server)
    if mesh["disable_events"]:
        errors = [v["last_error"] for v in mesh["views"].values()]
        raise SmokeFailure(f"mesh serving disabled {mesh['disable_events']}x: {errors}")
    served = sum(mesh["served_by_shape"].values()) - served_before
    if served != n_requests:
        raise SmokeFailure(f"mesh served {served} of {n_requests} requests: {mesh}")
    # The stacked planes the one program reads: each must hold one shard
    # per device, across n_shards devices.
    view = server.rest.node.get_index(INDEX).search.mesh_view
    devices = set()
    for leaf in jax.tree.leaves(view._snap.index.seg_stacked):
        held = {shard.device for shard in leaf.addressable_shards}
        if len(held) != n_shards:
            raise SmokeFailure(f"a mesh plane sits on {len(held)} devices, want {n_shards}")
        devices |= held
    if len(devices) != n_shards:
        raise SmokeFailure(f"mesh planes span {len(devices)} devices, want {n_shards}")
    return {"served": served, "devices": sorted(str(d) for d in devices)}


def run(n_docs: int, seed: int, n_shards: int) -> dict:
    """Every phase once; raises SmokeFailure (or whatever a phase raised)
    on the first failure. Returns the figures main() prints."""
    from elasticsearch_tpu.native import available as native_available
    from elasticsearch_tpu.obs import device as device_obs

    device_obs.ensure_compile_listener()
    out: dict = {"native_indexer": native_available()}
    t0 = time.monotonic()
    corpus = Corpus(n_docs, seed, n_shards)
    out["corpus_build_s"] = time.monotonic() - t0
    bodies = corpus.queries(seed)
    server = Server()
    try:
        out["ingest_s"] = ingest(server, corpus)
        out["docs"] = n_docs
        served0 = sum(mesh_stats(server)["served_by_shape"].values())
        census0 = device_obs.process_census()
        latencies = serve_and_check(server, corpus, bodies)
        census1 = device_obs.process_census()
        out["requests"] = len(latencies)
        out["compiles"] = census1["compiles"] - census0["compiles"]
        out["compile_ms"] = (census1["compile_s"] - census0["compile_s"]) * 1e3
        out["request_p50_ms"] = float(np.median(latencies)) * 1e3
        stats = server.call("GET", "/_nodes/stats")
        node = next(iter(stats["nodes"].values()))
        out["hbm_ledger_bytes"] = node["device"]["hbm"]["total_bytes"]
        if n_shards > 1:
            out["mesh"] = check_mesh(server, n_shards, served0, len(bodies))
    finally:
        server.close()
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--docs", type=int, default=1_000_000)
    parser.add_argument("--seed", type=int, default=13)
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the 4-shard SPMD mesh path across four chips",
    )
    args = parser.parse_args(argv)

    import jax
    import jaxlib

    try:
        device = require_tpu(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    from elasticsearch_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    # The planner's oracle exploration would serve early requests of each
    # plan class on the CPU; the smoke must see the device serve them all.
    os.environ["ESTPU_EXEC_PLANNER"] = "0"
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: {device}")
    log(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}")
    log(f"compile cache: {cache_dir}")
    out = run(args.docs, args.seed, n_shards=args.chips)
    log(f"native indexer loaded: {out['native_indexer']}")
    log(f"docs ingested: {out['docs']} via _bulk+_refresh in {out['ingest_s']:.3f} s "
        f"(corpus generated in {out['corpus_build_s']:.3f} s)")
    log(f"requests: {out['requests']} _search, all matched the CPU oracle, "
        f">=1 device launch each")
    log(f"compiles: {out['compiles']} taking {out['compile_ms']:.1f} ms")
    log(f"request p50 (smoke figure, not a benchmark): {out['request_p50_ms']:.3f} ms")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"hbm ledger total: {out['hbm_ledger_bytes']} bytes; device 0 "
        f"peak_bytes_in_use {mem.get('peak_bytes_in_use')} "
        f"bytes_limit {mem.get('bytes_limit')}")
    if "mesh" in out:
        log(f"mesh: served {out['mesh']['served']} requests on {out['mesh']['devices']}; "
            "disable_events 0")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
