#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip, starts the program's REST server in
process (`rest/server.create_server(port=0)`), restores the cell's index
through REST `_snapshot` (the corpus and the repository are built on a
checkout's first run and kept under benchmark/.cache/), warms up with the
cell's own traffic (queries the window never sends) until the planner's
routing holds still, drives the window over loopback HTTP, checks what
the window's requests returned against the plain reference, and prints
one JSON line last on stdout. The seed draws the queries. Everything a
cell is made of is found by name: BENCHMARK.json's entry, then
benchmark/configs/<config>.json, benchmark/mixes/<traffic>.json and
benchmark/layers/<metric>.py.

With --trace 0 the line holds the cell's end-to-end metrics; with
--trace 1 the window runs under the JAX profiler and the line holds the
per-layer metrics, the device's busy and window seconds, and a breakdown.
Refuses (non-zero exit, no result line) without a TPU or with fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Caches sit at fixed paths inside the checkout: the path is part of what
# makes a compile-cache entry found again, and the driver gives each side
# of a comparison its own checkout.
CACHE_ROOT = os.path.join(BENCH, ".cache")
CACHE_DIR = os.path.join(CACHE_ROOT, "jax")

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import loadgen, scrape, trace_reduce  # noqa: E402
from benchmark.gen import corpus as gen_corpus  # noqa: E402
from benchmark.gen import queries as gen_queries  # noqa: E402
from benchmark.reference import Gaps, Reference  # noqa: E402


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ spec


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(spec: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell, config, mix) of a workload named in BENCHMARK.json."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "mixes", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return cell, config, mix


def cell_metrics(spec: dict, cell: dict, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries this cell reports."""
    e2e_here = {
        m["name"] for m in spec["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    }
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def load_reader(entry: dict):
    """benchmark/layers/<metric>.py, checked against its BENCHMARK.json entry."""
    path = os.path.join(BENCH, "layers", entry["name"] + ".py")
    mod_spec = importlib.util.spec_from_file_location("layer_" + entry["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    for attr, key in (("UNIT", "unit"), ("LAYER", "layer"), ("MOVES", "moves")):
        if getattr(mod, attr) != entry[key]:
            raise SystemExit(f"{path}: {attr} {getattr(mod, attr)!r} != BENCHMARK.json {entry[key]!r}")
    return mod


@dataclass
class Window:
    """What a per-layer reader gets: the window's counters, its reduced
    trace, and its searches."""

    completed: int  # successful searches answered inside the window
    counters: scrape.Delta
    trace: dict | None  # trace_reduce.reduce(), None when nothing was traced
    postings: float  # logical postings of the scored terms of `completed`


# ---------------------------------------------------------------- device


def init_jax(chips: int, require_tpu: bool):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # Sub-second programs would otherwise never reach the cache and be
    # compiled again in every run (PERF.md, PR 21).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devices)}")
    return jax, devices


# Programs loaded from the persistent compile cache: no compile, but not
# free either, so a load inside the window is a shape the warm-up missed.
_CACHE_HITS = {"loads": 0, "listening": False}


def _count_cache_hit(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_HITS["loads"] += 1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------- server


class Server:
    def __init__(self):
        from elasticsearch_tpu.rest.server import create_server

        self.http, self.rest = create_server(port=0)
        self.port = self.http.server_address[1]
        self.thread = threading.Thread(target=self.http.serve_forever, daemon=True)
        self.thread.start()
        self.conn = loadgen.Conn(self.port, timeout=1200.0)

    def call(self, method: str, path: str, body=None):
        data = json.dumps(body).encode() if body is not None else None
        c = self.conn.c
        c.request(method, path, data, {"Content-Type": "application/json"} if data else {})
        r = c.getresponse()
        raw = r.read()
        if r.status >= 300:
            raise RuntimeError(f"{method} {path} -> HTTP {r.status}: {raw[:2000]!r}")
        return json.loads(raw) if "json" in (r.headers.get("Content-Type") or "") else raw.decode()

    def close(self) -> None:
        self.conn.close()
        self.http.shutdown()
        self.http.server_close()
        self.thread.join(timeout=30)
        self.rest.close()


# ------------------------------------------------------------------- run


@dataclass
class Session:
    """A cell's node, loaded and ready for traffic."""

    cell: dict
    config: dict
    mix: dict
    jax: object
    devices: list
    corpus: gen_corpus.Corpus
    server: Server
    split: dict


def open_session(name: str, *, spec: dict | None = None,
                 config: dict | None = None, mix: dict | None = None,
                 require_tpu: bool = True) -> Session:
    """Check the chip, generate the corpus, start the server and restore
    the index. `spec` and `config` stand in for BENCHMARK.json and the
    config file (tests run a small corpus on the CPU, require_tpu=False)."""
    spec = spec or load_spec()
    cell, file_config, file_mix = load_cell(spec, name)
    config = config or file_config
    mix = mix or file_mix
    jax, devices = init_jax(cell["chips"], require_tpu)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        if require_tpu and devices[0].device_kind not in json.load(f):
            raise SystemExit(f"no peaks for device kind {devices[0].device_kind!r} in peaks.json")
    from elasticsearch_tpu.obs import device as device_obs

    device_obs.ensure_compile_listener()
    if not _CACHE_HITS["listening"]:
        jax.monitoring.register_event_listener(_count_cache_hit)
        _CACHE_HITS["listening"] = True
    split: dict[str, float] = {}
    t = time.monotonic()
    corpus, hit = gen_corpus.load(config, CACHE_ROOT)
    split["corpus_s"] = time.monotonic() - t
    log(f"corpus: {corpus.n_docs} passages, {corpus.n_tokens} tokens "
        f"({'from the cache' if hit else 'drawn and cached'})")
    server = Server()
    try:
        from benchmark import load

        settings = config["index"]["settings"]
        repo = os.path.join(CACHE_ROOT, "repo-" + load.repo_key(gen_corpus.cache_key(config), settings))
        t = time.monotonic()
        hit = load.ensure_repo(server.call, corpus, settings, repo)
        split["build_s"] = time.monotonic() - t
        log(f"snapshot repository: {'from the cache' if hit else 'built by the program and cached'}")
        t = time.monotonic()
        load.restore(server.call, repo)
        # Each shard holds what the program's `_id` routing puts there, as
        # a client reads it, or the run measures a layout no user has.
        want = load.shard_counts(corpus.n_docs, load.n_shards(settings))
        rows = sorted((int(r["shard"]), int(r["docs"]))
                      for r in server.call("GET", "/_cat/shards?format=json")
                      if r["index"] == gen_queries.INDEX and r["prirep"] == "p")
        have = [docs for _, docs in rows]
        if have != want:
            raise RuntimeError(f"docs per shard {have} after the restore; the routing of "
                               f"{corpus.n_docs} passages wants {want}")
        split["restore_s"] = time.monotonic() - t
        log(f"index: docs per shard {have} (_cat/shards), as the routing wants")
    except BaseException:
        server.close()
        raise
    return Session(cell, config, mix, jax, devices, corpus, server, split)


def device_stamp(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak(devices),
    }


def run(name: str, seed: int, seconds: float, trace: bool, *, spec: dict | None = None,
        config: dict | None = None, mix: dict | None = None, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    spec = spec or load_spec()
    s = open_session(name, spec=spec, config=config, mix=mix, require_tpu=require_tpu)
    trace_dir = None
    try:
        out = drive(s.server, s.corpus, s.config, s.mix, s.cell, seed, seconds, trace,
                    s.split, s.jax)
        trace_dir = out.pop("trace_dir", None)
        out["device"] = device_stamp(s.devices)
        log(f"hbm: peak {out['device']['memory_peak_bytes']} bytes in use on the fullest chip")
    finally:
        s.server.close()
    if trace_dir:
        tr = trace_reduce.load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["reduced"] = trace_reduce.reduce(tr, s.cell["chips"])
    out["seed"] = seed
    finish(out, spec, s.cell, s.config, s.corpus, trace)
    return out["result"]


def _sequential(server, path: str, bodies: list[bytes]) -> None:
    bad = [r for r in loadgen.sequential(server.port, path, bodies) if r.status != 200]
    if bad:
        raise RuntimeError(f"warm-up request failed: HTTP {bad[0].status}: {bad[0].raw[:2000]!r}")


def planner_table(server) -> dict:
    """The exec planner's table from `GET /_nodes/stats`: plan class ->
    backend -> {"ewma_ms", "observations"}."""
    def find(node):
        if isinstance(node, dict):
            if "planner" in node and isinstance(node["planner"], dict) and "ewma" in node["planner"]:
                return node["planner"]["ewma"]
            for v in node.values():
                hit = find(v)
                if hit is not None:
                    return hit
        return None

    return find(server.call("GET", "/_nodes/stats")) or {}


def drive(server, corpus, config, mix, cell, seed, seconds, trace, split, jax) -> dict:
    """Warm up, open the window, drive it, and collect what it returned."""
    from elasticsearch_tpu.obs import device as device_obs

    path = f"/{gen_queries.INDEX}/_search"
    n_tags = len(corpus.tag_values)
    seen: set = set()
    enc = lambda qs: [json.dumps(q.body(mix, corpus.tag_values)).encode() for q in qs]
    warm = mix["warmup"]
    if mix["loop"] == "open":
        due = gen_queries.arrivals(mix["rate"], seconds, seed)
        n_window = len(due)
    else:
        n_window = int(mix["max_qps"] * seconds)
    window_q = gen_queries.draw_queries(mix, n_tags, seed, 0, n_window, seen)
    n_warm_load = int(mix.get("rate", mix.get("max_qps", 0)) * warm["seconds"]) + 1
    warm_load = gen_queries.draw_queries(mix, n_tags, seed, 2, n_warm_load, seen)

    t = time.monotonic()
    census0 = device_obs.process_census()
    # The process's first searches pay what happens once (the planes'
    # first use, first transfers): they go to a plan class the window never
    # sends (one term), so no class the window uses is priced by them.
    head = mix["query"]["groups"][0]["ranks"]
    primer = [json.dumps({"query": {"match": {"body": gen_corpus.term_name(head[0] + i % (head[1] - head[0]))}},
                          **mix.get("body", {})}).encode() for i in range(warm["primer"])]
    _sequential(server, path, primer)
    # Then the cell's own queries, round by round, until a number of rounds
    # in a row found no new plan class and left no backend of a class with
    # fewer observations than it had: the planner's exploration is over
    # for every class the rounds reach.
    rounds, steady, state = 0, 0, None
    while steady < warm["steady_rounds"] and rounds < warm["max_rounds"]:
        qs = gen_queries.draw_queries(mix, n_tags, seed, 10 + rounds, warm["round"], seen)
        _sequential(server, path, enc(qs))
        rounds += 1
        table = planner_table(server)
        now = (frozenset(table), frozenset((c, b) for c, bs in table.items()
                                           for b, v in bs.items() if v["observations"] < 2))
        steady = steady + 1 if now == state else 0
        state = now
    table = planner_table(server)
    on_oracle = sum(min(b, key=lambda k: b[k]["ewma_ms"]) == "oracle" for b in table.values() if len(b) > 1)
    log(f"warm-up: {warm['primer']} primer searches, then {rounds} rounds of {warm['round']} "
        f"({rounds * warm['round']} searches); planner steady: {steady >= warm['steady_rounds']}; "
        f"{len(table)} plan classes, {on_oracle} routed to the oracle at the window's start")
    split["warm_sequential_s"] = time.monotonic() - t

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="estpu-bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    snap: dict = {}

    def at_window(t0: float) -> None:
        snap["census"] = device_obs.process_census()
        snap["cache_hits"] = _CACHE_HITS["loads"]
        snap["metrics"] = scrape.parse(server.call("GET", "/_metrics"))
        snap["setup_s"] = t0 - T_START

    t = time.monotonic()
    bodies = enc(window_q)
    if mix["loop"] == "open":
        warm_due = -warm["seconds"] + gen_queries.arrivals(mix["rate"], warm["seconds"], seed + 1)
        _, recs, t0, t1 = loadgen.open_loop(
            server.port, path, enc(warm_load), warm_due, bodies, due, seconds,
            mix["workers"], at_window=at_window)
    else:
        _, recs, t0, t1 = loadgen.closed_loop(
            server.port, path, enc(warm_load), bodies, warm["seconds"], seconds,
            mix["clients"], at_window=at_window)
    split["warm_load_s"] = t0 - t
    if trace:
        jax.profiler.stop_trace()
    census1 = device_obs.process_census()
    after = scrape.parse(server.call("GET", "/_metrics"))
    return {
        "trace_dir": trace_dir,
        "queries": window_q,
        "recs": recs,
        "t0": t0,
        "t1": t1,
        "seconds": seconds,
        "split": split,
        "setup_s": snap["setup_s"],
        "compiles_setup": snap["census"]["compiles"] - census0["compiles"],
        "compile_s_setup": snap["census"]["compile_s"] - census0["compile_s"],
        "compiles_window": census1["compiles"] - snap["census"]["compiles"],
        "cache_loads_window": _CACHE_HITS["loads"] - snap["cache_hits"],
        "delta": scrape.Delta(snap["metrics"], after),
        "loop": mix["loop"],
        "k": int(mix["body"].get("size", 10)),
        "exhausted": mix["loop"] == "closed" and len(recs) >= len(window_q),
    }


def finish(out: dict, spec: dict, cell: dict, config: dict, corpus, trace: bool) -> None:
    """Metrics, the comparison with the reference, and the result line."""
    recs, t0, t1, seconds = out["recs"], out["t0"], out["t1"], out["seconds"]
    ok = [r for r in recs if r.status == 200]
    attempted, failed = len(recs), len(recs) - len(ok)
    in_window = [r for r in ok if t0 <= r.done <= t1]
    split = out["split"]
    log("setup split: " + ", ".join(f"{k} {v:.3f}" for k, v in split.items())
        + f"; compiles in set-up {out['compiles_setup']} ({out['compile_s_setup']:.3f} s)")
    log(f"compiles inside the window: {out['compiles_window']}; "
        f"programs loaded from the compile cache inside it: {out['cache_loads_window']}")
    late = [(r.sent - r.due) * 1e3 for r in recs if r.sent]
    if out["loop"] == "open":
        log(f"generator send lateness p95: {loadgen.percentile(np.asarray(late), 95):.3f} ms "
            f"over {len(late)} requests")
    bad = [r for r in recs if r.status != 200]
    if bad:
        log(f"first failure: status {bad[0].status}: {bad[0].raw[:300]!r}")
    lat = loadgen.latency_ms(recs)
    log(f"window: {attempted} attempted, {failed} failed, {len(in_window)} answered inside "
        f"the window, {int(np.isfinite(lat).sum())} completions with a latency")
    d = out["delta"]
    log(f"launches by backend: {d.launches()}; planner decisions: "
        f"{ {k: v for k, v in d.by_label('estpu_exec_planner_decisions_total', 'backend').items() if v} }")
    if out["exhausted"]:
        log("WARNING: the closed loop used every pre-drawn query; raise max_qps")

    metrics: dict[str, dict] = {}
    if not trace:
        values = {
            "setup_s": out["setup_s"],
            "search_p50_ms": loadgen.percentile(lat, 50),
            "search_p95_ms": loadgen.percentile(lat, 95),
            "search_qps": len(in_window) / seconds,
        }
        for m in cell_metrics(spec, cell, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        df = corpus.df
        queries = out["queries"]
        w = Window(
            completed=len(in_window),
            counters=d,
            trace=out.get("reduced"),
            postings=float(sum(int(df[list(queries[r.i].terms)].sum()) for r in in_window)),
        )
        for m in cell_metrics(spec, cell, "per_layer"):
            v = load_reader(m).read(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = out["device"]
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    red = out.get("reduced")
    if trace and red:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        log(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s window, "
            f"in flight {red['inflight_s']:.6f} s, idle share {red['idle_share']}")

    t = time.monotonic()
    gaps = check(out, config, corpus)
    log(f"reference check: {time.monotonic() - t:.3f} s")
    limits = config["limits"]
    lines = gaps.lines(limits)
    result["correct"] = gaps.correct(limits) and failed == 0
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in lines}
    result["checks"]["failed"] = {"value": failed, "limit": 0}
    out["result"] = result
    for n, v, lim in lines + [("failed", failed, 0)]:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr, flush=True)
    log(f"checked {gaps.checked} answers against the reference")


def sample(out: dict, config: dict, corpus) -> list:
    """The window's answered requests the comparison reads: all of them,
    or past `check_sample` a sample drawn from the seed with the 20 of
    most postings in it."""
    queries = out["queries"]
    ok = [r for r in out["recs"] if r.status == 200]
    limit = config["check_sample"]
    if len(ok) <= limit:
        return ok
    df = corpus.df
    heavy = sorted(ok, key=lambda r: -int(df[list(queries[r.i].terms)].sum()))[:20]
    rng = np.random.default_rng(np.random.SeedSequence([out["seed"] & (2**64 - 1), 0xC4]))
    rest = [ok[i] for i in rng.choice(len(ok), limit - len(heavy), replace=False)]
    return list({id(r): r for r in heavy + rest}.values())


def check(out: dict, config: dict, corpus, control: bool = False) -> Gaps:
    """Compare the sampled answers with the reference. With `control`, the
    reference computed in bfloat16 stands in the program's place."""
    sc = config["scoring"]
    ref = Reference(corpus, sc["k1"], sc["b"])
    queries = out["queries"]
    recs = sample(out, config, corpus)
    ref.prepare([queries[r.i] for r in recs])
    gaps = Gaps()
    k = out["k"]
    for r in recs:
        q = queries[r.i]
        if control:
            import ml_dtypes

            low = ref.answer(q, k, dtype=ml_dtypes.bfloat16)
            ids, scores, total = low.ids, low.scores.tolist(), low.total
        else:
            resp = loadgen.parse(r)
            hits = resp["hits"]["hits"]
            ids = [int(h["_id"]) for h in hits]
            scores, total = [float(h["_score"]) for h in hits], resp["hits"]["total"]
        gaps.add(ids, scores, total, ref.answer(q, k, ask=ids), k)
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    # Client threads still waiting on a request the drain gave up on, or
    # the runtime's own threads, must not run into interpreter teardown:
    # everything the run owed is printed.
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
