#!/usr/bin/env python3
"""Where a traced window's time goes, by the program's own spans.

The program marks each step of a search in the profiler trace: every span
it opens is an `estpu/<span>` host event on the thread that ran it
(elasticsearch_tpu/obs/tracing.py), and the kernels name their phases
with `jax.named_scope`, which the trace keeps in each device op's
metadata. This reduces one traced window to:

- spans: per span name, how many overlap the window, their time clipped
  to it (`total_s`), and their self time (`self_s`: total less the union
  of the `estpu/` spans nested inside on the same thread). Threads are
  the lines of the `/host:CPU` plane, keyed by position: every Python
  thread's line may carry the same name;
- idle by span: the in-flight time with no op on device 0 (the time
  trace_reduce.py splits into `idle_gaps`), instant by instant, to the
  `estpu/` span that covers the instant and started latest, on any
  thread (ties to the shortest); time no span covers is
  `untraced_idle_s`;
- device op time by named scope (ops outside every known scope read
  `unscoped`).

    python3 benchmark/spans.py --workload <cell> --seed <n> --seconds <s>

runs one cell once as `run.py --trace 1` does, logs these before its
result line, and prints the same result line last.
"""

from __future__ import annotations

import glob
import os
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace_reduce import REQUEST, WINDOW, clip, length, subtract, union  # noqa: E402

PREFIX = "estpu/"
# Named scopes of the program's kernels, innermost first.
SCOPES = ("bm25.topk", "bm25.total", "bm25.score", "mesh.reduce")
UNSCOPED = "unscoped"


@dataclass
class Host:
    # per `/host:CPU` line, in plane order: [(name, start_ns, end_ns)] of
    # the `estpu/` and `bench.` events
    lines: list[list[tuple[str, float, float]]] = field(default_factory=list)
    # device index -> [(scope, start_ns, end_ns)] of its "XLA Ops"
    ops: dict[int, list[tuple[str, float, float]]] = field(default_factory=dict)


def op_scope(op_name: str) -> str:
    """The innermost known named scope in an op's name metadata
    (`jit(f)/bm25.score/sort`), else UNSCOPED."""
    for scope in SCOPES:
        if scope in op_name:
            return scope
    return UNSCOPED


def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value is its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def hlo_scopes(xspace: bytes) -> dict[tuple[str, str], str]:
    """{(program, instruction): scope} from the HLO modules the profiler
    keeps in its `/host:metadata` plane. The device's op events name the
    instruction only; its name metadata (the named scopes) is in the
    module. Read from the wire format: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .bytes_value = 6; HloProto
    .hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2."""
    out: dict[tuple[str, str], str] = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if next((v for f, v in fields if f == 2), b"") != b"/host:metadata":
            continue
        proto_stat = set()
        for f, entry in fields:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                if meta.get(2) == b"Hlo Proto":
                    proto_stat.add(meta.get(1))
        for f, entry in fields:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            program = next((v.decode() for g, v in meta if g == 2), "").split("(")[0]
            for g, stat in meta:
                stat = dict(_fields(stat)) if g == 5 else {}
                if stat.get(1) not in proto_stat or 6 not in stat:
                    continue
                module = dict(_fields(stat[6])).get(1, b"")
                for h, comp in _fields(module):
                    if h != 3:
                        continue
                    for k, ins in _fields(comp):
                        if k != 2:
                            continue
                        ins = dict(_fields(ins))
                        op_name = dict(_fields(ins.get(7, b""))).get(2, b"").decode()
                        scope = op_scope(op_name)
                        if scope != UNSCOPED:
                            out[(program, ins[1].decode())] = scope
    return out


def load(trace_dir: str) -> Host:
    """The newest xplane.pb under `trace_dir`, as a Host."""
    import bisect

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        scopes = hlo_scopes(f.read())
    host = Host()
    for plane in ProfileData.from_file(files[-1]).planes:
        name = plane.name
        if name == "/host:CPU":
            for ln in plane.lines:
                host.lines.append([
                    (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                    for ev in ln.events if ev.name.startswith((PREFIX, "bench."))
                ])
        elif name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            # Each op belongs to the program running when it starts.
            mods = sorted((float(ev.start_ns), ev.name.split("(")[0])
                          for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
            starts = [m[0] for m in mods]
            ops = []
            for ev in lines["XLA Ops"].events:
                s0 = float(ev.start_ns)
                j = bisect.bisect_right(starts, s0) - 1
                program = mods[j][1] if j >= 0 else ""
                instr = ev.name.partition(" = ")[0].lstrip("%")
                ops.append((scopes.get((program, instr), UNSCOPED), s0, s0 + float(ev.duration_ns)))
            host.ops[int(name[len("/device:TPU:"):])] = ops
    return host


def _self_times(line, w0: float, w1: float) -> list[tuple[str, float, float, float, float]]:
    """[(name, start, end, clipped total, self)] of one thread's `estpu/`
    spans: each span's children are the spans it contains on this line."""
    own = sorted(((s, e, n[len(PREFIX):]) for n, s, e in line if n.startswith(PREFIX)),
                 key=lambda t: (t[0], -t[1]))
    children: list[list] = [[] for _ in own]
    stack: list[int] = []
    for i, (s, e, _n) in enumerate(own):
        while stack and own[stack[-1]][1] <= s:
            stack.pop()
        if stack and own[stack[-1]][1] >= e:
            children[stack[-1]].append((s, e))
        stack.append(i)
    out = []
    for (s, e, n), kids in zip(own, children):
        cs, ce = max(s, w0), min(e, w1)
        if ce <= cs:
            continue
        nested = length(clip(union(kids), cs, ce))
        out.append((n, s, e, ce - cs, ce - cs - nested))
    return out


def attribute(gaps, spans) -> tuple[dict[str, float], float]:
    """Split `gaps` (sorted, disjoint rows) among `spans` [(name, start,
    end)]: each instant to the covering span that started latest (ties to
    the shortest). Returns ({name: ns}, ns no span covers)."""
    times = sorted({t for g in gaps for t in g} | {t for _n, s, e in spans for t in (s, e)})
    starts: dict[float, list[int]] = {}
    ends: dict[float, list[int]] = {}
    for i, (_n, s, e) in enumerate(spans):
        if e > s:
            starts.setdefault(s, []).append(i)
            ends.setdefault(e, []).append(i)
    active: set[int] = set()
    out: dict[str, float] = {}
    untraced = 0.0
    g = 0
    for a, b in zip(times, times[1:]):
        active.difference_update(ends.get(a, ()))
        active.update(starts.get(a, ()))
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps) or not (gaps[g][0] <= a and b <= gaps[g][1]):
            continue
        if not active:
            untraced += b - a
            continue
        best = max(active, key=lambda i: (spans[i][1], spans[i][1] - spans[i][2]))
        out[spans[best][0]] = out.get(spans[best][0], 0.0) + (b - a)
    return out, untraced


def reduce_spans(host: Host, chips: int) -> dict | None:
    """The window's span table, idle attribution and op time by scope, or
    None when the trace holds no window or no device op."""
    events = [ev for ln in host.lines for ev in ln]
    windows = [(s, e) for n, s, e in events if n == WINDOW]
    devices = sorted(host.ops)[:chips]
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    inflight = clip(union((s, e) for n, s, e in events if n == REQUEST), w0, w1)
    busy = clip(union((s, e) for _, s, e in host.ops[devices[0]]), w0, w1)
    gaps = subtract(inflight, busy)
    table: dict[str, dict] = {}
    spans = []
    for line in host.lines:
        for n, s, e, total, own in _self_times(line, w0, w1):
            row = table.setdefault(n, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += total / 1e9
            row["self_s"] += own / 1e9
            spans.append((n, s, e))
    idle, untraced = attribute(gaps, spans)
    scopes: dict[str, float] = {}
    for d in devices:
        for scope, s, e in host.ops[d]:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                scopes[scope] = scopes.get(scope, 0.0) + (e - s) / 1e9
    desc = lambda d: [[k, float(v)] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_total_s": float(length(gaps)) / 1e9,
        "spans": table,
        "idle_s": desc({k: v / 1e9 for k, v in idle.items()}),
        "untraced_idle_s": float(untraced) / 1e9,
        "scopes_s": desc(scopes),
    }


def report(red: dict, completed: int) -> list[str]:
    """The log lines of one reduced window."""
    per = (lambda s: s * 1e3 / completed) if completed else (lambda s: float("nan"))
    lines = [f"spans ({completed} searches answered in the window; ms per search):"]
    for n, row in sorted(red["spans"].items(), key=lambda kv: -kv[1]["total_s"]):
        lines.append(f"  span {n}: count {row['count']}, total_s {row['total_s']!r} "
                     f"({per(row['total_s'])!r}), self_s {row['self_s']!r} ({per(row['self_s'])!r})")
    lines.append(f"idle by program span: {red['idle_s']!r}; untraced_idle_s {red['untraced_idle_s']!r} "
                 f"of {red['idle_total_s']!r} s in-flight device idle")
    lines.append(f"device op time by scope: {red['scopes_s']!r}")
    return lines


def main(argv=None) -> int:
    import argparse
    import json
    import shutil

    from benchmark import run, trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.load_spec()
    try:
        s = run.open_session(args.workload, spec=spec)
    except run.NoChip as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2
    try:
        out = run.drive(s.server, s.corpus, s.config, s.mix, s.cell, args.seed, args.seconds,
                        True, s.split, s.jax)
        out["device"] = run.device_stamp(s.devices)
    finally:
        s.server.close()
    trace_dir = out.pop("trace_dir")
    out["reduced"] = trace_reduce.reduce(trace_reduce.load_xplane(trace_dir), s.cell["chips"])
    red = reduce_spans(load(trace_dir), s.cell["chips"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    completed = sum(1 for r in out["recs"] if r.status == 200 and out["t0"] <= r.done <= out["t1"])
    for line in report(red, completed) if red else ["spans: the trace holds no window or no device op"]:
        run.log(line)
    out["seed"] = args.seed
    run.finish(out, spec, s.cell, s.config, s.corpus, True)
    print(json.dumps(out["result"]), flush=True)
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
