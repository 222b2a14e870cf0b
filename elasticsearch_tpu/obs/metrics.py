"""Unified metrics registry: counters, gauges, fixed-bucket histograms.

The single write path behind the node's operational counters: the
scattered per-subsystem dicts (exec planner decisions, micro-batcher
telemetry, search-resilience counters, request-cache hit/miss/eviction,
replication gateway retries) write through registry instruments, and
`GET /_nodes/stats` is rebuilt as a VIEW over the registry — one source
of truth, two renderings (the ES-shaped stats JSON and the Prometheus
text exposition at `GET /_metrics`).

Device-level instruments (DeviceInstruments) hook the kernel-launch
sites: XLA compile count and compile-ms per plan class (first launch of a
new (kernel, spec, k) shape is the compile), padding-waste ratio of
coalesced launches (padded nt vs. actual), host→device transfer bytes,
and launch counts — the signals BENCH_r05-style regressions (cfg3_conj at
0.07×) need span-level attribution for.

Prometheus exposition follows the text format 0.0.4: `# TYPE` per family,
`name{label="value"} <float>` samples, histogram `_bucket`/`_sum`/`_count`
series with cumulative `le` buckets.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Any, Callable

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _escape_label(value: Any) -> str:
    return (
        str(value)
        .replace("\\", r"\\")
        .replace("\n", r"\n")
        .replace('"', r'\"')
    )


def _format_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if float(v).is_integer():
        return str(int(v))
    return repr(float(v))


class Counter:
    """Monotonically increasing value (one labeled sample)."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counters only go up, got {n}")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Settable value, or a callback evaluated at scrape time."""

    __slots__ = ("_value", "_fn", "_lock")

    def __init__(self, fn: Callable[[], float] | None = None):
        self._value = 0.0
        self._fn = fn
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            # staticcheck: ignore[broad-except] a failing gauge callback must not 500 the scrape; the sample reads 0
            except Exception:
                return 0.0
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram: per-bucket (non-cumulative) counts, sum,
    count. Buckets are upper bounds; values above the last bound land in
    the implicit +Inf bucket. The exposition renders the cumulative
    `le`-labeled series Prometheus expects."""

    __slots__ = ("buckets", "_counts", "_inf", "_sum", "_count", "_lock")

    def __init__(self, buckets: tuple[float, ...]):
        if not buckets:
            raise ValueError("histogram requires at least one bucket bound")
        ordered = tuple(sorted(float(b) for b in buckets))
        if len(set(ordered)) != len(ordered):
            raise ValueError(f"duplicate histogram bucket in {buckets}")
        self.buckets = ordered
        self._counts = [0] * len(ordered)
        self._inf = 0
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            self._sum += v
            self._count += 1
            for i, bound in enumerate(self.buckets):
                if v <= bound:
                    self._counts[i] += 1
                    return
            self._inf += 1

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "buckets": {
                    _format_value(b): c
                    for b, c in zip(self.buckets, self._counts)
                },
                "inf": self._inf,
                "sum": self._sum,
                "count": self._count,
            }

class WindowedHistogram:
    """Rolling-window latency sketch: a ring of fixed-interval buckets,
    each holding a bounded sample list, covering the trailing
    ``window_s`` seconds. Every existing instrument is cumulative since
    boot; health indicators need RECENT behavior — "is the queue backing
    up NOW", not "did it ever". ``record`` is lock-cheap (one lock, one
    append); ``snapshot`` computes p50/p99/rate over only the buckets
    still inside the window, so stale load ages out within one bucket
    interval of leaving it."""

    __slots__ = (
        "window_s", "interval_s", "n_buckets", "cap_per_bucket",
        "_lock", "_samples", "_counts", "_epochs",
    )

    def __init__(
        self,
        window_s: float = 60.0,
        interval_s: float = 5.0,
        cap_per_bucket: int = 512,
    ):
        self.window_s = float(window_s)
        self.interval_s = max(0.05, float(interval_s))
        # +1 ring slot: the current (partial) bucket plus a full window
        # of sealed buckets.
        self.n_buckets = max(1, int(round(window_s / self.interval_s))) + 1
        self.cap_per_bucket = max(1, int(cap_per_bucket))
        self._lock = threading.Lock()
        self._samples: list[list[float]] = [
            [] for _ in range(self.n_buckets)
        ]
        # Full count per bucket (the sample list caps; the count doesn't,
        # so rates stay honest under bursts past the cap).
        self._counts = [0] * self.n_buckets
        self._epochs = [-1] * self.n_buckets

    def _slot(self, now: float) -> int:
        """Rotate to the bucket owning `now`; returns its ring index.
        Caller holds the lock."""
        epoch = int(now / self.interval_s)
        idx = epoch % self.n_buckets
        if self._epochs[idx] != epoch:
            self._samples[idx] = []
            self._counts[idx] = 0
            self._epochs[idx] = epoch
        return idx

    def record(self, value: float) -> None:
        now = time.monotonic()
        with self._lock:
            idx = self._slot(now)
            self._counts[idx] += 1
            bucket = self._samples[idx]
            if len(bucket) < self.cap_per_bucket:
                bucket.append(float(value))

    def snapshot(self) -> dict[str, Any]:
        """{count, rate_per_s, p50, p99, mean, max} over the trailing
        window (zeros when the window is empty)."""
        now = time.monotonic()
        floor = int(now / self.interval_s) - (self.n_buckets - 1)
        samples: list[float] = []
        count = 0
        with self._lock:
            for i in range(self.n_buckets):
                if self._epochs[i] >= floor:
                    samples.extend(self._samples[i])
                    count += self._counts[i]
        if not samples:
            return {
                "count": 0, "rate_per_s": 0.0, "p50": 0.0, "p99": 0.0,
                "mean": 0.0, "max": 0.0,
            }
        ordered = sorted(samples)
        n = len(ordered)

        def pct(q: float) -> float:
            return ordered[min(n - 1, int(q * (n - 1) + 0.5))]

        return {
            "count": int(count),
            "rate_per_s": round(count / self.window_s, 4),
            "p50": round(pct(0.50), 4),
            "p99": round(pct(0.99), 4),
            "mean": round(sum(ordered) / n, 4),
            "max": round(ordered[-1], 4),
        }

    def stat(self, name: str) -> float:
        return float(self.snapshot().get(name, 0.0))

    def count(self) -> float:
        """Samples inside the trailing window (WindowedCounter parity)."""
        return float(self.snapshot()["count"])


class WindowedCounter:
    """Rolling-window event counter: ring of per-interval counts; the
    windowed sibling of a cumulative Counter for rate-style health rules
    (shed rate, eviction bursts, transport churn)."""

    __slots__ = ("window_s", "interval_s", "n_buckets", "_lock", "_counts",
                 "_epochs")

    def __init__(self, window_s: float = 60.0, interval_s: float = 5.0):
        self.window_s = float(window_s)
        self.interval_s = max(0.05, float(interval_s))
        self.n_buckets = max(1, int(round(window_s / self.interval_s))) + 1
        self._lock = threading.Lock()
        self._counts = [0.0] * self.n_buckets
        self._epochs = [-1] * self.n_buckets

    def inc(self, n: float = 1.0) -> None:
        now = time.monotonic()
        epoch = int(now / self.interval_s)
        idx = epoch % self.n_buckets
        with self._lock:
            if self._epochs[idx] != epoch:
                self._counts[idx] = 0.0
                self._epochs[idx] = epoch
            self._counts[idx] += n

    def count(self) -> float:
        """Events inside the trailing window."""
        now = time.monotonic()
        floor = int(now / self.interval_s) - (self.n_buckets - 1)
        with self._lock:
            return float(
                sum(
                    c
                    for c, e in zip(self._counts, self._epochs)
                    if e >= floor
                )
            )

    def rate_per_s(self) -> float:
        return round(self.count() / self.window_s, 4)

    def snapshot(self) -> dict[str, Any]:
        count = self.count()
        return {
            "count": int(count),
            "rate_per_s": round(count / self.window_s, 4),
        }

    def stat(self, name: str) -> float:
        return float(self.snapshot().get(name, 0.0))


class MetricsRegistry:
    """Thread-safe instrument registry with Prometheus text exposition.

    Instruments are keyed by (name, sorted label items): repeated
    ``counter(name, **labels)`` calls return the same instrument, so call
    sites don't pre-register anything."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> (kind, help, {label_tuple: instrument})
        self._families: dict[str, tuple[str, str, dict]] = {}
        # Rolling-window instruments, keyed (name, label_key). They live
        # OUTSIDE _families (their exposition is the `stat`-labeled gauge
        # series windowed_* registers), so _collect/merge stay unchanged.
        self._windows: dict[tuple, Any] = {}

    # ------------------------------------------------------------ creation

    def _family(self, name: str, kind: str, help_text: str) -> dict:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name [{name}]")
        with self._lock:
            entry = self._families.get(name)
            if entry is None:
                entry = (kind, help_text, {})
                self._families[name] = entry
            elif entry[0] != kind:
                raise ValueError(
                    f"metric [{name}] already registered as {entry[0]}, "
                    f"not {kind}"
                )
            return entry[2]

    @staticmethod
    def _label_key(labels: dict[str, Any]) -> tuple:
        for k in labels:
            if not _LABEL_RE.match(k):
                raise ValueError(f"invalid label name [{k}]")
        return tuple(sorted((k, str(v)) for k, v in labels.items()))

    def counter(self, name: str, help_text: str = "", **labels) -> Counter:
        series = self._family(name, "counter", help_text)
        key = self._label_key(labels)
        with self._lock:
            inst = series.get(key)
            if inst is None:
                inst = series[key] = Counter()
            return inst

    def gauge(
        self,
        name: str,
        help_text: str = "",
        fn: Callable[[], float] | None = None,
        **labels,
    ) -> Gauge:
        series = self._family(name, "gauge", help_text)
        key = self._label_key(labels)
        with self._lock:
            inst = series.get(key)
            if inst is None:
                inst = series[key] = Gauge(fn)
            elif fn is not None:
                inst._fn = fn
            return inst

    def histogram(
        self,
        name: str,
        buckets: tuple[float, ...],
        help_text: str = "",
        **labels,
    ) -> Histogram:
        series = self._family(name, "histogram", help_text)
        key = self._label_key(labels)
        with self._lock:
            inst = series.get(key)
            if inst is None:
                inst = series[key] = Histogram(buckets)
            return inst

    # ------------------------------------------------- rolling windows

    def windowed_histogram(
        self,
        name: str,
        help_text: str = "",
        window_s: float = 60.0,
        interval_s: float = 5.0,
        **labels,
    ) -> WindowedHistogram:
        """A rolling-window histogram surfaced as `stat`-labeled gauge
        samples of family `name` (p50 / p99 / rate) — the `estpu_*_recent`
        exposition shape. Hot paths call ``.record(value)`` on the
        returned object; scrapes and health indicators read the gauges /
        ``snapshot()``. Names must end in `_recent` by convention (and
        `_recent_ms` for millisecond-valued families) so recent-window
        series are recognizable at a glance; the staticcheck catalog rule
        covers them like any other estpu_* instrument."""
        key = (name, self._label_key(labels))
        with self._lock:
            existing = self._windows.get(key)
        if existing is not None:
            return existing
        wh = WindowedHistogram(window_s=window_s, interval_s=interval_s)
        with self._lock:
            raced = self._windows.get(key)
            if raced is not None:
                return raced
            self._windows[key] = wh
        for stat in ("p50", "p99", "rate_per_s"):
            self.gauge(
                name,
                help_text,
                fn=lambda s=stat, w=wh: w.stat(s),
                stat=stat,
                **labels,
            )
        return wh

    def windowed_counter(
        self,
        name: str,
        help_text: str = "",
        window_s: float = 60.0,
        interval_s: float = 5.0,
        **labels,
    ) -> WindowedCounter:
        """A rolling-window counter surfaced as `stat`-labeled gauge
        samples (count / rate_per_s over the trailing window)."""
        key = (name, self._label_key(labels))
        with self._lock:
            existing = self._windows.get(key)
        if existing is not None:
            return existing
        wc = WindowedCounter(window_s=window_s, interval_s=interval_s)
        with self._lock:
            raced = self._windows.get(key)
            if raced is not None:
                return raced
            self._windows[key] = wc
        for stat in ("count", "rate_per_s"):
            self.gauge(
                name,
                help_text,
                fn=lambda s=stat, w=wc: w.stat(s),
                stat=stat,
                **labels,
            )
        return wc

    def window(self, name: str, **labels):
        """The windowed instrument registered under (name, labels), or
        None — the health indicators' read accessor."""
        with self._lock:
            return self._windows.get((name, self._label_key(labels)))

    def windows(self, name: str) -> list[tuple[dict[str, str], Any]]:
        """Every windowed instrument of one family as (labels, window)
        pairs — the multi-label read (e.g. launch outcomes grouped by
        backend AND outcome)."""
        with self._lock:
            return [
                (dict(key), w)
                for (n, key), w in self._windows.items()
                if n == name
            ]

    def window_counts(self, name: str, label: str) -> dict[str, float]:
        """Windowed-counter counts keyed by ONE label's value (e.g.
        transport events by `event`) over the trailing window."""
        with self._lock:
            items = [
                (key, w)
                for (n, key), w in self._windows.items()
                if n == name
            ]
        out: dict[str, float] = {}
        for key, window in items:
            for k, v in key:
                if k == label:
                    out[v] = out.get(v, 0.0) + float(window.count())
        return out

    # -------------------------------------------------------------- views

    def value(self, name: str, **labels) -> float:
        """Current value of one counter/gauge sample (0 when absent) —
        the `_nodes/stats` view accessor."""
        with self._lock:
            entry = self._families.get(name)
            if entry is None:
                return 0.0
            inst = entry[2].get(self._label_key(labels))
        return 0.0 if inst is None else inst.value

    def values(self, name: str) -> dict[tuple, float]:
        """Every labeled sample of a family: {label_items: value}."""
        with self._lock:
            entry = self._families.get(name)
            if entry is None:
                return {}
            items = list(entry[2].items())
        return {key: inst.value for key, inst in items}

    def family(self, name: str) -> tuple[str, str, dict] | None:
        """(kind, help, {label_key: value | histogram snapshot}) of one
        family — the public read for consumers that need histogram
        snapshots (scripts/profile_capture.py's launch-ms summaries)."""
        return self._collect().get(name)

    def label_values(self, name: str, label: str) -> dict[str, float]:
        """Family samples keyed by ONE label's value (counters with a
        single distinguishing label, e.g. decisions by backend)."""
        out: dict[str, float] = {}
        for key, value in self.values(name).items():
            for k, v in key:
                if k == label:
                    out[v] = out.get(v, 0.0) + value
        return out

    # ---------------------------------------------------------- exposition

    def _collect(self) -> dict[str, tuple[str, str, dict]]:
        """{name: (kind, help, {label_key: float | histogram snapshot})}"""
        with self._lock:
            families = {
                name: (kind, help_text, dict(series))
                for name, (kind, help_text, series) in self._families.items()
            }
        out: dict[str, tuple[str, str, dict]] = {}
        for name, (kind, help_text, series) in families.items():
            samples = {}
            for key, inst in series.items():
                samples[key] = (
                    inst.snapshot() if kind == "histogram" else inst.value
                )
            out[name] = (kind, help_text, samples)
        return out

    def to_wire(self, *others: "MetricsRegistry") -> dict:
        """JSON-serializable snapshot of every family (optionally merged
        with other registries) — the federation payload the `metrics_wire`
        cluster action ships so a worker process' instruments re-expose at
        the coordinator's `GET /_metrics` (wrap the result in
        WireRegistrySnapshot with a `node` label)."""
        merged = _merge_collected(
            [registry._collect() for registry in (self, *others)]
        )
        return {
            name: {
                "kind": kind,
                "help": help_text,
                "samples": [
                    [[list(kv) for kv in key], sample]
                    for key, sample in samples.items()
                ],
            }
            for name, (kind, help_text, samples) in merged.items()
        }

    def exposition(self, *others) -> str:
        """The Prometheus text format 0.0.4 rendering of every family —
        optionally merged with other registries (the node merges its own
        with the replication gateway's and each cluster node's; samples
        that collide on (name, labels) sum, so per-node series should
        carry a distinguishing label). `others` accepts anything with a
        `_collect()` view, including WireRegistrySnapshot (remote
        registries shipped over the wire)."""
        merged = _merge_collected(
            [registry._collect() for registry in (self, *others)]
        )
        lines: list[str] = []
        for name, (kind, help_text, samples) in sorted(merged.items()):
            if help_text:
                lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {kind}")
            for key, sample in sorted(samples.items()):
                labels = ",".join(
                    f'{k}="{_escape_label(v)}"' for k, v in key
                )
                if kind == "histogram":
                    cumulative = 0
                    for bound_str, count in sample["buckets"].items():
                        cumulative += count
                        le = (labels + "," if labels else "") + (
                            f'le="{bound_str}"'
                        )
                        lines.append(f"{name}_bucket{{{le}}} {cumulative}")
                    cumulative += sample["inf"]
                    le = (labels + "," if labels else "") + 'le="+Inf"'
                    lines.append(f"{name}_bucket{{{le}}} {cumulative}")
                    suffix = f"{{{labels}}}" if labels else ""
                    lines.append(
                        f"{name}_sum{suffix} {_format_value(sample['sum'])}"
                    )
                    lines.append(f"{name}_count{suffix} {sample['count']}")
                else:
                    suffix = f"{{{labels}}}" if labels else ""
                    lines.append(
                        f"{name}{suffix} {_format_value(sample)}"
                    )
        return "\n".join(lines) + "\n"


def _merge_collected(
    collected: list[dict[str, tuple[str, str, dict]]],
) -> dict[str, tuple[str, str, dict]]:
    """Fold several `_collect()` views into one family map: samples that
    collide on (name, labels) sum (histograms bucket-wise); families that
    collide on name with a different kind keep the first registration."""
    merged: dict[str, tuple[str, str, dict]] = {}
    for families in collected:
        for name, (kind, help_text, samples) in families.items():
            entry = merged.get(name)
            if entry is None:
                merged[name] = (kind, help_text, dict(samples))
                continue
            if entry[0] != kind:  # conflicting kinds: keep the first
                continue
            for key, sample in samples.items():
                prior = entry[2].get(key)
                if prior is None:
                    entry[2][key] = sample
                elif kind == "histogram":
                    entry[2][key] = {
                        "buckets": {
                            b: prior["buckets"].get(b, 0) + c
                            for b, c in sample["buckets"].items()
                        },
                        "inf": prior["inf"] + sample["inf"],
                        "sum": prior["sum"] + sample["sum"],
                        "count": prior["count"] + sample["count"],
                    }
                else:
                    entry[2][key] = prior + sample
    return merged


class WireRegistrySnapshot:
    """Re-exposes a remote registry's wire families (`to_wire` output) in
    `exposition()` merges, stamping extra labels onto every sample — the
    federation `node` label that keeps one worker's series from colliding
    with another's at the coordinator scrape."""

    def __init__(self, families: dict | None, **labels):
        self.families = families or {}
        self.labels = {k: str(v) for k, v in labels.items()}

    def _collect(self) -> dict[str, tuple[str, str, dict]]:
        out: dict[str, tuple[str, str, dict]] = {}
        for name, fam in self.families.items():
            samples: dict = {}
            for key, sample in fam.get("samples", ()):
                labels = {str(k): str(v) for k, v in key}
                labels.update(self.labels)
                samples[tuple(sorted(labels.items()))] = sample
            out[name] = (
                str(fam.get("kind", "counter")),
                str(fam.get("help", "")),
                samples,
            )
        return out


class _CollectedView:
    """A pre-built `_collect()` view (exposition merge input)."""

    def __init__(self, families: dict[str, tuple[str, str, dict]]):
        self._families = families

    def _collect(self) -> dict[str, tuple[str, str, dict]]:
        return self._families


def fold_cluster_counters(
    snapshots: list[WireRegistrySnapshot],
    label: str = "node",
    value: str = "_cluster",
) -> _CollectedView:
    """Cluster-total series for a federated scrape: every COUNTER sample
    of the per-node snapshots sums into one `node="_cluster"` sample per
    (family, labels). Samples whose original key already carried the fold
    label are skipped — they are per-node by construction and folding
    them would double-count across the label dimension. Gauges and
    histograms stay per-node only (a summed gauge is not a meaningful
    cluster value)."""
    totals: dict[str, tuple[str, str, dict]] = {}
    for snap in snapshots:
        for name, fam in snap.families.items():
            if fam.get("kind") != "counter":
                continue
            for key, sample in fam.get("samples", ()):
                labels = {str(k): str(v) for k, v in key}
                if label in labels:
                    continue
                labels[label] = value
                fkey = tuple(sorted(labels.items()))
                entry = totals.setdefault(
                    name, ("counter", str(fam.get("help", "")), {})
                )
                entry[2][fkey] = entry[2].get(fkey, 0.0) + float(sample)
    return _CollectedView(totals)


# Instrument catalog: every estpu_* instrument in the codebase, its
# kind, and the `_nodes/stats` section that renders it. This is the
# machine-checked contract (staticcheck registry-metric rule) that keeps
# `GET /_metrics` (automatic: every registered family is exposed) and
# `GET /_nodes/stats` (hand-built views) over the SAME instruments: a
# new instrument must be cataloged with its stats section, a renamed one
# must update its catalog entry, and a dead entry fails the gate.
CATALOG = {
    "estpu_exec_planner_decisions_total": ("counter", "exec.planner"),
    "estpu_exec_batcher_batches_total": ("counter", "exec.batcher"),
    "estpu_exec_batcher_requests_total": ("counter", "exec.batcher"),
    "estpu_exec_batcher_coalesced_requests_total": (
        "counter",
        "exec.batcher",
    ),
    "estpu_exec_batcher_queue_cancellations_total": (
        "counter",
        "exec.batcher",
    ),
    "estpu_exec_batcher_shed_total": ("counter", "exec.batcher"),
    "estpu_exec_batcher_retried_individually_total": (
        "counter",
        "exec.batcher",
    ),
    "estpu_exec_batcher_groups_quarantined_total": (
        "counter",
        "exec.batcher",
    ),
    "estpu_exec_batcher_quarantine_hits_total": ("counter", "exec.batcher"),
    "estpu_exec_batcher_occupancy": ("histogram", "exec.batcher"),
    "estpu_exec_batcher_queue_wait_ms": ("histogram", "exec.batcher"),
    "estpu_exec_batcher_queued": ("gauge", "exec.batcher"),
    "estpu_device_launches_total": ("counter", "device"),
    "estpu_device_compile_total": ("counter", "device"),
    "estpu_device_compile_ms_total": ("counter", "device"),
    "estpu_device_h2d_bytes_total": ("counter", "device"),
    "estpu_device_padded_tiles_total": ("counter", "device"),
    "estpu_device_actual_tiles_total": ("counter", "device"),
    "estpu_device_padding_waste_ratio": ("histogram", "device"),
    "estpu_device_blockmax_pruned_tile_fraction": ("histogram", "device"),
    # Device observability (ISSUE 14, obs/device.py): per-launch wall
    # times split queue (dispatch return) vs execute (block_until_ready)
    # per backend/plan class — the split is honest only on real devices
    # (XLA:CPU executes synchronously inside dispatch); real-XLA-compile
    # retraces per plan class (a compile during a launch whose plan key
    # was already seen — the shape-polymorphism alarm); and the HBM
    # ledger's per-(label, index) resident bytes + lifetime peak.
    "estpu_launch_ms": ("histogram", "device"),
    "estpu_device_retraces_total": ("counter", "device.compile"),
    "estpu_hbm_bytes": ("gauge", "device.hbm"),
    "estpu_hbm_high_watermark_bytes": ("gauge", "device.hbm"),
    # Packed multi-tenant execution (exec/packed.py): one launch scores
    # many small indices' lanes against a shared plane.
    "estpu_packed_launches_total": ("counter", "exec.packed"),
    "estpu_packed_lanes_total": ("counter", "exec.packed"),
    "estpu_packed_plane_rebuilds_total": ("counter", "exec.packed"),
    "estpu_packed_fallback_solo_total": ("counter", "exec.packed"),
    "estpu_packed_tenants_per_launch": ("histogram", "exec.packed"),
    "estpu_packed_lanes_per_launch": ("histogram", "exec.packed"),
    "estpu_packed_plane_docs": ("gauge", "exec.packed"),
    "estpu_packed_plane_tenants": ("gauge", "exec.packed"),
    # SPMD mesh serving (parallel/mesh_serving.py): one-launch servings by
    # request shape, and fallbacks to the host-loop coordinator by reason
    # (ineligible_shape, sort_shape, agg_shape, nested, breaker,
    # non_uniform_plan, execute_error) — a silent mesh decline is a bug.
    "estpu_mesh_served_total": ("counter", "mesh_serving"),
    "estpu_mesh_fallback_total": ("counter", "mesh_serving"),
    # Delta-scaled refresh (ROADMAP item 4): shard segments re-packed vs
    # served from unchanged buffers per mesh refresh, and device planes
    # re-uploaded vs shared with the previous snapshot (field-granular
    # upload skipping in tiles.pack_segment_delta).
    "estpu_mesh_segments_packed_total": ("counter", "mesh_serving"),
    "estpu_mesh_segments_reused_total": ("counter", "mesh_serving"),
    "estpu_mesh_field_planes_packed_total": ("counter", "mesh_serving"),
    "estpu_mesh_field_planes_reused_total": ("counter", "mesh_serving"),
    # Engine refresh/merge accounting (index/engine.py; the reference's
    # RefreshStats/MergeStats): totals + wall-clock ms + docs moved by
    # posting-concatenation merges.
    "estpu_refresh_total": ("counter", "indices.refresh"),
    "estpu_refresh_ms_total": ("counter", "indices.refresh"),
    "estpu_merge_total": ("counter", "indices.merges"),
    "estpu_merge_docs_moved_total": ("counter", "indices.merges"),
    "estpu_merge_ms_total": ("counter", "indices.merges"),
    # Analysis-call accounting (analysis/analyzers.py): every tokenize/
    # analyze invocation — the hook that makes "merges never re-tokenize"
    # a measured invariant (tests/test_merge_concat.py, cfg10_ingest).
    "estpu_analysis_calls_total": ("counter", "indices.analysis"),
    # Filter/bitset cache (index/filter_cache.py): device-resident mask
    # planes for repeated filter-context subtrees — the IndicesQueryCache
    # analog, surfaced under `_nodes/stats` indices.filter_cache.
    "estpu_ann_builds_total": ("counter", "search.ann"),
    "estpu_ann_evictions_total": ("counter", "search.ann"),
    "estpu_ann_searches_total": ("counter", "search.ann"),
    "estpu_ann_probes_total": ("counter", "search.ann"),
    "estpu_ann_candidate_fraction": ("histogram", "search.ann"),
    "estpu_ann_recall_gate_total": ("counter", "search.ann"),
    "estpu_ann_bytes_resident": ("gauge", "search.ann"),
    "estpu_ann_partitions_resident": ("gauge", "search.ann"),
    "estpu_ann_centroids_resident": ("gauge", "search.ann"),
    "estpu_filter_cache_hits_total": ("counter", "indices.filter_cache"),
    "estpu_filter_cache_misses_total": ("counter", "indices.filter_cache"),
    "estpu_filter_cache_admissions_total": (
        "counter",
        "indices.filter_cache",
    ),
    "estpu_filter_cache_evictions_total": (
        "counter",
        "indices.filter_cache",
    ),
    "estpu_filter_cache_mask_reuse_total": (
        "counter",
        "indices.filter_cache",
    ),
    "estpu_filter_cache_bytes_resident": ("gauge", "indices.filter_cache"),
    "estpu_filter_cache_entries": ("gauge", "indices.filter_cache"),
    "estpu_request_cache_hits_total": ("counter", "indices.request_cache"),
    "estpu_request_cache_misses_total": (
        "counter",
        "indices.request_cache",
    ),
    "estpu_request_cache_evictions_total": (
        "counter",
        "indices.request_cache",
    ),
    "estpu_request_cache_entries": ("gauge", "indices.request_cache"),
    "estpu_faults_armed": ("gauge", "faults"),
    "estpu_traces_buffered": ("gauge", "obs"),
    "estpu_search_resilience_total": ("counter", "search_resilience"),
    "estpu_cluster_search_resilience_total": (
        "counter",
        "replication.search_resilience",
    ),
    "estpu_replication_gateway_total": ("counter", "replication.gateway"),
    # Control-plane stepper errors (cluster/cluster.py, cluster/procs.py):
    # a step that raised and was swallowed by a background loop — counted
    # so a wedged control plane is visible in `_nodes/stats`.
    "estpu_cluster_step_errors_total": ("counter", "replication.stepper"),
    # TCP transport (cluster/tcp_transport.py) + the in-memory hub's
    # shared deadline counter: connection/reconnect/handshake/frame/
    # timeout instruments, surfaced under replication.transport.
    "estpu_transport_connections_total": ("counter", "replication.transport"),
    "estpu_transport_reconnects_total": ("counter", "replication.transport"),
    "estpu_transport_handshake_rejects_total": (
        "counter",
        "replication.transport",
    ),
    "estpu_transport_send_timeouts_total": (
        "counter",
        "replication.transport",
    ),
    "estpu_transport_frames_total": ("counter", "replication.transport"),
    "estpu_transport_frame_bytes_total": ("counter", "replication.transport"),
    "estpu_transport_open_connections": ("gauge", "replication.transport"),
    # Graceful-shutdown drain barriers entered (cluster/tcp_transport.py
    # drain(): SIGTERM'd workers waiting out their in-flight requests).
    "estpu_transport_drains_total": ("counter", "replication.transport"),
    # Cluster-scope observability fan-in (cluster/transport.scatter_nodes
    # + the node_stats / metrics_wire / trace_fragment / hot_threads wire
    # actions): scatter rounds by action, named per-node failures,
    # wall-clock fan latency, trace-fragment spans shipped from / spliced
    # at nodes, and hot-threads stack snapshots taken by this process.
    "estpu_nodes_stats_fanouts_total": ("counter", "obs.cluster"),
    "estpu_nodes_stats_fan_failures_total": ("counter", "obs.cluster"),
    "estpu_nodes_stats_fan_latency_ms": ("histogram", "obs.cluster"),
    "estpu_trace_fragments_shipped_total": ("counter", "obs.cluster"),
    "estpu_trace_fragments_collected_total": ("counter", "obs.cluster"),
    "estpu_hot_threads_samples_total": ("counter", "obs.cluster"),
    # Rolling-window (`estpu_*_recent`) instruments (ISSUE 15): every
    # cumulative instrument above answers "since boot"; these answer
    # "right now" — the health indicators' inputs, exposed as
    # `stat`-labeled gauge series (p50/p99/rate_per_s for histograms,
    # count/rate_per_s for counters) over a trailing 60s window.
    "estpu_rest_latency_recent_ms": ("windowed_histogram", "obs.recent"),
    "estpu_exec_batcher_queue_wait_recent_ms": (
        "windowed_histogram",
        "exec.batcher",
    ),
    "estpu_exec_batcher_shed_recent": ("windowed_counter", "exec.batcher"),
    "estpu_device_launch_recent": ("windowed_counter", "device"),
    "estpu_filter_cache_evictions_recent": (
        "windowed_counter",
        "indices.filter_cache",
    ),
    "estpu_ann_evictions_recent": ("windowed_counter", "search.ann"),
    "estpu_transport_events_recent": (
        "windowed_counter",
        "replication.transport",
    ),
    # Per-peer attribution of the trailing window's send timeouts
    # (cluster/tcp_transport.py): the transport health indicator reads
    # these to NAME the slow/wedged peer in a brownout diagnosis.
    "estpu_transport_peer_events_recent": (
        "windowed_counter",
        "replication.transport",
    ),
    # Whole-gateway-op latency (retries + backoff included) by op class
    # (cluster/gateway.py): the middle term of the bench's per-hop
    # http -> gateway -> shard split over the socketed topology.
    "estpu_gateway_latency_recent_ms": (
        "windowed_histogram",
        "replication.gateway",
    ),
    # Shard-side search execution latency (cluster/cluster.py,
    # _on_shard_search): the innermost term of the per-hop split — what
    # the shard owner spent executing, net of every wire/queue cost.
    "estpu_shard_exec_latency_recent_ms": (
        "windowed_histogram",
        "replication.search",
    ),
    # Health report (obs/health.py, GET /_health_report): report rounds
    # and the last-computed status per indicator (0 green / 1 yellow /
    # 2 red), surfaced under `_nodes/stats → health`.
    "estpu_health_reports_total": ("counter", "health"),
    "estpu_health_status": ("gauge", "health"),
    # Query insights ring (obs/insights.py, GET /_insights/queries): the
    # structured top-N slowest-searches sample fed from the slowlog's
    # SearchResponse.phases hook.
    "estpu_insights_recorded_total": ("counter", "obs.insights"),
    "estpu_insights_entries": ("gauge", "obs.insights"),
    # Per-tenant QoS lanes (exec/qos.py): windowed per-lane cost/wait
    # accounting behind weighted deficit-round-robin drain and weighted
    # shedding; the exec_saturation indicator names tenants from these.
    "estpu_qos_lanes": ("gauge", "exec.qos"),
    "estpu_qos_shed_total": ("counter", "exec.qos"),
    "estpu_qos_shed_recent": ("windowed_counter", "exec.qos"),
    "estpu_qos_queue_wait_recent_ms": (
        "windowed_histogram",
        "exec.qos",
    ),
    "estpu_qos_lane_cost_recent_ms": ("windowed_counter", "exec.qos"),
    # Async search (exec/async_search.py): the stored progressive-search
    # store and its per-fold reduce timing.
    "estpu_async_searches_total": ("counter", "exec.async_search"),
    "estpu_async_partials_served_total": ("counter", "exec.async_search"),
    "estpu_async_expired_total": ("counter", "exec.async_search"),
    "estpu_async_running": ("gauge", "exec.async_search"),
    "estpu_async_stored": ("gauge", "exec.async_search"),
    "estpu_async_reduce_recent_ms": (
        "windowed_histogram",
        "exec.async_search",
    ),
    # Self-driving remediation (cluster/remediation.py): rounds planned,
    # actions executed, per-attempt failures (the chaos arc's counter),
    # suppressions (hysteresis/cooldown/cap/advisory), plus the trailing
    # window's action count and per-round wall cost (the quiet-cluster
    # overhead gate in bench cfg16_remediation).
    "estpu_remediation_ticks_total": ("counter", "remediation"),
    "estpu_remediation_actions_total": ("counter", "remediation"),
    "estpu_remediation_failures_total": ("counter", "remediation"),
    "estpu_remediation_suppressed_total": ("counter", "remediation"),
    "estpu_remediation_actions_recent": ("windowed_counter", "remediation"),
    "estpu_remediation_tick_recent_ms": (
        "windowed_histogram",
        "remediation",
    ),
    # Per-index write rate over the trailing window (node.py write
    # chokepoint): the lifecycle loop schedules background force-merges
    # only when an index went quiet.
    "estpu_index_writes_recent": ("windowed_counter", "indices"),
    # ANN cache lookup outcomes at the get_or_build sites (index/ann.py):
    # the remediation budget loop and incident capsules read a TRUE hit
    # rate instead of leaning on the eviction window (PR-18 residue).
    "estpu_ann_cache_hits_total": ("counter", "search.ann"),
    "estpu_ann_cache_misses_total": ("counter", "search.ann"),
    "estpu_ann_cache_events_recent": ("windowed_counter", "search.ann"),
    # Flight recorder + incident autopsy (obs/recorder.py +
    # obs/incidents.py, GET /_incidents): frames recorded on the health
    # poll cadence, frames resident in the bounded ring, capsules frozen
    # (auto triggers + manual grabs), incidents resolved back to green,
    # and the open-incident count.
    "estpu_recorder_frames_total": ("counter", "incidents"),
    "estpu_recorder_frames": ("gauge", "incidents"),
    "estpu_incident_captures_total": ("counter", "incidents"),
    "estpu_incident_resolved_total": ("counter", "incidents"),
    "estpu_incident_open": ("gauge", "incidents"),
}

# Pow-2-ish bounds for the padding-waste ratio and occupancy/wait shapes.
PADDING_RATIO_BUCKETS = (0.0, 0.05, 0.1, 0.2, 0.3, 0.5, 0.7, 0.9)
# Fraction of a worklist the two-phase block-max prune dropped.
BLOCKMAX_PRUNE_BUCKETS = (0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99)
OCCUPANCY_BUCKETS = tuple(float(1 << i) for i in range(9))  # 1..256
QUEUE_WAIT_MS_BUCKETS = (
    0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 1024.0,
)
# Wall-clock latency of one cluster-wide stats/obs scatter round; the
# top bounds cover a fan that rode its per-send deadline out.
NODES_FAN_LATENCY_MS_BUCKETS = (
    1.0, 4.0, 16.0, 64.0, 256.0, 1024.0, 4096.0, 16384.0,
)
# Per-launch queue/execute wall times: sub-ms dispatch up through
# compile-dominated first launches.
LAUNCH_MS_BUCKETS = (
    0.1, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 512.0,
    2048.0,
)


class DeviceInstruments:
    """Launch-site instruments over one registry.

    ``launch(kind, plan_key, elapsed_s)`` counts every kernel launch; the
    FIRST launch of a given plan_key is recorded as the XLA compile for
    its plan class (jit compiles on first call of a new static shape, so
    first-launch wall time is compile-dominated — the honest in-band
    measure without reaching into XLA internals). Plan classes are
    labeled by the spec kind (bounded cardinality), never the full spec.

    ``timed(kind, plan_key, backend)`` is the per-launch timing wrapper
    (ISSUE 14): it brackets the kernel dispatch so wall time splits into
    queue (dispatch return) vs execute (block_until_ready), feeds the
    ``estpu_launch_ms{plan_class,backend,phase}`` histograms, and arms
    the obs/device.py compile-census attribution — a REAL XLA compile
    observed during a launch whose plan key was already seen counts as a
    retrace (``estpu_device_retraces_total{plan_class}``), the alarm for
    accidental shape-polymorphism regressions. The queue/execute split
    is honest only on real devices: XLA:CPU executes synchronously
    inside dispatch, so there queue absorbs the work and execute ~0.
    """

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._lock = threading.Lock()
        self._seen: set = set()
        # Real-compile census per plan class (fed by obs/device.py's
        # jax.monitoring listener through timed() windows):
        # kind -> {"compiles": int, "retraces": int, "compile_s": float}
        self._census: dict[str, dict[str, float]] = {}

    def launch(
        self,
        kind: str,
        plan_key: Any,
        elapsed_s: float,
        backend: str = "device",
        queue_s: float | None = None,
    ) -> bool:
        """Record one launch. Returns True when this was the plan key's
        FIRST launch (the inferred-compile signal `profile: true` device
        blocks report as a compile miss)."""
        self.registry.counter(
            "estpu_device_launches_total",
            "Kernel launches by plan class",
            plan_class=kind,
        ).inc()
        with self._lock:
            first = plan_key not in self._seen
            if first:
                self._seen.add(plan_key)
        if first:
            self.registry.counter(
                "estpu_device_compile_total",
                "XLA compiles (first launch of a new plan shape)",
                plan_class=kind,
            ).inc()
            self.registry.counter(
                "estpu_device_compile_ms_total",
                "Wall-clock ms spent in first (compiling) launches",
                plan_class=kind,
            ).inc(elapsed_s * 1e3)
        self.launch_outcome(backend, "ok")
        if queue_s is not None:
            execute_s = max(0.0, elapsed_s - queue_s)
            self._launch_hist(kind, backend, "queue").observe(queue_s * 1e3)
            self._launch_hist(kind, backend, "execute").observe(
                execute_s * 1e3
            )
        else:
            # Untimed site: the whole elapsed is one total-phase sample,
            # so every backend's latency shape is in the histogram even
            # where the dispatch/block split is not instrumented.
            self._launch_hist(kind, backend, "total").observe(
                elapsed_s * 1e3
            )
        return first

    def launch_outcome(self, backend: str, outcome: str) -> None:
        """Per-backend launch outcomes over the trailing window (the
        `device_compile`/`exec_saturation` indicators' error-rate input):
        every completed launch records "ok"; a timed window that raises
        records "error"."""
        self.registry.windowed_counter(
            "estpu_device_launch_recent",
            "Kernel-launch outcomes per backend over the trailing window",
            backend=backend,
            outcome=outcome,
        ).inc()

    def _launch_hist(self, kind: str, backend: str, phase: str) -> Histogram:
        return self.registry.histogram(
            "estpu_launch_ms",
            LAUNCH_MS_BUCKETS,
            "Per-launch wall ms by plan class/backend, split queue "
            "(dispatch return) vs execute (block_until_ready); the split "
            "is honest only on real devices — XLA:CPU runs inside "
            "dispatch",
            plan_class=kind,
            backend=backend,
            phase=phase,
        )

    def timed(
        self, kind: str, plan_key: Any, backend: str = "device"
    ) -> "_TimedLaunch":
        """Context manager for one instrumented launch: call
        ``out = t.dispatched(out)`` right after the kernel call — it
        records the queue split, blocks until the device finishes, and
        returns the ready outputs."""
        return _TimedLaunch(self, kind, plan_key, backend)

    def seen(self, plan_key: Any) -> bool:
        with self._lock:
            return plan_key in self._seen

    def _note_retrace(
        self, kind: str, compiles: int, compile_s: float, retrace: bool
    ) -> None:
        """Census write-back from a timed launch window."""
        with self._lock:
            entry = self._census.setdefault(
                kind, {"compiles": 0, "retraces": 0, "compile_s": 0.0}
            )
            entry["compiles"] += compiles
            entry["compile_s"] += compile_s
            if retrace:
                entry["retraces"] += compiles
        if retrace:
            self.registry.counter(
                "estpu_device_retraces_total",
                "XLA compiles observed on a plan key's NON-first launch "
                "— the plan key failed to capture a varying shape "
                "(shape-polymorphism regression alarm)",
                plan_class=kind,
            ).inc(compiles)
            from . import device as _device

            _device.note_retraces(compiles)

    def h2d(self, arrays: Any) -> int:
        """Host→device transfer bytes: the numpy leaves staged for upload
        by this launch. Returns the byte count (profile device blocks)."""
        try:
            import jax

            nbytes = sum(
                getattr(leaf, "nbytes", 0)
                for leaf in jax.tree.leaves(arrays)
            )
        # staticcheck: ignore[broad-except] H2D byte accounting is best-effort observability; fall back to a plain .nbytes read
        except Exception:
            nbytes = getattr(arrays, "nbytes", 0)
        if nbytes:
            self.registry.counter(
                "estpu_device_h2d_bytes_total",
                "Host-to-device plan-array bytes staged at launch sites",
            ).inc(float(nbytes))
        return int(nbytes)

    def padding(self, actual_tiles: int, padded_tiles: int) -> None:
        """Padding waste of one coalesced launch: padded worklist tiles
        vs. the tiles the lanes actually needed."""
        padded_tiles = max(1, int(padded_tiles))
        waste = max(0.0, 1.0 - float(actual_tiles) / padded_tiles)
        self.registry.counter(
            "estpu_device_padded_tiles_total",
            "Worklist tiles launched (after pad/coalesce)",
        ).inc(float(padded_tiles))
        self.registry.counter(
            "estpu_device_actual_tiles_total",
            "Worklist tiles the lanes actually required",
        ).inc(float(actual_tiles))
        self.registry.histogram(
            "estpu_device_padding_waste_ratio",
            PADDING_RATIO_BUCKETS,
            "Per-coalesced-launch padding waste ratio",
        ).observe(waste)

    def blockmax_pruned(self, fraction: float) -> None:
        """Per-query fraction of worklist tiles a two-phase block-max
        execution pruned before the exact launch (0 = kept everything) —
        prune effectiveness, observable in production at every two-phase
        launch site (ops/bm25_device.execute_batch_blockmax[_conj])."""
        self._prune_hist().observe(min(1.0, max(0.0, float(fraction))))

    def _prune_hist(self) -> Histogram:
        return self.registry.histogram(
            "estpu_device_blockmax_pruned_tile_fraction",
            BLOCKMAX_PRUNE_BUCKETS,
            "Per-query fraction of worklist tiles pruned by two-phase "
            "block-max execution",
        )

    # ------------------------------------------------------------- views

    def compile_count(self) -> int:
        return int(
            sum(
                self.registry.label_values(
                    "estpu_device_compile_total", "plan_class"
                ).values()
            )
        )

    def compile_ms_total(self) -> float:
        return round(
            sum(
                self.registry.label_values(
                    "estpu_device_compile_ms_total", "plan_class"
                ).values()
            ),
            3,
        )

    def padding_waste_pct(self) -> float:
        padded = self.registry.value("estpu_device_padded_tiles_total")
        actual = self.registry.value("estpu_device_actual_tiles_total")
        if padded <= 0:
            return 0.0
        return round(100.0 * (1.0 - actual / padded), 2)

    def retraces_total(self) -> int:
        return int(
            sum(
                self.registry.label_values(
                    "estpu_device_retraces_total", "plan_class"
                ).values()
            )
        )

    def compile_census(self, top_n: int = 8) -> dict[str, Any]:
        """The `device.compile` section of `_nodes/stats`: inferred
        compiles per plan class (first-launch detection), REAL attributed
        XLA compiles + retraces (jax.monitoring census through timed
        windows), and the top-N recompiling classes — any class with a
        nonzero retrace count is the shape-polymorphism alarm firing."""
        with self._lock:
            census = {
                kind: dict(entry) for kind, entry in self._census.items()
            }
        retraced = {
            kind: int(entry["retraces"])
            for kind, entry in census.items()
            if entry["retraces"]
        }
        top = sorted(
            census.items(),
            key=lambda kv: (-kv[1]["compiles"], kv[0]),
        )[:top_n]
        return {
            "compiles_by_plan_class": {
                k: int(v)
                for k, v in sorted(
                    self.registry.label_values(
                        "estpu_device_compile_total", "plan_class"
                    ).items()
                )
            },
            "attributed_xla_compiles": {
                kind: {
                    "compiles": int(entry["compiles"]),
                    "compile_ms": round(entry["compile_s"] * 1e3, 3),
                    "retraces": int(entry["retraces"]),
                }
                for kind, entry in top
            },
            "retraces_total": self.retraces_total(),
            "retraced_plan_classes": {
                k: retraced[k] for k in sorted(retraced)
            },
        }

    def snapshot(self) -> dict[str, Any]:
        """The `_nodes/stats` device section."""
        return {
            "compile_count": self.compile_count(),
            "compile_ms_total": self.compile_ms_total(),
            "compiles_by_plan_class": {
                k: int(v)
                for k, v in sorted(
                    self.registry.label_values(
                        "estpu_device_compile_total", "plan_class"
                    ).items()
                )
            },
            "launches_by_plan_class": {
                k: int(v)
                for k, v in sorted(
                    self.registry.label_values(
                        "estpu_device_launches_total", "plan_class"
                    ).items()
                )
            },
            "h2d_bytes_total": int(
                self.registry.value("estpu_device_h2d_bytes_total")
            ),
            "padding_waste_pct": self.padding_waste_pct(),
            "blockmax_pruned_tile_fraction": self._prune_summary(),
            # Retrace census (ISSUE 14): real attributed XLA compiles +
            # the top-N recompiling classes — `device.compile`.
            "compile": self.compile_census(),
        }

    def _prune_summary(self) -> dict[str, Any]:
        snap = self._prune_hist().snapshot()
        count = snap["count"]
        return {
            "count": int(count),
            "mean": round(snap["sum"] / count, 4) if count else 0.0,
        }


class _NullTimedLaunch:
    """timed() stand-in for uninstrumented paths: same surface, records
    nothing, and dispatched() is a passthrough (device_get blocks later
    anyway)."""

    queue_ms = 0.0
    execute_ms = 0.0
    first = False
    compiles = 0

    def __enter__(self) -> "_NullTimedLaunch":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    @staticmethod
    def dispatched(out: Any) -> Any:
        return out


NULL_TIMED = _NullTimedLaunch()


def timed_launch(instruments, kind: str, plan_key: Any, backend: str):
    """`instruments.timed(...)` or the null stand-in when uninstrumented —
    the one-liner launch sites use so the wrapped/unwrapped code path is
    identical."""
    if instruments is None:
        return NULL_TIMED
    return instruments.timed(kind, plan_key, backend)


class _TimedLaunch:
    """One instrumented kernel launch (DeviceInstruments.timed).

    Usage::

        with instruments.timed(kind, plan_key, backend) as t:
            out = t.dispatched(kernel(...))  # queue split + block

    On exit it records the launch (counts, launch-ms histograms with the
    queue/execute split, first-launch compile inference) and folds the
    compile-census attribution: real XLA compiles that fired on this
    thread during the window (obs/device.py's jax.monitoring listener)
    attribute to this plan class, and count as retraces when the plan
    key had already launched before. A window that raises records
    nothing — a failed launch's timings would poison the histograms."""

    __slots__ = (
        "instruments", "kind", "plan_key", "backend",
        "t0", "t_disp", "t_done", "compiles", "compile_s",
        "_seen_before", "_prev_window", "queue_ms", "execute_ms", "first",
    )

    def __init__(self, instruments, kind, plan_key, backend):
        self.instruments = instruments
        self.kind = kind
        self.plan_key = plan_key
        self.backend = backend
        self.t0 = self.t_disp = self.t_done = 0.0
        self.compiles = 0
        self.compile_s = 0.0
        self.queue_ms = 0.0
        self.execute_ms = 0.0
        self.first = False

    def __enter__(self) -> "_TimedLaunch":
        from . import device as _device

        _device.ensure_compile_listener()
        self._seen_before = self.instruments.seen(self.plan_key)
        self._prev_window = getattr(_device._TLS, "launch_window", None)
        _device._TLS.launch_window = self
        self.t0 = time.monotonic()
        return self

    def note_compile(self, duration_s: float) -> None:
        """Called by the process compile listener on this thread."""
        self.compiles += 1
        self.compile_s += duration_s

    def dispatched(self, out: Any) -> Any:
        """Mark the dispatch return (queue split), then block until the
        device finishes (execute split) and return the ready outputs."""
        import jax

        self.t_disp = time.monotonic()
        out = jax.block_until_ready(out)
        self.t_done = time.monotonic()
        return out

    def __exit__(self, exc_type, exc, tb) -> bool:
        from . import device as _device

        _device._TLS.launch_window = self._prev_window
        if exc is not None:
            # A failed launch records no timings (they would poison the
            # histograms) but DOES count as a windowed error outcome —
            # the recent-failure-rate input health indicators watch.
            self.instruments.launch_outcome(self.backend, "error")
            return False
        now = time.monotonic()
        t_disp = self.t_disp or now
        t_done = self.t_done or now
        queue_s = t_disp - self.t0
        self.queue_ms = round(queue_s * 1e3, 3)
        self.execute_ms = round(max(0.0, t_done - t_disp) * 1e3, 3)
        self.first = self.instruments.launch(
            self.kind,
            self.plan_key,
            t_done - self.t0,
            backend=self.backend,
            queue_s=queue_s,
        )
        if self.compiles:
            self.instruments._note_retrace(
                self.kind,
                self.compiles,
                self.compile_s,
                retrace=self._seen_before,
            )
        return False
