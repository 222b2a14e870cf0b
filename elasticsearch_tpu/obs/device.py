"""Device observability: the HBM ledger, the XLA compile census, and the
on-demand profiler capture — the single source of truth for device-side
telemetry (ISSUE 14).

Three pieces, all feeding `_nodes/stats` and `GET /_metrics`:

- **HbmLedger** — every byte that lands on device registers here under
  (label, scope): packed segments (index/tiles.py uploads, charged by the
  engine), filter-cache mask planes, ANN IVF tiles, packed multi-tenant
  planes, and SPMD mesh snapshots. The node HBM circuit breaker
  (common/breaker.py) WRITES THROUGH to the ledger on every
  add/add_unchecked/release, so breaker accounting and ledger accounting
  cannot drift — the consistency law (tests/test_device_obs.py): ledger
  totals equal the sum of each component's own byte stats through
  refresh / evict / `_cache/clear` / delete_index cycles, drift zero.
  Surfaced as `estpu_hbm_bytes{label,index}` gauges + a high-watermark
  gauge, the `device.hbm` section of `_nodes/stats` (fanned per node via
  the PR-13 scatter), and `GET /_cat/hbm`.

- **Compile census** — a process-wide `jax.monitoring` listener counts
  REAL backend compiles (`/jax/core/compile/backend_compile_duration`),
  attributed to the plan class of the launch in flight on the compiling
  thread (DeviceInstruments.timed sets the attribution window). A compile
  that fires during a launch whose plan key was ALREADY seen is a
  **retrace** (`estpu_device_retraces_total{plan_class}`): the plan key
  failed to capture a varying shape — the alarm that catches accidental
  shape-polymorphism regressions (a recompile-per-query silently triples
  p50 long before anyone reads a profile).

- **ProfilerCapture** — `POST /_profiler/start` / `POST /_profiler/stop`
  drive `jax.profiler.start_trace`/`stop_trace` (single-flight, bounded
  duration, 409 on double-start), return the Perfetto-loadable trace
  directory, and stamp the capture window into the obs trace ring
  (`profiler.capture` trace) so device traces and the PR-4/13 request
  traces can be laid side by side on one clock.

`LEDGER_LABELS` is the machine-checked label registry: staticcheck's
registry-breaker-label rule fails the gate on any `CircuitBreaker.add`
(or release) whose literal label is not declared here — a breaker label
allocated outside the ledger would silently split the two accountings.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any

# Canonical HBM ledger labels. Every breaker/ledger byte carries one of
# these (f-string labels match by prefix, like fault-site patterns);
# staticcheck registry-breaker-label enforces the registry at every
# breaker call site.
LEDGER_LABELS = (
    "segment",  # packed engine segments (index/tiles.pack_segment)
    "filter_cache",  # device-resident filter mask planes
    "ann_cache",  # IVF partition tiles (index/ann.py)
    "packed_plane",  # multi-tenant packed planes (exec/packed.py)
    "mesh_plane",  # SPMD mesh snapshot buffers (parallel/mesh_serving)
)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# ---------------------------------------------------------------------------
# Process-wide accounting (bench.py reads these across every Node the
# configs construct): total resident ledger bytes, lifetime high
# watermark, a resettable measurement-window peak, and the compile
# census. One lock — these are tiny counter updates.
# ---------------------------------------------------------------------------

_PROC_LOCK = threading.Lock()
_PROC = {"total": 0, "hwm": 0, "window_floor": 0, "window_peak": 0}
_CENSUS = {"compiles": 0, "compile_s": 0.0, "retraces": 0}
_LISTENER_REGISTERED = False
# Thread-local attribution window: (DeviceInstruments, plan_class,
# retraceable) while a wrapped launch is dispatching on this thread.
_TLS = threading.local()


def _proc_register(nbytes: int) -> None:
    with _PROC_LOCK:
        _PROC["total"] += nbytes
        if _PROC["total"] > _PROC["hwm"]:
            _PROC["hwm"] = _PROC["total"]
        if _PROC["total"] > _PROC["window_peak"]:
            _PROC["window_peak"] = _PROC["total"]


def _proc_release(nbytes: int) -> None:
    with _PROC_LOCK:
        _PROC["total"] = max(0, _PROC["total"] - nbytes)


def begin_hbm_window() -> None:
    """Start a process-wide HBM measurement window (bench.py brackets
    each config with one so `hbm_high_watermark_bytes` is the CONFIG's
    incremental peak, not whatever an earlier config left resident)."""
    with _PROC_LOCK:
        _PROC["window_floor"] = _PROC["total"]
        _PROC["window_peak"] = _PROC["total"]


def hbm_window_peak() -> int:
    """Peak ledger bytes ABOVE the window floor since begin_hbm_window."""
    with _PROC_LOCK:
        return max(0, _PROC["window_peak"] - _PROC["window_floor"])


def process_census() -> dict[str, Any]:
    """Process-wide compile census snapshot: real XLA backend compiles
    (jax.monitoring), wall seconds spent compiling, and retraces (a
    compile during a launch whose plan key was already seen)."""
    with _PROC_LOCK:
        return {
            "compiles": _CENSUS["compiles"],
            "compile_s": round(_CENSUS["compile_s"], 3),
            "retraces": _CENSUS["retraces"],
        }


def note_retraces(n: int) -> None:
    """Fold retraces detected by a DeviceInstruments timed window into
    the process census (bench.py's per-config gate reads deltas here)."""
    with _PROC_LOCK:
        _CENSUS["retraces"] += int(n)


def _on_compile_event(key: str, duration_s: float, **_kw: Any) -> None:
    if key != _COMPILE_EVENT:
        return
    with _PROC_LOCK:
        _CENSUS["compiles"] += 1
        _CENSUS["compile_s"] += duration_s
    window = getattr(_TLS, "launch_window", None)
    if window is not None:
        window.note_compile(duration_s)


def accelerator_info() -> dict[str, Any]:
    """The devices this process serves from, as JAX reports them — the
    `accelerator` entry of `_nodes` and of every node's `_nodes/stats`
    section (a ProcCluster worker reports its own, CPU unless told)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def ensure_compile_listener() -> None:
    """Register the process-wide compile-event listener once. jax offers
    no unregister, so this is a lifetime hook — it only bumps counters."""
    global _LISTENER_REGISTERED
    with _PROC_LOCK:
        if _LISTENER_REGISTERED:
            return
        _LISTENER_REGISTERED = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


class HbmLedger:
    """Byte accounting for everything resident on device, by (label,
    scope). Scopes are the components' own cache-scope tokens (engine
    uid, mesh scope tuple, "_packed"); `name_scope` maps them to index
    names for the {label,index} gauge rendering. The breaker writes
    through (`breaker_backed=True`), so `breaker_drift_bytes` is
    structurally zero; components the breaker does not guard (packed
    planes, mesh snapshots) register directly."""

    def __init__(self, metrics=None, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._bytes: dict[tuple[str, Any], int] = {}
        self._names: dict[Any, str] = {}
        self._total = 0
        self._hwm = 0
        self._breaker_bytes = 0
        self.breaker = None  # set by CircuitBreaker(ledger=...)
        self.metrics = metrics
        self._gauged: set[tuple[str, str]] = set()
        if metrics is not None:
            metrics.gauge(
                "estpu_hbm_high_watermark_bytes",
                "Lifetime peak of total ledger-resident device bytes",
                fn=lambda: self._hwm,
            )

    # ------------------------------------------------------------- naming

    def name_scope(self, scope: Any, index_name: str) -> None:
        """Associate a component scope token with an index name (the
        gauge/cat `index` column). Idempotent; unknown scopes render as
        `_node`. Bytes may register BEFORE naming (boot recovery packs
        segments while the engine is constructed, before the node can
        name its uid) — re-ensure the named gauge series for any label
        already holding bytes under this scope, so the recovered HBM is
        visible at `/_metrics` immediately (the old `_node` series reads
        0 from then on)."""
        with self._lock:
            self._names[scope] = index_name
            labels = {
                label for (label, s) in self._bytes if s == scope
            }
        for label in labels:
            self._ensure_gauge(label, index_name)

    def forget_scope(self, scope: Any) -> None:
        with self._lock:
            self._names.pop(scope, None)

    def _index_of(self, scope: Any) -> str:
        name = self._names.get(scope)
        if name is not None:
            return name
        return "_node"

    # --------------------------------------------------------- accounting

    def register(
        self,
        label: str,
        scope: Any,
        nbytes: int,
        breaker_backed: bool = False,
    ) -> None:
        """Account `nbytes` landing on device under (label, scope)."""
        if not self.enabled or nbytes <= 0:
            return
        nbytes = int(nbytes)
        base = _base_label(label)
        key = (base, scope)
        with self._lock:
            self._bytes[key] = self._bytes.get(key, 0) + nbytes
            self._total += nbytes
            if self._total > self._hwm:
                self._hwm = self._total
            if breaker_backed:
                self._breaker_bytes += nbytes
            index = self._index_of(scope)
        _proc_register(nbytes)
        self._ensure_gauge(base, index)

    def release(
        self,
        label: str,
        scope: Any,
        nbytes: int,
        breaker_backed: bool = False,
    ) -> None:
        """Account `nbytes` leaving the device. Clamped per key: the
        ledger can never go negative, mirroring the breaker's own clamp."""
        if not self.enabled or nbytes <= 0:
            return
        nbytes = int(nbytes)
        key = (_base_label(label), scope)
        with self._lock:
            held = self._bytes.get(key, 0)
            taken = min(held, nbytes)
            if taken:
                remaining = held - taken
                if remaining:
                    self._bytes[key] = remaining
                else:
                    del self._bytes[key]
                self._total -= taken
            if breaker_backed:
                self._breaker_bytes = max(0, self._breaker_bytes - nbytes)
        _proc_release(nbytes)

    def _ensure_gauge(self, label: str, index: str) -> None:
        if self.metrics is None:
            return
        with self._lock:
            if (label, index) in self._gauged:
                return
            self._gauged.add((label, index))
        self.metrics.gauge(
            "estpu_hbm_bytes",
            "Device bytes resident per ledger label and index",
            fn=lambda l=label, i=index: self._label_index_bytes(l, i),
            label=label,
            index=index,
        )

    def _label_index_bytes(self, label: str, index: str) -> int:
        with self._lock:
            return sum(
                n
                for (lbl, scope), n in self._bytes.items()
                if lbl == label and self._index_of(scope) == index
            )

    # -------------------------------------------------------------- views

    def bytes_for(self, label: str, scope: Any = None) -> int:
        """Resident bytes of one label (optionally one scope) — the
        consistency-law accessor the tests gate on."""
        base = _base_label(label)
        with self._lock:
            if scope is not None:
                return self._bytes.get((base, scope), 0)
            return sum(
                n for (lbl, _s), n in self._bytes.items() if lbl == base
            )

    @property
    def total_bytes(self) -> int:
        with self._lock:
            return self._total

    @property
    def high_watermark_bytes(self) -> int:
        with self._lock:
            return self._hwm

    def snapshot(self) -> dict[str, Any]:
        """The `device.hbm` section of `_nodes/stats`."""
        if not self.enabled:
            return self.disabled_snapshot()
        with self._lock:
            by_label: dict[str, int] = {}
            rows: dict[tuple[str, str], int] = {}
            for (label, scope), n in self._bytes.items():
                by_label[label] = by_label.get(label, 0) + n
                rk = (label, self._index_of(scope))
                rows[rk] = rows.get(rk, 0) + n
            total = self._total
            hwm = self._hwm
            breaker_bytes = self._breaker_bytes
        out: dict[str, Any] = {
            "enabled": True,
            "total_bytes": total,
            "high_watermark_bytes": hwm,
            "by_label": {k: by_label[k] for k in sorted(by_label)},
            "by_label_index": [
                {"label": label, "index": index, "bytes": rows[(label, index)]}
                for label, index in sorted(rows)
            ],
        }
        if self.breaker is not None:
            used = self.breaker.stats()["estimated_size_in_bytes"]
            out["breaker_used_bytes"] = used
            # Structurally zero: every breaker mutation writes through.
            out["breaker_drift_bytes"] = used - breaker_bytes
        return out

    @staticmethod
    def disabled_snapshot() -> dict[str, Any]:
        """Section shape under ESTPU_DEVICE_OBS=0 — present, inert."""
        return {
            "enabled": False,
            "total_bytes": 0,
            "high_watermark_bytes": 0,
            "by_label": {},
            "by_label_index": [],
        }

    @staticmethod
    def computed_section(
        engines=(),
        filter_cache=None,
        ann_cache=None,
        engines_by_index: dict[str, list] | None = None,
    ) -> dict[str, Any]:
        """A ledger-shaped `device.hbm` section computed from component
        stats — the per-ClusterNode form (workers carry no breaker, so
        no write-through ledger; by the consistency law the computed
        totals ARE the ledger totals). A computed section carries NO
        high watermark — the instantaneous total is not a peak, and a
        fake one would silently mean something different from the
        coordinating node's real lifetime peak. `engines_by_index`
        (index name -> engines) attributes segment rows per index; the
        flat `engines` form lands under `_node`."""
        by_label: dict[str, int] = {}
        rows: list[dict[str, Any]] = []
        if engines_by_index:
            seg = 0
            for index in sorted(engines_by_index):
                n = int(
                    sum(e.device_bytes for e in engines_by_index[index])
                )
                if n:
                    rows.append(
                        {"label": "segment", "index": index, "bytes": n}
                    )
                seg += n
        else:
            seg = int(sum(e.device_bytes for e in engines))
            if seg:
                rows.append(
                    {"label": "segment", "index": "_node", "bytes": seg}
                )
        if seg:
            by_label["segment"] = seg
        if filter_cache is not None:
            fc = int(filter_cache.stats()["bytes_resident"])
            if fc:
                by_label["filter_cache"] = fc
                rows.append(
                    {"label": "filter_cache", "index": "_node", "bytes": fc}
                )
        if ann_cache is not None:
            ann = int(ann_cache.stats()["bytes_resident"])
            if ann:
                by_label["ann_cache"] = ann
                rows.append(
                    {"label": "ann_cache", "index": "_node", "bytes": ann}
                )
        return {
            "enabled": True,
            "source": "computed",
            "total_bytes": sum(by_label.values()),
            "by_label": by_label,
            "by_label_index": sorted(
                rows, key=lambda r: (r["label"], r["index"])
            ),
        }


def _base_label(label: str) -> str:
    """Canonical ledger label of a (possibly decorated) breaker label:
    the longest LEDGER_LABELS entry the label starts with, so dynamic
    suffixes collapse onto one bounded-cardinality series."""
    for known in LEDGER_LABELS:
        if label == known or label.startswith(known):
            return known
    return label


# ---------------------------------------------------------------------------
# Profiler capture
# ---------------------------------------------------------------------------


class ProfilerConflictError(Exception):
    """A capture is already running (HTTP 409)."""


class ProfilerInactiveError(Exception):
    """No capture is running (HTTP 400)."""


class ProfilerCapture:
    """Single-flight `jax.profiler` capture with a bounded duration.

    `start()` opens `jax.profiler.start_trace(trace_dir)`; a watchdog
    timer force-stops the capture at `duration_s` (clamped to
    ESTPU_PROFILER_MAX_S, default 120) so a forgotten capture can never
    grow a trace directory unbounded. `stop()` closes the capture,
    returns the Perfetto trace directory, and stamps the capture window
    into the obs trace ring as a `profiler.capture` trace whose span
    covers [start, stop] on the same clock as every request trace."""

    def __init__(self, base_dir: str | None = None):
        self._lock = threading.Lock()
        self._active: dict[str, Any] | None = None
        self._timer: threading.Timer | None = None
        self._captures = 0
        self.base_dir = base_dir

    @staticmethod
    def _max_duration_s() -> float:
        return float(os.environ.get("ESTPU_PROFILER_MAX_S", 120.0))

    def start(
        self, duration_s: float | None = None, trace_dir: str | None = None
    ) -> dict[str, Any]:
        import tempfile

        import jax

        bound = self._max_duration_s()
        if duration_s is None:
            duration_s = bound
        duration_s = min(float(duration_s), bound)
        if duration_s <= 0:
            raise ValueError(
                f"profiler duration must be positive, got {duration_s}"
            )
        with self._lock:
            if self._active is not None:
                raise ProfilerConflictError(
                    "a profiler capture is already running "
                    f"(trace_dir [{self._active['trace_dir']}]); stop it "
                    "before starting another"
                )
            if trace_dir is None:
                trace_dir = tempfile.mkdtemp(
                    prefix="estpu_profile_", dir=self.base_dir
                )
            jax.profiler.start_trace(trace_dir)
            self._captures += 1
            self._active = {
                "trace_dir": trace_dir,
                # staticcheck: ignore[wallclock-duration] user-facing capture start epoch timestamp; durations come from the monotonic twin
                "started_at_ms": time.time() * 1e3,
                "started_mono": time.monotonic(),
                "bound_s": duration_s,
            }
            timer = threading.Timer(duration_s, self._expire)
            timer.daemon = True
            timer.start()
            self._timer = timer
            return {
                "acknowledged": True,
                "trace_dir": trace_dir,
                "max_duration_s": duration_s,
            }

    def _expire(self) -> None:
        """Watchdog: force-stop a capture that outlived its bound."""
        try:
            self.stop(reason="expired")
        except ProfilerInactiveError:
            pass  # raced a user stop; nothing to do

    def stop(self, reason: str = "requested") -> dict[str, Any]:
        import jax

        with self._lock:
            active = self._active
            if active is None:
                raise ProfilerInactiveError("no profiler capture is running")
            self._active = None
            timer, self._timer = self._timer, None
            jax.profiler.stop_trace()
        if timer is not None:
            timer.cancel()
        duration_ms = (time.monotonic() - active["started_mono"]) * 1e3
        # Stamp the capture window into the obs trace ring: one
        # `profiler.capture` trace whose root span covers the window, so
        # `GET /_traces` lays the device capture alongside request traces.
        from .tracing import TRACER

        handle = TRACER.start_trace(
            "profiler.capture",
            trace_dir=active["trace_dir"],
            reason=reason,
        )
        if handle.span is not None:
            handle.span.start_ms = active["started_at_ms"]
            handle.span.start_mono = active["started_mono"]
        with handle:
            pass  # enter+exit: finish() seals the window into the ring
        return {
            "acknowledged": True,
            "trace_dir": active["trace_dir"],
            "duration_ms": round(duration_ms, 3),
            "stopped": reason,
            "trace_id": (
                handle.span.trace_id if handle.span is not None else None
            ),
        }

    def status(self) -> dict[str, Any]:
        with self._lock:
            active = self._active
            captures = self._captures
        if active is None:
            return {"running": False, "captures_total": captures}
        return {
            "running": True,
            "captures_total": captures,
            "trace_dir": active["trace_dir"],
            "elapsed_ms": round(
                (time.monotonic() - active["started_mono"]) * 1e3, 3
            ),
            "max_duration_s": active["bound_s"],
        }
