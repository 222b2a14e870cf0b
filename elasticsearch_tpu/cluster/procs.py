"""Multi-process cluster serving: N OS processes over the TCP transport.

The production topology the in-process LocalCluster simulates: each
worker is a SPAWNED OS process owning one node id, its own data_path, and
its own engines/device context, talking to its peers over
cluster/tcp_transport.py sockets. `kill -9` of a worker is therefore a
real failure mode — half-written frames, connection-refused dials, a
process that vanishes without unwinding a single lock — and the
promotion / zero-acked-write-loss / partition-heal guarantees are proven
against it, not against a simulated `close()`.

Topology: `ProcCluster(n_workers)` boots the workers plus (by default) a
voting-only TIEBREAKER node living in the supervisor process — the
classic two-data-nodes-plus-tiebreaker shape, so a 2-process cluster
survives kill -9 of either data process with an intact election quorum
while the tiebreaker (ClusterState.voting_only) never holds shard
copies. The tiebreaker doubles as the supervisor's coordinating node:
client writes/searches/reads enter there and route over real sockets.
With `tiebreaker=False` the supervisor instead drives a non-member
client endpoint through the `client_*` transport actions.

Supervisor API mirrors LocalCluster where it matters:

- `kill_9(node_id)` — SIGKILL the worker process (no goodbye; its
  address file stays behind, stale, exactly like a crashed host).
- `restart(node_id)` — spawn a fresh process for that node id; it boots
  from its persisted cluster state and re-acquires copies via peer
  recovery. The supervisor re-broadcasts the current interception rules
  to it.
- `partition(*groups)` / `heal_partition()` / `drop_action(...)` /
  `set_delay(...)` — broadcast over a dedicated, never-intercepted
  control endpoint; each worker applies the rules to its OWN sender-side
  TransportIntercepts, so a partition blocks at every node's real socket
  layer symmetrically.

Device ownership: workers force the JAX platform named in
`jax_platforms` (default "cpu"), so on a chip host this topology serves
from the workers' CPUs unless told otherwise — each worker's `_nodes`
`accelerator` entry shows the platform it really uses. A chip belongs to
one process: workers are never asked for it while the supervisor has
imported JAX (which holds the chip). Passing
`jax_distributed={"coordinator_address": ..., "num_processes": ...,
"process_id": ...}` per worker initializes `jax.distributed` so each
process owns a device subset on real hardware; this is plumbing only —
CI never exercises it (no multi-host TPU in the loop) and it is honest
residue until a real pod run.
"""

from __future__ import annotations

import multiprocessing
import os
import signal
import sys
import threading
import time
from typing import Any, Callable

from .gateway import _RETRYABLE_LOCAL_TYPES, _RETRYABLE_REMOTE_TYPES
from .transport import ConnectTransportError, RemoteActionError

TIEBREAKER_ID = "tiebreaker"


class ProcClusterUnavailableError(Exception):
    """Supervisor-side retries exhausted against the process cluster."""


def _worker_main(cfg: dict) -> None:
    """One spawned worker: TCP endpoint + ClusterNode + stepper loop.

    Runs until a `_shutdown` control frame arrives or the supervisor
    process disappears (getppid flip). Every swallowed step error counts
    into estpu_cluster_step_errors_total — visible via `client_state`."""
    import jax

    jax.config.update("jax_platforms", cfg.get("jax_platforms") or "cpu")
    dist = cfg.get("jax_distributed")
    if dist:
        jax.distributed.initialize(**dist)
    from .cluster import ClusterNode
    from .tcp_transport import (
        FileAddressBook,
        StaticAddressBook,
        TcpTransport,
    )

    seed_addrs = cfg.get("seed_addrs")
    host, port = "127.0.0.1", 0
    if seed_addrs:
        # Multi-host form: peers resolve from the pre-agreed static map
        # (no shared addr directory), and this worker must bind exactly
        # the address the map promised for it.
        book = StaticAddressBook(seed_addrs)
        own = book.lookup(cfg["node_id"])
        if own is not None:
            host, port = own
    else:
        book = FileAddressBook(cfg["addr_dir"])
    transport = TcpTransport(
        cfg["node_id"],
        book,
        cluster_name=cfg["cluster_name"],
        default_timeout_s=cfg.get("send_timeout_s"),
        host=host,
        port=port,
        auth_key=cfg.get("auth_key"),
    )
    node = ClusterNode(
        cfg["node_id"],
        transport,
        tuple(cfg["seeds"]),
        state_path=cfg["data_path"],
        voting_only=tuple(cfg.get("voting_only", ())),
    )
    stop = threading.Event()

    def handler(from_id: str, action: str, payload: dict):
        # Control plane of the control plane: supervisor-only frames the
        # ClusterNode never sees.
        if action == "_shutdown":
            stop.set()
            return {"ok": True}
        if action == "_intercepts":
            transport.intercepts.load(payload)
            return {"ok": True}
        return node._handle(from_id, action, payload)

    transport.register(cfg["node_id"], handler)

    # Graceful stop: SIGTERM means "finish what you are doing, then
    # leave" — the rolling-restart signal, distinct from kill -9's
    # no-goodbye death. The handler only flips the stop event; the
    # drain/flush/close sequence below runs on the main thread.
    signal.signal(signal.SIGTERM, lambda _s, _f: stop.set())
    parent = os.getppid()
    interval = float(cfg.get("step_interval_s", 0.05))
    while not stop.wait(interval):
        if os.getppid() != parent:
            break  # supervisor died: no one owns this process anymore
        try:
            node.try_elect()
            if node.is_master():
                node.health_round()
            node.check_recoveries()
        # staticcheck: ignore[broad-except] daemon control-plane stepper: must survive any transient step error and retry next tick — every swallowed error is COUNTED (estpu_cluster_step_errors_total), never silent
        except Exception:
            node._step_errors.inc()
    # Drain before teardown: in-flight requests (a search mid-scatter, a
    # replica op mid-apply) finish and answer instead of dying as resets,
    # then every engine flushes segments + commit point so the restarted
    # process replays only the translog tail. A failed drain/flush must
    # never block exit — shutdown terminates, honestly degraded.
    try:
        transport.drain(timeout_s=float(cfg.get("drain_timeout_s", 5.0)))
        with node.lock:
            engines = list(node.engines.values())
        for engine in engines:
            engine.flush()
    # staticcheck: ignore[broad-except] shutdown path: a wedged drain or a flush error (disk full, injected transport.drain fault) must not keep a SIGTERM'd process alive
    except Exception:
        pass
    node.close()
    transport.close()


class ProcCluster:
    """Supervisor for a multi-process TCP cluster (LocalCluster's API
    shape over real OS processes)."""

    def __init__(
        self,
        n_workers: int = 2,
        data_path: str | None = None,
        tiebreaker: bool = True,
        cluster_name: str = "estpu-procs",
        jax_platforms: str = "cpu",
        jax_distributed: dict[str, dict] | None = None,
        step_interval_s: float = 0.05,
        send_timeout_s: float | None = 5.0,
        boot_timeout_s: float = 90.0,
        seed_addrs: dict[str, str] | None = None,
        auth_key: str | None = None,
        drain_timeout_s: float = 5.0,
    ):
        import tempfile

        from .tcp_transport import (
            FileAddressBook,
            StaticAddressBook,
            TcpTransport,
        )

        if jax_platforms != "cpu" and "jax" in sys.modules:
            raise RuntimeError(
                f"ProcCluster workers cannot take the {jax_platforms!r} "
                "devices: this process has imported JAX and holds them "
                "(one process per chip)"
            )
        self.data_path = data_path or tempfile.mkdtemp(prefix="estpu-procs-")
        self.addr_dir = os.path.join(self.data_path, "_addr")
        self.cluster_name = cluster_name
        self.jax_platforms = jax_platforms
        self.jax_distributed = jax_distributed or {}
        self.step_interval_s = step_interval_s
        self.send_timeout_s = send_timeout_s
        self.boot_timeout_s = boot_timeout_s
        self.drain_timeout_s = drain_timeout_s
        # Shared-key wire authn rides the worker cfg (NOT just the env:
        # a spawned worker must authenticate even when the supervisor got
        # the key programmatically). None falls back to ESTPU_TRANSPORT_KEY.
        self.auth_key = auth_key
        # Multi-host form: explicit node -> "host:port" seeds replace the
        # shared-filesystem address directory (discovery is configuration,
        # like the reference's discovery.seed_hosts).
        self.seed_addrs = dict(seed_addrs) if seed_addrs else None
        self.workers = tuple(f"node-{i}" for i in range(n_workers))
        self.voting_only = (TIEBREAKER_ID,) if tiebreaker else ()
        self.seeds = self.workers + self.voting_only
        self._ctx = multiprocessing.get_context("spawn")
        self._procs: dict[str, Any] = {}
        self._lock = threading.Lock()
        self._intercept_state: dict = {}
        self._metrics_cache: tuple[float, list] | None = None
        # Lazily-built health report service (obs/health.py): holds the
        # re-election/step-error history between report rounds.
        self._health = None
        # Transition hook (obs/incidents.py) handed down by the fronting
        # Node so the incident capture law holds in the proc topology
        # too — assigned onto the lazy HealthService at first report.
        self.health_transition_hook = None
        self._closed = False
        if self.seed_addrs:
            missing = [n for n in self.seeds if n not in self.seed_addrs]
            if missing:
                raise ValueError(
                    f"seed_addrs must name every cluster member; "
                    f"missing {missing}"
                )
            self._book = StaticAddressBook(self.seed_addrs)
        else:
            self._book = FileAddressBook(self.addr_dir)
        # Dedicated control endpoint: its intercepts stay EMPTY forever,
        # so partition/heal broadcasts always reach every worker even
        # when the cluster's own channels are partitioned.
        self._ctl = TcpTransport(
            "_ctl",
            self._book,
            cluster_name=cluster_name,
            default_timeout_s=send_timeout_s,
            auth_key=auth_key,
        )
        self._ctl.start()
        for node_id in self.workers:
            self._spawn(node_id)
        self._local_node = None
        self._tb_transport = None
        self._stepper: threading.Thread | None = None
        self._stop = threading.Event()
        if tiebreaker:
            from .cluster import ClusterNode

            tb_host, tb_port = "127.0.0.1", 0
            if self.seed_addrs:
                tb_addr = self._book.lookup(TIEBREAKER_ID)
                if tb_addr is not None:
                    tb_host, tb_port = tb_addr
            self._tb_transport = TcpTransport(
                TIEBREAKER_ID,
                self._book,
                cluster_name=cluster_name,
                default_timeout_s=send_timeout_s,
                host=tb_host,
                port=tb_port,
                auth_key=auth_key,
            )
            self._local_node = ClusterNode(
                TIEBREAKER_ID,
                self._tb_transport,
                self.seeds,
                state_path=os.path.join(self.data_path, TIEBREAKER_ID),
                voting_only=self.voting_only,
            )
            self._start_tiebreaker_stepper()
        self.wait_ready()

    # ------------------------------------------------------------ workers

    def _spawn(self, node_id: str) -> None:
        cfg = {
            "node_id": node_id,
            "seeds": list(self.seeds),
            "voting_only": list(self.voting_only),
            "addr_dir": self.addr_dir,
            "data_path": os.path.join(self.data_path, node_id),
            "cluster_name": self.cluster_name,
            "jax_platforms": self.jax_platforms,
            "jax_distributed": self.jax_distributed.get(node_id),
            "step_interval_s": self.step_interval_s,
            "send_timeout_s": self.send_timeout_s,
            "seed_addrs": self.seed_addrs,
            "auth_key": self.auth_key,
            "drain_timeout_s": self.drain_timeout_s,
        }
        proc = self._ctx.Process(
            target=_worker_main, args=(cfg,), name=f"estpu-{node_id}"
        )
        proc.daemon = True
        proc.start()
        with self._lock:
            self._procs[node_id] = proc

    def _start_tiebreaker_stepper(self) -> None:
        node = self._local_node

        def loop():
            while not self._stop.wait(self.step_interval_s):
                try:
                    node.try_elect()
                    if node.is_master():
                        node.health_round()
                    node.check_recoveries()
                # staticcheck: ignore[broad-except] daemon control-plane stepper: must survive any transient step error and retry next tick — every swallowed error is COUNTED (estpu_cluster_step_errors_total), never silent
                except Exception:
                    node._step_errors.inc()

        self._stepper = threading.Thread(
            target=loop, daemon=True, name="estpu-tiebreaker-stepper"
        )
        self._stepper.start()

    def pid(self, node_id: str) -> int | None:
        with self._lock:
            proc = self._procs.get(node_id)
        return None if proc is None else proc.pid

    def wait_ready(
        self,
        timeout_s: float | None = None,
        node_ids: tuple[str, ...] | None = None,
    ) -> None:
        """Block until the given workers (default: all) answer a ping
        over their sockets."""
        deadline = time.monotonic() + (timeout_s or self.boot_timeout_s)
        for node_id in node_ids if node_ids is not None else self.workers:
            while True:
                try:
                    self._ctl.send(
                        "_ctl", node_id, "ping", {}, timeout_s=2.0
                    )
                    break
                except (ConnectTransportError, RemoteActionError) as e:
                    if time.monotonic() >= deadline:
                        raise ProcClusterUnavailableError(
                            f"worker [{node_id}] never came up: {e}"
                        ) from e
                    time.sleep(0.1)

    def kill_9(self, node_id: str) -> None:
        """Real process death: SIGKILL, no goodbye, stale address file."""
        with self._lock:
            proc = self._procs.get(node_id)
        if proc is None or proc.pid is None:
            return
        os.kill(proc.pid, signal.SIGKILL)
        proc.join(timeout=10)

    def sigterm(self, node_id: str, timeout_s: float = 20.0) -> None:
        """Graceful stop (the rolling-restart signal): SIGTERM, then wait
        for the worker's drain → translog/segment flush → close sequence
        to finish. Escalates to SIGKILL past the deadline — shutdown must
        terminate even when the drain wedges."""
        with self._lock:
            proc = self._procs.get(node_id)
        if proc is None or proc.pid is None:
            return
        try:
            os.kill(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            return
        proc.join(timeout=timeout_s)
        if proc.is_alive():
            os.kill(proc.pid, signal.SIGKILL)
            proc.join(timeout=5)

    def restart(self, node_id: str) -> None:
        """Fresh process for the node id: boots from its persisted
        cluster state, rejoins, re-acquires copies via peer recovery."""
        with self._lock:
            proc = self._procs.pop(node_id, None)
        if proc is not None and proc.is_alive():
            raise ValueError(f"[{node_id}] is still running; kill it first")
        self._spawn(node_id)
        # Wait for THIS worker only: other workers may be intentionally
        # dead (multi-failure chaos) and must not block the restart.
        self.wait_ready(node_ids=(node_id,))
        if self._intercept_state:
            # A restarted worker boots with empty interception rules;
            # converge it onto the cluster's current ruleset.
            self._send_intercepts(node_id, self._intercept_state)

    # ------------------------------------------------- interception control

    def _send_intercepts(self, node_id: str, state: dict) -> None:
        try:
            self._ctl.send(
                "_ctl", node_id, "_intercepts", state, timeout_s=5.0
            )
        except (ConnectTransportError, RemoteActionError):
            pass  # dead worker: it gets the ruleset again on restart

    def _broadcast_intercepts(self, state: dict) -> None:
        self._intercept_state = state
        for node_id in self.workers:
            self._send_intercepts(node_id, state)
        if self._local_node is not None:
            self._tb_transport.intercepts.load(state)

    def partition(self, *groups) -> None:
        """Socket-layer partition: every node refuses sends that cross
        group lines, symmetrically."""
        state = dict(self._intercept_state or {"drops": [], "delay_s": 0.0})
        state["partitions"] = [sorted(g) for g in groups]
        self._broadcast_intercepts(state)

    def heal_partition(self) -> None:
        state = dict(self._intercept_state or {})
        state["partitions"] = []
        self._broadcast_intercepts(state)

    def drop_action(self, from_id: str, to_id: str, pattern: str) -> None:
        state = dict(self._intercept_state or {})
        state.setdefault("drops", []).append([from_id, to_id, pattern])
        self._broadcast_intercepts(state)

    def clear_drops(self) -> None:
        state = dict(self._intercept_state or {})
        state["drops"] = []
        self._broadcast_intercepts(state)

    def set_delay(
        self, seconds: float, from_id: str = "*", to_id: str = "*"
    ) -> None:
        """Injected latency, broadcast to every node's sender-side
        intercepts. The all-pairs default keeps the historical global
        knob; the targeted form (``set_delay(2.0, to_id="node-1")``)
        models ONE browned-out peer: every send toward it crawls while
        healthy paths stay fast. ``set_delay(0)`` clears everything."""
        state = dict(self._intercept_state or {})
        if from_id == "*" and to_id == "*":
            state["delay_s"] = float(seconds)
            if not seconds:
                state["delays"] = []
        else:
            delays = [
                d
                for d in state.get("delays", [])
                if (d[0], d[1]) != (from_id, to_id)
            ]
            if seconds:
                delays.append([from_id, to_id, float(seconds)])
            state["delays"] = delays
        self._broadcast_intercepts(state)

    # ------------------------------------------------------------- client

    def _retry(
        self,
        fn: Callable[[], Any],
        timeout_s: float = 30.0,
        backoff_s: float = 0.05,
    ):
        """Bounded supervisor-side retry over topology-shaped failures —
        the gateway's exact classification (shared sets) — while the
        workers' own steppers drive detection/promotion between attempts
        (there is no cluster.step() to call across processes)."""
        deadline = time.monotonic() + timeout_s
        last: Exception | None = None
        while True:
            try:
                return fn()
            except RemoteActionError as e:
                if e.remote_type not in _RETRYABLE_REMOTE_TYPES:
                    raise
                last = e
            except _RETRYABLE_LOCAL_TYPES as e:
                last = e
            if time.monotonic() >= deadline:
                raise ProcClusterUnavailableError(
                    f"cluster operation failed within {timeout_s}s: {last}"
                ) from last
            time.sleep(backoff_s)

    def _send_any(self, action: str, payload: dict):
        """client_* action against any answering worker."""
        last: Exception | None = None
        for node_id in self.workers:
            try:
                return self._ctl.send("_ctl", node_id, action, payload)
            except (ConnectTransportError, RemoteActionError) as e:
                if (
                    isinstance(e, RemoteActionError)
                    and e.remote_type not in _RETRYABLE_REMOTE_TYPES
                ):
                    raise
                last = e
        raise ConnectTransportError(f"no worker answered [{action}]: {last}")

    def create_index(
        self,
        name: str,
        n_shards: int = 1,
        n_replicas: int = 1,
        mappings: dict | None = None,
        timeout_s: float = 30.0,
    ) -> dict:
        payload = {
            "name": name,
            "n_shards": n_shards,
            "n_replicas": n_replicas,
            "mappings": mappings or {},
        }
        if self._local_node is not None:
            node = self._local_node

            def do():
                return node._on_client_create_index("supervisor", payload)

        else:

            def do():
                return self._send_any("client_create_index", payload)

        return self._retry(do, timeout_s=timeout_s)

    def write(
        self,
        index: str,
        doc_id: str,
        source: dict | None,
        op: str = "index",
        timeout_s: float = 30.0,
    ) -> dict:
        if self._local_node is not None:
            node = self._local_node

            def do():
                return node.execute_write(index, doc_id, source, op=op)

        else:
            payload = {"index": index, "id": doc_id, "source": source, "op": op}

            def do():
                return self._send_any("client_write", payload)

        return self._retry(do, timeout_s=timeout_s)

    def read(self, index: str, doc_id: str, timeout_s: float = 30.0):
        if self._local_node is not None:
            node = self._local_node

            def do():
                return node.read_doc(index, doc_id)

        else:
            payload = {"index": index, "id": doc_id}

            def do():
                return self._send_any("client_read", payload)

        return self._retry(do, timeout_s=timeout_s)

    def search(self, index: str, body: dict, timeout_s: float = 30.0) -> dict:
        if self._local_node is not None:
            node = self._local_node

            def do():
                return node.search(index, body)

        else:
            payload = {"index": index, "body": body}

            def do():
                return self._send_any("client_search", payload)

        return self._retry(do, timeout_s=timeout_s)

    def state_of(self, node_id: str, timeout_s: float = 5.0) -> dict:
        """client_state of one worker (routing table, master, counters)."""
        return self._ctl.send(
            "_ctl", node_id, "client_state", {}, timeout_s=timeout_s
        )

    # --------------------------------------------- gateway-facing surface
    # The LocalCluster shape a ProcGateway / front Node expects: `hub`
    # (the coordinating transport), `nodes` (member ids), `step()` (one
    # synchronous control-plane round), `step_errors()`.

    @property
    def hub(self):
        """The coordinating endpoint cluster-facing code sends through:
        the tiebreaker's transport — INTERCEPTED like any member's, so a
        front Node's serving path honestly feels partitions/brownouts —
        or the control endpoint when no tiebreaker exists."""
        return self._tb_transport if self._tb_transport is not None else self._ctl

    @property
    def nodes(self) -> tuple[str, ...]:
        """Cluster member ids (sorted/len/iteration surface; the actual
        members live in other OS processes)."""
        return self.seeds

    def step(self) -> None:
        """One synchronous control-plane round on the supervisor-resident
        tiebreaker — the gateway's between-retries nudge (election /
        health round / recovery check). Worker processes run their own
        steppers; without a tiebreaker this is a no-op and detection is
        entirely theirs."""
        node = self._local_node
        if node is None:
            return
        node.try_elect()
        if node.is_master():
            node.health_round()
        node.check_recoveries()

    def step_errors(self) -> int:
        node = self._local_node
        return 0 if node is None else int(node._step_errors.value)

    def wait_for_status(
        self, wanted: str = "green", timeout_s: float = 60.0
    ) -> None:
        """Block until the shard summary over the tiebreaker's published
        state reaches `wanted` AND every worker is back in the
        membership — the heal barrier the chaos arcs use (`GET
        /_cluster/health?wait_for_status=green` over the REST front polls
        the same summary)."""
        from ..obs.health import shard_summary, status_at_least

        node = self._local_node
        if node is None:
            raise ProcClusterUnavailableError(
                "wait_for_status needs the supervisor-resident tiebreaker"
            )

        def ok() -> bool:
            state = node.state
            if not set(self.workers) <= set(state.nodes):
                return False
            return status_at_least(shard_summary(state)["status"], wanted)

        self.wait_for(
            ok, timeout_s=timeout_s, what=f"cluster status {wanted}"
        )

    # ------------------------------------------- cluster-scope observability

    def _fan(
        self,
        action: str,
        payload: dict | None = None,
        timeout_s: float | None = None,
    ):
        """Scatter one wire action over every worker via the `_ctl`
        endpoint (never intercepted, so observability keeps working under
        armed partitions): partial-tolerant, deadline-bounded, named
        failure entries for dead/wedged processes."""
        from .transport import scatter_nodes

        if timeout_s is None:
            timeout_s = self.send_timeout_s or 5.0

        def send(node_id: str):
            return self._ctl.send(
                "_ctl", node_id, action, dict(payload or {}),
                timeout_s=timeout_s,
            )

        return scatter_nodes(
            list(self.workers), send, action, timeout_s,
            metrics=self._ctl.metrics,
        )

    def nodes_stats(self, extra: dict[str, dict] | None = None) -> dict:
        """`GET /_nodes/stats` over the process cluster: the `node_stats`
        wire action fanned to every worker plus the supervisor-resident
        tiebreaker, with a `_nodes: {total, successful, failed}` header —
        a kill -9'd worker shows up as a named failure entry within the
        per-send deadline, never a hang. `extra` grafts additional
        sections (the REST front's own node) into the payload."""
        results, failures = self._fan("node_stats")
        nodes: dict[str, dict] = {}
        if self._local_node is not None:
            nodes[TIEBREAKER_ID] = self._local_node.node_stats_local()
        for node_id in self.workers:
            if node_id in results:
                nodes[node_id] = results[node_id]
        for name, section in (extra or {}).items():
            nodes[name] = section
        local = (1 if self._local_node is not None else 0) + len(extra or {})
        header: dict[str, Any] = {
            "total": len(self.workers) + local,
            "successful": len(results) + local,
            "failed": len(failures),
        }
        if failures:
            header["failures"] = failures
        return {
            "_nodes": header,
            "cluster_name": self.cluster_name,
            "nodes": nodes,
        }

    def health_report(
        self,
        verbose: bool = True,
        indicator: str | None = None,
        extra_inputs: dict[str, dict] | None = None,
    ) -> dict:
        """`GET /_health_report` over the process cluster: the
        `health_inputs` wire action fanned to every worker over the
        never-intercepted `_ctl` socket path plus the supervisor-resident
        tiebreaker's own inputs, interpreted by the SAME obs/health.py
        indicator functions the in-process forms use. A kill -9'd worker
        becomes a named per-indicator diagnosis within the per-send
        deadline — never a hang. ``verbose=False`` skips the worker fan
        (cheap liveness probe: statuses + symptoms from the supervisor's
        view alone)."""
        from ..obs.health import HealthContext, HealthService

        if self._health is None:
            self._health = HealthService(metrics=self._ctl.metrics)
        self._health.transition_hook = self.health_transition_hook
        node_inputs: dict[str, dict] = {}
        failures: list[dict] = []
        state = None
        coordinator = "_ctl"
        if self._local_node is not None:
            coordinator = TIEBREAKER_ID
            state = self._local_node.state
            node_inputs[TIEBREAKER_ID] = (
                self._local_node.health_inputs_local()
            )
        if verbose:
            results, failures = self._fan("health_inputs")
            for node_id in self.workers:
                if node_id in results:
                    node_inputs[node_id] = results[node_id]
        for name, inputs in (extra_inputs or {}).items():
            node_inputs.setdefault(name, inputs)
        if state is None:
            # No tiebreaker: adopt an answering worker's published state
            # for the shard/master rules — in BOTH modes (a terse probe
            # with no state would report a healthy cluster red). Verbose
            # prefers the freshest fanned section's node; terse asks the
            # workers in order until one answers.
            from .state import ClusterState

            candidates = list(self.workers)
            if verbose and results:
                candidates = sorted(
                    results,
                    key=lambda n: (
                        results[n].get("cluster_state", {}).get("term", 0),
                        results[n]
                        .get("cluster_state", {})
                        .get("version", 0),
                    ),
                    reverse=True,
                ) + [n for n in candidates if n not in results]
            for node_id in candidates:
                try:
                    raw = self.state_of(node_id)
                    state = ClusterState.from_json(raw["state"])
                    break
                except (ConnectTransportError, RemoteActionError):
                    continue
        ctx = HealthContext(
            cluster_name=self.cluster_name,
            coordinator=coordinator,
            standalone=False,
            state=state,
            expected_nodes=tuple(self.workers),
            node_inputs=node_inputs,
            fan_failures=failures,
            fanned=verbose,
        )
        return self._health.report(
            ctx, verbose=verbose, indicator=indicator
        )

    def metrics_text(
        self,
        max_age_s: float | None = None,
        extra_snapshots: tuple = (),
    ) -> str:
        """Federated `GET /_metrics`: every live worker's registry ships
        over the `metrics_wire` action and re-exposes here with a
        `node=<id>` label per series; counters additionally fold into
        `node="_cluster"` totals. The worker fan caches for
        ESTPU_METRICS_FED_TTL_S (default 0.5s) so a scrape storm cannot
        multiply fan-outs; the fan itself is deadline-bounded and runs
        only at scrape time — never on the serving hot path.
        `extra_snapshots` (WireRegistrySnapshot, e.g. the REST front's
        own registry) join the exposition and the cluster fold uncached."""
        from ..analysis.analyzers import ANALYSIS_METRICS
        from ..obs.metrics import WireRegistrySnapshot, fold_cluster_counters

        if max_age_s is None:
            max_age_s = float(
                os.environ.get("ESTPU_METRICS_FED_TTL_S", "0.5") or 0.5
            )
        now = time.monotonic()
        with self._lock:
            cached = self._metrics_cache
        if cached is not None and now - cached[0] <= max_age_s:
            snapshots = cached[1]
        else:
            results, _failures = self._fan("metrics_wire")
            snapshots = [
                WireRegistrySnapshot(
                    (results[node_id] or {}).get("families"), node=node_id
                )
                for node_id in sorted(results)
            ]
            if self._local_node is not None:
                snapshots.append(
                    WireRegistrySnapshot(
                        self._local_node.metrics.to_wire(
                            self._tb_transport.metrics
                        ),
                        node=TIEBREAKER_ID,
                    )
                )
            with self._lock:
                self._metrics_cache = (time.monotonic(), snapshots)
        merged = list(snapshots) + list(extra_snapshots)
        return self._ctl.metrics.exposition(
            ANALYSIS_METRICS, *merged, fold_cluster_counters(merged)
        )

    def hot_threads(
        self,
        threads: int = 3,
        interval_s: float = 0.5,
        snapshots: int = 10,
    ) -> str:
        """`GET /_nodes/hot_threads` over the process cluster: every
        worker samples its OWN interpreter's thread stacks; the texts
        concatenate under `::: {node}` headers, with a failure line for
        any process that could not be sampled."""
        from ..obs.hot_threads import fan_text_blocks, hot_threads_text

        payload = {
            "threads": threads,
            "interval_s": interval_s,
            "snapshots": snapshots,
        }
        local_box: dict[str, str] = {}
        sampler = None
        if self._local_node is not None:
            # Supervisor sample runs CONCURRENTLY with the fan: one
            # interval of wall clock for the whole cluster.
            local_node = self._local_node

            def sample_local() -> None:
                local_box["text"] = hot_threads_text(
                    node_name=TIEBREAKER_ID,
                    threads=threads,
                    interval_s=interval_s,
                    snapshots=snapshots,
                    metrics=local_node.metrics,
                )

            sampler = threading.Thread(target=sample_local, daemon=True)
            sampler.start()
        results, failures = self._fan(
            "hot_threads",
            payload,
            timeout_s=(self.send_timeout_s or 5.0) + float(interval_s),
        )
        blocks = []
        if sampler is not None:
            sampler.join()
            blocks.append(local_box.get("text", ""))
        blocks.extend(
            fan_text_blocks(results, failures, order=list(self.workers))
        )
        return "\n".join(blocks)

    def search_traced(
        self, index: str, body: dict, timeout_s: float = 30.0
    ) -> tuple[dict, str]:
        """Search under a ROOT trace span: (response, trace_id). The
        remote shard executions' spans land in the worker processes'
        rings; `trace(trace_id)` splices them back into one tree."""
        from ..obs.tracing import TRACER

        with TRACER.start_trace("procs.search", index=index) as root:
            out = self.search(index, body, timeout_s=timeout_s)
        return out, root.trace_id

    def trace(self, trace_id: str, fmt: str | None = None):
        """Distributed trace assembly: collect this trace's fragments
        from the supervisor's own ring and every live worker, splice ONE
        tree. None when no process buffered the trace; `fmt="chrome"`
        renders Perfetto-loadable trace-event JSON covering the whole
        cluster (one track per node)."""
        from ..obs.tracing import TRACER, chrome_trace, collect_fragments

        results, failures = self._fan(
            "trace_fragment", {"trace_id": trace_id}
        )
        spans, collected = collect_fragments(TRACER.get(trace_id), results)
        if collected:
            self._ctl.metrics.counter(
                "estpu_trace_fragments_collected_total",
                "Trace-fragment spans collected from cluster nodes",
            ).inc(collected)
        if not spans:
            return None
        if fmt == "chrome":
            return chrome_trace(spans)
        tb = 1 if self._local_node is not None else 0
        header: dict[str, Any] = {
            "total": len(self.workers) + tb,
            "successful": len(results) + tb,
            "failed": len(failures),
        }
        if failures:
            header["failures"] = failures
        return {"trace_id": trace_id, "_nodes": header, "spans": spans}

    def wait_for(
        self,
        predicate: Callable[[], bool],
        timeout_s: float = 30.0,
        interval_s: float = 0.1,
        what: str = "condition",
    ) -> None:
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                if predicate():
                    return
            except (ConnectTransportError, RemoteActionError):
                pass  # mid-failover flakes: keep polling
            if time.monotonic() >= deadline:
                raise ProcClusterUnavailableError(
                    f"timed out after {timeout_s}s waiting for {what}"
                )
            time.sleep(interval_s)

    # ------------------------------------------------------------ teardown

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        try:
            for node_id in self.workers:
                try:
                    self._ctl.send(
                        "_ctl", node_id, "_shutdown", {}, timeout_s=2.0
                    )
                except (ConnectTransportError, RemoteActionError):
                    pass  # already dead
            with self._lock:
                procs = dict(self._procs)
            deadline = time.monotonic() + 10.0
            for node_id, proc in procs.items():
                proc.join(timeout=max(0.1, deadline - time.monotonic()))
                if proc.is_alive():
                    os.kill(proc.pid, signal.SIGKILL)
                    proc.join(timeout=5)
            if self._stepper is not None:
                self._stepper.join(timeout=2)
        finally:
            # Child reaping must NEVER leak the supervisor's sockets: the
            # tiebreaker endpoint and the `_ctl` listener close even when
            # a join/kill above throws (a leaked `_ctl` listener holds
            # its port and fd for the supervisor's lifetime).
            try:
                if self._local_node is not None:
                    self._local_node.close()
                    self._tb_transport.close()
            finally:
                self._ctl.close()
