"""Host cluster layer: membership, primaries/replicas, failover, recovery.

The control plane the reference spreads over cluster/coordination
(Coordinator.java:87 — elections, quorum publication), action/support/
replication (ReplicationOperation.java:111 — primary→replica write
fan-out), index/seqno (ReplicationTracker.java:68 — in-sync sets and
checkpoints), and indices/recovery (RecoverySourceHandler.java:94 —
ops-based peer recovery). On TPU pods the *data* plane (search) stays
in-program over ICI (parallel/sharded.py, mesh_serving.py); this module is
the *host* plane: which host owns which shard copy, how writes reach every
in-sync copy before acking, and how copies fail over and catch up.

Simplifications vs the reference, chosen to keep the safety story intact:

- Election: the candidate is the lowest node id among reachable seeds; it
  must win votes from a QUORUM of the seed configuration for a bumped
  term. (The reference adds randomized pre-voting to reduce churn; the
  quorum + term rules — the safety part — are the same.)
- Publication is synchronous best-effort; the master steps down when it
  cannot reach a quorum, and every state-mutating master action requires
  a quorum-acked publication before the caller proceeds.
- Acknowledged-write safety is the reference's exact invariant chain:
  a write acks only after every in-sync copy applied it; only in-sync
  copies are promotable; a replica rejects ops from a stale primary term;
  failing a copy out of the in-sync set requires a quorum-published
  state change. Therefore a promoted primary has every acknowledged op.
- Health checking is a master-driven ping round (`LocalCluster.step`),
  deterministic for tests; a background stepper thread makes it live.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any

import numpy as np

from ..index.engine import Engine, VersionConflictError
from ..index.mapping import Mappings
from ..index.seqno import ReplicationTracker
from ..parallel.routing import shard_for_id
from .response_collector import ResponseCollectorService
from .state import ClusterState, IndexMeta, ShardRouting
from .transport import ConnectTransportError, RemoteActionError, TransportHub

# How long a node trusts its last contact with the master before a
# non-member client request forces an active master ping (the minority-
# side stale-serving guard: a node cut off from the master must refuse
# to serve possibly-stale data to external clients instead of answering
# from a state the majority may have moved past).
MASTER_LEASE_S = float(os.environ.get("ESTPU_MASTER_LEASE_S", "1.0") or 1.0)


class NoShardAvailableError(Exception):
    pass


class ShardSearchFailedError(Exception):
    """A shard failed every copy while allow_partial_search_results=false:
    the request must surface as 503, never a silently-partial 200. Carries
    the per-shard failure entries for the error body."""

    def __init__(self, message: str, failures: list | None = None):
        super().__init__(message)
        self.failures = failures or []


class NotMasterError(Exception):
    pass


class StalePrimaryTermError(Exception):
    pass


class ReplicationFailedError(Exception):
    pass


class ClusterNode:
    """One host: engines for its assigned shard copies + cluster duties."""

    def __init__(
        self,
        node_id: str,
        hub: TransportHub,
        seeds: tuple[str, ...],
        state_path: str | None = None,
        voting_only: tuple[str, ...] = (),
    ):
        self.node_id = node_id
        self.hub = hub
        self.state = ClusterState(
            seed_nodes=seeds, voting_only=set(voting_only)
        )
        self._voting_only = tuple(voting_only)
        self.current_term = 0  # highest term voted for / seen
        # Monotonic time of the last proof the master can reach us (its
        # ping or an accepted publication) — the master lease the client-
        # entry stale-serving guard checks.
        self._master_contact = 0.0
        # Durable cluster-state directory (the reference's gateway/
        # PersistedClusterStateService): every accepted publication and
        # vote persists {current_term, state} so a full-cluster restart
        # recovers membership/in-sync sets/primary terms instead of
        # re-bootstrapping empty metadata — without it, the first election
        # after a full restart could promote a stale (empty) copy under a
        # fresh term 1 and silently lose every index.
        self._state_path = state_path
        self.engines: dict[tuple[str, int], Engine] = {}
        self.trackers: dict[tuple[str, int], ReplicationTracker] = {}
        # Last-applied mappings blob per index: existing engines adopt
        # published mapping updates (put_mapping propagation) only when
        # the blob actually changed.
        self._applied_mappings: dict[str, str] = {}
        self.lock = threading.RLock()
        # Serializes every master-side copy→mutate→publish sequence: the
        # stepper's health_round racing a request thread's fail_shard would
        # otherwise publish colliding versions, demoting a healthy master.
        self.master_lock = threading.RLock()
        # Shards this node was just promoted for: their replicas must be
        # reset to the new primary's ops line (the reference's primary-
        # replica resync, TransportResyncReplicationAction) before the old
        # term's never-acknowledged divergent ops could surface.
        self._pending_term_resync: set[tuple[str, int]] = set()
        self.closed = False
        # Incarnation id: a restarted process answers pings with a new
        # session, which the master compares against the PUBLISHED session
        # map (state.node_sessions) to detect "same node id, fresh (empty)
        # copies" and strip their stale in-sync memberships — the
        # in-memory stand-in for the reference's per-copy allocation ids.
        # Because the map rides in the committed state, a new master
        # inherits it and recognizes even its OWN restart.
        import uuid

        self.session = uuid.uuid4().hex
        # Adaptive replica selection: EWMA rank per target copy observed
        # by THIS coordinating node (node/ResponseCollectorService.java:33)
        # + degraded-search counters for `GET /_nodes/stats`.
        self.response_collector = ResponseCollectorService()
        # Degraded-search counters write through a per-node metrics
        # registry (obs/metrics.py) — search_resilience_stats() and the
        # gateway's cluster-wide rollup are views over it.
        from ..obs.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()
        # Per-node filter/bitset cache (index/filter_cache.py): replicated
        # shard searches consult it exactly like the single-process
        # coordinator's shard services do. Admission counts ONE sighting
        # per user request per node — the coordinating node marks the
        # FIRST shard request it sends to each target node as the
        # recording one (payload flag), so an n-shard scatter landing
        # several shards on one node cannot self-admit one-off filters
        # past min_freq within a single request.
        from ..index.filter_cache import FilterCache

        self.filter_cache = None
        if os.environ.get("ESTPU_FILTER_CACHE", "1") != "0":
            self.filter_cache = FilterCache(metrics=self.metrics)
        self._search_counters = {
            key: self.metrics.counter(
                "estpu_cluster_search_resilience_total",
                "Coordinator degraded-search events",
                kind=key,
                node=node_id,
            )
            for key in (
                "searches",
                "partial_results",
                "shard_failures",
                "copy_retries",
                "rerouted",
            )
        }
        self._inflight_searches = 0
        # Control-plane steps that raised and were swallowed by a stepper
        # loop (LocalCluster's thread or a procs.py worker loop): a wedged
        # control plane must be countable, never silent.
        self._step_errors = self.metrics.counter(
            "estpu_cluster_step_errors_total",
            "Control-plane step errors swallowed by the background stepper",
            node=node_id,
        )
        # Member-side flight recorder (obs/recorder.py): lazily armed on
        # the first health_inputs ship — each frame is the inputs dict
        # already being assembled for the coordinator, so the member-side
        # ring costs nothing the health fan wasn't paying already. Serves
        # the `incidents` wire action (GET /_incidents cluster fan).
        self._recorder = None
        self._recover_persisted_state()
        hub.register(node_id, self._handle)

    # -------------------------------------------------- state persistence

    def _state_file(self) -> str | None:
        if self._state_path is None:
            return None
        return os.path.join(self._state_path, f"{self.node_id}.cluster.json")

    def _save_state(self) -> None:
        """Atomically persist {current_term, state}. Caller holds either
        self.lock or is single-threaded at boot."""
        path = self._state_file()
        if path is None:
            return
        os.makedirs(self._state_path, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "current_term": self.current_term,
                    "state": self.state.to_json(),
                },
                f,
            )
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _recover_persisted_state(self) -> None:
        """Boot recovery: adopt the persisted state and voting term, then
        strip THIS node from every copy set — in-memory shard copies never
        survive a restart, so any membership the old incarnation held is
        stale by definition (the allocation-id invalidation the master's
        session round performs for peers, done locally and immediately so
        the window between boot and the first health round cannot ack
        writes against an empty resurrected 'primary')."""
        path = self._state_file()
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                data = json.load(f)
            recovered = ClusterState.from_json(data["state"])
        except (json.JSONDecodeError, OSError, KeyError, TypeError, ValueError):
            return  # broken persisted state is never boot-fatal
        self.state = recovered
        # Static role config survives even a pre-roles persisted state.
        self.state.voting_only |= set(self._voting_only)
        self.current_term = max(
            int(data.get("current_term", 0)), recovered.term
        )
        for meta in self.state.indices.values():
            for routing in meta.shards.values():
                if routing.primary == self.node_id:
                    routing.primary = None
                if self.node_id in routing.replicas:
                    routing.replicas.remove(self.node_id)
                if self.node_id in routing.recovering:
                    routing.recovering.remove(self.node_id)
                routing.in_sync.discard(self.node_id)

    # ------------------------------------------------------------ identity

    def is_master(self) -> bool:
        return self.state.master == self.node_id

    def close(self) -> None:
        self.closed = True
        self.hub.unregister(self.node_id)

    # ------------------------------------------------------------- handler

    def _handle(self, from_id: str, action: str, payload: dict):
        if self.closed:
            raise ConnectTransportError(f"[{self.node_id}] closed")
        fn = getattr(self, f"_on_{action}", None)
        if fn is None:
            raise ValueError(f"unknown transport action [{action}]")
        wire_trace = payload.pop("_trace", None)
        if wire_trace is None:
            return fn(from_id, payload)
        # Re-activate the sender's wire context EXPLICITLY (never via
        # thread locals — this is what a cross-host receive would do), so
        # the remote execution's spans (per-segment launches inside
        # _on_shard_search) parent into the caller's trace tree.
        from ..obs.tracing import TRACER

        with TRACER.span_from(
            (wire_trace["trace_id"], wire_trace["parent"]),
            f"cluster.{action}",
            node=self.node_id,
            from_node=from_id,
        ):
            return fn(from_id, payload)

    def _on_ping(self, from_id: str, payload: dict):
        if from_id == self.state.master:
            self._master_contact = time.monotonic()
        return {
            "node": self.node_id,
            "term": self.current_term,
            "session": self.session,
        }

    def _on_request_vote(self, from_id: str, payload: dict):
        """Grant iff the term is new AND the candidate's accepted state is
        at least as fresh as ours — a stale (e.g. freshly restarted)
        candidate must never win and publish backlevel state over the
        cluster (CoordinationState.isElectionQuorum's safety rule)."""
        with self.lock:
            term = int(payload["term"])
            cand = (
                int(payload.get("state_term", -1)),
                int(payload.get("state_version", -1)),
            )
            if term > self.current_term and cand >= (
                self.state.term,
                self.state.version,
            ):
                self.current_term = term
                self._save_state()  # a vote must survive restarts
                return {"granted": True}
            return {"granted": False}

    def _on_get_state(self, from_id: str, payload: dict):
        return {"state": self.state.to_json()}

    def _on_publish_state(self, from_id: str, payload: dict):
        new = ClusterState.from_json(payload["state"])
        with self.lock:
            if not new.newer_than(self.state):
                return {"accepted": False}
            self.current_term = max(self.current_term, new.term)
            self.state = new
            self._apply_assignments()
            self._save_state()
            # An accepted publication is proof of a live master quorum.
            self._master_contact = time.monotonic()
            return {"accepted": True}

    # ------------------------------------------------- assignment handling

    def _apply_assignments(self) -> None:
        """Create engines for newly assigned copies; adopt primary terms.
        Caller holds self.lock."""
        for key in list(self.engines):
            if key[0] not in self.state.indices:
                # Index deleted cluster-wide: release the copy.
                del self.engines[key]
                self.trackers.pop(key, None)
                self._pending_term_resync.discard(key)
                self._applied_mappings.pop(key[0], None)
        for index, meta in self.state.indices.items():
            mappings = Mappings.from_json(meta.mappings)
            blob = json.dumps(meta.mappings, sort_keys=True)
            mappings_changed = self._applied_mappings.get(index) != blob
            self._applied_mappings[index] = blob
            for shard_id, routing in meta.shards.items():
                key = (index, shard_id)
                involved = (
                    self.node_id in routing.assigned()
                    or self.node_id in routing.recovering
                )
                if involved and key not in self.engines:
                    self.engines[key] = Engine(mappings)
                elif mappings_changed and key in self.engines:
                    # put_mapping propagation: existing copies adopt the
                    # published field set in place (the Mappings object is
                    # shared with the engine's buffers); locally-derived
                    # dynamic fields absent from the update survive.
                    live = self.engines[key].mappings
                    live.fields.update(mappings.fields)
                    live.nested.update(mappings.nested)
                if routing.primary == self.node_id:
                    engine = self.engines[key]
                    if engine.primary_term != routing.primary_term:
                        # Promotion: the translog/ops line this copy holds
                        # is authoritative from here on (it is in-sync, so
                        # it has every acknowledged op). Surviving replicas
                        # may hold the OLD primary's never-acked ops — they
                        # get reset to this line (term resync) next step.
                        engine.primary_term = routing.primary_term
                        engine.refresh()
                        if routing.primary_term > 1:
                            self._pending_term_resync.add(key)
                    self.trackers.setdefault(key, ReplicationTracker())
                    tracker = self.trackers[key]
                    for node in routing.in_sync:
                        tracker.mark_in_sync(node)
                    # Reconcile: copies failed out of the published set must
                    # leave the tracker or they pin the global checkpoint.
                    tracker.retain(set(routing.in_sync))

    def check_term_resyncs(self) -> None:
        """New-primary duty: reset every replica to this copy's ops line.

        A replica that followed the OLD primary may hold ops that were
        never acknowledged (fan-out died with the primary); seqno-wins
        application alone cannot purge them. Until this completes, such a
        phantom op is only visible via that replica — the same window the
        reference closes with its post-promotion primary-replica resync.
        """
        for key in list(self._pending_term_resync):
            index, shard_id = key
            try:
                routing = self._routing(index, shard_id)
            except (NoShardAvailableError, KeyError):
                self._pending_term_resync.discard(key)
                continue
            if routing.primary != self.node_id:
                self._pending_term_resync.discard(key)
                continue
            engine = self.engines[key]
            with engine.lock:  # freeze the ops line during the handoff
                payload = engine.resync_payload()
                ok = True
                for node in routing.replicas:
                    if node == self.node_id:
                        continue
                    try:
                        self.hub.send(
                            self.node_id,
                            node,
                            "recovery_resync",
                            {
                                "index": index,
                                "shard": shard_id,
                                "payload": payload,
                                "term": routing.primary_term,
                            },
                        )
                    except (ConnectTransportError, RemoteActionError):
                        ok = False  # retried next step
                if ok:
                    self._pending_term_resync.discard(key)

    def check_recoveries(self) -> None:
        """Start peer recovery for copies this node should be acquiring."""
        self.check_term_resyncs()
        with self.lock:
            todo = []
            for index, meta in self.state.indices.items():
                for shard_id, routing in meta.shards.items():
                    if (
                        self.node_id in routing.recovering
                        and routing.primary is not None
                    ):
                        todo.append((index, shard_id, routing.primary))
        for index, shard_id, primary in todo:
            try:
                self._recover_from(index, shard_id, primary)
            except (ConnectTransportError, RemoteActionError):
                pass  # retried on the next step

    def _recover_from(self, index: str, shard_id: int, primary: str) -> None:
        """Replica-side peer recovery: ops-based catch-up, else full copy.
        The primary finalizes under its engine lock and reports us in-sync
        to the master (RecoverySourceHandler.finalizeRecovery analog)."""
        engine = self.engines.get((index, shard_id))
        if engine is None:
            with self.lock:
                meta = self.state.indices[index]
                engine = Engine(Mappings.from_json(meta.mappings))
                self.engines[(index, shard_id)] = engine
        self.hub.send(
            self.node_id,
            primary,
            "start_recovery",
            {
                "index": index,
                "shard": shard_id,
                "node": self.node_id,
                "local_checkpoint": engine.local_checkpoint,
                "max_seqno": engine.max_seqno,
                "max_op_term": engine.max_op_term,
            },
        )

    # --------------------------------------------------- primary-side ops

    def _routing(self, index: str, shard_id: int) -> ShardRouting:
        meta = self.state.indices.get(index)
        if meta is None:
            raise NoShardAvailableError(f"no such index [{index}]")
        return meta.shards[shard_id]

    def _on_primary_op(self, from_id: str, payload: dict):
        return self.execute_write(
            payload["index"],
            payload["id"],
            payload.get("source"),
            op=payload["op"],
            op_type=payload.get("op_type", "index"),
            if_seq_no=payload.get("if_seq_no"),
            if_primary_term=payload.get("if_primary_term"),
        )

    def execute_write(
        self,
        index: str,
        doc_id: str,
        source: dict | None,
        op: str = "index",
        op_type: str = "index",
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
    ) -> dict:
        """Client write entry on ANY node: route to the primary, execute,
        fan out to in-sync copies, ack only when all of them applied
        (ReplicationOperation.java:111 semantics)."""
        meta = self.state.indices.get(index)
        if meta is None:
            raise NoShardAvailableError(f"no such index [{index}]")
        shard_id = shard_for_id(doc_id, meta.n_shards)
        routing = self._routing(index, shard_id)
        if routing.primary is None:
            raise NoShardAvailableError(
                f"[{index}][{shard_id}] has no promotable copy"
            )
        if routing.primary != self.node_id:
            return self.hub.send(
                self.node_id,
                routing.primary,
                "primary_op",
                {
                    "index": index,
                    "id": doc_id,
                    "source": source,
                    "op": op,
                    "op_type": op_type,
                    "if_seq_no": if_seq_no,
                    "if_primary_term": if_primary_term,
                },
            )
        return self._replicate(
            index, shard_id, doc_id, source, op, op_type,
            if_seq_no=if_seq_no, if_primary_term=if_primary_term,
        )

    def _replicate(
        self,
        index: str,
        shard_id: int,
        doc_id: str,
        source: dict | None,
        op: str,
        op_type: str,
        if_seq_no: int | None = None,
        if_primary_term: int | None = None,
    ) -> dict:
        key = (index, shard_id)
        routing = self._routing(index, shard_id)
        engine = self.engines[key]
        tracker = self.trackers.setdefault(key, ReplicationTracker())
        term = routing.primary_term
        if op == "index":
            result = engine.index(
                source, doc_id, op_type=op_type,
                if_seq_no=if_seq_no, if_primary_term=if_primary_term,
            )
            rep_op = {
                "seqno": result["_seq_no"],
                "op": "index",
                "id": doc_id,
                "version": result["_version"],
                "source": source,
                "term": term,
            }
        else:
            result = engine.delete(
                doc_id, if_seq_no=if_seq_no, if_primary_term=if_primary_term
            )
            if result["result"] == "not_found":
                return result
            rep_op = {
                "seqno": result["_seq_no"],
                "op": "delete",
                "id": doc_id,
                "version": result["_version"],
                "term": term,
            }
        tracker.update_checkpoint(self.node_id, engine.local_checkpoint)
        # Re-read the routing AFTER the op took its seqno: a recovery
        # finalize holds the engine lock while flipping its target in-sync,
        # so any copy it promoted while we waited for the lock is visible
        # here and becomes REQUIRED for this op's ack.
        routing = self._routing(index, shard_id)
        # Fan out to every tracked copy; in-sync copies must apply (or be
        # failed out of the set via a quorum-published state change) before
        # the client sees an ack; recovering copies are best-effort.
        targets = [
            n
            for n in routing.replicas + routing.recovering
            if n != self.node_id
        ]
        for node in targets:
            required = node in routing.in_sync
            try:
                resp = self.hub.send(
                    self.node_id,
                    node,
                    "replica_op",
                    {
                        "index": index,
                        "shard": shard_id,
                        "term": term,
                        "op": rep_op,
                    },
                )
                tracker.update_checkpoint(node, resp["local_checkpoint"])
            except (ConnectTransportError, RemoteActionError) as e:
                if (
                    isinstance(e, RemoteActionError)
                    and e.remote_type == "StalePrimaryTermError"
                ):
                    # We were deposed: never ack through a stale term.
                    raise StalePrimaryTermError(str(e)) from e
                if not required:
                    continue
                self._fail_copy(index, shard_id, node, term, str(e))
        result["_primary_term"] = term
        result["_global_checkpoint"] = tracker.global_checkpoint
        return result

    def _fail_copy(
        self, index: str, shard_id: int, node: str, term: int, reason: str
    ) -> None:
        """Ask the master to remove a copy from the in-sync set. The write
        can only proceed once the removal is quorum-published; otherwise
        acking would race a possible promotion of the unreached copy."""
        master = self.state.master
        if master is None:
            raise ReplicationFailedError(
                f"cannot fail [{node}] for [{index}][{shard_id}]: no master"
            )
        try:
            resp = self.hub.send(
                self.node_id,
                master,
                "fail_shard",
                {
                    "index": index,
                    "shard": shard_id,
                    "node": node,
                    "term": term,
                    "reason": reason,
                },
            )
        except (ConnectTransportError, RemoteActionError) as e:
            raise ReplicationFailedError(
                f"master unreachable failing [{node}]: {e}"
            ) from e
        if not resp.get("acked"):
            raise ReplicationFailedError(
                f"master refused to fail [{node}]: {resp}"
            )

    # --------------------------------------------------- replica-side ops

    def _on_replica_op(self, from_id: str, payload: dict):
        index, shard_id = payload["index"], payload["shard"]
        term = int(payload["term"])
        routing = self._routing(index, shard_id)
        if term < routing.primary_term:
            raise StalePrimaryTermError(
                f"stale primary term [{term}] < [{routing.primary_term}] "
                f"for [{index}][{shard_id}]"
            )
        engine = self.engines.get((index, shard_id))
        if engine is None:
            with self.lock:
                meta = self.state.indices[index]
                engine = Engine(Mappings.from_json(meta.mappings))
                self.engines[(index, shard_id)] = engine
        return engine.apply_replica(payload["op"])

    # ----------------------------------------------- recovery (source side)

    def _on_start_recovery(self, from_id: str, payload: dict):
        """Primary-side peer recovery (RecoverySourceHandler.java:94):
        stream retained ops above the target's checkpoint (or a full copy
        when history is gone), then finalize under the engine write lock so
        no concurrent op can slip between catch-up and in-sync handoff."""
        index, shard_id = payload["index"], payload["shard"]
        target = payload["node"]
        key = (index, shard_id)
        routing = self._routing(index, shard_id)
        if routing.primary != self.node_id:
            raise ValueError(f"not primary for [{index}][{shard_id}]")
        engine = self.engines[key]
        term = routing.primary_term
        ckpt = int(payload["local_checkpoint"])
        # Ops catch-up is only sound when the target's ops line cannot have
        # diverged: it is empty, or it already follows the CURRENT term and
        # is a seqno-prefix of this primary. A line ending in an older term
        # may hold the old primary's never-acked ops — full reset copy.
        target_term = int(payload.get("max_op_term", 0))
        target_max_seqno = int(payload.get("max_seqno", 0 if ckpt >= 0 else -1))
        # "Empty" must mean NO ops at all: a copy can hold out-of-order
        # old-term ops while its contiguous checkpoint is still -1.
        empty = target_max_seqno == -1 and target_term == 0
        prefix_ok = ckpt <= engine.local_checkpoint and (
            empty or target_term == term
        )
        ops = engine.ops_since(ckpt) if prefix_ok else None
        if ops is None:
            resync = engine.resync_payload()
            self.hub.send(
                self.node_id, target, "recovery_resync",
                {
                    "index": index,
                    "shard": shard_id,
                    "payload": resync,
                    "term": term,
                },
            )
            ckpt = int(resync["max_seqno"])
        else:
            for op_batch in _batches(ops, 256):
                self.hub.send(
                    self.node_id, target, "recovery_ops",
                    {
                        "index": index,
                        "shard": shard_id,
                        "ops": op_batch,
                        "term": term,
                    },
                )
                if op_batch:
                    ckpt = max(ckpt, int(op_batch[-1]["seqno"]))
        # Finalize: block the write path briefly so the remaining tail is
        # final, ship it, then flip the copy in-sync via the master.
        with engine.lock:
            tail = engine.ops_since(ckpt)
            if tail is None:
                # Concurrent writes trimmed the history past our cursor:
                # the batched phase is unusable, fall back to a full copy
                # (under the lock, so it IS final).
                resync = engine.resync_payload()
                self.hub.send(
                    self.node_id, target, "recovery_resync",
                    {
                        "index": index,
                        "shard": shard_id,
                        "payload": resync,
                        "term": term,
                    },
                )
            elif tail:
                self.hub.send(
                    self.node_id, target, "recovery_ops",
                    {
                        "index": index,
                        "shard": shard_id,
                        "ops": tail,
                        "term": term,
                    },
                )
            master = self.state.master
            if master is None:
                raise ReplicationFailedError("no master to finalize recovery")
            resp = self.hub.send(
                self.node_id,
                master,
                "shard_recovered",
                {
                    "index": index,
                    "shard": shard_id,
                    "node": target,
                    "term": term,
                },
            )
            if not resp.get("acked"):
                raise ReplicationFailedError(f"finalize refused: {resp}")
            self.trackers.setdefault(key, ReplicationTracker()).mark_in_sync(
                target
            )
        return {"done": True}

    def _check_recovery_term(self, index: str, shard_id: int, term: int):
        """A deposed primary must not rewrite copies through the recovery
        channel — the stale-term fence replica_op has, for the channel
        that can do strictly more damage."""
        routing = self._routing(index, shard_id)
        if term < routing.primary_term:
            raise StalePrimaryTermError(
                f"stale recovery term [{term}] < [{routing.primary_term}] "
                f"for [{index}][{shard_id}]"
            )

    def _on_recovery_ops(self, from_id: str, payload: dict):
        self._check_recovery_term(
            payload["index"], payload["shard"], int(payload.get("term", -1))
        )
        engine = self.engines[(payload["index"], payload["shard"])]
        for op in payload["ops"]:
            engine.apply_replica(op)
        return {"local_checkpoint": engine.local_checkpoint}

    def _on_recovery_resync(self, from_id: str, payload: dict):
        key = (payload["index"], payload["shard"])
        self._check_recovery_term(key[0], key[1], int(payload.get("term", -1)))
        # Build the replacement line DETACHED, then swap: a search routed
        # here mid-install must never see a half-empty engine.
        meta = self.state.indices[payload["index"]]
        engine = Engine(Mappings.from_json(meta.mappings))
        engine.apply_resync(payload["payload"])
        # The installed line belongs to the sender's term: future
        # recoveries may ops-catch-up from here.
        engine.max_op_term = max(
            engine.max_op_term, int(payload.get("term", 0))
        )
        engine.refresh()
        with self.lock:
            self.engines[key] = engine
        return {"local_checkpoint": engine.local_checkpoint}

    # ------------------------------------------------------- search path

    def _on_shard_search(self, from_id: str, payload: dict):
        from dataclasses import replace as dc_replace

        from ..search.aggs import (
            Aggregator,
            state_to_wire,
            wire_agg_ineligible_reason,
        )
        from ..search.service import SearchRequest, SearchService

        engine = self.engines[(payload["index"], payload["shard"])]
        shard_t0 = time.monotonic()
        with self.lock:
            self._inflight_searches += 1
            queue = self._inflight_searches - 1
        try:
            engine.refresh()
            request = SearchRequest.from_json(payload["body"])
            # One admission sighting per user request per node: only the
            # scatter's FIRST shard request to this node records (the
            # coordinator sets the flag; absent = a direct single-shard
            # search, which is its own user request).
            record_usage = bool(payload.get("record_filter_usage", True))
            # One segment snapshot shared by the agg pass and the hits
            # pass, like the single-process shard service.
            segments = list(engine.segments)
            agg_wire = None
            agg_total = None
            if request.aggs is not None:
                reason = wire_agg_ineligible_reason(request.aggs)
                if reason:
                    raise ValueError(
                        f"{reason} are not supported on replicated "
                        f"indices yet"
                    )
                agg = Aggregator(
                    engine, request.aggs, handles=segments,
                    index_name=payload["index"],
                )
                agg_total, states = agg.run_states(request.query)
                agg_wire = [
                    state_to_wire(node, state, agg._plan)
                    for node, state in zip(request.aggs, states)
                ]
                request = dc_replace(request, aggs=None)
            k = max(0, request.from_) + max(0, request.size)
            if k > 0 or agg_total is None:
                resp = SearchService(
                    engine, payload["index"],
                    filter_cache=self.filter_cache,
                ).search(
                    request, segments=segments,
                    record_filter_usage=record_usage,
                )
                total = agg_total if agg_total is not None else resp.total
                max_score, hits = resp.max_score, resp.hits
            else:  # agg-only: the agg program already counted totals
                total, max_score, hits = agg_total, None, []
        finally:
            with self.lock:
                self._inflight_searches -= 1
            # Shard-hop term of the http -> gateway -> shard latency
            # split (bench cfg14_socket): time spent executing on the
            # shard owner, excluding every wire/queue cost above it.
            self.metrics.windowed_histogram(
                "estpu_shard_exec_latency_recent_ms",
                "Per-shard search execution latency over the trailing "
                "window, ms (the shard-side term of the per-hop split)",
                node=self.node_id,
            ).record((time.monotonic() - shard_t0) * 1e3)
        return {
            "total": total,
            "max_score": max_score,
            # Copy-side load signal for the coordinator's adaptive replica
            # selection (the reference piggybacks queue size the same way).
            "queue": queue,
            # Pre-render aggregation merge states: the coordinator reduce
            # folds these across shards and renders once (the wire analog
            # of InternalAggregations.topLevelReduce).
            "aggs": agg_wire,
            "hits": [
                {
                    "_id": h.doc_id,
                    "_score": h.score,
                    "_source": h.source,
                    "sort": h.sort,
                }
                for h in hits
            ],
        }

    # How many ordered passes over a shard's copies the query phase makes
    # before declaring the shard failed, and the backoff between passes.
    COPY_RETRY_ROUNDS = 2
    COPY_RETRY_BACKOFF_S = 0.01

    def _count_search(self, key: str, n: int = 1) -> None:
        counter = self._search_counters.get(key)
        if counter is None:
            # Cache novel keys so search_resilience_stats reports them.
            counter = self._search_counters[key] = self.metrics.counter(
                "estpu_cluster_search_resilience_total",
                "Coordinator degraded-search events",
                kind=key,
                node=self.node_id,
            )
        counter.inc(n)

    def search_resilience_stats(self) -> dict:
        return {
            **{
                key: int(c.value)
                for key, c in list(self._search_counters.items())
            },
            "response_collector": self.response_collector.snapshot(),
        }

    def search(
        self, index: str, body: dict, allow_partial: bool = True
    ) -> dict:
        """Scatter to one alive copy per shard, merge like the coordinator
        (score desc, then shard index, then per-shard rank).

        Degraded-mode query phase: copies are tried in the response
        collector's EWMA rank order (adaptive replica selection) instead
        of the fixed primary-then-replicas order, each shard gets
        COPY_RETRY_ROUNDS bounded-backoff passes over its copies, and a
        shard whose every copy failed degrades to a PARTIAL result with an
        honest `_shards.failed` + `failures[]` entry — unless
        `allow_partial=False`, which turns any shard failure into
        ShardSearchFailedError (HTTP 503). Only an index with zero
        successful shards raises NoShardAvailableError. Per-shard user
        errors (a malformed query raising remotely) re-raise: a bad
        request must be a 400, never "0 of N shards"."""
        meta = self.state.indices.get(index)
        if meta is None:
            raise NoShardAvailableError(f"no such index [{index}]")
        from ..exec.async_search import ProgressiveShardReduce
        from ..index.mapping import Mappings
        from ..search.aggs import wire_agg_ineligible_reason
        from ..search.service import SearchRequest, sort_merge_key

        # The coordinator's view of the request: merge keys (sort spec,
        # missing directives) and the agg node tree for the wire reduce.
        # Parsing errors are request-shaped (ValueError -> 400).
        request = SearchRequest.from_json(body)
        if request.aggs is not None:
            reason = wire_agg_ineligible_reason(request.aggs)
            if reason:
                raise ValueError(
                    f"{reason} are not supported on replicated indices yet"
                )
        self._count_search("searches")
        size = int(body.get("size", 10))
        shard_body = dict(body)
        shard_body["from"] = 0
        shard_body["size"] = int(body.get("from", 0)) + size
        # The same progressive reducer async search drives shard-by-shard:
        # the synchronous path is just "feed every shard, render once".
        # Folding in ascending shard order keeps the merge (and its f64
        # agg arithmetic) bit-identical whatever order parts arrive in.
        reduce = ProgressiveShardReduce(
            request,
            from_=int(body.get("from", 0)),
            size=size,
            n_shards=len(meta.shards),
            index_name=index,
            mappings=lambda: Mappings.from_json(meta.mappings),
        )
        # Target nodes that already recorded this REQUEST's filter-cache
        # sighting: the first shard request sent to a node records, later
        # shards of the same scatter pass record_filter_usage=False — one
        # sighting per user request per node cache.
        recorded_nodes: set[str] = set()
        for shard_id in sorted(meta.shards):
            resp, failure = self.search_shard(
                index, shard_id, shard_body, recorded_nodes=recorded_nodes
            )
            if resp is None:
                reduce.add_failure(shard_id, failure)
                continue
            keyed = [
                # Merge contract identical to the single-process
                # coordinator: (sort key per the request's sort spec with
                # missing-value placement, shard index, per-shard rank).
                (
                    sort_merge_key(
                        request, hit.get("_score"), hit.get("sort")
                    ),
                    rank,
                    hit,
                )
                for rank, hit in enumerate(resp["hits"])
            ]
            reduce.add_part(
                shard_id,
                resp["total"] or 0,
                resp["max_score"],
                keyed,
                agg_wires=resp.get("aggs"),
            )
        failures = reduce.failures()
        failed = len(failures)
        if failed:
            self._count_search("shard_failures", failed)
        if reduce.successful_count() == 0 and failed > 0:
            raise NoShardAvailableError(
                f"all shards of [{index}] failed: "
                f"{failures[-1]['reason']['reason']}"
            )
        if failed and not allow_partial:
            raise ShardSearchFailedError(
                f"[{index}] {failed} of {len(meta.shards)} shards failed "
                f"and allow_partial_search_results is false",
                failures=failures,
            )
        if failed:
            self._count_search("partial_results")
        return reduce.render()

    def search_meta(self, index: str) -> dict:
        """Shard map + mappings for a coordinating async-search runner:
        the list of shard ids to scatter over and the mappings JSON its
        reducer renders aggs against."""
        meta = self.state.indices.get(index)
        if meta is None:
            raise NoShardAvailableError(f"no such index [{index}]")
        return {
            "shards": sorted(meta.shards),
            "mappings": meta.mappings,
        }

    def search_shard(
        self, index: str, shard_id: int, shard_body: dict,
        recorded_nodes: set | None = None,
    ) -> tuple[dict | None, dict | None]:
        """One shard's leg of the scatter: EWMA-ranked copies, bounded
        retry, traced; returns (shard response, None) or (None, failure
        entry). The async-search runner calls this per shard and folds
        each part into its progressive reduce; the synchronous search()
        above is the same calls in a tight loop."""
        meta = self.state.indices.get(index)
        if meta is None:
            raise NoShardAvailableError(f"no such index [{index}]")
        routing = meta.shards.get(shard_id)
        if routing is None:
            raise NoShardAvailableError(
                f"[{index}][{shard_id}] no such shard"
            )
        from ..obs.tracing import TRACER

        copies = [
            n
            for n in ([routing.primary] if routing.primary else [])
            + routing.replicas
            if n is not None
        ]
        with TRACER.span(
            "cluster.shard", shard=shard_id, index=index
        ) as shard_span:
            resp, failure = self._search_one_shard(
                index, shard_id, copies, shard_body,
                recorded_nodes=recorded_nodes,
            )
            if shard_span is not None and failure is not None:
                shard_span.status = "error"
                shard_span.tags["failed"] = True
                shard_span.tags["error_reason"] = failure["reason"][
                    "reason"
                ][:200]
        return resp, failure

    def _search_one_shard(
        self, index: str, shard_id: int, copies: list[str],
        shard_body: dict, recorded_nodes: set | None = None,
    ) -> tuple[dict | None, dict | None]:
        """Query one shard across its copies: EWMA-ranked order, bounded
        backoff between rounds. Returns (response, None) on success or
        (None, failure entry) once every copy of every round failed.
        `recorded_nodes` tracks which target nodes already counted this
        request's filter-cache admission sighting (first send records,
        every other shard/retry to that node passes False)."""
        from ..obs.tracing import TRACER

        ordered = self.response_collector.ordered(copies)
        if ordered and copies and ordered[0] != copies[0]:
            # Adaptive selection steered away from the default
            # primary-first order.
            self._count_search("rerouted")
            TRACER.event(
                "search.rerouted",
                shard=shard_id,
                index=index,
                chosen=ordered[0],
                default=copies[0],
            )
        last_err: Exception | None = None
        last_node: str | None = None
        attempts = 0
        for round_i in range(self.COPY_RETRY_ROUNDS):
            if round_i and ordered:
                time.sleep(self.COPY_RETRY_BACKOFF_S * round_i)
            for node in ordered:
                attempts += 1
                if attempts > 1:
                    self._count_search("copy_retries")
                    TRACER.event(
                        "search.copy_retry",
                        shard=shard_id,
                        index=index,
                        copy=node,
                        attempt=attempts,
                    )
                t0 = time.monotonic()
                record = (
                    recorded_nodes is not None and node not in recorded_nodes
                )
                if record:
                    # Marked at SEND time: a search that fails mid-shard
                    # may still have counted its sighting, exactly like a
                    # failed solo request.
                    recorded_nodes.add(node)
                try:
                    resp = self.hub.send(
                        self.node_id,
                        node,
                        "shard_search",
                        {
                            "index": index,
                            "shard": shard_id,
                            "body": shard_body,
                            "record_filter_usage": record,
                        },
                    )
                except RemoteActionError as e:
                    if e.remote_type in ("ValueError", "TypeError"):
                        raise  # request-shaped error, not a copy failure
                    last_err, last_node = e, node
                    self.response_collector.record_failure(node)
                except ConnectTransportError as e:
                    last_err, last_node = e, node
                    self.response_collector.record_failure(node)
                else:
                    self.response_collector.record_response(
                        node,
                        time.monotonic() - t0,
                        queue_size=int(resp.get("queue", 0)),
                    )
                    return resp, None
        reason = (
            str(last_err) if last_err is not None else "no copy assigned"
        )
        return None, {
            "shard": shard_id,
            "index": index,
            "node": last_node,
            "reason": {
                "type": type(last_err).__name__ if last_err else "unassigned",
                "reason": reason,
            },
        }

    def get_doc(self, index: str, doc_id: str) -> dict | None:
        meta = self.state.indices[index]
        shard_id = shard_for_id(doc_id, meta.n_shards)
        routing = meta.shards[shard_id]
        if routing.primary is None:
            raise NoShardAvailableError(f"[{index}][{shard_id}] unassigned")
        if routing.primary == self.node_id:
            return self.engines[(index, shard_id)].get(doc_id)
        return self.hub.send(
            self.node_id,
            routing.primary,
            "get_doc",
            {"index": index, "id": doc_id},
        )

    def _on_get_doc(self, from_id: str, payload: dict):
        meta = self.state.indices[payload["index"]]
        shard_id = shard_for_id(payload["id"], meta.n_shards)
        return self.engines[(payload["index"], shard_id)].get(payload["id"])

    def read_doc(self, index: str, doc_id: str) -> dict | None:
        """Failover realtime get: the primary first, then any in-sync
        replica (the REST router's read path — a dead or unassigned
        primary degrades to a possibly-slightly-stale replica read instead
        of an error, like the reference's `preference` replica reads).
        Returns {_source, _version, _seq_no, _primary_term} or None."""
        meta = self.state.indices.get(index)
        if meta is None:
            raise NoShardAvailableError(f"no such index [{index}]")
        shard_id = shard_for_id(doc_id, meta.n_shards)
        routing = meta.shards[shard_id]
        candidates = [] if routing.primary is None else [routing.primary]
        candidates += [
            n
            for n in routing.replicas
            if n in routing.in_sync and n not in candidates
        ]
        last_err: Exception | None = None
        for node in candidates:
            if node == self.node_id:
                engine = self.engines.get((index, shard_id))
                if engine is None:
                    continue
                return engine.get_with_meta(doc_id)
            try:
                return self.hub.send(
                    self.node_id,
                    node,
                    "read_doc",
                    {"index": index, "shard": shard_id, "id": doc_id},
                )
            except (ConnectTransportError, RemoteActionError) as e:
                last_err = e
        raise NoShardAvailableError(
            f"no readable copy of [{index}][{shard_id}]: {last_err}"
        )

    def _on_read_doc(self, from_id: str, payload: dict):
        engine = self.engines.get((payload["index"], payload["shard"]))
        if engine is None:
            raise NoShardAvailableError(
                f"[{payload['index']}][{payload['shard']}] not allocated "
                f"on [{self.node_id}]"
            )
        return engine.get_with_meta(payload["id"])

    # -------------------------------------------------------- client entry
    # Coordinating-node entry points addressable over the wire: a
    # supervisor/REST process that is NOT a cluster member reaches the
    # multi-process cluster through these (the role TransportService's
    # client channels play in the reference). Each simply enters the same
    # coordinating paths a local caller uses.

    def _ensure_master_lease(self) -> None:
        """Client-entry stale-serving guard: a node answering an EXTERNAL
        client must hold a recent proof that the elected master can reach
        it — otherwise it may be the minority side of a partition serving
        a state the majority has moved past (promoted primaries, failed
        copies). Recent contact (the master's ping round or an accepted
        publication within MASTER_LEASE_S) serves immediately; a stale
        lease forces one active master ping; an unreachable master
        REFUSES with NotMasterError (retryable at the gateway, an honest
        503 at REST — the reference's no-master block, not a stale 200).
        Cluster-internal paths (replication fan-out, peer recovery) are
        deliberately unguarded: their safety comes from primary terms and
        in-sync quorums, not from this lease."""
        master = self.state.master
        if master is None:
            raise NotMasterError(
                f"[{self.node_id}] has no elected master; refusing a "
                f"possibly-stale serve"
            )
        if master == self.node_id:
            return
        if time.monotonic() - self._master_contact < MASTER_LEASE_S:
            return
        try:
            self.hub.send(self.node_id, master, "ping", {})
        except (ConnectTransportError, RemoteActionError) as e:
            raise NotMasterError(
                f"[{self.node_id}] cannot reach master [{master}] "
                f"({e}); refusing a possibly-stale serve (minority side "
                f"of a partition)"
            ) from e
        self._master_contact = time.monotonic()

    def _on_client_write(self, from_id: str, payload: dict):
        self._ensure_master_lease()
        return self.execute_write(
            payload["index"],
            payload["id"],
            payload.get("source"),
            op=payload.get("op", "index"),
            op_type=payload.get("op_type", "index"),
            if_seq_no=payload.get("if_seq_no"),
            if_primary_term=payload.get("if_primary_term"),
        )

    def _on_client_search(self, from_id: str, payload: dict):
        self._ensure_master_lease()
        return self.search(
            payload["index"],
            payload["body"],
            allow_partial=bool(payload.get("allow_partial", True)),
        )

    def _on_client_read(self, from_id: str, payload: dict):
        self._ensure_master_lease()
        return self.read_doc(payload["index"], payload["id"])

    def _on_client_state(self, from_id: str, payload: dict):
        return {
            "node": self.node_id,
            "master": self.state.master,
            "term": self.state.term,
            "version": self.state.version,
            "state": self.state.to_json(),
            "step_errors": int(self._step_errors.value),
        }

    def _on_client_create_index(self, from_id: str, payload: dict):
        """Create-index from a non-member client: route to the master."""
        return self._route_to_master(from_id, "create_index", payload)

    def _on_client_put_mappings(self, from_id: str, payload: dict):
        return self._route_to_master(from_id, "put_mappings", payload)

    def _on_client_delete_index(self, from_id: str, payload: dict):
        return self._route_to_master(from_id, "delete_index", payload)

    def _route_to_master(self, from_id: str, action: str, payload: dict):
        """Master-scoped admin op from a non-member client: execute
        locally when this node IS the master, else one wire hop to it."""
        master = self.state.master
        if master is None:
            raise NotMasterError("no elected master")
        if master == self.node_id:
            return getattr(self, f"_on_{action}")(from_id, payload)
        return self.hub.send(self.node_id, master, action, payload)

    def _on_refresh_index(self, from_id: str, payload: dict):
        """Refresh this node's local engines for one index (the per-node
        leg of the broadcast refresh a non-member client fans out)."""
        index = payload["index"]
        refreshed = 0
        with self.lock:
            engines = dict(self.engines)
        for (idx, _shard), engine in engines.items():
            if idx == index:
                engine.refresh()
                refreshed += 1
        return {"node": self.node_id, "refreshed": refreshed}

    def _on_shard_docs(self, from_id: str, payload: dict):
        """Primary-side doc count of one local shard copy."""
        engine = self.engines.get((payload["index"], payload["shard"]))
        if engine is None:
            raise NoShardAvailableError(
                f"[{payload['index']}][{payload['shard']}] not allocated "
                f"on [{self.node_id}]"
            )
        return {"count": int(engine.num_docs)}

    def num_docs(self, index: str) -> int:
        """Coordinating primary-side doc count across shards: each
        shard's primary answers over the wire (the over-socket form of
        the gateway's in-process engine walk; cat/stats APIs)."""
        meta = self.state.indices.get(index)
        if meta is None:
            return 0
        total = 0
        for shard_id, routing in meta.shards.items():
            if routing.primary is None:
                continue
            if routing.primary == self.node_id:
                engine = self.engines.get((index, shard_id))
                if engine is not None:
                    total += int(engine.num_docs)
                continue
            try:
                resp = self.hub.send(
                    self.node_id,
                    routing.primary,
                    "shard_docs",
                    {"index": index, "shard": shard_id},
                )
                total += int(resp.get("count", 0))
            except (ConnectTransportError, RemoteActionError):
                continue  # dead primary: the count is honestly partial
        return total

    def _on_client_num_docs(self, from_id: str, payload: dict):
        return self.num_docs(payload["index"])

    # -------------------------------------------- cluster-scope observability

    def roles(self) -> list[str]:
        """Reference-style role names: every member is master-eligible;
        voting-only tiebreakers vote but never hold shard copies."""
        if self.node_id in self.state.voting_only:
            return ["master", "voting_only"]
        return ["data", "master"]

    def node_stats_local(self) -> dict:
        """This node's `_nodes/stats` section — the per-node payload the
        `node_stats` wire action ships (the reference's NodeStats shape):
        identity/roles/master marker, doc+shard+segment counts, the
        per-node filter cache, degraded-search counters, process identity
        (the pid is what distinguishes real worker processes), stepper
        errors, and this node's transport counters."""
        from ..index.filter_cache import FilterCache
        from ..obs.device import HbmLedger, accelerator_info

        with self.lock:
            engines = dict(self.engines)
            inflight = self._inflight_searches
        docs = 0
        segments = 0
        for engine in engines.values():
            docs += engine.num_docs
            segments += len(engine.segments)
        out: dict[str, Any] = {
            "name": self.node_id,
            "roles": self.roles(),
            "master": self.is_master(),
            "process": {
                "pid": os.getpid(),
                "inflight_searches": int(inflight),
            },
            "accelerator": accelerator_info(),
            "indices": {
                "docs": {"count": int(docs)},
                "shards": {"count": len(engines)},
                "segments": {"count": int(segments)},
                "filter_cache": (
                    self.filter_cache.stats()
                    if self.filter_cache is not None
                    else FilterCache.disabled_stats()
                ),
            },
            "search_resilience": self.search_resilience_stats(),
            "cluster_state": {
                "term": self.state.term,
                "version": self.state.version,
                "master_node": self.state.master,
            },
            "step_errors": int(self._step_errors.value),
            # Per-node device.hbm section (ISSUE 14): cluster data nodes
            # carry no write-through ledger (their engines run without a
            # breaker), so the section is COMPUTED from component stats —
            # by the consistency law the totals are the ledger totals.
            # The coordinating front's cat_hbm reads this fanned shape.
            "device": {
                "hbm": HbmLedger.computed_section(
                    engines_by_index=_engines_by_index(engines),
                    filter_cache=self.filter_cache,
                )
            },
        }
        # Per-node transport view: a node owning its own endpoint (a
        # procs worker, or a TcpTransportHub member) reports endpoint-
        # scoped counters; the in-memory hub reports its hub-wide view.
        endpoint = None
        get_endpoint = getattr(self.hub, "endpoint", None)
        if get_endpoint is not None:
            endpoint = get_endpoint(self.node_id)
        elif getattr(self.hub, "node_id", None) == self.node_id:
            endpoint = self.hub
        if endpoint is not None:
            out["transport"] = endpoint.stats()
        else:
            hub_stats = getattr(self.hub, "stats", None)
            if hub_stats is not None:
                out["transport"] = hub_stats()
        return out

    def _on_node_stats(self, from_id: str, payload: dict):
        return self.node_stats_local()

    def health_inputs_local(self) -> dict:
        """This node's `health_inputs` wire section (obs/health.py): the
        small, cheap-to-collect slice of per-node state the health
        indicators interpret — identity/roles/master, the published
        state's term (re-election tracking), swallowed stepper errors,
        transport counters with their trailing-window events, and recent
        cache-eviction pressure. Deliberately much lighter than
        node_stats_local: a 1/s health poll must not cost a stats
        assembly per node."""
        out: dict[str, Any] = {
            "name": self.node_id,
            "roles": self.roles(),
            "master": self.is_master(),
            "cluster_state": {
                "term": self.state.term,
                "version": self.state.version,
                "master_node": self.state.master,
            },
            "step_errors": int(self._step_errors.value),
            "process": {"pid": os.getpid()},
        }
        evictions: dict[str, int] = {}
        window = self.metrics.window(
            "estpu_filter_cache_evictions_recent"
        )
        if window is not None:
            evictions["filter"] = int(window.count())
        if evictions:
            out["evictions_recent"] = evictions
        endpoint = None
        get_endpoint = getattr(self.hub, "endpoint", None)
        if get_endpoint is not None:
            endpoint = get_endpoint(self.node_id)
        elif getattr(self.hub, "node_id", None) == self.node_id:
            endpoint = self.hub
        if endpoint is not None:
            out["transport"] = endpoint.stats()
            recent = getattr(endpoint, "recent_events", None)
            if recent is not None:
                out["transport_events_recent"] = recent()
        else:
            hub_stats = getattr(self.hub, "stats", None)
            if hub_stats is not None:
                out["transport"] = hub_stats()
            hub_metrics = getattr(self.hub, "metrics", None)
            if hub_metrics is not None:
                recent = hub_metrics.window_counts(
                    "estpu_transport_events_recent", "event"
                )
                if recent:
                    out["transport_events_recent"] = {
                        k: int(v) for k, v in recent.items()
                    }
        if os.environ.get("ESTPU_INCIDENTS", "1") != "0":
            if self._recorder is None:
                from ..obs.recorder import FlightRecorder

                self._recorder = FlightRecorder(metrics=self.metrics)
            self._recorder.record(
                extras={
                    "node": self.node_id,
                    "step_errors": out["step_errors"],
                    "evictions_recent": out.get("evictions_recent"),
                    "transport_events_recent": out.get(
                        "transport_events_recent"
                    ),
                }
            )
        return out

    def _on_health_inputs(self, from_id: str, payload: dict):
        return self.health_inputs_local()

    def _on_incidents(self, from_id: str, payload: dict):
        """Incident ship side (GET /_incidents cluster fan): this
        member's flight-recorder summary plus its newest frames, so a
        coordinator capsule reader sees per-member evidence without a
        second bespoke wire action."""
        if self._recorder is None:
            return {"node": self.node_id, "recorder": None}
        limit = int(payload.get("frames", 3))
        return {
            "node": self.node_id,
            "recorder": self._recorder.stats(),
            "frames": self._recorder.frames(limit=max(0, limit)),
        }

    def _on_metrics_wire(self, from_id: str, payload: dict):
        """Federated `/_metrics` ship side: this node's registry as a
        wire snapshot. Process-wide registries (the transport endpoint's,
        the analysis counter's) ride along only when this node OWNS its
        process (a procs worker) — in-process cluster members would
        otherwise each re-ship the same process globals and the cluster
        fold would multiply them."""
        others = []
        if getattr(self.hub, "node_id", None) == self.node_id:
            from ..analysis.analyzers import ANALYSIS_METRICS

            hub_metrics = getattr(self.hub, "metrics", None)
            if hub_metrics is not None and hub_metrics is not self.metrics:
                others.append(hub_metrics)
            others.append(ANALYSIS_METRICS)
        return {
            "node": self.node_id,
            "families": self.metrics.to_wire(*others),
        }

    def _on_trace_fragment(self, from_id: str, payload: dict):
        """Distributed trace assembly ship side: the spans THIS process
        buffered for one trace id (its fragment of the cluster-wide
        tree). None when the trace never reached this process."""
        from ..obs.tracing import TRACER

        spans = TRACER.get(str(payload.get("trace_id", "")))
        if spans is None:
            return {"node": self.node_id, "spans": None}
        self.metrics.counter(
            "estpu_trace_fragments_shipped_total",
            "Trace-fragment spans shipped to a collecting coordinator",
            node=self.node_id,
        ).inc(len(spans))
        return {
            "node": self.node_id,
            "spans": [s.to_json() for s in spans],
        }

    def _on_hot_threads(self, from_id: str, payload: dict):
        """Hot-threads ship side: sample THIS process' thread stacks over
        the requested interval and return the rendered text block."""
        from ..obs.hot_threads import hot_threads_text

        return {
            "node": self.node_id,
            "text": hot_threads_text(
                node_name=self.node_id,
                threads=int(payload.get("threads", 3)),
                interval_s=float(payload.get("interval_s", 0.5)),
                snapshots=int(payload.get("snapshots", 10)),
                metrics=self.metrics,
            ),
        }

    # ------------------------------------------------------- master duties

    def _require_master(self) -> None:
        if not self.is_master():
            raise NotMasterError(f"[{self.node_id}] is not the master")

    def _publish(self, new_state: ClusterState) -> bool:
        """Publish a state; True when a quorum of seeds accepted (committed).
        The master steps down on losing quorum (Coordinator publication)."""
        new_state.version += 1
        acks = 0
        for node in new_state.seed_nodes:
            if node == self.node_id:
                continue
            try:
                resp = self.hub.send(
                    self.node_id,
                    node,
                    "publish_state",
                    {"state": new_state.to_json()},
                )
                if resp.get("accepted"):
                    acks += 1
            except (ConnectTransportError, RemoteActionError):
                continue
        committed = new_state.quorum(acks + 1)  # self counts
        if committed:
            with self.lock:
                self.state = new_state
                self._apply_assignments()
                self._save_state()
        else:
            with self.lock:  # lost the cluster: stop acting as master
                if self.state.master == self.node_id:
                    demoted = self.state.copy()
                    demoted.master = None
                    self.state = demoted
                    self._save_state()
        return committed

    def _on_fail_shard(self, from_id: str, payload: dict):
        with self.master_lock:
            self._require_master()
            index, shard_id = payload["index"], payload["shard"]
            node, term = payload["node"], int(payload["term"])
            new = self.state.copy()
            routing = new.indices[index].shards[shard_id]
            if term != routing.primary_term:
                return {"acked": False, "reason": "stale primary term"}
            if node in routing.replicas:
                routing.replicas.remove(node)
            if node in routing.recovering:
                routing.recovering.remove(node)
            routing.in_sync.discard(node)
            return {"acked": self._publish(new)}

    def _on_shard_recovered(self, from_id: str, payload: dict):
        with self.master_lock:
            self._require_master()
            index, shard_id = payload["index"], payload["shard"]
            node = payload["node"]
            new = self.state.copy()
            routing = new.indices[index].shards[shard_id]
            # A deposed primary must not vouch copies into the in-sync
            # set: its recovery ran without the current term's acked
            # writes.
            if int(payload.get("term", -1)) != routing.primary_term:
                return {"acked": False, "reason": "stale primary term"}
            if from_id != routing.primary:
                return {"acked": False, "reason": "not the primary"}
            if node in routing.recovering:
                routing.recovering.remove(node)
            if node not in routing.replicas and node != routing.primary:
                routing.replicas.append(node)
            routing.in_sync.add(node)
            return {"acked": self._publish(new)}

    def move_shard_replica(
        self, index: str, shard_id: int, from_node: str, to_node: str
    ) -> dict:
        """Master action (remediation allocation loop): move one REPLICA
        copy off a hot node. The replica leaves the routing table and the
        destination enters `recovering`, so the move completes through
        the ordinary peer-recovery machinery (`check_recoveries` +
        shard_recovered). The primary is never touched — promotion
        safety, and therefore every acked write, is untouched."""
        with self.master_lock:
            self._require_master()
            new = self.state.copy()
            meta = new.indices.get(index)
            if meta is None:
                raise ValueError(f"no such index [{index}]")
            routing = meta.shards[shard_id]
            if from_node == routing.primary:
                raise ValueError(
                    f"refusing to move primary {index}[{shard_id}] — "
                    "only replicas relocate"
                )
            if from_node not in routing.replicas:
                raise ValueError(
                    f"[{from_node}] holds no replica of {index}[{shard_id}]"
                )
            if to_node in routing.assigned() or to_node in routing.recovering:
                raise ValueError(
                    f"[{to_node}] already holds a copy of {index}[{shard_id}]"
                )
            if to_node not in new.nodes or to_node in new.voting_only:
                raise ValueError(
                    f"[{to_node}] is not a data-eligible cluster member"
                )
            routing.replicas.remove(from_node)
            routing.in_sync.discard(from_node)
            routing.recovering.append(to_node)
            return {"acked": self._publish(new)}

    def note_remediation(self, record: dict) -> dict:
        """Master action: ride one executed remediation action into the
        published state, making it an observable, versioned cluster-state
        transition every member sees."""
        with self.master_lock:
            self._require_master()
            new = self.state.copy()
            new.log_remediation(record)
            return {"acked": self._publish(new)}

    def _on_create_index(self, from_id: str, payload: dict):
        with self.master_lock:
            return self._create_index_locked(payload)

    def _create_index_locked(self, payload: dict):
        self._require_master()
        name = payload["name"]
        n_shards = int(payload.get("n_shards", 1))
        n_replicas = int(payload.get("n_replicas", 1))
        new = self.state.copy()
        if name in new.indices:
            raise ValueError(f"index [{name}] already exists")
        # Voting-only members never hold shard copies.
        nodes = sorted(n for n in new.nodes if n not in new.voting_only)
        if not nodes:
            raise NoShardAvailableError(
                f"cannot allocate [{name}]: no data-eligible nodes"
            )
        meta = IndexMeta(
            name=name,
            mappings=payload.get("mappings") or {},
            n_shards=n_shards,
            n_replicas=n_replicas,
        )
        for shard_id in range(n_shards):
            ordered = nodes[shard_id % len(nodes):] + nodes[: shard_id % len(nodes)]
            primary = ordered[0]
            replicas = ordered[1 : 1 + n_replicas]
            meta.shards[shard_id] = ShardRouting(
                primary=primary,
                replicas=replicas,
                in_sync={primary, *replicas},  # empty copies: trivially in sync
                primary_term=1,
            )
        new.indices[name] = meta
        if not self._publish(new):
            raise ReplicationFailedError("create_index lost quorum")
        return {"acknowledged": True}

    def _on_put_mappings(self, from_id: str, payload: dict):
        """Master action: replace an index's mappings and publish, so every
        copy's engine adopts the update (the reference's put-mapping
        cluster-state task). Validation happened at the REST layer."""
        with self.master_lock:
            self._require_master()
            name = payload["name"]
            new = self.state.copy()
            meta = new.indices.get(name)
            if meta is None:
                raise NoShardAvailableError(f"no such index [{name}]")
            meta.mappings = payload["mappings"] or {}
            if not self._publish(new):
                raise ReplicationFailedError("put_mappings lost quorum")
            return {"acknowledged": True}

    def _on_delete_index(self, from_id: str, payload: dict):
        with self.master_lock:
            self._require_master()
            name = payload["name"]
            new = self.state.copy()
            if name not in new.indices:
                return {"acknowledged": True}
            del new.indices[name]
            if not self._publish(new):
                raise ReplicationFailedError("delete_index lost quorum")
            return {"acknowledged": True}

    def health_round(self) -> None:
        """Master ping round: drop dead members, promote/heal shards."""
        with self.master_lock:
            if not self.is_master():
                return
            self._health_round_locked()

    def _health_round_locked(self) -> None:
        alive = {self.node_id}
        restarted: set[str] = set()
        sessions = {self.node_id: self.session}
        for node in self.state.seed_nodes:
            if node == self.node_id:
                continue
            try:
                pong = self.hub.send(self.node_id, node, "ping", {})
                alive.add(node)
                sessions[node] = pong.get("session", "")
            except (ConnectTransportError, RemoteActionError):
                continue
        for node, session in sessions.items():
            last = self.state.node_sessions.get(node)
            if last is not None and session and session != last:
                # Same node id, new process: its in-memory copies are gone
                # — every membership it held is stale and must be stripped
                # BEFORE any promotion decision below. Applies to the
                # master itself after its own restart.
                restarted.add(node)
        new = self.state.copy()
        changed = alive != new.nodes or sessions != {
            n: new.node_sessions.get(n) for n in sessions
        }
        new.nodes = alive
        new.node_sessions.update(sessions)
        if restarted:
            changed = True
            for meta in new.indices.values():
                for routing in meta.shards.values():
                    for node in restarted:
                        if routing.primary == node:
                            routing.primary = None  # promotion path below
                        if node in routing.replicas:
                            routing.replicas.remove(node)
                        if node in routing.recovering:
                            routing.recovering.remove(node)
                        routing.in_sync.discard(node)
        for meta in new.indices.values():
            for routing in meta.shards.values():
                if routing.primary is None or routing.primary not in alive:
                    # Promote: any in-sync replica has every acked op.
                    dead = routing.primary
                    candidates = sorted(
                        n for n in routing.replicas
                        if n in alive and n in routing.in_sync
                    )
                    if dead is not None:
                        routing.in_sync.discard(dead)
                        changed = True
                    if candidates:
                        routing.primary = candidates[0]
                        routing.replicas.remove(candidates[0])
                        routing.primary_term += 1
                        changed = True
                    elif dead is not None:
                        routing.primary = None  # red: refuse writes
                for node in list(routing.replicas):
                    if node not in alive:
                        routing.replicas.remove(node)
                        routing.in_sync.discard(node)
                        changed = True
                for node in list(routing.recovering):
                    if node not in alive:
                        routing.recovering.remove(node)
                        changed = True
                # Heal: allocate missing copies to nodes without one.
                want = meta.n_replicas
                have = len(routing.replicas) + len(routing.recovering)
                if routing.primary is not None and have < want:
                    holders = set(routing.assigned()) | set(routing.recovering)
                    for node in sorted(alive):
                        if have >= want:
                            break
                        if node in new.voting_only:
                            continue  # tiebreakers never take copies
                        if node not in holders:
                            routing.recovering.append(node)
                            have += 1
                            changed = True
        if changed:
            self._publish(new)

    def try_elect(self) -> bool:
        """Non-master path: if the master looks dead and we are the lowest
        reachable seed, run a quorum election and take over."""
        master = self.state.master
        if master == self.node_id:
            return True
        if master is not None:
            try:
                self.hub.send(self.node_id, master, "ping", {})
                return False  # master healthy
            except (ConnectTransportError, RemoteActionError):
                pass
        reachable = {self.node_id}
        for node in self.state.seed_nodes:
            if node == self.node_id:
                continue
            try:
                self.hub.send(self.node_id, node, "ping", {})
                reachable.add(node)
            except (ConnectTransportError, RemoteActionError):
                continue
        if min(reachable) != self.node_id:
            return False  # defer to the lower-id candidate
        # Adopt the newest accepted state among reachable peers before
        # standing: a restarted candidate with empty state would otherwise
        # be vetoed by every voter (and must never publish empty state
        # over live cluster metadata).
        for node in sorted(reachable - {self.node_id}):
            try:
                resp = self.hub.send(self.node_id, node, "get_state", {})
                peer_state = ClusterState.from_json(resp["state"])
            except (ConnectTransportError, RemoteActionError, KeyError):
                continue
            with self.lock:
                if peer_state.newer_than(self.state):
                    self.state = peer_state
                    self.current_term = max(
                        self.current_term, peer_state.term
                    )
                    self._apply_assignments()
                    self._save_state()
        term = self.current_term + 1
        votes = 1
        for node in sorted(reachable - {self.node_id}):
            try:
                resp = self.hub.send(
                    self.node_id,
                    node,
                    "request_vote",
                    {
                        "term": term,
                        "state_term": self.state.term,
                        "state_version": self.state.version,
                    },
                )
                if resp.get("granted"):
                    votes += 1
            except (ConnectTransportError, RemoteActionError):
                continue
        if not self.state.quorum(votes):
            return False
        with self.lock:
            self.current_term = term
            self._save_state()  # our own vote for this term is durable too
            new = self.state.copy()
            new.term = term
            new.master = self.node_id
            new.nodes = reachable
        if not self._publish(new):  # commit the mastership itself
            return False
        self.health_round()  # reroute around dead nodes under the new term
        return self.is_master()


def _batches(items: list, n: int):
    for i in range(0, len(items), n):
        yield items[i : i + n]


def _engines_by_index(engines: dict) -> dict[str, list]:
    """Group a ClusterNode's (index, shard) -> Engine map by index name
    (the per-index attribution of the computed device.hbm section)."""
    out: dict[str, list] = {}
    for (index, _shard), engine in engines.items():
        out.setdefault(index, []).append(engine)
    return out


class LocalCluster:
    """N in-process nodes over one interceptable hub — the test-cluster
    form of the reference's InternalTestCluster (+ MockTransportService).

    `transport` picks the wire: "hub" (in-memory switchboard, default) or
    "tcp" (every node gets a real loopback socket endpoint via
    TcpTransportHub — same interception API, actual frames). Defaults
    from ESTPU_CLUSTER_TRANSPORT so whole suites re-run over sockets
    unchanged."""

    def __init__(
        self,
        n_nodes: int = 3,
        data_path: str | None = None,
        transport: str | None = None,
    ):
        if transport is None:
            transport = os.environ.get("ESTPU_CLUSTER_TRANSPORT", "hub")
        self.transport_kind = transport
        if transport == "tcp":
            from .tcp_transport import TcpTransportHub

            self.hub = TcpTransportHub()
        elif transport == "hub":
            self.hub = TransportHub()
        else:
            raise ValueError(
                f"unknown cluster transport [{transport}]; "
                f"expected 'hub' or 'tcp'"
            )
        seeds = tuple(f"node-{i}" for i in range(n_nodes))
        self.seeds = seeds
        # Durable cluster-state root: with a data_path, every node persists
        # accepted publications, so a new LocalCluster over the same path
        # is a full-cluster restart that RECOVERS metadata (and refuses to
        # promote stale copies) instead of bootstrapping empty.
        self.data_path = data_path
        self.nodes: dict[str, ClusterNode] = {
            node_id: ClusterNode(node_id, self.hub, seeds, state_path=data_path)
            for node_id in seeds
        }
        # Cluster-level stepper error counter (the per-node counters cover
        # procs.py worker loops); surfaced through gateway.stats() into
        # `_nodes/stats` so a wedged control plane is visible.
        from ..obs.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()
        self._step_errors = self.metrics.counter(
            "estpu_cluster_step_errors_total",
            "Control-plane step errors swallowed by the background stepper",
            node="_cluster",
        )
        self._stepper: threading.Thread | None = None
        self._stop = threading.Event()
        # The remediation tick (cluster/remediation.py) rides the same
        # stepper as the master's health round: the owning node registers
        # a zero-arg callable; it runs only while a master holds office.
        self.remediation_hook = None
        self.step()  # bootstrap election

    # ------------------------------------------------------------ control

    def step(self) -> None:
        """One deterministic control-plane round: election checks, master
        health round, recovery kicks."""
        for node in list(self.nodes.values()):
            if node.closed:
                continue
            node.try_elect()
        master = self.master()
        if master is not None:
            master.health_round()
            hook = self.remediation_hook
            if hook is not None:
                hook()
        for node in list(self.nodes.values()):
            if not node.closed:
                node.check_recoveries()

    def start_stepper(self, interval_s: float = 0.05) -> None:
        def loop():
            while not self._stop.is_set():
                try:
                    self.step()
                # staticcheck: ignore[broad-except] daemon control-plane stepper: must survive any transient step error and retry next tick; owns no task — but every swallowed error is COUNTED (estpu_cluster_step_errors_total), never silent
                except Exception:
                    self._step_errors.inc()
                time.sleep(interval_s)

        self._stop.clear()
        self._stepper = threading.Thread(target=loop, daemon=True)
        self._stepper.start()

    def stop_stepper(self) -> None:
        self._stop.set()
        if self._stepper is not None:
            self._stepper.join(timeout=2)

    def master(self) -> ClusterNode | None:
        for node in self.nodes.values():
            if not node.closed and node.is_master():
                return node
        return None

    def any_node(self) -> ClusterNode:
        for node in self.nodes.values():
            if not node.closed:
                return node
        raise RuntimeError("no live nodes")

    def kill(self, node_id: str) -> None:
        """Hard-stop a node (process death: no goodbye, state lost)."""
        self.nodes[node_id].close()

    def restart(self, node_id: str) -> ClusterNode:
        """Bring a node back empty (in-memory copies are lost; it rejoins
        and re-acquires shard copies via peer recovery). With a data_path
        the node boots from its persisted cluster state — metadata intact,
        its own stale copy memberships already stripped."""
        node = ClusterNode(
            node_id, self.hub, self.seeds, state_path=self.data_path
        )
        self.nodes[node_id] = node
        return node

    def step_errors(self) -> int:
        """Swallowed stepper errors: cluster-level loop + per-node loops."""
        total = int(self._step_errors.value)
        for node in self.nodes.values():
            total += int(node._step_errors.value)
        return total

    def close(self) -> None:
        self.stop_stepper()
        for node in self.nodes.values():
            node.close()
        close_hub = getattr(self.hub, "close", None)
        if close_hub is not None:
            close_hub()

    # ------------------------------------------------------------- client

    def create_index(
        self,
        name: str,
        n_shards: int = 1,
        n_replicas: int = 1,
        mappings: dict | None = None,
    ) -> dict:
        master = self.master()
        if master is None:
            raise NotMasterError("cluster has no master")
        resp = master._on_create_index(
            "client",
            {
                "name": name,
                "n_shards": n_shards,
                "n_replicas": n_replicas,
                "mappings": mappings or {},
            },
        )
        return resp
