"""Multi-process cluster serving (cluster/procs.py): 2 spawned OS worker
processes + a voting-only tiebreaker in the supervisor, all RPC over real
TCP sockets. kill -9 is a REAL SIGKILL of a data-owning process here —
half-open sockets, stale address files, no lock ever unwound — and the
headline claims (promotion within deadline, zero acked-write loss,
socket-layer partition + heal convergence) are asserted against it.

The tier-1 slice is ONE end-to-end scenario per cluster boot (workers
pay a full JAX import each, so boots are amortized); the restart/rejoin
matrix rides the `slow` lane."""

import tempfile

import pytest

from elasticsearch_tpu.cluster.procs import ProcCluster

MAPPINGS = {
    "properties": {
        "body": {"type": "text"},
        "tag": {"type": "keyword"},
    }
}

QUERIES = [
    {"query": {"match": {"body": "payload"}}, "size": 50},
    {"query": {"term": {"tag": "red"}}, "size": 50},
    {"query": {"match_all": {}}, "size": 50},
]


def _routing(cluster, node_id, index="s", shard="0"):
    return cluster.state_of(node_id)["state"]["indices"][index]["shards"][
        shard
    ]


def test_workers_are_refused_the_chip_this_process_holds():
    """One process per chip: once this process has imported JAX it holds
    the devices, so workers may only be asked for the CPU."""
    import jax  # noqa: F401

    with pytest.raises(RuntimeError, match="one process per chip"):
        ProcCluster(2, jax_platforms="tpu")


@pytest.fixture(scope="module")
def procs():
    cluster = ProcCluster(
        2, data_path=tempfile.mkdtemp(prefix="estpu-socket-smoke-")
    )
    yield cluster
    cluster.close()


class TestTwoProcessCluster:
    def test_kill9_promotion_partition_heal_zero_acked_loss(self, procs):
        """The acceptance scenario, one boot: index through real sockets,
        serve the search mix, SIGKILL the primary-owning process, verify
        promotion + every acked write, write on, partition at the socket
        layer, heal, verify convergence and the restarted process'
        rejoin."""
        cluster = procs
        cluster.create_index("s", n_shards=1, n_replicas=1, mappings=MAPPINGS)
        acked = []
        for i in range(24):
            resp = cluster.write(
                "s",
                f"d{i}",
                {
                    "body": f"payload term{i % 5}",
                    "tag": "red" if i % 2 else "blue",
                },
            )
            assert resp["result"] == "created", resp
            acked.append(f"d{i}")
        # The search mix serves through real sockets (scatter from the
        # supervisor's coordinating node to the worker-owned copies).
        for body in QUERIES:
            out = cluster.search("s", body)
            assert out["_shards"]["failed"] == 0, out["_shards"]
            assert out["hits"]["total"]["value"] > 0
        out = cluster.search("s", {"query": {"match_all": {}}, "size": 50})
        assert out["hits"]["total"]["value"] == len(acked)

        routing = _routing(cluster, cluster.workers[0])
        primary = routing["primary"]
        assert primary in cluster.workers
        assert "tiebreaker" not in (
            [routing["primary"]] + routing["replicas"]
        ), "voting-only tiebreaker must never hold a copy"
        survivor = [w for w in cluster.workers if w != primary][0]

        # ------------------------------------------------ kill -9 the owner
        cluster.kill_9(primary)
        cluster.wait_for(
            lambda: _routing(cluster, survivor)["primary"] == survivor,
            timeout_s=30.0,
            what="promotion after kill -9",
        )
        new_routing = _routing(cluster, survivor)
        assert new_routing["primary_term"] == routing["primary_term"] + 1
        # Zero acked-write loss through real process death.
        missing = [d for d in acked if cluster.read("s", d) is None]
        assert not missing, f"acked docs lost: {missing}"
        out = cluster.search("s", {"query": {"match_all": {}}, "size": 50})
        assert out["hits"]["total"]["value"] == len(acked)
        # Writes continue against the promoted primary.
        resp = cluster.write("s", "after-kill", {"body": "payload after"})
        assert resp["result"] == "created"
        acked.append("after-kill")

        # -------------------------------------------- restart: rejoin
        cluster.restart(primary)
        cluster.wait_for(
            lambda: primary in _routing(cluster, survivor)["in_sync"],
            timeout_s=60.0,
            what="restarted worker rejoining in-sync",
        )

        # ------------------------- socket-layer partition, then heal
        minority = primary  # freshly rejoined worker gets isolated
        majority = [survivor, "tiebreaker"]
        cluster.partition({minority}, set(majority))
        # The majority side keeps accepting acked writes (the isolated
        # copy is failed out of the in-sync set via quorum publication).
        resp = cluster.write("s", "during-split", {"body": "payload split"})
        assert resp["result"] == "created"
        acked.append("during-split")
        cluster.wait_for(
            lambda: minority
            not in _routing(cluster, survivor)["in_sync"],
            timeout_s=30.0,
            what="isolated copy failed out of in-sync",
        )
        cluster.heal_partition()
        cluster.wait_for(
            lambda: minority in _routing(cluster, survivor)["in_sync"],
            timeout_s=60.0,
            what="healed worker recovered back in-sync",
        )
        missing = [d for d in acked if cluster.read("s", d) is None]
        assert not missing, f"acked docs lost through split: {missing}"
        out = cluster.search("s", {"query": {"match_all": {}}, "size": 50})
        assert out["hits"]["total"]["value"] == len(acked)

        # Step errors are cataloged and visible, not silent.
        for worker in cluster.workers:
            assert "step_errors" in cluster.state_of(worker)


class TestStaticAddressBook:
    """Multi-host address-book mode (the `discovery.seed_hosts` analog):
    every member's transport address is explicit configuration — no
    shared-filesystem address directory, no inherited fds — the form a
    REAL multi-host deployment (one process per TPU host over DCN) would
    use. Workers bind their configured ports, discover each other from
    the static map alone, and the serving path works end to end."""

    def test_boot_discover_and_serve_with_explicit_seeds(self):
        import socket as socketlib

        # Pre-pick free ports by binding then releasing them; the gap to
        # the worker's own bind is the standard best-effort race.
        ports = []
        holders = []
        for _ in range(3):
            s = socketlib.socket()
            s.bind(("127.0.0.1", 0))
            holders.append(s)
            ports.append(s.getsockname()[1])
        for s in holders:
            s.close()
        seed_addrs = {
            "node-0": f"127.0.0.1:{ports[0]}",
            "node-1": f"127.0.0.1:{ports[1]}",
            "tiebreaker": f"127.0.0.1:{ports[2]}",
        }
        cluster = ProcCluster(
            2,
            data_path=tempfile.mkdtemp(prefix="estpu-static-book-"),
            seed_addrs=seed_addrs,
        )
        try:
            # Members really bound their CONFIGURED addresses.
            for node_id in cluster.workers:
                transport = cluster.state_of(node_id)
                assert transport["node"] == node_id
            for node_id, addr in seed_addrs.items():
                host, port = addr.split(":")
                looked_up = cluster._book.lookup(node_id)
                assert looked_up == (host, int(port))
            # Discovery: an elected master whose membership names every
            # seed — from the static map alone.
            cluster.wait_for_status("green", timeout_s=60.0)
            assert set(cluster._local_node.state.nodes) >= set(
                cluster.workers
            )
            # Serving path over the configured addresses.
            cluster.create_index(
                "s", n_shards=1, n_replicas=1, mappings=MAPPINGS
            )
            for i in range(5):
                cluster.write("s", f"d{i}", {"body": f"payload {i}"})
            out = cluster.search(
                "s", {"query": {"match": {"body": "payload"}}, "size": 10}
            )
            assert out["hits"]["total"]["value"] == 5
            assert cluster.read("s", "d0") is not None
        finally:
            cluster.close()


@pytest.mark.slow
class TestProcessChurn:
    def test_repeated_kill9_restart_cycles(self):
        """Two full kill -9 → promote → restart → rejoin cycles, killing a
        DIFFERENT owner each time; every acked write survives both."""
        cluster = ProcCluster(
            2, data_path=tempfile.mkdtemp(prefix="estpu-churn-")
        )
        try:
            cluster.create_index(
                "c", n_shards=1, n_replicas=1, mappings=MAPPINGS
            )
            acked = []
            for i in range(10):
                cluster.write("c", f"seed{i}", {"body": f"payload {i}"})
                acked.append(f"seed{i}")
            for round_i in range(2):
                routing = _routing(cluster, cluster.workers[0], index="c")
                primary = routing["primary"]
                survivor = [w for w in cluster.workers if w != primary][0]
                cluster.kill_9(primary)
                cluster.wait_for(
                    lambda s=survivor: _routing(cluster, s, index="c")[
                        "primary"
                    ]
                    == s,
                    timeout_s=30.0,
                    what=f"promotion round {round_i}",
                )
                for i in range(5):
                    doc = f"r{round_i}-{i}"
                    resp = cluster.write(
                        "c", doc, {"body": f"payload {doc}"}
                    )
                    assert resp["result"] == "created"
                    acked.append(doc)
                cluster.restart(primary)
                cluster.wait_for(
                    lambda s=survivor, p=primary: p
                    in _routing(cluster, s, index="c")["in_sync"],
                    timeout_s=60.0,
                    what=f"rejoin round {round_i}",
                )
                missing = [
                    d for d in acked if cluster.read("c", d) is None
                ]
                assert not missing, f"round {round_i} lost: {missing}"
        finally:
            cluster.close()
