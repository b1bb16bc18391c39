"""GCS fault tolerance: kill + restart the control plane mid-run.

Parity: reference python/ray/tests/test_gcs_fault_tolerance.py — the GCS
restarts with persisted state (Redis there, msgpack snapshot here), raylets
re-register under the same node id, live actors keep serving (actor calls
never touch the GCS), and new work schedules after recovery.
"""

import time

import pytest

import ray_tpu
from ray_tpu._private.config import Config


@pytest.fixture
def ft_cluster():
    from ray_tpu.cluster_utils import Cluster

    cfg = Config()
    cfg.health_check_period_s = 0.2
    cfg.num_heartbeats_timeout = 10
    cfg.gcs_reconnect_timeout_s = 30.0
    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 4}, config=cfg)
    yield cluster
    cluster.shutdown()


def test_gcs_restart_preserves_cluster(ft_cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.options(name="survivor").remote()
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 1
    ray_tpu.get(ray_tpu.put("kv-sentinel"))  # exercise the data plane too
    time.sleep(1.0)  # let the persistence loop snapshot the state

    node = ft_cluster._node
    node.kill_gcs()

    # Actor calls go direct worker-to-worker: they keep working with the
    # control plane DOWN (the reference's key resilience property).
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 2

    node.restart_gcs()

    # Raylet re-registers; driver reconnects; new tasks schedule.
    deadline = time.monotonic() + 30
    alive = []
    while time.monotonic() < deadline:
        try:
            alive = [n for n in ray_tpu.nodes() if n["alive"]]
            if alive:
                break
        except Exception:
            pass
        time.sleep(0.5)
    assert alive, "raylet never re-registered after GCS restart"

    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(41), timeout=90) == 42
    # Existing actor still reachable AND still findable by name (the actor
    # directory was persisted).
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 3
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            again = ray_tpu.get_actor("survivor")
            break
        except Exception:
            time.sleep(0.5)
    else:
        raise AssertionError("named actor lost after GCS restart")
    assert ray_tpu.get(again.inc.remote(), timeout=60) == 4


def test_gcs_restart_preserves_kv(ft_cluster):
    from ray_tpu._private.api_internal import get_core_worker

    cw = get_core_worker()
    cw._run(cw.gcs.call("KVPut", {"ns": "t", "key": b"k", "value": b"v1"}))
    time.sleep(1.0)  # snapshot interval

    node = ft_cluster._node
    node.kill_gcs()
    node.restart_gcs()

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            got = cw._run(cw.gcs.call("KVGet", {"ns": "t", "key": b"k"}))
            if got.get("value") == b"v1":
                return
        except Exception:
            pass
        time.sleep(0.5)
    raise AssertionError("KV entry lost across GCS restart")


def test_write_through_survives_immediate_kill9(ft_cluster):
    """Per-mutation durability: an acknowledged mutation must survive a
    GCS SIGKILL delivered IMMEDIATELY after the ack — no persistence-
    window sleep (reference: redis store_client gives the GCS
    write-through per mutation, store_client_kv.h). The WAL append runs
    before the RPC reply, so there is nothing left to lose."""
    from ray_tpu._private.api_internal import get_core_worker

    cw = get_core_worker()

    @ray_tpu.remote
    class Pinger:
        def ping(self):
            return "pong"

    # Acked mutations: KVPut + named-actor registration. NO sleep after.
    cw._run(cw.gcs.call("KVPut", {"ns": "wt", "key": b"k", "value": b"v"}))
    a = Pinger.options(name="wt-actor").remote()
    del a  # handle not needed; registration was acknowledged

    node = ft_cluster._node
    node.kill_gcs()  # SIGKILL, immediately after the acks
    node.restart_gcs()

    deadline = time.monotonic() + 60
    kv_ok = actor_ok = False
    while time.monotonic() < deadline and not (kv_ok and actor_ok):
        try:
            if not kv_ok:
                got = cw._run(cw.gcs.call(
                    "KVGet", {"ns": "wt", "key": b"k"}), timeout=5)
                kv_ok = got.get("value") == b"v"
            if not actor_ok:
                # The registration was PENDING at kill time; the restarted
                # GCS must replay it and re-kick scheduling.
                h = ray_tpu.get_actor("wt-actor")
                actor_ok = ray_tpu.get(h.ping.remote(), timeout=30) == "pong"
        except Exception:
            time.sleep(0.5)
    assert kv_ok, "acknowledged KVPut lost across immediate kill -9"
    assert actor_ok, "acknowledged actor registration lost across kill -9"


def test_pg_ready_promise_survives_gcs_restart(ft_cluster):
    """pg.ready() is a GCS-pubsub-backed promise (r5): a CREATED that
    lands while the driver's GCS conn is down must still resolve — the
    reconnect handshake re-queries every armed waiter (worker.py
    _reconnect_gcs). Sequence: PG stays PENDING (infeasible), ready()
    arms, GCS dies and restarts, THEN capacity arrives and the PG
    creates — the promise must fire, not hang."""
    import threading

    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)

    # Infeasible until a bigger node joins: 16 CPUs on a 4-CPU head.
    pg = placement_group([{"CPU": 16.0}])
    ref = pg.ready()

    got = []
    waiter = threading.Thread(
        target=lambda: got.append(ray_tpu.get(ref, timeout=120)),
        daemon=True)
    waiter.start()
    time.sleep(1.0)
    assert not got, "PG resolved before capacity existed"

    node = ft_cluster._node
    node.kill_gcs()
    time.sleep(0.5)
    node.restart_gcs()

    # New capacity arrives AFTER the restart; the PG schedules and the
    # promise must resolve through the re-subscribed channel (or the
    # reconnect re-query), not hang forever.
    ft_cluster.add_node(num_cpus=16)
    waiter.join(timeout=90)
    assert got == [True], f"pg.ready() promise lost across GCS restart: {got}"
    remove_placement_group(pg)


# ---------------------------------------------------------------------------
# Network partitions (PR 10): a raylet whose GCS link flaps inside the
# heartbeat grace window is a NON-EVENT — SUSPECT, then restored, with
# zero reconstructions, zero duplicate actor creations, and the workload
# unbothered. Only an outage that outlives the grace window promotes
# SUSPECT -> DEAD. The link runs through a seeded NetChaos proxy so the
# fault schedule is deterministic.
# ---------------------------------------------------------------------------


def _node_row(node_id):
    return next((n for n in ray_tpu.nodes()
                 if n["node_id"] == node_id), {})


def test_partition_flap_is_a_non_event(ft_cluster):
    """~500 tasks flow while the target raylet's GCS link flaps twice
    (each outage well under the 0.2s x 10 = 2s grace). Every result must
    arrive, the node must end ALIVE with suspect_recoveries bumped, the
    pinned actor must keep its process (no duplicate creation), and the
    driver must count zero lineage reconstructions — the raylet's
    resilient session reconnected instead of the node dying."""
    from ray_tpu._private.api_internal import get_core_worker
    from ray_tpu.test_utils import NetChaos, wait_for_condition
    from ray_tpu.util import state as util_state

    cw = get_core_worker()
    chaos = NetChaos(seed=7).start()
    try:
        gcs_host, gcs_port = ft_cluster.gcs_address.rsplit(":", 1)
        proxy = chaos.link("flap-gcs", gcs_host, int(gcs_port))
        target = ft_cluster.add_node(num_cpus=4, resources={"part": 1},
                                     gcs_addr=proxy)
        ft_cluster.wait_for_nodes()

        @ray_tpu.remote
        class Pinned:
            def __init__(self):
                import os
                self.pid = os.getpid()
                self.n = 0

            def incr(self):
                self.n += 1
                return (self.pid, self.n)

        actor = Pinned.options(max_restarts=5,
                               resources={"part": 0.1}).remote()
        pid0, n0 = ray_tpu.get(actor.incr.remote(), timeout=30)
        assert n0 == 1

        @ray_tpu.remote(resources={"part": 0.01})
        def inc(x):
            return x + 1

        def back_from(flaps):
            row = _node_row(target.node_id)
            return row.get("state") in ("ALIVE", "DEAD") \
                and (row.get("suspect_recoveries", 0) >= flaps
                     or not row.get("alive"))

        refs = []
        for i in range(500):
            if i == 300:
                # TWO outages, each well under the grace: the second begins
                # when the node is back from the first. (Run together they
                # are one of over a second, and with the redial's backoff
                # on top of it, up to 1 s a step, that is not under 2 s.)
                wait_for_condition(lambda: back_from(1), timeout=15)
            if i in (100, 300):
                chaos.flap("flap-gcs", down_s=0.5)
            refs.append(inc.remote(i))
        assert ray_tpu.get(refs, timeout=180) == [i + 1 for i in range(500)]

        wait_for_condition(lambda: back_from(2), timeout=15)
        row = _node_row(target.node_id)
        assert row.get("state") == "ALIVE" \
            and row.get("suspect_recoveries", 0) == 2, \
            f"a flap was not a SUSPECT rung climbed and left: {row}"
        # Same actor process, same counter: no duplicate creation, no
        # restart — the flap was invisible to it.
        pid1, n1 = ray_tpu.get(actor.incr.remote(), timeout=30)
        assert (pid1, n1) == (pid0, 2), "actor restarted across a flap"
        assert cw._num_reconstructions == 0
        # The raylet rode its resilient session through the cuts instead
        # of re-dialing ad hoc.
        stats = util_state.node_stats(node_id=target.node_id)
        sess = stats[0].get("rpc_sessions", {}) if stats else {}
        assert sess.get("reconnects_total", 0) >= 1, sess
        status = util_state.cluster_status()
        assert status.get("suspect_nodes") == 0
    finally:
        chaos.stop()


def test_partition_longer_than_grace_promotes_to_dead(ft_cluster):
    """The other side of the contract: an outage that OUTLIVES the grace
    window must not be forgiven. The node walks ALIVE -> SUSPECT (on
    connection loss) -> DEAD (on grace expiry), observably from the
    driver, while the outage is still in progress."""
    import threading

    from ray_tpu.test_utils import NetChaos, wait_for_condition

    chaos = NetChaos(seed=8).start()
    try:
        gcs_host, gcs_port = ft_cluster.gcs_address.rsplit(":", 1)
        proxy = chaos.link("dead-gcs", gcs_host, int(gcs_port))
        target = ft_cluster.add_node(num_cpus=2, resources={"gone": 1},
                                     gcs_addr=proxy)
        ft_cluster.wait_for_nodes()
        assert _node_row(target.node_id).get("state") == "ALIVE"

        # Outage (6s) > grace (0.2s x 10 = 2s). flap() blocks for the
        # full outage, so run it on the side and watch the ladder.
        flapper = threading.Thread(
            target=lambda: chaos.flap("dead-gcs", down_s=6.0), daemon=True)
        flapper.start()
        wait_for_condition(
            lambda: _node_row(target.node_id).get("state") == "SUSPECT",
            timeout=10)
        wait_for_condition(
            lambda: _node_row(target.node_id).get("state") == "DEAD",
            timeout=10)
        assert _node_row(target.node_id).get("alive") is False
        flapper.join(timeout=15)
    finally:
        chaos.stop()
