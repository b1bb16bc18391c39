"""Chaos tests: workloads survive random node kills (parity model:
reference python/ray/tests/chaos/ + NodeKillerActor suites), and
PREEMPTED nodes — drain-with-deadline then kill, via NodePreempter —
die as non-events: zero lineage reconstructions, zero actor errors."""

import time

import pytest

import ray_tpu
from ray_tpu.test_utils import (NodeKiller, NodePreempter,
                                wait_for_condition)


@ray_tpu.remote
def _compute(x):
    time.sleep(0.05)
    return x * 2


def test_tasks_survive_node_churn(ray_start_cluster_head):
    cluster = ray_start_cluster_head
    for _ in range(2):
        cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes()

    with NodeKiller(cluster, interval_s=0.7, respawn=True,
                    node_args={"num_cpus": 2}, max_kills=2, seed=0) as killer:
        refs = [_compute.options(max_retries=10).remote(i) for i in range(60)]
        results = ray_tpu.get(refs, timeout=120)
    assert results == [i * 2 for i in range(60)]
    assert killer.kills >= 1


def test_actor_restart_after_chaos_kill(ray_start_cluster_head):
    cluster = ray_start_cluster_head
    n2 = cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.wait_for_nodes()

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    # Actor pinned to the doomed node; max_restarts lets GCS reschedule it.
    a = Counter.options(max_restarts=5, resources={"side": 0.1}).remote()
    assert ray_tpu.get(a.incr.remote()) == 1
    cluster.remove_node(n2)
    # Replacement node also offers the 'side' resource.
    cluster.add_node(num_cpus=2, resources={"side": 1})

    def restarted():
        try:
            return ray_tpu.get(a.incr.remote(), timeout=10) >= 1
        except ray_tpu.exceptions.RayTpuError:
            return False

    wait_for_condition(restarted, timeout=60)


def test_wait_for_condition_raises():
    with pytest.raises(TimeoutError):
        wait_for_condition(lambda: False, timeout=0.3)


@pytest.mark.smoke
def test_preempted_node_is_a_non_event(ray_start_cluster_head):
    """NodeKiller's inverse: a node that is DRAINED before it dies must
    cost nothing — the workload finishes with zero lineage
    reconstructions and zero actor-death errors (drain evacuated the
    queued leases, the actor, and the primary object copies first)."""
    from ray_tpu._private.api_internal import get_core_worker

    cluster = ray_start_cluster_head
    target = cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.wait_for_nodes()
    cw = get_core_worker()

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    actor = Counter.options(max_restarts=5, name="preempt-counter",
                            resources={"side": 0.1}).remote()
    assert ray_tpu.get(actor.incr.remote(), timeout=30) == 1

    @ray_tpu.remote(resources={"side": 0.1})
    def payload():
        return bytes(bytearray(1 << 18))

    blob = payload.remote()
    ray_tpu.wait([blob], timeout=30)
    refs = [_compute.options(max_retries=10).remote(i) for i in range(30)]

    preempter = NodePreempter(cluster, deadline_s=10, reason="preemption")
    result = preempter.preempt(target)
    assert result.get("state") == "DRAINED", result
    assert preempter.preemptions == 1

    assert ray_tpu.get(refs, timeout=120) == [i * 2 for i in range(30)]
    assert len(ray_tpu.get(blob, timeout=30)) == 1 << 18
    # Actor calls never error — at worst they wait out a RESTARTING
    # window while the GCS migrates the actor off the draining node.
    assert ray_tpu.get(actor.incr.remote(), timeout=60) >= 1
    assert cw._num_reconstructions == 0


@pytest.mark.smoke
def test_preemption_deadline_fail_fast(ray_start_cluster_head, tmp_path):
    """Work that exceeds the drain deadline is failed fast and
    RETRYABLE: the drain completes on time and the task finishes on a
    surviving node instead of being failed infeasible."""
    cluster = ray_start_cluster_head
    target = cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.wait_for_nodes()
    started = tmp_path / "started"

    @ray_tpu.remote(resources={"side": 0.1}, max_retries=3)
    def outlives_deadline(x):
        # (the first attempt, which the drain fails, and not its retry)
        if not started.exists():
            started.touch()
            time.sleep(20.0)
        return x * 3

    ref = outlives_deadline.remote(5)
    wait_for_condition(started.exists)      # running, and on the target
    cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.wait_for_nodes()
    preempter = NodePreempter(cluster, deadline_s=2)
    t0 = time.monotonic()
    result = preempter.preempt(target)
    assert result.get("state") == "DRAINED", result
    assert time.monotonic() - t0 < 15
    assert ray_tpu.get(ref, timeout=90) == 15


@ray_tpu.remote(resources={"side": 0.1})
def _side_compute(x):
    time.sleep(0.05)
    return x * 2


@pytest.mark.smoke
def test_stochastic_step_schedule_preemption(ray_start_cluster_head):
    """NodePreempter's seeded STEP schedule (spot-reclamation model for
    elastic training): a preemption fires once the workload's own step
    counter crosses a gap drawn from the seeded rng (~step_interval
    ± jitter), the fired step is recorded in step_schedule, and the
    drain-then-kill stays a non-event for the retried tasks."""
    cluster = ray_start_cluster_head
    for _ in range(2):
        cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.wait_for_nodes()

    done = []
    preempter = NodePreempter(
        cluster, deadline_s=5, step_interval=10, step_jitter=0.2,
        seed=1, respawn=True, max_preemptions=1,
        node_args={"num_cpus": 2, "resources": {"side": 1}},
        step_source=lambda: len(done))
    with preempter:
        for i in range(30):
            done.append(ray_tpu.get(
                _side_compute.options(max_retries=10).remote(i),
                timeout=60))
    assert done == [i * 2 for i in range(30)]
    assert preempter.preemptions == 1
    # Fired at (or a poll past) the first seeded gap ∈ [8, 12].
    assert preempter.step_schedule
    assert 8 <= preempter.step_schedule[0] <= 20


@pytest.mark.smoke
def test_partition_flap_composes_with_preemption(ray_start_cluster_head):
    """The two seeded fault injectors together (PR 10): one node's GCS
    link runs through a NetChaos proxy and flaps inside the heartbeat
    grace window while ANOTHER node is spot-preempted (drain-then-kill).
    The workload still finishes exactly, the flapped node recovers
    through the SUSPECT rung (a non-event), and the driver counts zero
    lineage reconstructions — neither fault is allowed to amplify the
    other into a false death."""
    from ray_tpu._private.api_internal import get_core_worker
    from ray_tpu.test_utils import NetChaos

    cluster = ray_start_cluster_head
    cw = get_core_worker()
    chaos = NetChaos(seed=3).start()
    try:
        gcs_host, gcs_port = cluster.gcs_address.rsplit(":", 1)
        proxy = chaos.link("flappy-gcs", gcs_host, int(gcs_port))
        flappy = cluster.add_node(num_cpus=2, resources={"side": 1},
                                  gcs_addr=proxy)
        doomed = cluster.add_node(num_cpus=2, resources={"side": 1})
        cluster.wait_for_nodes()

        refs = [_side_compute.options(max_retries=10).remote(i)
                for i in range(40)]
        # Flap (0.4s, under the 0.2s x 5 = 1s grace) then immediately
        # preempt the other 'side' node while the flapped one may still
        # be SUSPECT — its capacity must come back for the re-spilled
        # leases.
        chaos.flap("flappy-gcs", down_s=0.4)
        preempter = NodePreempter(cluster, deadline_s=10,
                                  reason="preemption")
        result = preempter.preempt(doomed)
        assert result.get("state") == "DRAINED", result

        assert ray_tpu.get(refs, timeout=120) == [i * 2 for i in range(40)]

        def row():
            return next((n for n in ray_tpu.nodes()
                         if n["node_id"] == flappy.node_id), {})

        wait_for_condition(lambda: row().get("state") == "ALIVE",
                           timeout=15)
        assert row().get("suspect_recoveries", 0) >= 1, row()
        assert preempter.preemptions == 1
        assert cw._num_reconstructions == 0
    finally:
        chaos.stop()
