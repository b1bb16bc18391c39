"""Preemption-aware graceful node drain (raylet._run_drain +
gcs.handle_drain_node): the DRAINING→DRAINED ladder, lease respill,
proactive actor migration, object + pinned-HBM evacuation, the
relocation directory that replaces lineage reconstruction for foreseen
deaths, and the failure-propagation / retry-elsewhere satellites.

Smoke-marked tier-1 gates; each test keeps its cluster small and its
deadlines short so the suite stays inside the tier-1 budget.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.api_internal import get_core_worker
from ray_tpu._private.config import Config
from ray_tpu.cluster_utils import Cluster
from ray_tpu.test_utils import NodePreempter, wait_for_condition

pytestmark = pytest.mark.smoke


def _drain_config() -> Config:
    cfg = Config()
    cfg.health_check_period_s = 0.2
    cfg.num_heartbeats_timeout = 5
    cfg.worker_lease_timeout_s = 10.0
    cfg.object_store_memory = 64 * 1024 * 1024
    # Idle-pool trimming must not reap a worker holding device pins
    # between task end and the drain (the drain itself pauses trimming,
    # but the pin exists before the drain starts).
    cfg.num_workers_soft_limit = 16
    return cfg


@pytest.fixture
def drain_cluster():
    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 2},
                      config=_drain_config())
    yield cluster
    cluster.shutdown()


@ray_tpu.remote(resources={"pin": 0.1})
def _slow(x):
    time.sleep(0.5)
    return x * 2


@ray_tpu.remote(resources={"pin": 0.1})
def _blob(i):
    return bytes(bytearray([i & 0xFF])) * (1 << 19)


@ray_tpu.remote(resources={"pin": 0.1})
class _Counter:
    def __init__(self):
        self.n = 0

    def incr(self):
        self.n += 1
        return self.n


def _node_info(node_id):
    return next((n for n in ray_tpu.nodes()
                 if n["node_id"] == node_id), None)


def _raylet_state(node):
    from ray_tpu.util import state

    return state.node_stats(node.node_id)[0]


def test_drain_e2e_evacuates_everything(drain_cluster):
    """The acceptance scenario: a 3-node cluster with queued + running
    tasks, a restartable named actor, primary object copies, and an
    HBM-pinned device object all on the drain target. After
    drain(deadline=10) + kill: everything completes with ZERO lineage
    reconstructions and zero client-visible actor errors, and the drain
    stats account for every evacuated item."""
    jnp = pytest.importorskip("jax.numpy")  # noqa: F841
    cluster = drain_cluster
    target = cluster.add_node(num_cpus=4, resources={"pin": 2})
    cluster.wait_for_nodes()
    cw = get_core_worker()

    @ray_tpu.remote(resources={"pin": 0.1}, tensor_transport="device")
    def dev():
        import jax.numpy as jnp

        return jnp.arange(64, dtype=jnp.float32).reshape(8, 8)

    actor = _Counter.options(name="drain-e2e", max_restarts=4).remote()
    assert ray_tpu.get(actor.incr.remote(), timeout=30) == 1
    # Three primary copies on the target: with two surviving peers the
    # round-robin evacuation lands at least one object on the non-head
    # peer, which forces the GCS relocation-directory recovery path.
    blob_refs = [_blob.remote(i) for i in range(3)]
    dev_ref = dev.remote()
    ray_tpu.wait(blob_refs, num_returns=len(blob_refs), timeout=30)
    ray_tpu.wait([dev_ref], timeout=30)
    # Queued + running work that outlives the drain trigger.
    task_refs = [_slow.remote(i) for i in range(8)]

    peer = cluster.add_node(num_cpus=4, resources={"pin": 2})  # noqa: F841
    cluster.wait_for_nodes()

    preempter = NodePreempter(cluster, deadline_s=10, reason="preemption")
    result = preempter.preempt(target)
    assert result.get("ok") and result.get("state") == "DRAINED", result

    info = _node_info(target.node_id)
    stats = info["drain_stats"]
    assert info["state"] == "DRAINED"
    assert info["drain_reason"] == "preemption"
    # Every evacuated item is accounted for.
    assert stats["evacuated_objects"] >= 3, stats
    assert stats["evacuated_bytes"] >= 3 * (1 << 19), stats
    assert stats["evacuated_device_objects"] == 1, stats
    assert stats["migrated_actors"] == 1, stats
    assert stats["unevacuated_objects"] == 0, stats
    assert stats["duration_s"] <= 10 + 5, stats

    # All work completes; no lineage storm, no actor errors.
    assert ray_tpu.get(task_refs, timeout=60) == [i * 2 for i in range(8)]
    for i, ref in enumerate(blob_refs):
        got = ray_tpu.get(ref, timeout=30)
        assert len(got) == 1 << 19 and got[0] == i
    val = ray_tpu.get(dev_ref, timeout=30)
    assert float(np.asarray(val).sum()) == float(np.arange(64).sum())
    assert ray_tpu.get(actor.incr.remote(), timeout=30) >= 1
    assert cw._num_reconstructions == 0
    # With 3 objects round-robined over 2 peers, at least one landed on
    # the non-head peer — recovered through the relocation directory.
    assert cw._num_relocation_recoveries >= 1


def test_drain_deadline_fails_running_lease_retryable(drain_cluster):
    """Work that exceeds the deadline is failed RETRYABLE (killed lease
    → owner retries elsewhere), never infeasible."""
    cluster = drain_cluster
    target = cluster.add_node(num_cpus=2, resources={"pin": 1})
    cluster.wait_for_nodes()
    cw = get_core_worker()

    @ray_tpu.remote(resources={"pin": 0.1}, max_retries=3)
    def stuck(x, on):
        # (stuck where the drain kills it, and nowhere else: the retry
        # answers at once)
        if ray_tpu.get_runtime_context().get_node_id() == on:
            time.sleep(20.0)
        return x + 1

    ref = stuck.remote(1, target.node_id)
    # Running on the target: a fresh node takes seconds to attach its
    # first worker, and until then the drain has no lease to kill.
    wait_for_condition(lambda: _raylet_state(target)["leases_granted"] == 1)
    cluster.add_node(num_cpus=2, resources={"pin": 1})
    cluster.wait_for_nodes()

    t0 = time.monotonic()
    resp = cluster.drain_node(target, deadline_s=2, reason="preemption")
    assert resp.get("state") == "DRAINED", resp
    assert time.monotonic() - t0 < 15
    stats = _node_info(target.node_id)["drain_stats"]
    assert stats["killed_leases"] == 1, stats
    cluster.remove_node(target)
    assert ray_tpu.get(ref, timeout=90) == 2
    assert cw._num_reconstructions == 0


def test_drain_rejects_grant_still_attaching_its_worker(drain_cluster):
    """A lease whose resources are acquired while its worker still starts
    is no running lease yet: the drain neither waits for it nor kills it.
    Whichever side of that the drain lands on, the drained node has
    granted only what the drain killed (a grant whose worker comes up
    after the drain began is refused), and the task completes elsewhere."""
    cluster = drain_cluster
    target = cluster.add_node(num_cpus=2, resources={"pin": 1})
    cluster.wait_for_nodes()

    @ray_tpu.remote(resources={"pin": 0.1}, max_retries=3)
    def stuck_on(node_id):
        here = ray_tpu.get_runtime_context().get_node_id()
        time.sleep(20.0 if here == node_id else 0.0)
        return here

    ref = stuck_on.remote(target.node_id)
    wait_for_condition(lambda: _raylet_state(target)["active_leases"] == 1,
                       retry_interval_ms=10)
    other = cluster.add_node(num_cpus=2, resources={"pin": 1})
    cluster.wait_for_nodes()
    resp = cluster.drain_node(target, deadline_s=2, reason="preemption")
    assert resp.get("state") == "DRAINED", resp
    wait_for_condition(lambda: _raylet_state(target)["active_leases"] == 0)
    assert _raylet_state(target)["leases_granted"] \
        == _node_info(target.node_id)["drain_stats"]["killed_leases"]
    assert ray_tpu.get(ref, timeout=60) == other.node_id


def test_drain_rejection_is_retry_elsewhere(drain_cluster, tmp_path):
    """Regression (satellite): a lease that races the drain flag used to
    be failed INFEASIBLE by the owner ({"error": "node draining"} with
    no retry classification → _fail_queued_infeasible). It must stay
    pending and complete once capacity exists elsewhere."""
    cluster = drain_cluster
    target = cluster.add_node(num_cpus=1, resources={"pin": 1})
    cluster.wait_for_nodes()

    released = str(tmp_path / "released")

    @ray_tpu.remote(resources={"pin": 0.1})
    def hold(x):
        while not os.path.exists(released):
            time.sleep(0.02)
        return x

    @ray_tpu.remote(resources={"pin": 0.1})
    def quick(x):
        return x * 10

    # One running lease occupies the node; the next requests queue at
    # the target raylet (no other node offers "pin").
    running = hold.remote(0)
    wait_for_condition(lambda: _raylet_state(target)["active_leases"] == 1)
    queued = [quick.remote(i) for i in range(3)]
    wait_for_condition(lambda: _raylet_state(target)["pending_leases"] >= 1)
    # Drain with nowhere to respill: the queued leases get the
    # {"error": "node draining", "draining": True} rejection.
    resp = cluster.drain_node(target, deadline_s=4, reason="manual",
                              wait=False)
    assert resp.get("ok"), resp
    open(released, "w").close()  # the running lease ends within the deadline
    # New capacity arrives while the owner is in its drain-retry loop.
    cluster.add_node(num_cpus=2, resources={"pin": 1})
    cluster.wait_for_nodes()
    assert ray_tpu.get(queued, timeout=60) == [0, 10, 20]
    assert ray_tpu.get(running, timeout=60) == 0


def test_drain_node_failure_propagates(drain_cluster):
    """Satellite: DrainNode must NOT swallow failures — a caller about
    to terminate a VM needs to know the node never evacuated."""
    cluster = drain_cluster
    cw = get_core_worker()
    resp = cw._run(cw.gcs.call(
        "DrainNode", {"node_id": "deadbeef" * 8, "deadline_s": 5},
        timeout=30))
    assert resp.get("ok") is False
    assert "unknown node" in resp.get("error", "")

    # A dead node is reported as such, not silently "drained".
    doomed = cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes()
    cluster.remove_node(doomed)
    wait_for_condition(
        lambda: not (_node_info(doomed.node_id) or {}).get("alive", True),
        timeout=30)
    resp = cw._run(cw.gcs.call(
        "DrainNode", {"node_id": doomed.node_id, "deadline_s": 5},
        timeout=30))
    assert resp.get("ok") is False
    assert "not alive" in resp.get("error", "")

    # Bad reason is rejected up front.
    resp = cw._run(cw.gcs.call(
        "DrainNode", {"node_id": doomed.node_id, "reason": "because"},
        timeout=30))
    assert resp.get("ok") is False and "reason" in resp.get("error", "")


def test_preemption_sigterm_watcher(drain_cluster, monkeypatch):
    """The preemption-notice path: SIGTERM to a raylet self-initiates a
    GCS-coordinated drain with the platform deadline
    (RAY_TPU_PREEMPTION_DEADLINE_S), reaches DRAINED, evacuates the
    node's objects, and exits 0 — the spot-reclaim lifecycle end to
    end, no operator in the loop."""
    cluster = drain_cluster
    # Inherited by the raylet spawned next — the platform's grace window.
    monkeypatch.setenv("RAY_TPU_PREEMPTION_DEADLINE_S", "5")
    target = cluster.add_node(num_cpus=2, resources={"sig": 1})
    cluster.wait_for_nodes()
    cw = get_core_worker()

    @ray_tpu.remote(resources={"sig": 0.1})
    def payload():
        return bytes(bytearray(1 << 18))

    ref = payload.remote()
    ray_tpu.wait([ref], timeout=30)

    target.preempt()  # the platform's SIGTERM notice
    wait_for_condition(
        lambda: (_node_info(target.node_id) or {}).get("state")
        == "DRAINED", timeout=30)
    info = _node_info(target.node_id)
    assert info["drain_reason"] == "preemption"
    assert info["drain_stats"]["evacuated_objects"] >= 1
    # The raylet exits 0 by itself once DRAINED.
    wait_for_condition(lambda: target.proc.poll() is not None, timeout=30)
    assert target.proc.poll() == 0
    cluster.remove_node(target)  # reap the handle
    assert len(ray_tpu.get(ref, timeout=30)) == 1 << 18
    assert cw._num_reconstructions == 0


def test_drained_death_is_a_non_event(drain_cluster):
    """A DRAINED node's removal must not produce ERROR node-death
    events; the node table keeps the DRAINED state and drain stats
    after death (visible in state.list_nodes / the dashboard)."""
    cluster = drain_cluster
    target = cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes()
    resp = cluster.drain_node(target, deadline_s=5, reason="idle")
    assert resp.get("state") == "DRAINED", resp
    cluster.remove_node(target)
    wait_for_condition(
        lambda: not (_node_info(target.node_id) or {}).get("alive", True),
        timeout=30)
    info = _node_info(target.node_id)
    assert info["state"] == "DRAINED"  # not DEAD: the death was planned
    assert info["drain_reason"] == "idle"
    assert "duration_s" in info["drain_stats"]
    # events: the removal is recorded as INFO, never ERROR.
    from ray_tpu.util import events as events_api

    evs = events_api.list_events(cluster._node.session_dir,
                                 min_severity="ERROR")
    assert not [e for e in evs
                if (e.get("fields") or {}).get("node_id")
                == target.node_id], evs
