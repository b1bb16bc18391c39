"""Elastic gang training (trainer.py elastic path): a gang member's
node entering DRAINING is a resize, not a failure. The trainer pauses
the gang at a step boundary, re-homes the departing ranks' state through
the device object plane (no checkpoint write/read), rebuilds the
rendezvous for the smaller world, and resumes at step N+1; grow-back
re-seeds new members from rank 0. Fallback ladder: re-shard →
checkpoint restart (counted) → fail.

Smoke-marked tier-1 gates. Gang workers are pinned to dedicated
non-head nodes via a custom `trainer` resource — the driver (the
device-plane ref owner of every keep_state pin) must not share a node
with a drain victim, or the drain pipeline would skip evacuating its
pins (evacuating to the same dying node is pointless).
"""

import threading

import pytest

import ray_tpu
from ray_tpu._private.config import Config
from ray_tpu.cluster_utils import Cluster
from ray_tpu.test_utils import NodePreempter, wait_for_condition
from ray_tpu.train import (ElasticConfig, FailureConfig, JaxTrainer,
                           RunConfig, ScalingConfig)
from ray_tpu.util import metrics as util_metrics

pytestmark = pytest.mark.smoke


def _elastic_config() -> Config:
    cfg = Config()
    cfg.health_check_period_s = 0.2
    cfg.num_heartbeats_timeout = 5
    cfg.worker_lease_timeout_s = 10.0
    cfg.object_store_memory = 64 * 1024 * 1024
    cfg.num_workers_soft_limit = 16
    return cfg


@pytest.fixture
def elastic_cluster():
    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 2},
                      config=_elastic_config())
    yield cluster
    cluster.shutdown()


def _gang_node(cluster):
    return cluster.add_node(num_cpus=2, resources={"trainer": 1})


def _scaling(n, *, min_workers, max_workers=None, reshard_timeout_s=20.0,
             grow_poll_s=0.5):
    return ScalingConfig(
        num_workers=n,
        resources_per_worker={"trainer": 1.0, "CPU": 0.5},
        elastic=ElasticConfig(min_workers=min_workers,
                              max_workers=max_workers,
                              reshard_timeout_s=reshard_timeout_s,
                              grow_poll_s=grow_poll_s))


FINISH, LAST_STEP = "finish", "last_step"


def _elastic_loop(cfg):
    """Counts steps in a jax array preserved via session.keep_state, and
    trains UNTIL IT IS TOLD to finish (`_finish`): how long the gang
    trains is the test's to say, after it has seen what it came to see,
    and not the clock's. (The loop used to stop at a step count, and a
    step WAS (time - t0) / period: on a loaded host 200 steps of 0.05 s
    were over before a replacement node had registered, and nothing was
    left to grow; and members that fell behind the clock ran their steps
    back to back, each at its own pace, fifty steps apart.)

    The gang is in lockstep as an SPMD gang is, by meeting and not by the
    clock: a member starts step k when every member of its epoch has
    finished step k - 1 (each leaves its last finished step in a file of
    `cfg["signal_dir"]`), so at any moment the members are within a step
    of each other whatever the host is busy with, and
    max_step − min(survivor_step) — the steps-lost metric — is an honest
    ≈1 per resize. A pause or a stop ends the wait for the others as it
    ends a step (a collective the trainer aborts). `period` only paces.

    Told to finish, rank 0 names the last step, a few past its own: nobody
    is further ahead of it than one.

    The invariant w[0] == kept_step + 1 proves the re-sharded array
    really round-tripped through the device plane with its contents
    intact (state_ok). Rank 0 also reports dict checkpoints so the
    fallback rung WOULD be available — the happy-path assertions check
    it is never taken (restored stays False)."""
    import os
    import time as _t

    import jax.numpy as jnp

    from ray_tpu.train import session

    rank, world = session.get_world_rank(), session.get_world_size()
    epoch = session.get_elastic_epoch()

    def at(member):     # where a member of this epoch leaves its last step
        return os.path.join(cfg["signal_dir"], f"at-{epoch}-{member}")

    def read(path):
        try:
            with open(path) as f:
                return int(f.read())
        except (OSError, ValueError):
            return None

    def write(path, value):
        with open(f"{path}.{rank}.new", "w") as f:
            f.write(str(value))
        os.replace(f"{path}.{rank}.new", path)

    def gang_finished(step):
        done = [read(at(r)) for r in range(world)]
        return all(d is not None and d >= step for d in done)

    restored = session.get_checkpoint() is not None
    state = session.get_elastic_state()
    peers = session.get_peer_states()
    seeded = False
    if state is None and peers:
        # Freshly grown member: adopt a survivor's tree.
        state = next(iter(peers.values()))
        seeded = True
    state_ok = True
    if state is None:
        step = 0
        w = jnp.zeros((8,), jnp.float32)
    else:
        step = int(state["step"]) + 1
        w = state["w"]
        state_ok = abs(float(w[0]) - (int(state["step"]) + 1)) < 1e-6
    write(at(rank), step - 1)
    last_path = os.path.join(cfg["signal_dir"], LAST_STEP)
    finish = os.path.join(cfg["signal_dir"], FINISH)
    while (last := read(last_path)) is None or step <= last:
        while not gang_finished(step - 1):
            session.check_boundary()
            _t.sleep(0.005)
        if rank == 0 and last is None and os.path.exists(finish):
            write(last_path, step + 5)
        w = w + 1.0
        ckpt = ({"step": step} if rank == 0 and step % 10 == 0 else None)
        session.report({"step": step, "restored": restored, "world": world,
                        "epoch": epoch, "peers": len(peers),
                        "seeded": seeded, "state_ok": bool(state_ok)},
                       checkpoint=ckpt)
        session.keep_state({"step": step, "w": w}, step=step)
        write(at(rank), step)
        _t.sleep(cfg["period"])
        step += 1
    return float(w[0])


def _finish(th, holder, cfg, timeout) -> int:
    """Tells the gang to finish and waits for `fit()` -> the last step
    the gang agreed on."""
    import os

    open(os.path.join(cfg["signal_dir"], FINISH), "w").close()
    th.join(timeout=timeout)
    assert not th.is_alive(), "fit() did not finish"
    assert "error" not in holder, f"fit raised: {holder.get('error')}"
    with open(os.path.join(cfg["signal_dir"], LAST_STEP)) as f:
        return int(f.read())


def _fit_in_thread(trainer):
    holder = {}

    def run():
        try:
            holder["result"] = trainer.fit()
        except BaseException as e:  # noqa: BLE001
            holder["error"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th, holder


def test_elastic_shrink_then_grow_back(elastic_cluster, tmp_path):
    """The acceptance scenario: 4-worker gang, one node drained
    mid-run → training resumes on 3 workers at the next step with ZERO
    checkpoint restores; when a replacement node registers, the gang
    grows back to 4 re-seeded from rank 0."""
    cluster = elastic_cluster
    nodes = [_gang_node(cluster) for _ in range(4)]
    cluster.wait_for_nodes()
    gauges_before = util_metrics.train_elastic_snapshot()

    loop = {"period": 0.05, "signal_dir": str(tmp_path)}
    trainer = JaxTrainer(
        _elastic_loop, train_loop_config=loop,
        scaling_config=_scaling(4, min_workers=2, max_workers=4),
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)),
        collective_backend=None)
    th, holder = _fit_in_thread(trainer)

    # Let the gang take a few steps (keep_state pins exist everywhere).
    wait_for_condition(
        lambda: trainer.latest_metrics.get("step", -1) >= 5, timeout=60)

    # Preempt one gang node: drain → DRAINED → kill.
    preempter = NodePreempter(cluster, deadline_s=10)
    drain = preempter.preempt(nodes[1], kill=False)
    assert drain["state"] == "DRAINED"
    wait_for_condition(lambda: trainer.telemetry["shrinks"] >= 1, timeout=30)
    cluster.remove_node(nodes[1])

    # Capacity returns: the trainer must grow back on its own, while the
    # gang still trains.
    _gang_node(cluster)
    wait_for_condition(lambda: trainer.telemetry["grows"] >= 1, timeout=60)

    last = _finish(th, holder, loop, timeout=120)
    result = holder["result"]

    hist = result.metrics_history
    assert result.metrics["step"] == last
    # Membership went 4 → 3 → 4, and the run ended on the regrown gang.
    worlds = [h["world"] for h in hist]
    assert 3 in worlds and 4 in worlds
    assert hist[-1]["world"] == 4
    # Re-sharded state arrived intact at every resume.
    assert all(h["state_ok"] for h in hist)
    # After the shrink the survivors hold the departed rank's tree.
    assert any(h["peers"] >= 1 for h in hist if h["world"] == 3)
    # The grown member really was seeded through the device plane.
    assert any(h.get("seeded") for h in hist) or hist[-1]["world"] == 4
    # Zero checkpoint restores, zero full restarts: elastic resume only.
    assert not any(h["restored"] for h in hist)
    t = trainer.telemetry
    assert t["shrinks"] >= 1 and t["grows"] >= 1
    assert t["elastic_fallbacks"] == 0 and t["full_restarts"] == 0, \
        t.get("restart_reasons")
    # Steps-lost-per-resize ≤ 2 (target ≈ 1): pause lands at the NEXT
    # step boundary, so survivors resume within a step of the leader.
    assert t["steps_lost"] <= 2 * t["resizes"], str(t["resize_log"])
    # History is continuous across the resizes (no step goes backward by
    # more than the replayed boundary step).
    steps = [h["step"] for h in hist]
    assert steps[-1] == last
    assert all(b - a >= -2 for a, b in zip(steps, steps[1:]))
    # The resize/steps-lost counters reached the util.metrics gauges
    # (and through them /metrics + `ray_tpu status`).
    after = util_metrics.train_elastic_snapshot()
    assert after["resizes_total"] - gauges_before["resizes_total"] >= 2
    assert after["shrink"] - gauges_before["shrink"] >= 1
    assert after["grow"] - gauges_before["grow"] >= 1
    assert after["fallbacks_total"] == gauges_before["fallbacks_total"]
    delta_lost = after["steps_lost_total"] - gauges_before["steps_lost_total"]
    assert 0 <= delta_lost <= 2 * (after["resizes_total"]
                                   - gauges_before["resizes_total"])


def _deadline_loop(cfg):
    """Every worker blocks mid-step 3 (no report / keep_state boundary)
    after leaving a `blocked-<rank>` file, so a resize can never park the
    gang inside reshard_timeout_s — the deadline-expiry rung — and no
    worker runs on to the last step, which would leave the retry nothing
    to restore into. The block outlasts any timeout of the test: the
    fallback kills these workers. Only on a fresh, never-restored run:
    the checkpoint retry completes normally."""
    import os
    import time as _t

    from ray_tpu.train import session

    ck = session.get_checkpoint()
    start = int(ck.to_dict()["step"]) + 1 if ck is not None else 0
    for step in range(start, cfg["total_steps"]):
        ckpt = {"step": step} if session.get_world_rank() == 0 else None
        session.report({"step": step, "restored": ck is not None},
                       checkpoint=ckpt)
        if step == 3 and ck is None and session.get_elastic_epoch() == 0:
            open(os.path.join(cfg["signal_dir"],
                              f"blocked-{session.get_world_rank()}"),
                 "w").close()
            _t.sleep(300.0)
        _t.sleep(0.1)


def test_elastic_deadline_falls_back_to_checkpoint(elastic_cluster,
                                                   tmp_path):
    """When the gang cannot reach a step boundary within
    reshard_timeout_s, the elastic path gives up and the retry restores
    from the last checkpoint — COUNTED (elastic_fallbacks /
    ray_tpu_train_elastic_fallbacks_total), never silent."""
    cluster = elastic_cluster
    nodes = [_gang_node(cluster) for _ in range(3)]
    cluster.wait_for_nodes()
    before = util_metrics.train_elastic_snapshot()

    trainer = JaxTrainer(
        _deadline_loop,
        train_loop_config={"total_steps": 10, "signal_dir": str(tmp_path)},
        scaling_config=_scaling(3, min_workers=2, reshard_timeout_s=1.5),
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)),
        collective_backend=None)
    th, holder = _fit_in_thread(trainer)
    # The whole gang says it is inside its block, and rank 0's step-3
    # checkpoint has reached the trainer: the fallback has one to restore.
    wait_for_condition(
        lambda: all((tmp_path / f"blocked-{r}").exists() for r in range(3))
        and trainer.latest_metrics.get("step", -1) == 3, timeout=60)

    NodePreempter(cluster, deadline_s=6).preempt(nodes[0])
    _gang_node(cluster)  # capacity for the checkpoint-restart gang

    th.join(timeout=120)
    assert not th.is_alive(), "fit() did not finish"
    assert "error" not in holder, f"fit raised: {holder.get('error')}"
    result = holder["result"]

    assert result.metrics["step"] == 9
    # The retry really did restore from the checkpoint...
    assert result.metrics["restored"] is True
    # ...and the fallback was counted at every surface.
    assert trainer.telemetry["elastic_fallbacks"] == 1
    assert trainer.telemetry["full_restarts"] == 1
    after = util_metrics.train_elastic_snapshot()
    assert after["fallbacks_total"] - before["fallbacks_total"] >= 1


def test_chaos_spot_preemption_rate(elastic_cluster, tmp_path):
    """The ISSUE acceptance run: NodePreempter on a seeded stochastic
    STEP schedule (one preemption per ~20 steps, ±30% jitter) against an
    elastic 4-gang with respawn. The run completes with steps-lost ≤ 2
    per resize, zero full-job restarts, zero checkpoint restores."""
    cluster = elastic_cluster
    for _ in range(4):
        _gang_node(cluster)
    cluster.wait_for_nodes()

    loop = {"period": 0.06, "signal_dir": str(tmp_path)}
    trainer = JaxTrainer(
        _elastic_loop, train_loop_config=loop,
        scaling_config=_scaling(4, min_workers=2, max_workers=4,
                                grow_poll_s=0.5),
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)),
        collective_backend=None)
    th, holder = _fit_in_thread(trainer)

    preempter = NodePreempter(
        cluster, deadline_s=8, reason="spot-preemption",
        step_interval=20, step_jitter=0.3, seed=7,
        respawn=True, max_preemptions=2,
        node_args={"num_cpus": 2, "resources": {"trainer": 1}},
        step_source=lambda: int(trainer.latest_metrics.get("step", -1)))
    with preempter:
        # The gang trains until the schedule is spent (both preemptions,
        # each with its respawn: leaving `with` waits that out), a shrink
        # is behind it and the gang has grown back onto a respawned node;
        # or until the elastic path gave up, which the assertions name.
        gave_up = ("elastic_fallbacks", "full_restarts")
        wait_for_condition(
            lambda: "error" in holder
            or any(trainer.telemetry[k] for k in gave_up)
            or (preempter.preemptions >= 2
                and all(trainer.telemetry[k] for k in ("shrinks", "grows"))),
            timeout=180)
    last = _finish(th, holder, loop, timeout=60)
    result = holder["result"]

    assert preempter.preemptions == 2
    # The schedule is reproducible: fired near the seeded gaps.
    assert preempter.step_schedule
    assert preempter.step_schedule[0] >= 14  # first gap ∈ [14, 26]

    # Zero full-job restarts, zero checkpoint restores.
    t = trainer.telemetry
    assert t["full_restarts"] == 0 and t["elastic_fallbacks"] == 0, \
        t.get("restart_reasons")
    hist = result.metrics_history
    assert result.metrics["step"] == last
    assert all(h["state_ok"] for h in hist)
    assert not any(h["restored"] for h in hist)
    assert t["shrinks"] >= 1 and t["grows"] >= 1
    # steps-lost-per-preemption ≤ 2 (target ≈ 1).
    assert t["steps_lost"] <= 2 * t["resizes"], str(t["resize_log"])


def test_reclaims_during_a_resize_leave_with_it(elastic_cluster, tmp_path):
    """Spot capacity goes in batches: while the trainer answers one
    reclaim, a second node is reclaimed on a deadline too short for its
    member to leave by (dead when the pause polls it) and a third begins
    to drain (draining still when the gang has parked). Both leave WITH
    this resize, the dead one's shard lost, the draining one's handed
    over; nothing falls back. ("survivor died during pause" was a counted
    fallback: the node table had said why the member died.)"""
    cluster = elastic_cluster
    nodes = [_gang_node(cluster) for _ in range(4)]
    cluster.wait_for_nodes()

    loop = {"period": 0.05, "signal_dir": str(tmp_path)}
    trainer = JaxTrainer(
        _elastic_loop, train_loop_config=loop,
        scaling_config=_scaling(4, min_workers=1, max_workers=4),
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)),
        collective_backend=None)
    resize = trainer._resize

    def resize_after_two_more_reclaims(*args, **kwargs):
        trainer._resize = resize
        NodePreempter(cluster, deadline_s=1).preempt(nodes[2])
        cluster.drain_node(nodes[3], deadline_s=60, reason="preemption",
                           wait=False)
        return resize(*args, **kwargs)

    trainer._resize = resize_after_two_more_reclaims
    th, holder = _fit_in_thread(trainer)
    wait_for_condition(
        lambda: trainer.latest_metrics.get("step", -1) >= 5, timeout=60)

    cluster.drain_node(nodes[1], deadline_s=60, reason="preemption",
                       wait=False)
    gave_up = ("elastic_fallbacks", "full_restarts")
    wait_for_condition(
        lambda: "error" in holder
        or any(trainer.telemetry[k] for k in ("shrinks",) + gave_up),
        timeout=90)
    last = _finish(th, holder, loop, timeout=60)
    result = holder["result"]

    t = trainer.telemetry
    assert t["elastic_fallbacks"] == 0 and t["full_restarts"] == 0, \
        t.get("restart_reasons")
    # ONE resize took all three: 4 -> 1.
    assert (t["shrinks"], t["resizes"]) == (1, 1), str(t["resize_log"])
    hist = result.metrics_history
    assert result.metrics["step"] == last and hist[-1]["world"] == 1
    assert all(h["state_ok"] for h in hist)
    assert not any(h["restored"] for h in hist)
    # The survivor holds the trees of the two members that could still
    # hand theirs over (the first reclaim's and the draining one's).
    assert any(h["peers"] == 2 for h in hist if h["world"] == 1)
    # What the gang resumed from is the step of the one who stayed.
    log, = t["resize_log"]
    assert len(log["survivor_steps"]) == 1
    assert t["steps_lost"] <= 2, str(log)


def test_preempter_step_schedule_deterministic():
    """Same seed → same stochastic schedule (satellite: reproducible
    chaos)."""
    p1 = NodePreempter(None, step_interval=20, step_jitter=0.3, seed=3,
                       step_source=lambda: 0)
    p2 = NodePreempter(None, step_interval=20, step_jitter=0.3, seed=3,
                       step_source=lambda: 0)
    gaps1 = [p1._next_gap() for _ in range(8)]
    gaps2 = [p2._next_gap() for _ in range(8)]
    assert gaps1 == gaps2
    assert all(14 <= g <= 26 for g in gaps1)
    # A different seed really is a different schedule.
    p3 = NodePreempter(None, step_interval=20, step_jitter=0.3, seed=4,
                       step_source=lambda: 0)
    assert [p3._next_gap() for _ in range(8)] != gaps1


def test_train_worker_stop_joins_user_loop(ray_start_regular):
    """TrainWorker.stop(timeout): graceful session shutdown — the stop
    lands at a step boundary (never mid-report), the user-loop thread is
    JOINED, and the final buffered reports come back with the ack."""
    from ray_tpu._private import serialization
    from ray_tpu.train.worker_group import TrainWorker

    def loop(cfg):
        import time as _t

        from ray_tpu.train import session

        for step in range(100_000):
            session.report({"step": step})
            _t.sleep(0.01)

    w = TrainWorker.remote(0, 1, None)
    ray_tpu.get(w.run.remote(serialization.dumps_func(loop), {}),
                timeout=30)
    wait_for_condition(
        lambda: ray_tpu.get(w.poll.remote(), timeout=10)["reports"],
        timeout=30)
    out = ray_tpu.get(w.stop.remote(5.0), timeout=30)
    assert out["joined"] is True
    assert out["done"] is True
    assert out["error"] is None  # SessionStopped is shutdown, not failure
    assert out["reports"]  # the boundary report was drained, not lost
    ray_tpu.kill(w)
