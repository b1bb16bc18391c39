"""JaxTrainer: the Train entry point.

Parity: reference python/ray/train/data_parallel_trainer.py:59
(DataParallelTrainer.fit → BackendExecutor → WorkerGroup → per-worker
session) and base_trainer.py:608 (fit). The torch backend's
`dist.init_process_group(nccl)` (reference: train/torch/config.py:63)
becomes: (a) a host-plane collective group for multi-process DP, and
(b) on TPU pods, `jax.distributed.initialize` coordinator env wiring so
every worker joins one multi-host SPMD program.

Elastic mode (ScalingConfig.elastic): a gang member's node entering
DRAINING is a resize, not a failure. The trainer subscribes to GCS NODE
state transitions, pauses every worker at its next step boundary,
re-homes the departing ranks' params/opt-state through the device
object plane (the same re-pin machinery the drain pipeline uses —
device_objects.evacuate → DeviceObjectRepin), rebuilds the collective
rendezvous for the smaller world, and resumes at step N+1. Grow-back
re-seeds new members from rank 0 the same way. Fallback ladder:
re-shard → checkpoint restart (counted) → fail.
"""

from __future__ import annotations

import logging
import queue as _queue
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import serialization
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import (ElasticConfig, FailureConfig, RunConfig,
                                  ScalingConfig)
from ray_tpu.train.worker_group import WorkerGroup

logger = logging.getLogger(__name__)


@dataclass
class Result:
    """Parity: ray.air.result.Result."""

    metrics: dict
    checkpoint: Checkpoint | None
    error: str | None
    metrics_history: list = field(default_factory=list)

    @property
    def best_checkpoint(self):
        return self.checkpoint


class JaxTrainer:
    """Runs `train_loop_per_worker` on a gang of workers.

    collective_backend: "cpu" (host-plane allreduce group, the gloo-DDP
    analog) or "xla" (workers form one multi-host jax.distributed world;
    each worker then compiles the SPMD step over the global mesh) or None.
    """

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: dict | None = None,
                 scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None,
                 collective_backend: str | None = "cpu"):
        self._train_loop = train_loop_per_worker
        self._config = train_loop_config or {}
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.collective_backend = collective_backend
        # Run telemetry (also exported through util/metrics gauges):
        # resizes the gang survived, steps lost to them, checkpoint
        # fallbacks (elastic resume failed) and full restarts.
        self.telemetry = {"resizes": 0, "shrinks": 0, "grows": 0,
                          "steps_lost": 0, "elastic_fallbacks": 0,
                          "full_restarts": 0}
        # Rank-0's newest report, readable while fit() runs (chaos
        # harnesses key their step schedules off it).
        self.latest_metrics: dict = {}

    def fit(self) -> Result:
        max_failures = self.run_config.failure_config.max_failures
        elastic = self.scaling_config.elastic
        attempt = 0
        restore_from: Checkpoint | None = None
        # Survives retries AND resizes: Result.metrics_history reflects
        # the whole run, not just the last attempt.
        history: list[dict] = []
        while True:
            try:
                if elastic is not None:
                    return self._fit_elastic(restore_from, history)
                return self._fit_once(restore_from, history)
            except exc.RayTpuError as e:
                attempt += 1
                if attempt > max_failures:
                    raise
                # Checkpoint restart (reference: FailureConfig retries
                # restore from the latest reported checkpoint). In
                # elastic mode this is the COUNTED fallback rung: the
                # happy path resumes via device-plane re-shard and never
                # lands here.
                restore_from = getattr(e, "_last_checkpoint", None) \
                    or restore_from
                self.telemetry["full_restarts"] += 1
                # Never silent: WHY the attempt ended is what the next
                # reader of a counted fallback asks first.
                self.telemetry.setdefault("restart_reasons", []).append(
                    f"{type(e).__name__}: {e}")
                logger.warning("training attempt %d ended (%s: %s); "
                               "restarting from %s", attempt,
                               type(e).__name__, e,
                               restore_from.path if restore_from
                               else "the start")
                if elastic is not None:
                    self.telemetry["elastic_fallbacks"] += 1
                    _note_elastic("fallback")
                time.sleep(1.0)

    # ---------- fixed-gang path (unchanged semantics) ----------

    def _fit_once(self, restore_from: "Checkpoint | None" = None,
                  history: list | None = None) -> Result:
        run_id = uuid.uuid4().hex[:8]
        group = WorkerGroup(self.scaling_config)
        try:
            if self.collective_backend and self.scaling_config.num_workers > 1:
                group_name = f"train:{run_id}"
                group.run_on_all("setup_collective", group_name,
                                 self.collective_backend)
                cfg = dict(self._config)
                cfg["_collective_group"] = group_name
            else:
                cfg = dict(self._config)
            if restore_from is not None:
                cfg["_checkpoint_path"] = restore_from.path
            if self.run_config.storage_path:
                # Dict checkpoints land under durable storage instead of a
                # node-local tempdir — on real node loss the retry gang (on
                # other hosts) must still reach them (shared-fs semantics,
                # same as the reference's storage_path contract).
                cfg["_storage_path"] = self.run_config.storage_path
            blob = serialization.dumps_func(self._train_loop)
            group.run_on_all("run", blob, cfg)
            return self._drive(group, history if history is not None else [])
        finally:
            group.shutdown()

    def _drive(self, group: WorkerGroup, history: list) -> Result:
        """Poll workers, surface rank-0 reports (reference:
        TrainingIterator in data_parallel_trainer.py:429)."""
        last_ckpt: Checkpoint | None = None
        done = [False] * len(group.workers)
        error: str | None = None
        final_metrics: dict = dict(history[-1]) if history else {}
        while not all(done):
            try:
                polls = ray_tpu.get(
                    [w.poll.remote() for w in group.workers], timeout=300)
            except exc.RayTpuError as e:
                # Worker actor died (node loss, OOM kill): the retry loop
                # needs the newest checkpoint seen before the crash.
                e._last_checkpoint = last_ckpt
                raise
            for i, p in enumerate(polls):
                for rep in p["reports"]:
                    if rep["rank"] == 0:
                        history.append(rep["metrics"])
                        final_metrics = rep["metrics"]
                        self.latest_metrics = final_metrics
                        if rep.get("checkpoint_path"):
                            last_ckpt = Checkpoint(rep["checkpoint_path"])
                if p["done"]:
                    done[i] = True
                    if p["error"] and error is None:
                        error = f"worker {i}: {p['error']}"
            if error:
                err = exc.RayTpuError(f"training failed: {error}")
                # Carried to fit()'s retry loop for checkpoint restore.
                err._last_checkpoint = last_ckpt
                raise err
            if not all(done):
                time.sleep(0.05)
        return Result(metrics=final_metrics, checkpoint=last_ckpt,
                      error=None, metrics_history=history)

    # ---------- elastic path ----------

    def _fit_elastic(self, restore_from: "Checkpoint | None",
                     history: list) -> Result:
        from ray_tpu._private.api_internal import get_core_worker

        cw = get_core_worker()
        run_id = uuid.uuid4().hex[:8]
        blob = serialization.dumps_func(self._train_loop)
        node_events: "_queue.Queue" = _queue.Queue()
        listener = node_events.put
        cw.add_node_event_listener(listener)
        group = WorkerGroup(self.scaling_config)
        try:
            self._start_epoch(group, run_id, 0, blob, restore_from)
            return self._drive_elastic(group, node_events, history,
                                       run_id, blob)
        finally:
            cw.remove_node_event_listener(listener)
            group.shutdown()

    def _start_epoch(self, group: WorkerGroup, run_id: str, epoch: int,
                     blob: bytes, restore_from: "Checkpoint | None",
                     workers=None) -> None:
        """(Re-)launch the user loop on `workers` (default: the whole
        gang) for one membership epoch."""
        from ray_tpu._private.api_internal import get_core_worker

        cw = get_core_worker()
        cfg = dict(self._config)
        cfg["_elastic"] = True
        cfg["_elastic_epoch"] = epoch
        if cw.address is not None:
            # Makes the trainer the device-plane ref owner of every
            # keep_state pin: a node drain then evacuates the pins HERE
            # (DeviceObjectRepin), off the dying node.
            cfg["_elastic_owner"] = cw.address.to_wire()
        if self.collective_backend and len(group.workers) > 1:
            group_name = f"train:{run_id}:{epoch}"
            group.run_on_all("setup_collective", group_name,
                             self.collective_backend)
            cfg["_collective_group"] = group_name
        if restore_from is not None and epoch == 0:
            cfg["_checkpoint_path"] = restore_from.path
        if self.run_config.storage_path:
            cfg["_storage_path"] = self.run_config.storage_path
        targets = group.workers if workers is None else workers
        ray_tpu.get([w.run.remote(blob, cfg) for w in targets], timeout=300)

    def _drive_elastic(self, group: WorkerGroup,
                       node_events: "_queue.Queue",
                       history: list, run_id: str, blob: bytes) -> Result:
        elastic: ElasticConfig = self.scaling_config.elastic
        target_size = elastic.max_workers or self.scaling_config.num_workers
        last_ckpt: Checkpoint | None = None
        final_metrics: dict = dict(history[-1]) if history else {}
        epoch = 0
        node_of = dict(zip(group.workers, group.run_on_all("node_id")))
        next_grow_check = time.monotonic() + elastic.grow_poll_s
        grow_hint = False

        def fold(w_polls):
            nonlocal final_metrics, last_ckpt
            for p in w_polls:
                if p is None:
                    continue
                for rep in p.get("reports", []):
                    if rep.get("rank") == 0 and "metrics" in rep:
                        history.append(rep["metrics"])
                        final_metrics = rep["metrics"]
                        self.latest_metrics = final_metrics
                        if rep.get("checkpoint_path"):
                            last_ckpt = Checkpoint(rep["checkpoint_path"])

        while True:
            # 1. Pre-death signals: NODE state transitions from the GCS.
            shrink_nodes: set[str] = set()
            while True:
                try:
                    ev = node_events.get_nowait()
                except _queue.Empty:
                    break
                nid = ev.get("node_id") \
                    or (ev.get("node") or {}).get("node_id")
                if ev.get("event") in ("draining", "dead") \
                        and nid in node_of.values():
                    shrink_nodes.add(nid)
                elif ev.get("event") == "alive":
                    grow_hint = True  # capacity restored: probe now

            # 2. Poll the gang — per worker, because a drained member may
            # be killed (deadline expiry / spot reclaim) between the
            # pre-death signal and our resize. A death WITH a pre-death
            # signal (its node is draining or already recorded dead) is
            # still a resize; a death with no signal at all is the next
            # rung of the ladder.
            polls = []
            for w in list(group.workers):
                try:
                    polls.append(ray_tpu.get(w.poll.remote(), timeout=300))
                except exc.RayTpuError as e:
                    nid = node_of.get(w)
                    if nid and (nid in shrink_nodes
                                or not _node_is_alive(nid)):
                        shrink_nodes.add(nid)
                        polls.append(None)
                        continue
                    e._last_checkpoint = last_ckpt
                    raise
            fold(polls)
            error = next((f"worker {i}: {p['error']}"
                          for i, p in enumerate(polls)
                          if p and p["done"] and p["error"]), None)
            if error:
                err = exc.RayTpuError(f"training failed: {error}")
                err._last_checkpoint = last_ckpt
                raise err
            if not shrink_nodes and all(p["done"] for p in polls):
                return Result(metrics=final_metrics, checkpoint=last_ckpt,
                              error=None, metrics_history=history)

            # 3. Shrink: re-shard off the draining members.
            if shrink_nodes:
                survivors = [w for w in group.workers
                             if node_of.get(w) not in shrink_nodes]
                if len(survivors) < elastic.min_workers:
                    err = exc.RayTpuError(
                        f"elastic shrink would leave {len(survivors)} < "
                        f"min_workers={elastic.min_workers} workers")
                    err._last_checkpoint = last_ckpt
                    raise err
                epoch += 1
                self._resize(group, survivors, 0, elastic, run_id, blob,
                             epoch, fold, last_ckpt, node_of,
                             direction="shrink")
                node_of = dict(zip(group.workers,
                                   group.run_on_all("node_id")))
                continue

            # 4. Grow back when capacity returns.
            now = time.monotonic()
            if (grow_hint or now >= next_grow_check) \
                    and len(group.workers) < target_size \
                    and not any(p["done"] for p in polls if p):
                grow_hint = False
                next_grow_check = now + elastic.grow_poll_s
                room = _free_worker_slots(self.scaling_config,
                                          exclude=set(node_of.values()))
                n_new = min(room, target_size - len(group.workers))
                if n_new > 0:
                    epoch += 1
                    self._resize(group, list(group.workers), n_new,
                                 elastic, run_id, blob, epoch, fold,
                                 last_ckpt, node_of, direction="grow")
                    node_of = dict(zip(group.workers,
                                       group.run_on_all("node_id")))
            time.sleep(0.05)

    def _resize(self, group: WorkerGroup, survivors: list, n_new: int,
                elastic: ElasticConfig, run_id: str, blob: bytes,
                epoch: int, fold, last_ckpt, node_of: dict, *,
                direction: str) -> None:
        """One membership change: pause at the step boundary, re-home
        state through the device plane, rebuild the rendezvous, resume.
        Any failure raises RayTpuError carrying the newest checkpoint —
        fit()'s retry loop is the (counted) fallback rung.

        A second reclaim may land while this one is being answered (spot
        capacity goes in batches): a member whose node started to drain,
        or died drained, AFTER the resize began leaves with this resize
        too. Only a death with no pre-death signal gives the path up."""
        from ray_tpu._private import device_objects
        from ray_tpu._private.api_internal import get_core_worker

        cw = get_core_worker()
        deadline = time.monotonic() + elastic.reshard_timeout_s
        survivors = list(survivors)
        departing = [w for w in group.workers if w not in survivors]

        def fallback(why: str):
            err = exc.RayTpuError(f"elastic {direction} failed: {why}")
            err._last_checkpoint = last_ckpt
            return err

        def leaves_too(w) -> None:
            survivors.remove(w)
            departing.append(w)
            if len(survivors) < elastic.min_workers:
                raise fallback(
                    f"would leave {len(survivors)} < min_workers="
                    f"{elastic.min_workers} workers")

        # a. Pause everyone at the next step boundary.
        for w in group.workers:
            w.request_pause.remote()
        lost_alive: set = set()
        max_step = -1
        parked_at: dict = {}        # a parked member -> its kept step
        park_detail: list = []
        while True:
            parked = True
            parked_at = {}
            park_detail = []
            for i, w in enumerate(group.workers):
                if w in lost_alive:
                    continue
                try:
                    p = ray_tpu.get(w.poll.remote(), timeout=30)
                except exc.RayTpuError:
                    # Died mid-pause. A departing member may already have
                    # been killed by an expired drain deadline; survivors
                    # dying here means the elastic path is off the table.
                    if w in departing:
                        lost_alive.add(w)
                        continue
                    if _node_is_leaving(node_of.get(w)):
                        # (its node was draining or is recorded dead: a
                        # reclaim that came during this resize)
                        leaves_too(w)
                        lost_alive.add(w)
                        continue
                    raise fallback("survivor died during pause")
                fold([p])
                max_step = max(max_step, p.get("state_step", -1))
                park_detail.append({"i": i, "departing": w in departing,
                                    "paused": p.get("paused"),
                                    "done": p.get("done"),
                                    "state_step": p.get("state_step")})
                if not (p.get("paused") or p.get("done")):
                    parked = False
                elif p.get("state_step", -1) >= 0:
                    # (state_step < 0 = still warming up, never reached
                    # keep_state: zero steps computed, zero lost)
                    parked_at[w] = p["state_step"]
            if parked:
                break
            if time.monotonic() > deadline:
                raise fallback("gang did not reach a step boundary "
                               f"within {elastic.reshard_timeout_s:g}s")
            time.sleep(0.02)
        # The gang is parked. A survivor whose node has begun to drain
        # since leaves now, while it can still hand its state over, and
        # not when the drain's deadline kills it in the middle of e.-g.
        for w in list(survivors):
            if _node_is_leaving(node_of.get(w)):
                leaves_too(w)

        # b. Re-home departing state: resolve each departing rank's kept
        # tree through the device plane — pulled from the worker while
        # it lives, or found re-pinned in OUR registry if the drain
        # pipeline already evacuated it (same keys either way).
        peer_states: dict[int, Any] = {}
        for w in departing:
            if w in lost_alive:
                continue
            old_rank = group.workers.index(w)
            try:
                exp = ray_tpu.get(w.export_state.remote(),
                                  timeout=max(5.0, deadline - time.monotonic()))
            except exc.RayTpuError:
                lost_alive.add(w)
                continue
            if exp.get("stub") is None:
                continue
            try:
                peer_states[old_rank] = device_objects.resolve_value(
                    exp["stub"], cw)
            except Exception as e:
                raise fallback(f"could not re-shard rank {old_rank} "
                               f"state: {e}") from e
        if lost_alive and not peer_states and direction == "shrink":
            # The departing members died before handing anything over
            # and nothing was evacuated: survivors resume from their own
            # kept state; DP-style loops tolerate a lost shard. Counted
            # via steps_lost below.
            pass

        # c. Retire departing members NOW — frees their leases so the
        # draining raylet's bounded lease wait ends promptly.
        for w in departing:
            group.remove_worker(w, stop_timeout_s=1.0)

        # d. Grow: schedule the new members (DRAINING nodes are already
        # excluded from placement).
        new_world = len(survivors) + n_new
        new_workers = [group.add_worker(len(survivors) + j, new_world)
                       for j in range(n_new)]

        # e. New gang shape: ranks follow list order.
        ray_tpu.get([w.reconfigure.remote(i, new_world)
                     for i, w in enumerate(group.workers)], timeout=60)

        # f. Hand the re-homed state over. Shrink: every survivor gets
        # the departed ranks' trees through ONE device object. Grow: new
        # members get rank 0's stub tree and pull the arrays straight
        # from rank 0's process (no extra driver hop).
        try:
            if peer_states:
                ref = device_objects.device_put(peer_states)
                try:
                    ray_tpu.get([w.receive_peer_states.remote(ref)
                                 for w in survivors], timeout=120)
                finally:
                    del ref
            if new_workers:
                seed = ray_tpu.get(survivors[0].export_state.remote(),
                                   timeout=30)
                if seed.get("stub") is not None:
                    ray_tpu.get([w.receive_peer_states.remote(
                        {0: seed["stub"]}) for w in new_workers],
                        timeout=120)
        except exc.RayTpuError as e:
            raise fallback(f"state hand-off failed: {e}") from e

        # g. Rebuild the rendezvous + resume at step N+1.
        self._start_epoch(group, run_id, epoch, blob, None)

        # (of those who STAY: a member that left after parking is not one)
        survivor_steps = [parked_at[w] for w in survivors if w in parked_at]
        resumed_from = min(survivor_steps) if survivor_steps else -1
        lost = max(0, max_step - resumed_from) \
            if (max_step >= 0 and survivor_steps) else 0
        self.telemetry.setdefault("resize_log", []).append(
            {"direction": direction, "lost": lost, "max_step": max_step,
             "resumed_from": resumed_from,
             "survivor_steps": list(survivor_steps),
             "park_detail": park_detail})
        self.telemetry["resizes"] += 1
        self.telemetry[direction + "s"] += 1
        self.telemetry["steps_lost"] += lost
        _note_elastic(direction, steps_lost=lost)


def _node_row(node_id: "str | None") -> "dict | None":
    """The node table's row, or None: no such node, or no table."""
    try:
        return next((node for node in ray_tpu.nodes()
                     if node.get("node_id") == node_id), None)
    except Exception:
        return None


def _row_is_up(row: dict) -> bool:
    return bool(row.get("alive")) and row.get("state") in (None, "ALIVE")


def _node_is_alive(node_id: str) -> bool:
    row = _node_row(node_id)
    return row is not None and _row_is_up(row)


def _node_is_leaving(node_id: "str | None") -> bool:
    """True where the node table SAYS so: draining, or dead. (Not the
    negation of `_node_is_alive`: a table that could not be read is no
    pre-death signal.)"""
    row = _node_row(node_id)
    return row is not None and not _row_is_up(row)


def _free_worker_slots(scaling: ScalingConfig, exclude: set) -> int:
    """How many more workers the cluster could place right now, from
    the GCS node table's available resources (ALIVE, not draining, and
    not already hosting this gang's members when PACK-per-node
    semantics apply — excluded node_ids are simply skipped)."""
    need = scaling.worker_resources()
    slots = 0
    try:
        nodes = ray_tpu.nodes()
    except Exception:
        return 0
    for node in nodes:
        if not node.get("alive", False):
            continue
        if node.get("state") not in (None, "ALIVE"):
            continue
        if node.get("node_id") in exclude:
            continue
        avail = node.get("available_resources") or {}
        per_node = None
        for res, amount in need.items():
            if amount <= 0:
                continue
            fit = int(avail.get(res, 0.0) // amount)
            per_node = fit if per_node is None else min(per_node, fit)
        slots += per_node if per_node is not None else 0
    return slots


def _note_elastic(event: str, steps_lost: int = 0) -> None:
    try:
        from ray_tpu.util import metrics

        metrics.note_train_elastic(event, steps_lost=steps_lost)
    except Exception:
        pass
