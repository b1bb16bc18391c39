"""Per-worker training session: report() / rank info / gradient sync.

Parity: reference python/ray/train/_internal/session.py:132 (_TrainSession;
session.report streams metrics+checkpoints to the trainer) and
train/train_loop_utils.py (prepare_model/prepare_data_loader — here the
TPU-native equivalents are mesh/sharding helpers plus a host-plane gradient
allreduce for multi-process data parallelism).
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Any

_local = threading.local()


class ElasticPauseInterrupt(BaseException):
    """Raised inside the user loop at a step boundary (report() /
    keep_state()) when the trainer requested a pause for an elastic
    resize. A BaseException so user `except Exception` blocks cannot
    swallow it; TrainWorker.run catches it and parks the worker in the
    `paused` state — it is not an error."""


class SessionStopped(BaseException):
    """Raised at the next step boundary after TrainWorker.stop():
    graceful session shutdown, never mid-report()."""


class _SessionControl:
    """Trainer→worker control plane shared between the actor thread
    (request_pause/stop) and the user-loop thread (boundary checks)."""

    def __init__(self):
        self.pause_requested = threading.Event()
        self.stop_requested = threading.Event()


@dataclass
class _Session:
    rank: int
    world_size: int
    report_queue: "queue.Queue"
    collective_group: str | None = None
    # Set when this run restores: a retried trainer attempt (elastic
    # restart) or a Tune trial resuming/exploiting a checkpoint.
    restore_checkpoint_path: str | None = None
    # Durable root for dict checkpoints (RunConfig.storage_path); None =
    # node-local tempdir (single-host semantics).
    storage_path: str | None = None
    # Elastic gang training: pause/stop control, the state tree this
    # worker preserved across the last pause, peer state handed over
    # from departed ranks, and the resize epoch (0 = never resized).
    control: Any = None
    elastic_state: Any = None
    elastic_state_step: int | None = None
    peer_states: dict | None = None
    elastic_epoch: int = 0
    on_keep_state: Any = None


def _check_boundary(s: _Session) -> None:
    """Step-boundary control check: stop wins over pause."""
    c = s.control
    if c is None:
        return
    if c.stop_requested.is_set():
        raise SessionStopped()
    if c.pause_requested.is_set():
        raise ElasticPauseInterrupt()


def check_boundary() -> None:
    """For a loop that WAITS inside a step (for its peers, for data): the
    check `report` makes at a step's boundary, to be made while waiting,
    so that a stop or an elastic pause ends the wait as it ends a step
    (it raises what they raise)."""
    _check_boundary(_get_session())


def _set_session(s: _Session | None) -> None:
    _local.session = s


def _get_session() -> _Session:
    s = getattr(_local, "session", None)
    if s is None:
        raise RuntimeError(
            "No active train session: this API must be called inside "
            "train_loop_per_worker")
    return s


def get_checkpoint():
    """The checkpoint this run should resume from, or None on a fresh
    start (reference: ray.train.get_checkpoint() — set on elastic
    restarts and Tune restore/exploit)."""
    s = _get_session()
    if s.restore_checkpoint_path is None:
        return None
    from ray_tpu.train.checkpoint import Checkpoint

    return Checkpoint(s.restore_checkpoint_path)


def report(metrics: dict, checkpoint=None) -> None:
    """Stream metrics (and optionally a Checkpoint) to the trainer.
    A plain dict is wrapped via Checkpoint.from_dict (reference: air
    Checkpoint dict form)."""
    s = _get_session()
    payload = {"metrics": dict(metrics), "rank": s.rank}
    if checkpoint is not None:
        if isinstance(checkpoint, dict):
            import os
            import uuid

            from ray_tpu.train.checkpoint import Checkpoint

            path = None
            if s.storage_path:
                path = os.path.join(s.storage_path, "checkpoints",
                                    f"ckpt-{uuid.uuid4().hex[:12]}")
            checkpoint = Checkpoint.from_dict(checkpoint, path)
        payload["checkpoint_path"] = checkpoint.path
    s.report_queue.put(payload)
    # report() is THE step boundary: an elastic pause or a graceful stop
    # lands here, after the metrics (and checkpoint pointer) are safely
    # on the queue — never mid-report.
    _check_boundary(s)


def keep_state(state, step: int | None = None) -> None:
    """Preserve `state` (params/opt-state pytree) for elastic resume.

    The worker pins the tree's jax.Array leaves in its device registry
    with the trainer as ref owner, so a node drain evacuates them via
    the device plane (device_objects.evacuate → DeviceObjectRepin) and a
    resize re-shards them to the surviving gang — no checkpoint
    write/read. Survivors get their own tree back via
    get_elastic_state(); departed ranks' trees arrive at the survivors
    through get_peer_states(). Also a step boundary (pause/stop land
    here), so call it once per step, after report()."""
    s = _get_session()
    s.elastic_state = state
    s.elastic_state_step = int(step) if step is not None \
        else (s.elastic_state_step or 0) + 1
    if s.on_keep_state is not None:
        s.on_keep_state(state, s.elastic_state_step)
    _check_boundary(s)


def get_elastic_state():
    """This worker's own preserved state tree (from keep_state) when the
    run is resuming after an elastic pause; None on a fresh start."""
    return _get_session().elastic_state


def get_elastic_state_step() -> int | None:
    """Step recorded with the preserved state, or None."""
    return _get_session().elastic_state_step


def get_peer_states() -> dict:
    """{old_rank: state_tree} handed over from ranks that left (shrink)
    or, on a freshly grown worker, seeded from a survivor. Empty on a
    fresh start and for survivors whose membership didn't change."""
    return dict(_get_session().peer_states or {})


def get_elastic_epoch() -> int:
    """How many elastic resizes this run has been through (0 = none;
    bumps on every shrink/grow the gang survived)."""
    return _get_session().elastic_epoch


class _TrainContext:
    """Reference-shaped context object (ray.train.get_context() —
    python/ray/train/context.py): rank/size accessors bundled."""

    def get_world_rank(self) -> int:
        return get_world_rank()

    def get_world_size(self) -> int:
        return get_world_size()

    def get_local_rank(self) -> int:
        return get_local_rank()

    def get_local_world_size(self) -> int:
        return 1  # one worker per host in this topology

    def get_node_rank(self) -> int:
        return get_world_rank()


def get_context() -> _TrainContext:
    _get_session()  # raise outside a train loop, like the reference
    return _TrainContext()


def get_world_rank() -> int:
    return _get_session().rank


def get_world_size() -> int:
    return _get_session().world_size


def get_local_rank() -> int:
    return _get_session().rank  # one worker per host in this topology


def set_collective_group(name: str) -> None:
    _get_session().collective_group = name


def allreduce_gradients(grads, group_name: str | None = None):
    """Host-plane gradient mean across train workers (the CPU/DP path —
    the reference's gloo DDP equivalent). On a TPU pod, prefer compiling
    dp into the mesh instead; this exists for multi-process CPU training
    and cross-slice DCN averaging."""
    import jax
    import numpy as np

    from ray_tpu.util.collective import allreduce

    s = _get_session()
    group = group_name or s.collective_group
    if group is None or s.world_size == 1:
        return grads
    flat, treedef = jax.tree_util.tree_flatten(grads)
    out = []
    for g in flat:
        arr = np.asarray(g, dtype=np.float32)
        red = allreduce(arr, group_name=group) / s.world_size
        out.append(red.astype(np.asarray(g).dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
