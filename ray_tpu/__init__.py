"""ray_tpu: a TPU-native distributed computing framework.

The public API mirrors the reference's `ray` package surface
(reference: python/ray/_private/worker.py — init:1139, get:2475, put:2590,
wait:2653, kill:2819, cancel:2850, @ray.remote overloads :3027+) over a
runtime whose accelerator plane is JAX/XLA on TPU.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Iterable, Sequence

from ray_tpu import exceptions
from ray_tpu._private import api_internal
from ray_tpu._private.api_internal import (ActorClass, ActorHandle,
                                           DeviceObjectRef, ObjectRef,
                                           ObjectRefGenerator)
from ray_tpu._private.common import Address
from ray_tpu._private.config import Config

__version__ = "0.1.0"

_init_lock = threading.RLock()
_runtime_node = None  # RuntimeNode when this process started the cluster
_driver_core_worker = None
_client_ctx = None  # ClientContext when attached via address="client://..."


def init(address: str | None = None, *, resources: dict | None = None,
         labels: dict | None = None, num_cpus: float | None = None,
         object_store_memory: int | None = None, namespace: str | None = None,
         config: Config | None = None, ignore_reinit_error: bool = False,
         log_to_driver: bool | None = None, runtime_env: dict | None = None,
         _head_raylet: tuple[str, int] | None = None,
         _store_path: str | None = None, _node_id: str | None = None):
    """Start (or connect to) a cluster and attach this process as a driver.

    address=None starts a local head (GCS + raylet) like the reference's
    `ray.init()`; address="host:port" connects to an existing GCS
    (the reference's ray.init(address=...)); address="client://host:port"
    attaches as a remote client through a proxy (the reference's `ray://`).
    """
    global _runtime_node, _driver_core_worker, _client_ctx
    from ray_tpu._private.node import RuntimeNode
    from ray_tpu._private.worker import CoreWorker

    if address is not None and address.startswith("client://"):
        from ray_tpu.util.client.worker import ClientContext

        unsupported = {
            "resources": resources, "labels": labels, "num_cpus": num_cpus,
            "object_store_memory": object_store_memory,
            "namespace": namespace, "runtime_env": runtime_env,
        }
        bad = [k for k, v in unsupported.items() if v is not None]
        if bad:
            raise ValueError(
                f"init(address='client://...') does not support {bad}; these "
                "are driver/cluster options — set them on the server side")
        with _init_lock:
            if _client_ctx is not None or _driver_core_worker is not None:
                if ignore_reinit_error:
                    return
                raise exceptions.RayTpuError("ray_tpu.init() called twice")
            target = address[len("client://"):]
            host, sep, port_s = target.rpartition(":")
            if not sep or not port_s.isdigit():
                raise ValueError(
                    f"client address must be client://host:port, got {address!r}")
            _client_ctx = ClientContext(host, int(port_s))
            return

    with _init_lock:
        if _driver_core_worker is not None or _client_ctx is not None:
            if ignore_reinit_error:
                return
            raise exceptions.RayTpuError("ray_tpu.init() called twice")
        cfg = config or Config()
        if object_store_memory:
            cfg.object_store_memory = int(object_store_memory)
        if log_to_driver is not None:  # explicit kwarg wins over Config
            cfg.log_to_driver = bool(log_to_driver)
        if address is None:
            node = RuntimeNode(cfg)
            gcs_host, gcs_port = node.start_gcs()
            from ray_tpu._private import accelerator

            head_res = dict(resources or {})
            if num_cpus is not None:
                head_res.setdefault("CPU", num_cpus)
            # The local head offers the chips this host has (what
            # `ray_tpu start` does for a node): without it a task that
            # asks for TPU pends for ever on the machine that has one.
            detected, slice_labels = accelerator.node_resources_and_labels()
            if detected:
                head_res = {"CPU": float(os.cpu_count() or 1), **detected,
                            **head_res}
                labels = {**slice_labels, **(labels or {})}
            handle = node.start_raylet(resources=head_res or None, labels=labels,
                                       is_head=True)
            _runtime_node = node
            raylet_host, raylet_port = handle.host, handle.port
            store_path = handle.store_path
            node_id = handle.node_id
        else:
            gcs_host, gcs_port_s = address.rsplit(":", 1)
            gcs_port = int(gcs_port_s)
            if _head_raylet is not None:
                raylet_host, raylet_port = _head_raylet
                store_path = _store_path
                node_id = _node_id
            else:
                # Resolve a raylet to attach to from the GCS node table
                # (reference: ray.init(address=...) bootstraps from the GCS):
                # prefer this host's raylet (shared-memory store is local),
                # else the head node's.
                raylet_host = raylet_port = store_path = node_id = None
                import socket

                local_names = {"127.0.0.1", "localhost", socket.gethostname()}
                try:
                    local_names.add(socket.gethostbyname(socket.gethostname()))
                except OSError:
                    pass
                nodes = _query_nodes(gcs_host, gcs_port, cfg)
                alive = [n for n in nodes if n.get("alive")]
                alive.sort(key=lambda n: (n["host"] not in local_names,
                                          not n.get("is_head")))
                if not alive:
                    raise exceptions.RayTpuError(
                        f"no alive nodes in cluster at {address}")
                chosen = alive[0]
                raylet_host = chosen["host"]
                raylet_port = chosen["raylet_port"]
                store_path = chosen["store_path"]
                node_id = chosen["node_id"]
        cw = CoreWorker(
            gcs_host=gcs_host, gcs_port=gcs_port,
            raylet_host=raylet_host, raylet_port=raylet_port,
            store_path=store_path, node_id=node_id,
            is_driver=True, config=cfg, owns_cluster=address is None)
        _driver_core_worker = cw
        api_internal.set_core_worker(cw)
        if _runtime_node is not None:
            from ray_tpu._private.usage_stats import UsageStatsReporter

            cw._usage_reporter = UsageStatsReporter(_runtime_node.session_dir)
            cw._usage_reporter.start()
        if runtime_env is not None:
            from ray_tpu.runtime_env import set_job_runtime_env

            set_job_runtime_env(runtime_env)


def _query_nodes(gcs_host: str, gcs_port: int, cfg: Config) -> list[dict]:
    """One-shot GCS query usable before a CoreWorker exists."""
    import asyncio

    from ray_tpu._private import rpc

    async def go():
        conn = await rpc.dial(
            gcs_host, gcs_port, name="init-bootstrap",
            timeout=cfg.rpc_connect_timeout_s)
        try:
            resp = await conn.call("GetAllNodes", {},
                                   timeout=cfg.rpc_call_timeout_s)
            return resp["nodes"]
        finally:
            await conn.close()

    # A dedicated thread, not asyncio.run(): init() may be called from
    # inside a running event loop (notebook cell, async web handler).
    import concurrent.futures

    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(asyncio.run, go()).result()


def is_initialized() -> bool:
    return (api_internal.core_worker_or_none() is not None
            or _client_ctx is not None)


def shutdown():
    global _runtime_node, _driver_core_worker, _client_ctx
    with _init_lock:
        if _client_ctx is not None:
            _client_ctx.close()
            _client_ctx = None
            return
        cw = api_internal.core_worker_or_none()
        if cw is not None:
            cw.shutdown()
        api_internal.set_core_worker(None)
        _driver_core_worker = None
        from ray_tpu.runtime_env import set_job_runtime_env

        set_job_runtime_env(None)
        if _runtime_node is not None:
            _runtime_node.shutdown()
            _runtime_node = None


def _client_mode():
    """The active ClientContext, or None when a local CoreWorker exists.

    Mirrors the reference's client_mode_hook dispatch
    (reference: python/ray/_private/client_mode_hook.py): a worker-side
    CoreWorker always wins so library code running *on* the cluster is
    unaffected by a client connection in the same process.
    """
    if api_internal.core_worker_or_none() is not None:
        return None
    return _client_ctx


def remote(*args, **kwargs):
    """@ray_tpu.remote decorator for functions and classes."""
    if len(args) == 1 and not kwargs and (callable(args[0]) or isinstance(args[0], type)):
        ctx = _client_mode()
        if ctx is not None:
            return ctx.remote(args[0], {})
        return api_internal.make_remote(args[0], {})
    if args:
        raise TypeError("@ray_tpu.remote takes keyword options only")

    def wrap(obj):
        ctx = _client_mode()
        if ctx is not None:
            return ctx.remote(obj, kwargs)
        return api_internal.make_remote(obj, kwargs)

    return wrap


def put(value: Any) -> ObjectRef:
    ctx = _client_mode()
    if ctx is not None:
        return ctx.put(value)
    cw = api_internal.get_core_worker()
    if isinstance(value, ObjectRef):
        raise TypeError("ray_tpu.put() of an ObjectRef is not allowed")
    oid, owner = cw.put(value)
    return ObjectRef(oid, owner)


def get(refs, timeout: float | None = None):
    ctx = _client_mode()
    if ctx is not None:
        return ctx.get(refs, timeout=timeout)
    cw = api_internal.get_core_worker()
    single = isinstance(refs, ObjectRef)
    if single:
        refs = [refs]
    refs = list(refs)
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"ray_tpu.get() takes ObjectRefs, got {type(r)}")
    values = cw.get([(r.id, r.owner) for r in refs], timeout=timeout)
    return values[0] if single else values


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: float | None = None):
    ctx = _client_mode()
    if ctx is not None:
        return ctx.wait(refs, num_returns=num_returns, timeout=timeout)
    cw = api_internal.get_core_worker()
    refs = list(refs)
    if num_returns > len(refs):
        raise ValueError("num_returns exceeds number of refs")
    if len(set(refs)) != len(refs):
        raise ValueError("wait() got duplicate ObjectRefs")
    ready_idx, not_ready_idx = cw.wait(
        [(r.id, r.owner) for r in refs], num_returns=num_returns, timeout=timeout)
    return [refs[i] for i in ready_idx], [refs[i] for i in not_ready_idx]


def kill(actor, *, no_restart: bool = True):
    ctx = _client_mode()
    if ctx is not None:
        return ctx.kill(actor, no_restart=no_restart)
    cw = api_internal.get_core_worker()
    if not isinstance(actor, ActorHandle):
        raise TypeError("ray_tpu.kill() takes an ActorHandle")
    cw.kill_actor(actor._id_hex, no_restart=no_restart)


def cancel(ref: ObjectRef, *, force: bool = False):
    """Best-effort cancellation of a pending task (running-task interrupt
    lands with the richer cancel path; reference: worker.py:2850)."""
    ctx = _client_mode()
    if ctx is not None:
        return ctx.cancel(ref, force=force)
    cw = api_internal.get_core_worker()
    task_id = ref.id.task_id().hex()

    def _cancel_on_loop():
        # Queue/pending-task state is owned by the IO loop thread.
        pt = cw.pending_tasks.get(task_id)
        if pt is None or pt.pushed_to is not None:
            return
        from ray_tpu._private import serialization

        err = serialization.serialize_exception(
            exceptions.TaskCancelledError(f"task {task_id[:12]} cancelled"))
        for q in cw._queues.values():
            if task_id in q:
                q.remove(task_id)
        cw._complete_task_error(pt, err)

    cw.loop.call_soon_threadsafe(_cancel_on_loop)


def get_actor(name: str, namespace: str | None = None) -> ActorHandle:
    ctx = _client_mode()
    if ctx is not None:
        return ctx.get_actor(name, namespace=namespace)
    cw = api_internal.get_core_worker()
    resp = cw._run(cw.gcs.call("GetNamedActor", {
        "name": name, "namespace": namespace or "default"}))
    if not resp.get("found"):
        raise ValueError(f"named actor {name!r} not found")
    from ray_tpu._private.ids import ActorID

    return ActorHandle(ActorID.from_hex(resp["actor_id"]), name)


def nodes() -> list[dict]:
    ctx = _client_mode()
    if ctx is not None:
        return ctx.nodes()
    cw = api_internal.get_core_worker()
    return cw._run(cw.gcs.call("GetAllNodes", {}))["nodes"]


def cluster_resources() -> dict:
    ctx = _client_mode()
    if ctx is not None:
        return ctx.cluster_resources()
    total: dict[str, float] = {}
    for n in nodes():
        if n["alive"]:
            for k, v in n["total_resources"].items():
                total[k] = total.get(k, 0.0) + v
    return total


def available_resources() -> dict:
    ctx = _client_mode()
    if ctx is not None:
        return ctx.available_resources()
    total: dict[str, float] = {}
    for n in nodes():
        if n["alive"]:
            for k, v in n["available_resources"].items():
                total[k] = total.get(k, 0.0) + v
    return total


class _RuntimeContext:
    def __init__(self, cw):
        self._cw = cw

    @property
    def job_id(self) -> str:
        return self._cw.job_id

    @property
    def node_id(self) -> str:
        return self._cw.node_id

    @property
    def worker_id(self) -> str:
        return self._cw.worker_id

    @property
    def task_id(self) -> str:
        return self._cw._current_task_id.hex()

    @property
    def actor_id(self) -> str | None:
        return self._cw._actor_id

    def get_node_id(self) -> str:
        return self._cw.node_id


def get_runtime_context() -> _RuntimeContext:
    return _RuntimeContext(api_internal.get_core_worker())


def method(num_returns: int = 1):
    """@ray_tpu.method decorator for actor methods (parity: ray.method)."""

    def wrap(fn):
        fn._ray_tpu_num_returns = num_returns
        return fn

    return wrap


__all__ = [
    "init", "shutdown", "is_initialized", "remote", "put", "get", "wait",
    "ObjectRefGenerator",
    "kill", "cancel", "get_actor", "nodes", "cluster_resources",
    "available_resources", "get_runtime_context", "method",
    "ObjectRef", "DeviceObjectRef", "ActorHandle", "ActorClass", "Config",
    "exceptions",
]
