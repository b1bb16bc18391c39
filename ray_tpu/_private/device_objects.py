"""HBM-resident device object plane: pass jax.Arrays between tasks and
actors without a host round-trip.

Re-design target (reference: Ray GPU objects / compiled-graphs
accelerator-native transport; Pathways keeps tensors resident in device
memory and moves them over ICI/DCN): today every device array crossing a
task boundary pays device_get → pickle → shm → TCP → device_put. Here the
producing worker PINS the live jax.Array in a per-process registry keyed
by the return object id, and only a small descriptor (shape / dtype /
sharding / owner / device set) travels the plasma path as the object's
value (serialization.KIND_DEVICE). Resolution picks the cheapest route:

  same process   → hand over the live array (zero copy, identity)
  same-mesh peer → collective send/recv over the util/collective peer
                   plane (ICI/DCN framing: raw buffer + CollectiveDeliver
                   mailbox, no pickle, no object-store round trip)
  otherwise      → transparent host-path fallback (owner gathers to host,
                   consumer device_puts), counted so benchmarks and tests
                   can assert which route ran

Failure semantics: when the pinning worker dies the descriptor reports
the object lost; if the resolving process OWNS the object, the existing
lineage reconstruction in worker.py (_try_reconstruct) re-executes the
creating task, which re-pins fresh arrays. Refcount release of the
owning ObjectRef unpins the HBM bytes (worker._free_object notifies the
pinning worker).

Observability: pinned bytes/objects and per-route transfer counts export
through util/metrics gauges, util/state.list_device_objects(), the
`ray_tpu device-objects` CLI verb and the /api/device_objects dashboard
endpoint.

This module must stay importable without initializing jax (workers pin
their backend lazily per accelerator.py) — jax is only touched through
sys.modules.
"""

from __future__ import annotations

import itertools
import logging
import sys
import threading
import time

import numpy as np

from ray_tpu import exceptions as exc
from ray_tpu._private.common import require_fields

logger = logging.getLogger(__name__)

COLLECTIVE_GROUP = "__device_plane__"

_counter_lock = threading.Lock()
_counters = {
    "total_pinned": 0,       # arrays ever pinned
    "in_process": 0,         # zero-copy same-process handovers
    "collective": 0,         # peer-plane (ICI/DCN) transfers completed
    "collective_out": 0,     # peer-plane transfers served (producer side)
    "host_fallback": 0,      # host-path fallbacks completed
    "host_out": 0,           # host-path pulls served (producer side)
    "lost": 0,               # resolutions that found the pin gone
    "released": 0,           # arrays unpinned by refcount release
    "evacuated_out": 0,      # arrays shipped off a draining node
    "evacuated_in": 0,       # arrays re-pinned here by an evacuation
    "errors_total": 0,       # swallowed-but-logged failures on the
                             # pull/evacuation/repin degraded paths
}
_handoff_seq = itertools.count(1)


def _count(name: str, n: int = 1) -> None:
    with _counter_lock:
        _counters[name] = _counters.get(name, 0) + n
    _update_gauges()  # throttled: O(registry) work at most ~1/s


def counters() -> dict:
    with _counter_lock:
        return dict(_counters)


def _is_jax_array(value) -> bool:
    mod = type(value).__module__
    if not (mod.startswith("jax") or mod.startswith("jaxlib")):
        return False
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(value, jax.Array)


def _local_platform() -> str | None:
    """Backend THIS process's jax already runs on; None when jax isn't
    imported or no backend is up.  Asking must never be what opens the
    chip: a process without a backend shares no mesh with the producer,
    so it takes the host route."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    return jax.default_backend()


def _local_device_ids() -> list[int]:
    jax = sys.modules.get("jax")
    if jax is None:
        return []
    try:
        return sorted(d.id for d in jax.devices())
    except Exception:
        return []


def _to_device(np_value: np.ndarray):
    """One host→HBM DMA on the consumer; plain numpy when jax is absent
    (same restore contract as the host-path pickle restore — shared so
    the two paths cannot diverge)."""
    from ray_tpu._private.serialization import _restore_jax_array

    return _restore_jax_array(np_value)


def _np_dtype(name: str) -> np.dtype:
    """Resolve a wire dtype string. bfloat16/fp8 names are only
    registered with numpy once ml_dtypes loads — a jax-less consumer
    pulling a bf16 tensor must not crash in frombuffer."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # noqa: F401  (registers the extended dtypes)

        return np.dtype(name)


class DeviceObjectMeta:
    """Wire-light descriptor of one pinned array (the only thing that
    travels the object path for a device object)."""

    __slots__ = ("key", "shape", "dtype", "nbytes", "owner_addr",
                 "platform", "device_ids", "sharding")

    def __init__(self, key, shape, dtype, nbytes, owner_addr, platform,
                 device_ids, sharding):
        self.key = key
        self.shape = shape
        self.dtype = dtype
        self.nbytes = nbytes
        self.owner_addr = owner_addr  # Address.to_wire() of pin worker
        self.platform = platform
        self.device_ids = device_ids
        self.sharding = sharding

    def __reduce__(self):
        return (DeviceObjectMeta,
                (self.key, self.shape, self.dtype, self.nbytes,
                 self.owner_addr, self.platform, self.device_ids,
                 self.sharding))

    def to_dict(self) -> dict:
        return {s: getattr(self, s) for s in self.__slots__}


class DeviceObjectStub:
    """Placeholder stored in place of a pinned jax.Array inside a
    KIND_DEVICE payload; get() swaps it for the resolved array."""

    __slots__ = ("meta",)

    def __init__(self, meta: DeviceObjectMeta):
        self.meta = meta

    def __reduce__(self):
        return (DeviceObjectStub, (self.meta,))

    def __repr__(self):
        return (f"DeviceObjectStub({self.meta.key}, shape="
                f"{tuple(self.meta.shape)}, dtype={self.meta.dtype}, "
                f"{self.meta.nbytes}B @ {self.meta.platform})")


class DeviceRegistry:
    """Per-process pin table: key → live jax.Array. Pinning holds the
    array's HBM for as long as the owning object is referenced (the
    plasma analogue of a sealed buffer, except the buffer IS the device
    allocation)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[str, tuple] = {}  # key -> (array, meta, ts)
        # prefix -> Address wire of the process that OWNS the ObjectRef
        # whose payload these pins back. The pin worker needs it exactly
        # once: a drain evacuation re-homes the arrays to the ref owner
        # (evacuate()) — without it the pins die with the node.
        self._ref_owners: dict[str, list | None] = {}

    def note_ref_owner(self, prefix: str, owner_wire) -> None:
        with self._lock:
            self._ref_owners[prefix] = owner_wire

    def ref_owner(self, prefix: str):
        with self._lock:
            return self._ref_owners.get(prefix)

    def pin(self, key: str, array, cw=None) -> DeviceObjectMeta:
        try:
            devices = list(array.devices())
            device_ids = sorted(d.id for d in devices)
            platform = devices[0].platform if devices else "cpu"
        except Exception:
            device_ids, platform = [], "cpu"
        meta = DeviceObjectMeta(
            key=key,
            shape=[int(s) for s in array.shape],
            dtype=str(array.dtype),
            nbytes=int(getattr(array, "nbytes", 0)),
            owner_addr=(cw.address.to_wire()
                        if cw is not None and cw.address else None),
            platform=platform,
            device_ids=device_ids,
            sharding=str(getattr(array, "sharding", "")))
        with self._lock:
            self._entries[key] = (array, meta, time.time())
        _count("total_pinned")
        return meta

    def get(self, key: str):
        with self._lock:
            entry = self._entries.get(key)
        return entry[0] if entry is not None else None

    def get_entry(self, key: str):
        with self._lock:
            return self._entries.get(key)

    def release(self, key: str) -> bool:
        with self._lock:
            gone = self._entries.pop(key, None)
        if gone is not None:
            _count("released")
        return gone is not None

    def release_prefix(self, prefix: str, *, counted: bool = True) -> int:
        """Unpin every leaf of one device object (keys are
        '<prefix>#<leaf-index>'). counted=False for internal unpins
        (drain evacuation moves arrays, it does not release them — the
        'released' gauge must stay a pure refcount-release count)."""
        with self._lock:
            keys = [k for k in self._entries
                    if k == prefix or k.startswith(prefix + "#")]
            for k in keys:
                del self._entries[k]
            self._ref_owners.pop(prefix, None)
        if keys and counted:
            _count("released", len(keys))
        return len(keys)

    def stats(self) -> dict:
        with self._lock:
            n = len(self._entries)
            total = sum(e[1].nbytes for e in self._entries.values())
        return {"pinned_objects": n, "pinned_bytes": total,
                "counters": counters()}

    def entries(self) -> list[dict]:
        with self._lock:
            snap = [(k, e[1], e[2]) for k, e in self._entries.items()]
        return [{"key": k, "shape": m.shape, "dtype": m.dtype,
                 "nbytes": m.nbytes, "platform": m.platform,
                 "device_ids": m.device_ids, "pinned_ts": ts}
                for k, m, ts in snap]


_registry: DeviceRegistry | None = None
_registry_lock = threading.Lock()


def registry() -> DeviceRegistry:
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = DeviceRegistry()
        return _registry


# ---------- metrics ----------

_gauges = None
_gauge_ts = [float("-inf")]
_GAUGE_MIN_INTERVAL_S = 1.0


def _update_gauges(force: bool = False) -> None:
    """Keep the util/metrics gauges current (pinned-HBM bytes/objects,
    per-route transfer counts). Throttled: pin/resolve hot paths tick
    counters per leaf, and rebuilding five gauges plus an O(registry)
    byte sum per tick would make extraction O(N^2) — at most one rebuild
    per second unless a scrape forces it. Never allowed to break the
    data path."""
    global _gauges
    now = time.monotonic()
    if not force and now - _gauge_ts[0] < _GAUGE_MIN_INTERVAL_S:
        return
    _gauge_ts[0] = now
    try:
        from ray_tpu.util.metrics import Gauge

        if _gauges is None:
            _gauges = {
                "bytes": Gauge("ray_tpu_device_objects_pinned_bytes",
                               "bytes pinned in HBM by the device object "
                               "plane"),
                "count": Gauge("ray_tpu_device_objects_pinned",
                               "arrays pinned by the device object plane"),
                "transfers": Gauge("ray_tpu_device_object_transfers",
                                   "device-object resolutions by route",
                                   ("route",)),
                "lost": Gauge("ray_tpu_device_objects_lost",
                              "device objects found lost at resolution"),
                "released": Gauge("ray_tpu_device_objects_released",
                                  "arrays unpinned by refcount release"),
                "evacuated": Gauge("ray_tpu_device_objects_evacuated",
                                   "arrays moved by drain evacuation",
                                   ("direction",)),
            }
        reg = registry()
        with reg._lock:
            n = len(reg._entries)
            total = sum(e[1].nbytes for e in reg._entries.values())
        with _counter_lock:
            snap = dict(_counters)
        g = _gauges
        g["bytes"].set(total)
        g["count"].set(n)
        for route in ("in_process", "collective", "host_fallback"):
            g["transfers"].set(snap.get(route, 0), tags={"route": route})
        g["lost"].set(snap.get("lost", 0))
        g["released"].set(snap.get("released", 0))
        for direction in ("out", "in"):
            g["evacuated"].set(snap.get(f"evacuated_{direction}", 0),
                               tags={"direction": direction})
    except Exception:
        pass


def export_device_object_gauges() -> dict:
    """Refresh the device-plane gauges and return the local stats snap
    (scrape-path hook, like metrics.export_pump_stats)."""
    _update_gauges(force=True)
    return registry().stats()


# ---------- extract / resolve ----------

def tree_map(value, fn, is_leaf):
    """Minimal pytree map over dict/list/tuple/namedtuple containers:
    the ONE traversal shared by extraction, resolution, and consumers
    (a fifth hand-rolled walker is how container-type fixes diverge).

    It recurses through its own module-level name, not through an inner
    function that closes over itself: such a closure is a reference
    cycle, and through `fn` it kept every resolved array (a prefill's
    whole KV) alive until Python's cycle collector happened to run."""
    if is_leaf(value):
        return fn(value)
    if isinstance(value, dict):
        return {k: tree_map(x, fn, is_leaf) for k, x in value.items()}
    if isinstance(value, tuple):
        walked = tuple(tree_map(x, fn, is_leaf) for x in value)
        if type(value) is not tuple and hasattr(value, "_fields"):
            return type(value)(*walked)  # namedtuple
        return walked
    if isinstance(value, list):
        return [tree_map(x, fn, is_leaf) for x in value]
    return value


def extract_arrays(value, prefix: str, cw=None):
    """Pin every jax.Array leaf of `value` under '<prefix>#<i>' and
    replace it with a DeviceObjectStub. Returns (stubbed_value,
    total_bytes, n_leaves); n_leaves == 0 means `value` is returned
    untouched and should take the normal host path."""
    reg = registry()
    state = {"n": 0, "bytes": 0}

    def pin(v):
        key = f"{prefix}#{state['n']}"
        meta = reg.pin(key, v, cw)
        state["n"] += 1
        state["bytes"] += meta.nbytes
        return DeviceObjectStub(meta)

    out = tree_map(value, pin, _is_jax_array)
    if state["n"] == 0:
        return value, 0, 0
    return out, state["bytes"], state["n"]


def choose_route(meta: DeviceObjectMeta) -> str:
    """Transfer-route decision for a non-local stub (the same-process
    case never reaches here — the registry hit wins first):

      collective — producer and consumer share a mesh: same non-cpu
                   platform and overlapping device ids (ICI), or the
                   RAY_TPU_DEVICE_COLLECTIVE=1 override (DCN peers that
                   opted into the peer plane).
      host       — everything else: transparent host-path fallback.
    """
    import os

    if os.environ.get("RAY_TPU_DEVICE_COLLECTIVE") == "1":
        return "collective"
    plat = _local_platform()
    if plat and plat != "cpu" and plat == meta.platform:
        if set(meta.device_ids) & set(_local_device_ids()):
            return "collective"
    return "host"


def _is_stub(v) -> bool:
    return isinstance(v, DeviceObjectStub)


def retarget_stubs(value, owner_addr):
    """Point every stub at a fresh pinning worker. After lineage
    reconstruction the re-executed task pins under the SAME keys (the
    prefix embeds the task id), but a store-resident stub payload is not
    rewritten (_write_to_store skips existing objects) — the owner's
    refreshed dev_info carries the live address; the descriptor bytes
    may still carry the dead one."""

    def fix(stub):
        m = stub.meta
        return DeviceObjectStub(DeviceObjectMeta(
            m.key, m.shape, m.dtype, m.nbytes, owner_addr, m.platform,
            m.device_ids, m.sharding))

    return tree_map(value, fix, _is_stub)


def resolve_value(value, cw):
    """Swap every DeviceObjectStub in a deserialized KIND_DEVICE payload
    for the live array, via the cheapest route. Remote leaves are
    grouped by pinning worker and fetched with ONE batched pull per
    worker — an N-leaf param tree costs one round trip, not N. Raises
    DeviceObjectLostError when a pin is gone (owner handles lineage
    reconstruction; borrowers surface the loss)."""
    reg = registry()
    resolved: dict[str, object] = {}
    remote: dict[tuple, list[DeviceObjectMeta]] = {}

    def scan(stub):
        meta = stub.meta
        if meta.key not in resolved:
            local = reg.get(meta.key)
            if local is not None:
                _count("in_process")
                resolved[meta.key] = local
            else:
                addr_key = tuple(meta.owner_addr) if meta.owner_addr \
                    else None
                group = remote.setdefault(addr_key, [])
                if all(m.key != meta.key for m in group):
                    group.append(meta)
        return stub

    tree_map(value, scan, _is_stub)
    for metas in remote.values():
        resolved.update(_pull_batch(metas, cw))
    return tree_map(value, lambda s: resolved[s.meta.key], _is_stub)


def _pull_batch(metas: list[DeviceObjectMeta], cw) -> dict:
    """Fetch all pinned arrays of ONE pinning worker in a single RPC;
    returns {key: array}."""
    from ray_tpu._private import rpc
    from ray_tpu._private.common import Address

    first = metas[0]
    if cw is None or first.owner_addr is None:
        raise exc.DeviceObjectLostError(
            first.key, f"device object {first.key} has no reachable pin "
                       "owner (produced by a process with no runtime?)")
    addr = Address.from_wire(first.owner_addr)
    if addr.worker_id == cw.worker_id:
        # We ARE the pinning process but the registry missed: the pin was
        # released (or this is a restarted incarnation) — the data is gone.
        raise exc.DeviceObjectLostError(
            first.key, f"device object {first.key} is no longer pinned "
                       "in this process")
    route = choose_route(first)
    plane = None
    if route == "collective":
        # The peer plane's CollectiveDeliver mailbox must exist BEFORE
        # the producer's sends can arrive.
        try:
            from ray_tpu.util.collective.collective import _get_peer_plane

            plane = _get_peer_plane()
        except Exception:
            route = "host"
    keys = [m.key for m in metas]

    async def call():
        conn = await cw._owner_conn(addr)
        return await conn.call(
            "DeviceObjectPull",
            {"keys": keys, "route": route,
             "requester": cw.worker_id,
             "requester_addr": cw.address.to_wire()},
            timeout=cw.config.rpc_call_timeout_s)

    try:
        resp = cw._run(call())
    except (rpc.RpcError, OSError, ConnectionError, TimeoutError) as e:
        raise exc.DeviceObjectLostError(
            first.key, f"pin owner of device objects {keys[:3]} "
                       f"unreachable: {e}") from None
    missing = resp.get("missing") or []
    if missing:
        raise exc.DeviceObjectLostError(
            missing[0], f"device object {missing[0]} is no longer pinned "
                        f"on worker {addr.worker_id[:12]}")
    out = {}
    if resp.get("status") == "collective":
        for tag in resp["tags"]:
            try:
                np_value = plane.recv(COLLECTIVE_GROUP, tag, timeout=60.0)
            except TimeoutError as e:
                # The producer's notify only confirms a socket write; a
                # dropped connection after the reply loses the payload.
                # This IS an object loss — surface it through the
                # lineage-recovery contract, not a bare TimeoutError.
                raise exc.DeviceObjectLostError(
                    tag, f"collective transfer of device object {tag} "
                         f"never arrived: {e}") from None
            _count("collective")
            out[tag] = _to_device(np_value)
        return out
    if plane is not None:
        # A collective attempt that degraded mid-batch already delivered
        # some payloads into our mailbox: drop them (the host reply is
        # authoritative) or they strand for the process lifetime.
        for tag in resp.get("stray_tags") or []:
            plane.discard(COLLECTIVE_GROUP, tag)
    # host fallback: the reply carries the gathered bytes per key.
    for item in resp["items"]:
        np_value = np.frombuffer(
            bytearray(item["data"]),
            dtype=_np_dtype(item["dtype"])).reshape(item["shape"])
        _count("host_fallback")
        out[item["key"]] = _to_device(np_value)
    return out


# ---------- producer-side RPC handlers (worker.py delegates here) ----------

async def handle_pull(cw, payload: dict) -> dict:
    """Serve a batch of pinned arrays to one consumer. Collective route:
    push each raw buffer through the requester's util/collective
    peer-plane mailbox (direct worker→worker framing, no pickle, no
    object store — the DCN/ICI plane); host route: return the gathered
    bytes inline. Every host gather + copy runs in an executor — a
    multi-hundred-MB KV pull must not stall this worker's whole RPC
    loop (heartbeats, TaskDone) behind an HBM→host DMA."""
    import asyncio

    from ray_tpu._private.common import Address

    keys = payload.get("keys")
    if not keys:
        # Single-object form: the batch field is absent, 'key' is the
        # frame's one required field.
        require_fields(payload, "key", method="handle_pull")
        keys = [payload["key"]]
    reg = registry()
    entries, missing = [], []
    for key in keys:
        entry = reg.get_entry(key)
        if entry is None:
            missing.append(key)
        else:
            entries.append((key, entry[0]))
    if missing:
        return {"status": "gone", "missing": missing}
    loop = asyncio.get_running_loop()

    def gather(array):
        np_value = np.asarray(array)  # the (single) host gather
        return (str(np_value.dtype), list(np_value.shape),
                np_value.tobytes())

    gathered = [(key, await loop.run_in_executor(None, gather, array))
                for key, array in entries]
    delivered: list[str] = []
    if payload.get("route") == "collective" and payload.get("requester_addr"):
        require_fields(payload, "requester_addr", method="handle_pull")
        try:
            conn = await cw._owner_conn(
                Address.from_wire(payload["requester_addr"]))
            for key, (dtype, shape, data) in gathered:
                await conn.notify("CollectiveDeliver", {
                    "group": COLLECTIVE_GROUP, "tag": key,
                    "dtype": dtype, "shape": shape, "data": data})
                delivered.append(key)
            _count("collective_out", len(delivered))
            return {"status": "collective", "tags": delivered}
        except Exception:
            # Fall through to the host reply; tags already delivered
            # are reported so the consumer drains its mailbox (raw
            # tensor buffers must not strand in _PeerPlane._inbox).
            _count("errors_total")
            logger.warning(
                "handle_pull: collective push to %s failed after %d/%d "
                "tags; serving host route", payload["requester_addr"],
                len(delivered), len(gathered), exc_info=True)
    _count("host_out", len(gathered))
    return {"status": "host", "stray_tags": delivered,
            "items": [{"key": key, "dtype": dtype, "shape": shape,
                       "data": data}
                      for key, (dtype, shape, data) in gathered]}


async def handle_release(cw, payload: dict) -> dict:
    require_fields(payload, "prefix", method="handle_release")
    n = registry().release_prefix(payload["prefix"])
    return {"released": n}


async def handle_stats(cw, payload: dict) -> dict:
    _update_gauges(force=True)  # stats fan-out doubles as gauge refresh
    out = registry().stats()
    out["worker_id"] = cw.worker_id
    if payload.get("entries"):
        out["entries"] = registry().entries()
    return out


# ---------- drain-path evacuation ----------

# Callbacks that materialize last-moment pins (e.g. a serving engine
# snapshotting in-flight stream KV) — run to completion INSIDE
# evacuate() before the registry is snapshotted. A DrainNotice listener
# cannot do this: the raylet fires DeviceObjectEvacuate milliseconds
# after the notice, and pins created on a listener thread lose that
# race and silently miss the evacuation.
_evac_preparers: list = []


def add_evacuation_preparer(fn) -> None:
    """Register fn() to run (in an executor thread, awaited) before a
    drain evacuation gathers this process's pins."""
    if fn not in _evac_preparers:
        _evac_preparers.append(fn)


def remove_evacuation_preparer(fn) -> None:
    try:
        _evac_preparers.remove(fn)
    except ValueError:
        pass


async def evacuate(cw) -> dict:
    """Re-home every pinned array whose ObjectRef owner lives off this
    node — called by the raylet's drain pipeline before the node dies.
    Leaves are grouped per device object (prefix) and shipped to the
    ref-owner process, which re-pins them under the SAME keys and
    refreshes its descriptor (DeviceObjectRepin). Route: the peer-plane
    collective mailbox when RAY_TPU_DEVICE_COLLECTIVE=1 (raw buffers,
    no pickle), else the counted host fallback (gather + inline bytes).
    Pins whose ref owner dies with this node are skipped — there is no
    surviving reference to preserve them for."""
    import asyncio
    import os

    from ray_tpu._private.common import Address

    loop = asyncio.get_running_loop()
    for fn in list(_evac_preparers):
        try:
            await loop.run_in_executor(None, fn)
        except Exception:
            logger.warning("evacuation preparer failed; continuing with "
                           "existing pins", exc_info=True)
    reg = registry()
    with reg._lock:
        snap = list(reg._entries.items())
        owners = dict(reg._ref_owners)
    by_prefix: dict[str, list] = {}
    for key, entry in snap:
        by_prefix.setdefault(key.split("#", 1)[0], []).append((key, entry))
    stats = {"evacuated_objects": 0, "evacuated_bytes": 0, "skipped": 0,
             "routes": {}}
    want_collective = os.environ.get("RAY_TPU_DEVICE_COLLECTIVE") == "1"
    for prefix, leaves in by_prefix.items():
        owner_wire = owners.get(prefix)
        if not owner_wire:
            stats["skipped"] += len(leaves)
            continue
        addr = Address.from_wire(owner_wire)
        if addr.worker_id == cw.worker_id or addr.node_id == cw.node_id:
            # The owner's process dies with this node: its refs (and any
            # consumer's recovery path) die too — nothing to preserve.
            stats["skipped"] += len(leaves)
            continue

        def gather_all(leaves=leaves):
            out = []
            for key, (array, meta, _ts) in leaves:
                np_value = np.asarray(array)
                out.append((key, str(np_value.dtype),
                            list(np_value.shape), np_value.tobytes(),
                            meta.nbytes))
            return out

        try:
            gathered = await loop.run_in_executor(None, gather_all)
            conn = await cw._owner_conn(addr)
            resp = None
            route = "host"
            delivered_tags: list = []
            if want_collective:
                # Three steps, because the receiver's mailbox must exist
                # BEFORE any raw-buffer send (an unknown-handler notify
                # is silently dropped): prepare (owner arms its
                # _PeerPlane, or refuses and we go host with no stall) →
                # deliver the buffers → commit (owner recvs + pins, and
                # discards every tag on failure so nothing strands).
                # Exceptions anywhere degrade to the host route too —
                # the host Repin then carries the delivered tags as
                # stale so the owner sweeps its mailbox.
                route = "collective"
                tags = [key for key, *_ in gathered]
                try:
                    resp = await conn.call(
                        "DeviceObjectRepin",
                        {"prefix": prefix, "route": "collective",
                         "phase": "prepare", "tags": tags}, timeout=15)
                    if resp.get("ok"):
                        for key, dtype, shape, data, _nb in gathered:
                            await conn.notify("CollectiveDeliver", {
                                "group": COLLECTIVE_GROUP, "tag": key,
                                "dtype": dtype, "shape": shape,
                                "data": data})
                            delivered_tags.append(key)
                        resp = await conn.call(
                            "DeviceObjectRepin",
                            {"prefix": prefix, "route": "collective",
                             "phase": "commit", "tags": tags},
                            timeout=60)
                except Exception:
                    resp = {}
                if not resp.get("ok"):
                    resp = None
                    route = "host"
            if resp is None:
                resp = await conn.call("DeviceObjectRepin", {
                    "prefix": prefix, "route": "host",
                    "stale_tags": delivered_tags,
                    "items": [{"key": key, "dtype": dtype,
                               "shape": shape, "data": data}
                              for key, dtype, shape, data, _nb
                              in gathered]}, timeout=60)
            if not resp.get("ok"):
                stats["skipped"] += len(leaves)
                continue
        except Exception:
            stats["skipped"] += len(leaves)
            continue
        reg.release_prefix(prefix, counted=False)
        nbytes = sum(nb for *_rest, nb in gathered)
        stats["evacuated_objects"] += len(leaves)
        stats["evacuated_bytes"] += nbytes
        stats["routes"][route] = stats["routes"].get(route, 0) + len(leaves)
        _count("evacuated_out", len(leaves))
    return stats


async def handle_repin(cw, payload: dict) -> dict:
    """Ref-owner side of a drain evacuation: accept the arrays a dying
    node shipped over, pin them in THIS process under their original
    keys, and repoint the owned object's descriptor here — consumers
    (and our own gets) then resolve against a live pin instead of
    falling into lineage reconstruction."""
    import asyncio

    require_fields(payload, "prefix", method="handle_repin")
    prefix = payload["prefix"]
    arrays: dict[str, np.ndarray] = {}
    if payload.get("route") == "collective":
        try:
            from ray_tpu.util.collective.collective import _get_peer_plane

            plane = _get_peer_plane()
        except Exception as e:
            return {"ok": False, "error": f"no peer plane: {e}"}
        if payload.get("phase") == "prepare":
            # Mailbox armed; the sender may deliver now. Nothing was
            # sent yet, so a refusal above costs the drain nothing.
            return {"ok": True}
        loop = asyncio.get_running_loop()
        require_fields(payload, "tags", method="handle_repin")
        try:
            for tag in payload["tags"]:
                arrays[tag] = await loop.run_in_executor(
                    None, lambda t=tag: plane.recv(COLLECTIVE_GROUP, t,
                                                   timeout=10.0))
        except Exception as e:
            # Partial failure: raw tensor buffers already delivered for
            # the remaining tags must not strand in the mailbox for the
            # process lifetime (the sender retries via the host route).
            for tag in payload["tags"]:
                if tag not in arrays:
                    try:
                        plane.discard(COLLECTIVE_GROUP, tag)
                    except Exception:
                        _count("errors_total")
                        logger.warning(
                            "handle_repin: mailbox discard of %r failed "
                            "— buffer may strand until process exit",
                            tag, exc_info=True)
            return {"ok": False, "error": f"collective recv failed: {e}"}
    else:
        # Host route after a degraded collective attempt: buffers the
        # sender already delivered into our mailbox are stale (the host
        # payload is authoritative) — sweep them, but only from an
        # ALREADY-EXISTING plane (no plane = the notifies were dropped
        # at dispatch; arming one just to sweep would be waste).
        if payload.get("stale_tags"):
            from ray_tpu.util.collective import collective as _coll

            require_fields(payload, "stale_tags", method="handle_repin")
            plane = _coll._peer_plane
            if plane is not None:
                for tag in payload["stale_tags"]:
                    try:
                        plane.discard(COLLECTIVE_GROUP, tag)
                    except Exception:
                        _count("errors_total")
                        logger.warning(
                            "handle_repin: stale-tag discard of %r "
                            "failed", tag, exc_info=True)
        require_fields(payload, "items", method="handle_repin")
        for item in payload["items"]:
            arrays[item["key"]] = np.frombuffer(
                bytearray(item["data"]),
                dtype=_np_dtype(item["dtype"])).reshape(item["shape"])
    reg = registry()
    n = total = 0
    for key, np_value in arrays.items():
        meta = reg.pin(key, _to_device(np_value), cw)
        total += meta.nbytes
        n += 1
    own_wire = cw.address.to_wire() if cw.address else None
    reg.note_ref_owner(prefix, own_wire)
    _count("evacuated_in", n)
    cw._post(cw._repoint_device_pin, prefix, own_wire)
    return {"ok": True, "repinned": n, "bytes": total}


def note_lost() -> None:
    _count("lost")


# ---------- driver/actor-facing helpers ----------

def device_put(value):
    """Pin a (tree of) jax.Array(s) in THIS process's registry and store
    only the descriptor as the object value — the device-plane analogue
    of ray_tpu.put. Consumers resolve via the cheapest route; freeing the
    returned ref unpins. Values with no jax.Array leaves fall back to a
    plain put."""
    import ray_tpu
    from ray_tpu._private import serialization
    from ray_tpu._private.api_internal import (DeviceObjectRef,
                                               collect_nested_refs,
                                               get_core_worker)
    from ray_tpu._private.ids import ObjectID

    cw = get_core_worker()
    oid = ObjectID.for_put(cw._current_task_id, next(cw._put_counter))
    prefix = f"put:{oid.hex()[:16]}:{next(_handoff_seq)}"
    stubbed, total, n = extract_arrays(value, prefix, cw)
    if n == 0:
        return ray_tpu.put(value)
    # Self-owned pin: evacuation has nothing to move (the ref dies with
    # this process), but the owner record keeps the table uniform.
    registry().note_ref_owner(prefix, cw.address.to_wire())
    # Refs embedded beside the arrays live as long as the put container
    # (the same container tracking put() applies).
    with collect_nested_refs() as sink:
        sobj = serialization.serialize(stubbed,
                                       kind=serialization.KIND_DEVICE)
    if sink:
        cw._post(cw._track_container, oid.hex(), list(sink))
    cw._run(cw._store_owned(oid, sobj))
    dev_info = [cw.address.to_wire(), prefix, total, n]
    cw._post(cw._set_device_info, oid.hex(), dev_info)
    return DeviceObjectRef(oid, cw.address)


def local_handoff(tag: str, value):
    """Same-process producer→consumer handoff through the plane (the
    serve prefill→decode KV route): pin, resolve (registry hit — zero
    copy), unpin. Ticks the in_process counters and the pinned-HBM gauge
    so the handoff is observable; returns the SAME live arrays."""
    prefix = f"{tag}:{next(_handoff_seq)}"
    stubbed, _total, n = extract_arrays(value, prefix, None)
    if n == 0:
        return value
    try:
        return resolve_value(stubbed, None)
    finally:
        registry().release_prefix(prefix)
