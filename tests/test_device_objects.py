"""Device object plane tests (_private/device_objects.py): the fallback
matrix (same-process handover / host-path fallback on CPU / forced
collective route / owner-death lineage reconstruction / refcount release
unpinning), the zero-host-copy acceptance claim (counter-asserted), and
the serialization out-of-band satellite.

Smoke-marked: these are tier-1 gates for the plane's routing and
lifecycle invariants.
"""

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import device_objects, serialization

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

pytestmark = pytest.mark.smoke


def _delta(before: dict, after: dict, key: str) -> int:
    return after.get(key, 0) - before.get(key, 0)


@ray_tpu.remote
class _Holder:
    """Pins a device array (make) and consumes it in-process (consume)."""

    def make(self):
        self._made = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        return self._made

    def consume(self, arr):
        # Identity IS the zero-copy proof: the resolved arg is the very
        # array object this process pinned — no device_get, no
        # re-device_put, no buffer copy of the payload.
        return {"identity": bool(arr is self._made),
                "sum": float(np.asarray(arr).sum())}

    def counters(self):
        return device_objects.counters()

    def pinned(self):
        return device_objects.registry().stats()["pinned_objects"]


def test_in_process_handover_is_zero_copy(ray_start_regular):
    """Acceptance gate: a device object consumed in the pinning process
    completes without any host round-trip of the payload — asserted by
    identity AND by the route counters (in_process ticks, the fallback
    counters do not)."""
    h = _Holder.remote()
    before = ray_tpu.get(h.counters.remote())
    ref = h.make.options(tensor_transport="device").remote()
    assert isinstance(ref, ray_tpu.DeviceObjectRef)
    out = ray_tpu.get(h.consume.remote(ref))
    assert out["identity"] is True
    assert out["sum"] == float(np.arange(64).sum())
    after = ray_tpu.get(h.counters.remote())
    assert _delta(before, after, "in_process") == 1
    assert _delta(before, after, "host_fallback") == 0
    assert _delta(before, after, "collective") == 0
    assert _delta(before, after, "total_pinned") == 1


def test_host_fallback_on_cpu(ray_start_regular):
    """Cross-process consumption on the CPU backend (no shared mesh)
    transparently falls back to the host path and says so in the
    counters."""
    h = _Holder.remote()
    ref = h.make.options(tensor_transport="device").remote()
    before = device_objects.counters()
    val = ray_tpu.get(ref, timeout=30)
    assert float(np.asarray(val).sum()) == float(np.arange(64).sum())
    after = device_objects.counters()
    assert _delta(before, after, "host_fallback") == 1
    assert _delta(before, after, "in_process") == 0


def test_forced_collective_route(ray_start_regular):
    """RAY_TPU_DEVICE_COLLECTIVE=1 drives the peer-plane (DCN) transfer:
    the payload arrives through the util/collective CollectiveDeliver
    mailbox, not the host-path reply."""
    h = _Holder.remote()
    ref = h.make.options(tensor_transport="device").remote()
    before = device_objects.counters()
    os.environ["RAY_TPU_DEVICE_COLLECTIVE"] = "1"
    try:
        val = ray_tpu.get(ref, timeout=30)
    finally:
        del os.environ["RAY_TPU_DEVICE_COLLECTIVE"]
    assert float(np.asarray(val).sum()) == float(np.arange(64).sum())
    after = device_objects.counters()
    assert _delta(before, after, "collective") == 1
    assert _delta(before, after, "host_fallback") == 0


def test_route_decision_table():
    """choose_route unit matrix: same non-cpu platform + overlapping
    device ids → collective; anything else → host."""
    def meta(platform, ids):
        return device_objects.DeviceObjectMeta(
            key="k", shape=[1], dtype="float32", nbytes=4,
            owner_addr=None, platform=platform, device_ids=ids,
            sharding="")

    local_ids = device_objects._local_device_ids()
    # CPU backend (this process): never collective without the override.
    assert device_objects.choose_route(meta("cpu", local_ids)) == "host"
    assert device_objects.choose_route(meta("tpu", [0, 1])) == "host"
    os.environ["RAY_TPU_DEVICE_COLLECTIVE"] = "1"
    try:
        assert device_objects.choose_route(
            meta("cpu", local_ids)) == "collective"
    finally:
        del os.environ["RAY_TPU_DEVICE_COLLECTIVE"]


@ray_tpu.remote(tensor_transport="device", num_returns=2, max_retries=2)
def _produce_pid_and_array():
    return os.getpid(), jnp.arange(128, dtype=jnp.float32) * 3.0


def test_owner_death_lineage_reconstruction(ray_start_regular):
    """Chaos gate: SIGKILL the worker pinning a device object, then
    consume it. The descriptor reports the object lost and the owner's
    lineage reconstruction re-executes the creating task, which re-pins
    fresh arrays on a live worker."""
    pid_ref, arr_ref = _produce_pid_and_array.remote()
    pid = ray_tpu.get(pid_ref)
    before = device_objects.counters()
    os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
            time.sleep(0.05)
        except ProcessLookupError:
            break
    val = ray_tpu.get(arr_ref, timeout=60)
    assert float(np.asarray(val).sum()) == float(np.arange(128).sum() * 3.0)
    after = device_objects.counters()
    assert _delta(before, after, "lost") >= 1
    # The recovered copy still resolved through a real route.
    assert (_delta(before, after, "host_fallback")
            + _delta(before, after, "collective")) >= 1


def test_device_payload_embedding_object_ref(ray_start_regular):
    """A device return that embeds an ObjectRef beside the arrays keeps
    the borrower protocol: the inner object survives the producer
    releasing its own hold, and the consumer can get it."""
    inner = ray_tpu.put({"inner": 41})

    @ray_tpu.remote(tensor_transport="device")
    def produce(box):
        # box[0] is the ObjectRef itself (nested refs are not
        # materialized) — embed it in the device return.
        return {"arr": jnp.ones(8), "ref": box[0]}

    ref = produce.remote([inner])
    out = ray_tpu.get(ref, timeout=30)
    del inner  # the container must keep the inner object alive
    time.sleep(0.3)
    assert float(np.asarray(out["arr"]).sum()) == 8.0
    assert ray_tpu.get(out["ref"], timeout=30) == {"inner": 41}


@ray_tpu.remote(tensor_transport="device", num_returns=2, max_retries=2)
def _produce_many_leaves():
    # Enough leaves that the stub payload exceeds max_inline_object_size
    # (100KB): the descriptor itself takes the shm-store path.
    return os.getpid(), [jnp.full((2,), float(i)) for i in range(1200)]


def test_owner_death_recovery_of_store_resident_descriptor(
        ray_start_regular):
    """Lineage recovery must also work when the stub payload was too big
    to inline (descriptor lives in the shm store, o.inline is None)."""
    pid_ref, tree_ref = _produce_many_leaves.remote()
    pid = ray_tpu.get(pid_ref)
    os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
            time.sleep(0.05)
        except ProcessLookupError:
            break
    tree = ray_tpu.get(tree_ref, timeout=120)
    assert len(tree) == 1200
    assert float(np.asarray(tree[7])[0]) == 7.0


def test_refcount_release_unpins(ray_start_regular):
    """Dropping the last ObjectRef frees the descriptor AND unpins the
    HBM bytes on the producing worker."""
    h = _Holder.remote()
    ref = h.make.options(tensor_transport="device").remote()
    ray_tpu.get(h.consume.remote(ref))  # force materialization
    assert ray_tpu.get(h.pinned.remote()) == 1
    del ref
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if ray_tpu.get(h.pinned.remote()) == 0:
            break
        time.sleep(0.1)
    assert ray_tpu.get(h.pinned.remote()) == 0


def test_device_put_pytree_and_in_process_get(ray_start_regular):
    """device_put pins a whole param tree locally; a local get hands the
    SAME arrays back (driver-side zero copy); a worker pulls real
    values."""
    # (the registry is the process's: an engine of an earlier file of this
    # worker may have left pins of its own in it)
    def pinned():
        return device_objects.registry().stats()["pinned_objects"]

    others = pinned()
    params = {"w": jnp.ones((4, 4)), "b": (jnp.zeros(4), jnp.full(2, 2.0))}
    ref = device_objects.device_put(params)
    assert isinstance(ref, ray_tpu.DeviceObjectRef)
    local = ray_tpu.get(ref)
    assert local["w"] is params["w"]
    assert local["b"][1] is params["b"][1]

    @ray_tpu.remote
    def consume(p):
        return (float(np.asarray(p["w"]).sum()),
                float(np.asarray(p["b"][1]).sum()))

    assert ray_tpu.get(consume.remote(ref), timeout=30) == (16.0, 4.0)

    # A DeviceObjectRef nested in a container survives the pickle hop
    # as a DeviceObjectRef (isinstance routing must not silently break).
    @ray_tpu.remote
    def check_cls(box):
        return type(box[0]).__name__

    assert ray_tpu.get(check_cls.remote([ref]),
                       timeout=30) == "DeviceObjectRef"
    assert pinned() - others >= 3
    del ref, local
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and pinned() > others:
        time.sleep(0.1)
    assert pinned() <= others


def test_state_api_and_node_fanout(ray_start_regular):
    """list_device_objects surfaces the owned descriptor and the pinning
    worker's registry through the raylet fan-out."""
    h = _Holder.remote()
    ref = h.make.options(tensor_transport="device").remote()
    ray_tpu.get(h.consume.remote(ref))  # ensure the return registered
    from ray_tpu.util import state

    out = state.list_device_objects()
    owned = [o for o in out["owned"]
             if o["object_id"] == ref.id.hex()]
    assert owned and owned[0]["leaves"] == 1
    assert owned[0]["pinned_bytes"] == 64 * 4
    node_pins = sum(w.get("pinned_objects", 0)
                    for n in out["nodes"] if "error" not in n
                    for w in n.get("workers", []))
    assert node_pins >= 1
    summary = state.summarize_device_objects()
    assert summary["pinned_objects"] >= 1
    assert summary["pinned_bytes"] >= 64 * 4
    del ref


def test_serialize_jax_array_out_of_band():
    """Satellite: serialize() of a jax.Array must land the payload as an
    out-of-band pickle-5 buffer (single host gather, shm-alignable), not
    an inband pickle copy — and deserialize must hand back a jax.Array."""
    arr = jnp.arange(1024, dtype=jnp.float32)
    sobj = serialization.serialize(arr)
    assert sobj.buffers, "jax.Array payload must be out-of-band"
    total_buf = sum(b.raw().nbytes for b in sobj.buffers)
    assert total_buf >= arr.nbytes
    # The inband pickle is only the skeleton, not the tensor.
    assert len(sobj.inband) < arr.nbytes // 2
    kind, value = serialization.deserialize(sobj.meta, sobj.to_bytes())
    assert kind == serialization.KIND_PYTHON
    assert isinstance(value, jax.Array)
    np.testing.assert_array_equal(np.asarray(value), np.asarray(arr))


def test_local_handoff_identity_and_gauges():
    """The serve prefill→decode handoff primitive: same live arrays out,
    counters tick, nothing left pinned."""
    kv = [(jnp.ones((2, 8, 4)), jnp.zeros((2, 8, 4))) for _ in range(3)]
    before = device_objects.counters()
    out = device_objects.local_handoff("test-kv", kv)
    after = device_objects.counters()
    assert all(a is b and c is d
               for (a, c), (b, d) in zip(out, kv))
    assert _delta(before, after, "in_process") == 6
    assert _delta(before, after, "released") == 6
    # transient pins are gone
    assert not any(e["key"].startswith("test-kv")
                   for e in device_objects.registry().entries())


def test_local_handoff_leaves_no_cyclic_garbage():
    """What a handoff held dies with the last reference to it, without
    waiting for Python's cycle collector: a walker closing over itself
    kept each prefill's whole KV alive until a collection happened to run
    (1.2 GB a batched prefill, then RESOURCE_EXHAUSTED on the chip)."""
    import gc
    import weakref

    class Leaf:     # stands in for an array: weakly referenceable
        pass

    gc.collect()
    gc.disable()
    try:
        leaf = Leaf()
        gone = weakref.ref(leaf)
        tree = {"rings": [(leaf, 1)], "pool": (2, 3)}
        out = device_objects.tree_map(
            tree, lambda v: v, lambda v: isinstance(v, Leaf))
        assert out["rings"][0][0] is leaf
        kv = {"cache": (jnp.ones((2, 4)), jnp.zeros((2, 4)))}
        held = weakref.ref(kv["cache"][0])
        out2 = device_objects.local_handoff("test-cycle", kv)
        del leaf, tree, out, kv, out2
        assert gone() is None and held() is None
    finally:
        gc.enable()


def test_train_broadcast_weights(ray_start_regular):
    """Train consumer: WorkerGroup.broadcast_weights ships one device
    object to every worker; each receives the full tree."""
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.worker_group import WorkerGroup

    wg = WorkerGroup(ScalingConfig(num_workers=2))
    try:
        params = {"layer": {"w": jnp.ones((8, 8)), "b": jnp.zeros(8)}}
        out = wg.broadcast_weights(params)
        assert sorted(o["rank"] for o in out) == [0, 1]
        expect_bytes = 8 * 8 * 4 + 8 * 4
        assert all(o["leaves"] == 2 and o["bytes"] == expect_bytes
                   for o in out)
    finally:
        wg.shutdown()


def test_llm_engine_kv_handoff_uses_plane():
    """Serve consumer: a dense-mode prefill routes its KV through the
    device plane (in_process handover), and generation is unchanged."""
    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.serve.llm import LLMEngine, SamplingParams

    cfg = LlamaConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq_len=64,
                      dtype=jnp.float32, attention="reference",
                      remat=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    before = device_objects.counters()
    eng = LLMEngine(cfg, params, max_batch=2, max_len=48, page_size=16)
    try:
        toks = eng.generate([1, 2, 3], SamplingParams(max_new_tokens=4))
        assert len(toks) >= 1
    finally:
        eng.shutdown()
    after = device_objects.counters()
    # One prefill → n_layers * (k, v) in-process handovers, all unpinned.
    assert _delta(before, after, "in_process") >= 2 * cfg.n_layers
    assert _delta(before, after, "released") >= 2 * cfg.n_layers
