"""End-to-end basics: init / remote / get / put / wait.

Parity: reference python/ray/tests/test_basic.py family.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as exc


def test_put_get(ray_start_regular):
    ref = ray_tpu.put({"a": 1, "b": [1, 2, 3]})
    assert ray_tpu.get(ref) == {"a": 1, "b": [1, 2, 3]}


def test_put_get_numpy(ray_start_regular):
    arr = np.random.rand(512, 512)  # 2MB: goes through shm
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(out, arr)


def test_simple_task(ray_start_regular):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_task_with_kwargs(ray_start_regular):
    @ray_tpu.remote
    def f(a, b=10, c=20):
        return a + b + c

    assert ray_tpu.get(f.remote(1, c=2)) == 13


def test_many_tasks(ray_start_regular):
    @ray_tpu.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(50)]
    assert ray_tpu.get(refs) == [i * i for i in range(50)]


def test_task_chain_dependencies(ray_start_regular):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(9):
        ref = inc.remote(ref)
    assert ray_tpu.get(ref) == 10


def test_large_args_and_returns(ray_start_regular):
    @ray_tpu.remote
    def double(arr):
        return arr * 2

    arr = np.ones((1024, 1024))  # 8MB
    out = ray_tpu.get(double.remote(arr))
    np.testing.assert_array_equal(out, arr * 2)


def test_multiple_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_task_exception(ray_start_regular):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(exc.TaskError, match="kaboom"):
        ray_tpu.get(boom.remote())


def test_exception_propagates_through_dependency(ray_start_regular):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("kaboom")

    @ray_tpu.remote
    def consume(x):
        return x

    with pytest.raises(exc.TaskError, match="kaboom"):
        ray_tpu.get(consume.remote(boom.remote()))


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 2

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([f, s], num_returns=1, timeout=10)
    assert ready == [f]
    assert not_ready == [s]


def test_wait_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(30)

    ref = slow.remote()
    ready, not_ready = ray_tpu.wait([ref], num_returns=1, timeout=0.5)
    assert ready == []
    assert not_ready == [ref]


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(30)

    with pytest.raises(exc.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_nested_tasks(ray_start_regular):
    @ray_tpu.remote
    def child(x):
        return x * 10

    @ray_tpu.remote
    def parent():
        return ray_tpu.get(child.remote(4))

    assert ray_tpu.get(parent.remote()) == 40


def test_cluster_resources(ray_start_regular):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU") == 4.0


def test_runtime_context_in_task(ray_start_regular):
    @ray_tpu.remote
    def who():
        ctx = ray_tpu.get_runtime_context()
        return ctx.node_id, ctx.worker_id

    node_id, worker_id = ray_tpu.get(who.remote())
    assert len(node_id) == 40
    assert len(worker_id) == 40


def test_nested_fanout_wider_than_cpus(ray_start_regular):
    """Nested gets release the blocked worker's CPU (reference: raylet
    blocked-worker accounting) — a fan-out wider than the CPU count must
    not deadlock the worker pool."""
    import ray_tpu

    @ray_tpu.remote(num_cpus=1)
    def leaf(i):
        return i

    @ray_tpu.remote(num_cpus=1)
    def fan(width):
        import ray_tpu as rt

        return sum(rt.get([leaf.remote(i) for i in range(width)], timeout=60))

    # ray_start_regular gives 4 CPUs; two concurrent fan() calls each
    # spawning 6 leaves need blocked-release to make progress.
    out = ray_tpu.get([fan.remote(6), fan.remote(6)], timeout=120)
    assert out == [15, 15]


def test_get_from_other_threads_while_shutting_down():
    """Threads still calling get() when the driver shuts down are turned
    away at _run's gate: nothing is queued behind shutdown's last sweep
    of the loop, where it would be a task destroyed pending or a
    coroutine never awaited (the conftest fixture fails the test on
    either), and no thread hangs in a get that nothing will answer."""
    import gc
    import threading

    for _ in range(4):
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def one():
            return 1

        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    ray_tpu.get(one.remote(), timeout=5)
                except Exception:  # noqa: BLE001 - whatever shutdown raises
                    pass

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        ray_tpu.shutdown()
        stop.set()
        for t in threads:
            t.join(10)
        assert not any(t.is_alive() for t in threads)
        gc.collect()
