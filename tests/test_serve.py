"""Serve tests (parity: reference python/ray/serve/tests)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster(ray_start_regular):
    yield
    serve.shutdown()


def test_function_deployment(serve_cluster):
    @serve.deployment
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind())
    assert handle.remote(42).result() == {"echo": 42}


def test_class_deployment_with_state(serve_cluster):
    @serve.deployment
    class Model:
        def __init__(self, scale):
            self.scale = scale

        def __call__(self, x):
            return x * self.scale

        def describe(self):
            return {"scale": self.scale}

    handle = serve.run(Model.bind(10))
    assert handle.remote(4).result() == 40
    assert handle.options(method_name="describe").remote().result() == \
        {"scale": 10}


def test_multiple_replicas_route(serve_cluster):
    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __call__(self, _):
            import os

            return os.getpid()

    handle = serve.run(WhoAmI.bind())
    pids = {handle.remote(None).result() for _ in range(12)}
    assert len(pids) == 2  # both replicas served traffic


def test_batching(serve_cluster):
    @serve.deployment
    class Batched:
        def __call__(self, items):
            # Receives a list when called through a batching handle.
            return [i * 2 for i in items]

    serve.run(Batched.bind())
    handle = serve.get_deployment_handle("Batched").options(
        batching=(4, 0.05))
    responses = [handle.remote(i) for i in range(8)]
    assert [r.result() for r in responses] == [i * 2 for i in range(8)]


def test_status_and_delete(serve_cluster):
    @serve.deployment
    def f(x):
        return x

    serve.run(f.bind())
    st = serve.status()
    assert st["f"]["num_replicas"] == 1
    serve.delete("f")
    assert "f" not in serve.status()


def test_http_proxy(serve_cluster):
    @serve.deployment
    def classify(payload):
        return {"label": "ok", "score": payload.get("value", 0) * 2}

    serve.run(classify.bind())
    port = serve.start_http_proxy(port=0)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/classify",
        data=json.dumps({"value": 21}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        body = json.load(resp)
    assert body["result"] == {"label": "ok", "score": 42}


def test_autoscaling_up(serve_cluster):
    @serve.deployment(autoscaling_config={
        "min_replicas": 1, "max_replicas": 3,
        "target_ongoing_requests": 1.0, "upscale_delay_s": 0.0})
    class Slow:
        def __call__(self, _):
            time.sleep(0.5)
            return 1

    handle = serve.run(Slow.bind())
    responses = [handle.remote(None) for _ in range(9)]
    deadline = time.time() + 30
    while time.time() < deadline:
        if serve.status()["Slow"]["num_replicas"] > 1:
            break
        time.sleep(0.2)
    assert serve.status()["Slow"]["num_replicas"] > 1
    for r in responses:
        r.result(timeout=120)


def test_replica_replaced_on_crash(serve_cluster):
    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self, x):
            if x == "die":
                import os

                os._exit(1)
            return "alive"

    handle = serve.run(Fragile.bind())
    assert handle.remote("hi").result(timeout=60) == "alive"
    try:
        handle.remote("die").result(timeout=10)
    except Exception:
        pass
    # The controller health loop replaces the dead replica.
    deadline = time.time() + 40
    while time.time() < deadline:
        try:
            if handle.remote("hi").result(timeout=10) == "alive":
                break
        except Exception:
            time.sleep(0.5)
    assert handle.remote("hi").result(timeout=30) == "alive"


def test_multiplexed_models(serve_cluster):
    @serve.deployment(num_replicas=2)
    class MultiModel:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id):
            return {"id": model_id, "pid_loaded": __import__("os").getpid()}

        def __call__(self, _):
            mid = serve.get_multiplexed_model_id()
            model = self.get_model(mid)
            return {"model": model["id"], "pid": __import__("os").getpid()}

    handle = serve.run(MultiModel.bind())
    r1 = handle.options(multiplexed_model_id="m1").remote(None).result(timeout=60)
    assert r1["model"] == "m1"
    # Subsequent m1 requests stick to a replica that has m1 resident.
    pids = {handle.options(multiplexed_model_id="m1")
            .remote(None).result(timeout=60)["pid"] for _ in range(4)}
    assert pids == {r1["pid"]}


def test_route_prefix(serve_cluster):
    import json
    import urllib.request

    @serve.deployment
    def api(payload):
        return {"got": payload}

    serve.run(api.bind(), route_prefix="/v1/api")
    port = serve.start_http_proxy(port=0)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/api/anything",
        data=json.dumps({"k": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        body = json.load(resp)
    assert body["result"] == {"got": {"k": 1}}


def test_deployment_graph_composition(ray_start_regular):
    """serve.run of a bound graph deploys children first and hands the
    parent live handles (parity: deployment-graph DAG composition)."""
    from ray_tpu import serve

    @serve.deployment(name="adder")
    class Adder:
        def __call__(self, x):
            return x + 1

    @serve.deployment(name="doubler")
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment(name="ensemble")
    class Ensemble:
        def __init__(self, adder, doubler):
            self.adder = adder
            self.doubler = doubler

        def __call__(self, x):
            a = self.adder.remote(x).result(timeout=30)
            d = self.doubler.remote(x).result(timeout=30)
            return a + d

    try:
        handle = serve.run(Ensemble.bind(Adder.bind(), Doubler.bind()))
        # (5+1) + (5*2) = 16, through two nested deployment calls.
        assert handle.remote(5).result(timeout=60) == 16
        assert set(serve.status()) >= {"adder", "doubler", "ensemble"}
    finally:
        serve.shutdown()


def test_rolling_update_zero_downtime(serve_cluster):
    """Code redeploy rolls replicas one at a time: a client hammering the
    deployment throughout the rollout sees ZERO failed requests and
    eventually the new code's answers (reference: deployment_state.py:1149
    versioned rolling updates + graceful drain; long_poll.py pushes the
    changing replica set to handles)."""
    import threading

    import ray_tpu
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def versioned(payload=None):
        return "v1"

    handle = serve.run(versioned.bind(), name="roll")
    assert handle.remote().result(timeout=60) == "v1"

    results, errors = [], []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                results.append(handle.remote().result(timeout=60))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        import time

        time.sleep(1.0)

        @serve.deployment(num_replicas=2)
        def versioned(payload=None):  # noqa: F811  (new code version)
            return "v2"

        serve.run(versioned.bind(), name="roll")  # rolling redeploy
        # After the redeploy returns, answers must be v2.
        deadline = time.time() + 30
        while time.time() < deadline:
            if handle.remote().result(timeout=60) == "v2":
                break
        assert handle.remote().result(timeout=60) == "v2"
        time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)

    assert not errors, f"requests failed during rollout: {errors[:3]}"
    assert "v1" in results and "v2" in results
    # No interleaved stale answers after the rollout completed.
    serve.delete("roll")


def test_long_poll_pushes_updates(serve_cluster):
    """Handles learn of replica-set changes via the controller's held
    long-poll connection, not TTL polling (reference: long_poll.py:63)."""
    import time

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import CONTROLLER_NAME

    @serve.deployment(num_replicas=1)
    def app(payload=None):
        return "ok"

    handle = serve.run(app.bind(), name="lp")
    assert handle.remote().result(timeout=60) == "ok"
    router = handle._router
    assert router.poll_thread is not None and router.poll_thread.is_alive()
    deadline = time.time() + 20
    while time.time() < deadline and router.poll_version == 0:
        time.sleep(0.2)  # starved-box tolerance for the first push
    v0 = router.poll_version
    assert v0 > 0  # first push observed

    # Scale up through a redeploy; the push must bump the version and
    # grow the replica set without any request-driven refresh.
    @serve.deployment(num_replicas=3)
    def app(payload=None):  # noqa: F811
        return "ok"

    serve.run(app.bind(), name="lp")
    deadline = time.time() + 20
    while time.time() < deadline and len(router.replicas) != 3:
        time.sleep(0.2)
    assert len(router.replicas) == 3
    assert router.poll_version > v0
    serve.delete("lp")


def test_rpc_binary_ingress(serve_cluster):
    """The second ingress protocol (reference: the proxy's gRPC listener
    beside HTTP, proxy.py:13-38): a client calls a deployment over the
    binary msgpack-RPC framing — unary, routed-by-prefix, and a
    streaming response delivered as per-chunk notifies."""
    from ray_tpu.serve.rpc_ingress import RpcIngressClient

    @serve.deployment
    def echo(payload):
        return {"echo": payload.get("msg"), "n": payload.get("n", 0) + 1}

    @serve.deployment
    def tokens(payload):
        for i in range(payload.get("count", 3)):
            yield {"tok": i}

    serve.run(echo.bind(), route_prefix="/api/echo")
    serve.run(tokens.bind())
    port = serve.start_rpc_proxy(port=0)
    client = RpcIngressClient("127.0.0.1", port)
    try:
        # unary by deployment name
        out = client.call({"msg": "hi", "n": 41}, deployment="echo")
        assert out == {"echo": "hi", "n": 42}
        # unary by route prefix
        out = client.call({"msg": "routed"}, route="/api/echo/sub")
        assert out["echo"] == "routed"
        # unknown deployment -> error, connection stays usable
        import pytest as _pytest

        with _pytest.raises(RuntimeError):
            client.call({}, deployment="nope-not-here")
        assert client.call({"msg": "still-alive"},
                           deployment="echo")["echo"] == "still-alive"
        # streaming response
        chunks = list(client.stream({"count": 4}, deployment="tokens"))
        assert chunks == [{"tok": 0}, {"tok": 1}, {"tok": 2}, {"tok": 3}]
        # shutdown stops the ingress under a live client and frees its port
        import socket

        serve.shutdown()
        with _pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
    finally:
        client.close()
