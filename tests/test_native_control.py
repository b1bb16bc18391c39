"""Native control plane (graftgen, issue 18) e2e tests.

Under RAY_TPU_NATIVE_CONTROL=1 the GCS installs the actor plane
(src/gcs_actor.cc) and every raylet installs the lease plane
(src/raylet_lease.cc) into their fastpath pumps: the hot actor-creation
ladder (RegisterActor -> CreateActor -> ActorReady) and the hot lease
grant/return execute on the C++ loop threads, while Python stays the
policy/IO shell — named actors, placement groups, empty worker pools
and every other complex shape fall through per-method to the Python
handlers.

These tests drive a REAL GcsServer (pump transport) with real
rpc.connect_session clients acting as driver and raylet, then the full
stack through ray_tpu.init, asserting (a) the ladder end-state matches
the Python path (actor ALIVE, address mirrored), (b) the frames really
were handled natively (plane counters, stats surface), and (c) the
fallthrough shapes still work.
"""

import asyncio
import os

import pytest

import ray_tpu
from ray_tpu._private import rpc
from ray_tpu._private.gcs import ACTOR_ALIVE, GcsServer


def _native_control_available() -> bool:
    try:
        from ray_tpu._private import (native_actor_plane, native_fastpath,
                                      native_lease_plane)

        if not native_fastpath.available():
            return False
        native_actor_plane._load()
        native_lease_plane._load()
        return True
    except Exception:
        return False


pytestmark = pytest.mark.skipif(
    not _native_control_available(),
    reason="native control plane unavailable")


def run(coro):
    return asyncio.run(coro)


async def _wait_for(predicate, timeout=10.0, what="condition"):
    deadline = asyncio.get_event_loop().time() + timeout
    while True:
        if predicate():
            return
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError(f"timed out waiting for {what}")
        await asyncio.sleep(0.02)


NODE_ID = "aa" * 16


async def _fake_raylet(host, port):
    """A connect_session client that registers a node and answers the
    plane's CreateActor ladder: reply ok, then (once the test releases
    it) call ActorReady — the exact raylet-side protocol."""
    created = asyncio.Event()
    create_payloads = []
    sess_box = {}

    def on_create(conn, payload):
        create_payloads.append(payload)
        created.set()
        return {"ok": True}

    sess = await rpc.connect_session(host, port,
                                     handlers={"CreateActor": on_create},
                                     name="fake-raylet")
    sess_box["sess"] = sess
    r = await sess.call("RegisterNode", {
        "host": "127.0.0.1", "node_id": NODE_ID, "raylet_port": 47001,
        "total_resources": {"CPU": 4.0}})
    assert r["ok"]
    return sess, created, create_payloads


def test_actor_ladder_native(tmp_path, monkeypatch):
    """RegisterActor for a simple (nameless) actor runs the native
    ladder: driver acked from C++, CreateActor reaches the raylet with
    the spec bytes intact, ActorReady flips the Python mirror to ALIVE
    — and the Python RegisterActor handler never runs."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            assert gcs._actor_plane is not None, \
                "actor plane should install under RAY_TPU_NATIVE_CONTROL=1"
            raylet, created, create_payloads = await _fake_raylet(host, port)

            driver = await rpc.connect_session(host, port, name="driver")
            r = await driver.call("RegisterActor", {
                "actor_id": "nat-a1", "spec": b"\x01spec-bytes",
                "max_restarts": 0, "class_name": "Counter",
                "job_id": "job-1"})
            assert r["ok"]

            await asyncio.wait_for(created.wait(), 10)
            assert create_payloads[0]["actor_id"] == "nat-a1"
            assert create_payloads[0]["spec"] == b"\x01spec-bytes"

            # Python mirrored the registration off the inject events.
            await _wait_for(lambda: "nat-a1" in gcs.actors,
                            what="actor mirror")
            assert gcs.actors["nat-a1"]["native"] is True

            # ActorReady completes the ladder natively.
            await raylet.call("ActorReady", {
                "actor_id": "nat-a1", "address": ["127.0.0.1", 47002]})
            await _wait_for(
                lambda: gcs.actors["nat-a1"]["state"] == ACTOR_ALIVE,
                what="actor ALIVE")
            a = gcs.actors["nat-a1"]
            assert a["node_id"] == NODE_ID
            assert a["address"] == ["127.0.0.1", 47002]

            # The frames were handled in C++ (RegisterActor + ActorReady
            # at minimum) and surfaced through GetClusterStatus.
            handled, fallthrough, deduped = gcs._actor_plane.counters()
            assert handled >= 2
            assert gcs._actor_plane.proto_errors() == 0
            status = await driver.call("GetClusterStatus", {})
            nc = status["native_control"]
            assert nc["handled_total"] >= 2
            assert "native_fallthrough_total" in nc
            assert nc["actors"] >= 1

            # GetActorInfo (a Python handler) answers from the mirror.
            info = await driver.call("GetActorInfo",
                                     {"actor_id": "nat-a1"})
            assert info["state"] == ACTOR_ALIVE

            await driver.close()
            await raylet.close()
        finally:
            await gcs.stop()

    run(main())


def test_named_actor_falls_through_to_python(tmp_path, monkeypatch):
    """A NAMED actor is a complex shape the plane does not own: the
    frame must fall through (counted) and the Python handler must still
    complete the registration."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            raylet, created, create_payloads = await _fake_raylet(host, port)
            driver = await rpc.connect_session(host, port, name="driver")

            _, fb_before, _ = gcs._actor_plane.counters()
            r = await driver.call("RegisterActor", {
                "actor_id": "named-b1", "spec": b"\x02spec",
                "max_restarts": 0, "class_name": "Named",
                "name": "bob", "namespace": "default", "job_id": "job-1"})
            assert r["ok"]
            _, fb_after, _ = gcs._actor_plane.counters()
            assert fb_after > fb_before, \
                "named RegisterActor should fall through to Python"
            # The PYTHON path registered it (no native flag).
            await _wait_for(lambda: "named-b1" in gcs.actors,
                            what="python-side registration")
            assert not gcs.actors["named-b1"].get("native")

            await driver.close()
            await raylet.close()
        finally:
            await gcs.stop()

    run(main())


def test_malformed_register_actor_errors_natively(tmp_path, monkeypatch):
    """A RegisterActor missing a generated-validator required field
    ("spec") must come back as a Malformed RpcError from C++ — not
    crash the plane, not silently pass through."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            raylet, _, _ = await _fake_raylet(host, port)
            driver = await rpc.connect_session(host, port, name="driver")
            with pytest.raises(rpc.RpcError, match="malformed"):
                await driver.call("RegisterActor", {"actor_id": "no-spec"})
            assert gcs._actor_plane.proto_errors() == 1
            # The plane still works afterwards.
            r = await driver.call("RegisterActor", {
                "actor_id": "ok-after", "spec": b"\x03s",
                "max_restarts": 0})
            assert r["ok"]
            await driver.close()
            await raylet.close()
        finally:
            await gcs.stop()

    run(main())


def test_replay_dedup_across_session(tmp_path, monkeypatch):
    """The same (sid, rseq) RegisterActor replayed over a FRESH socket
    (session rebind, what a reconnect does) must be answered from the
    native reply cache — at-most-once across rebinds."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            raylet, created, _ = await _fake_raylet(host, port)
            driver = await rpc.connect_session(host, port, name="driver")
            assert (await driver.call("RegisterActor", {
                "actor_id": "dup-a1", "spec": b"\x04s",
                "max_restarts": 0}))["ok"]
            await asyncio.wait_for(created.wait(), 10)

            # Kill the driver's socket; the session layer replays over a
            # new connection on the next call after reconnecting — but
            # here we replay the SAME stamped request by hand to pin the
            # server side: same sid, same rseq, fresh socket.
            sid = driver.session_id
            frame = rpc.pack([rpc.MSG_REQUEST, 99, "RegisterActor", {
                "actor_id": "dup-a1", "spec": b"\x04s", "max_restarts": 0,
                "_session": sid, "_rseq": 1, "_acked": 0}])
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(len(frame).to_bytes(4, "big") + frame)
            await writer.drain()
            hdr = await asyncio.wait_for(reader.readexactly(4), 10)
            resp = rpc.unpack(await asyncio.wait_for(
                reader.readexactly(int.from_bytes(hdr, "big")), 10))
            assert resp[0] == rpc.MSG_RESPONSE and resp[3]["ok"]
            writer.close()

            handled, _, deduped = gcs._actor_plane.counters()
            assert deduped >= 1, "replay must hit the native reply cache"
            # Exactly one CreateActor ever reached the raylet.
            await asyncio.sleep(0.2)
            assert gcs._actor_plane.actor_count() == 1

            await driver.close()
            await raylet.close()
        finally:
            await gcs.stop()

    run(main())


async def _fake_raylet_ex(host, port, node_id, on_create=None,
                          handlers=None, reconnect_register=False):
    """Configurable fake raylet: custom CreateActor behavior, extra
    handlers (e.g. Drain), and optional re-registration on session
    reconnect (what the real raylet's _gcs_handshake does)."""
    created = asyncio.Event()
    create_payloads = []

    def default_create(conn, payload):
        create_payloads.append(payload)
        created.set()
        return {"ok": True}

    table = {"CreateActor": on_create or default_create}
    table.update(handlers or {})
    reg_payload = {
        "host": "127.0.0.1", "node_id": node_id, "raylet_port": 47001,
        "total_resources": {"CPU": 4.0}}

    async def _handshake(conn):
        r = await conn.call("RegisterNode", reg_payload, timeout=10)
        assert r["ok"]

    sess = await rpc.connect_session(
        host, port, handlers=table, name=f"fake-raylet-{node_id[:4]}",
        on_reconnect=_handshake if reconnect_register else None)
    r = await sess.call("RegisterNode", reg_payload)
    assert r["ok"]
    return sess, created, create_payloads


def test_create_replay_across_netchaos_flap(tmp_path, monkeypatch):
    """NetChaos flap mid-flight on a native CreateActor: the raylet
    executes the create but its reply is eaten, the link dies, the
    session rebinds and re-registers — the plane resends the SAME
    (sid, rseq) frame and the raylet's reply cache answers it. Exactly
    one actor, exactly one CreateActor execution."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")
    from ray_tpu.test_utils import NetChaos

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        chaos = NetChaos(seed=7).start()
        try:
            phost, pport = chaos.link("gcs", host, port)
            loop = asyncio.get_event_loop()
            executions = []

            def on_create(conn, payload):
                executions.append(payload)
                if len(executions) == 1:
                    # Eat the reply, then drop the link shortly after so
                    # the session redials and the plane replays the
                    # frame over the rebound connection.
                    chaos.partition("gcs")

                    def _flap():
                        chaos.heal("gcs")
                        chaos.cut("gcs")
                    loop.call_later(0.3, _flap)
                return {"ok": True}

            raylet, _, _ = await _fake_raylet_ex(
                phost, pport, NODE_ID, on_create=on_create,
                reconnect_register=True)
            driver = await rpc.connect_session(host, port, name="driver")
            r = await driver.call("RegisterActor", {
                "actor_id": "flap-a1", "spec": b"\x05s",
                "max_restarts": 0, "class_name": "Flap"})
            assert r["ok"]

            # The flap promotes the node to SUSPECT, the rebind restores
            # it, and the replayed CreateActor is answered from the
            # raylet's reply cache — never executed twice.
            await _wait_for(
                lambda: gcs.nodes[NODE_ID].suspect_recoveries >= 1,
                timeout=20, what="suspect recovery")
            await asyncio.sleep(0.5)  # window for a wrong re-execution
            assert len(executions) == 1, \
                f"CreateActor forked: {len(executions)} executions"
            assert gcs._actor_plane.actor_count() == 1

            await raylet.call("ActorReady", {
                "actor_id": "flap-a1", "address": ["127.0.0.1", 47002]})
            await _wait_for(
                lambda: gcs.actors["flap-a1"]["state"] == ACTOR_ALIVE,
                what="actor ALIVE after flap")
            await driver.close()
            await raylet.close()
        finally:
            chaos.stop()
            await gcs.stop()

    run(main())


def test_node_killed_mid_ladder_fails_over(tmp_path, monkeypatch):
    """The CreateActor target dies mid-ladder (no reply ever): on the
    death certificate the plane fails the create over to the surviving
    node — one restart consumed, no fork, no lost actor."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")
    node_a, node_b = "bb" * 16, "cc" * 16

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            a_creates, b_creates = [], []
            got_create = asyncio.Event()

            async def a_create(conn, payload):
                a_creates.append(payload)
                got_create.set()
                await asyncio.Event().wait()  # never replies: dies first

            def b_create(conn, payload):
                b_creates.append(payload)
                got_create.set()
                return {"ok": True}

            ra, _, _ = await _fake_raylet_ex(host, port, node_a,
                                             on_create=a_create)
            rb, _, _ = await _fake_raylet_ex(host, port, node_b,
                                             on_create=b_create)
            driver = await rpc.connect_session(host, port, name="driver")
            r = await driver.call("RegisterActor", {
                "actor_id": "kill-a1", "spec": b"\x06s",
                "max_restarts": 1, "class_name": "Kill"})
            assert r["ok"]
            await asyncio.wait_for(got_create.wait(), 10)
            first = node_a if a_creates else node_b
            survivor_sess = rb if first == node_a else ra
            survivor_creates = b_creates if first == node_a else a_creates
            got_create.clear()

            # Death certificate for the in-flight target: the plane
            # fails over (restart bookkeeping) and re-drives the ladder
            # at the survivor.
            await driver.call("NotifyNodeDead", {"node_id": first})
            await asyncio.wait_for(got_create.wait(), 10)
            assert len(survivor_creates) == 1
            assert survivor_creates[0]["actor_id"] == "kill-a1"

            await survivor_sess.call("ActorReady", {
                "actor_id": "kill-a1",
                "address": ["127.0.0.1", 47003]})
            await _wait_for(
                lambda: gcs.actors["kill-a1"]["state"] == ACTOR_ALIVE,
                what="actor ALIVE on survivor")
            assert gcs.actors["kill-a1"]["node_id"] != first
            assert gcs.actors["kill-a1"]["restarts"] == 1
            assert gcs._actor_plane.actor_count() == 1

            await driver.close()
            for s in (ra, rb):
                try:
                    await s.close()
                except Exception:
                    pass
        finally:
            await gcs.stop()

    run(main())


def test_draining_node_excluded_from_native_picks(tmp_path, monkeypatch):
    """Satellite of tests/test_drain.py drain-rejection: once a node is
    DRAINING, the native ladder must stop picking it — every new native
    create lands on the other node."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")
    node_a, node_b = "dd" * 16, "ee" * 16

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            a_creates, b_creates = [], []

            def mk_create(sink):
                def h(conn, payload):
                    sink.append(payload)
                    return {"ok": True}
                return h

            def drain_ok(conn, payload):
                return {"ok": True}

            ra, _, _ = await _fake_raylet_ex(
                host, port, node_a, on_create=mk_create(a_creates),
                handlers={"Drain": drain_ok})
            rb, _, _ = await _fake_raylet_ex(
                host, port, node_b, on_create=mk_create(b_creates),
                handlers={"Drain": drain_ok})
            driver = await rpc.connect_session(host, port, name="driver")

            r = await driver.call("DrainNode", {
                "node_id": node_a, "reason": "manual",
                "deadline_s": 30.0})
            assert r["ok"], r

            for i in range(4):
                r = await driver.call("RegisterActor", {
                    "actor_id": f"drain-a{i}", "spec": b"\x07s",
                    "max_restarts": 0, "class_name": "D"})
                assert r["ok"]
            await _wait_for(lambda: len(b_creates) == 4, timeout=10,
                            what="creates on the non-draining node")
            assert not a_creates, \
                "native ladder picked a DRAINING node"

            await driver.close()
            await ra.close()
            await rb.close()
        finally:
            await gcs.stop()

    run(main())


def test_a_creation_in_flight_stays_charged_across_a_heartbeat(
        tmp_path, monkeypatch):
    """A heartbeat tells what a raylet HOLDS.  A creation the GCS has sent
    there and the raylet has not got round to (seconds, on a loaded host)
    is not held yet: the view must keep it charged until the raylet
    answers, or the next creation of a burst is sent to the same node,
    waits out the lease timeout there and dies ("timeout acquiring actor
    resources": what failed `test_elastic_shrink_then_grow_back` beside
    busy workers, two of a gang's four members on one node)."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")
    node_a, node_b = "a1" * 16, "b2" * 16

    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            creates = {node_a: [], node_b: []}
            answer = asyncio.Event()

            def mk_create(node_id):
                async def h(conn, payload):
                    creates[node_id].append(payload["actor_id"])
                    await answer.wait()
                    return {"ok": True}
                return h

            raylets = {}
            for node_id in (node_a, node_b):
                raylets[node_id], _, _ = await _fake_raylet_ex(
                    host, port, node_id, on_create=mk_create(node_id))
            driver = await rpc.connect_session(host, port, name="driver")

            async def create(actor_id, cpus):
                r = await driver.call("RegisterActor", {
                    "actor_id": actor_id, "spec": b"\x08s",
                    "max_restarts": 0, "class_name": "G",
                    "resources": {"CPU": cpus}, "strategy": ["spread"]})
                assert r["ok"]

            await create("gang-0", 4.0)         # a whole node's CPUs
            await _wait_for(lambda: creates[node_a] or creates[node_b],
                            what="the first creation to reach a raylet")
            first = node_a if creates[node_a] else node_b
            other = node_b if first == node_a else node_a
            # The raylet has not acquired anything yet, and says so; the
            # other node is half taken by something else.
            for node_id, free in ((first, 4.0), (other, 2.0)):
                r = await raylets[node_id].call("Heartbeat", {
                    "node_id": node_id,
                    "available_resources": {"CPU": free}})
                assert r["ok"]
            assert gcs.nodes[first].available_resources["CPU"] == 0.0
            # Spread over what looks free: the other node, not the one a
            # creation is on its way to.
            await create("gang-1", 2.0)
            await _wait_for(lambda: creates[other], what="the second creation")
            assert creates == {first: ["gang-0"], other: ["gang-1"]}
            # Answered, the raylet's word is the whole truth again.
            answer.set()
            await _wait_for(lambda: not gcs._placing, what="the answers")
            r = await raylets[first].call("Heartbeat", {
                "node_id": first, "available_resources": {"CPU": 0.0}})
            assert gcs.nodes[first].available_resources["CPU"] == 0.0

            await driver.close()
            for raylet in raylets.values():
                await raylet.close()
        finally:
            await gcs.stop()

    run(main())


def test_a_call_older_than_the_grace_still_waits_for_the_rebind(tmp_path):
    """A GCS->raylet call waits out a socket flap for the node's grace and
    is replayed when the raylet has re-registered. The grace is counted
    from the moment the connection is MISSED, whatever the call's age: a
    CreateActor that had been in flight for longer than the grace (a
    worker's start on a loaded host) was failed the instant the socket
    dropped, and a live actor restarted over a 0.5-s flap
    (`test_partition_flap_is_a_non_event` beside busy workers)."""
    async def main():
        gcs = GcsServer(persistence_path=str(tmp_path / "gcs_state"))
        host, port = await gcs.start()
        try:
            held, answer = asyncio.Event(), asyncio.Event()

            async def slow(conn, payload):
                held.set()
                await answer.wait()
                return {"ok": True}

            raylet, _, _ = await _fake_raylet_ex(
                host, port, NODE_ID, handlers={"Slow": slow},
                reconnect_register=True)
            # The grace `_call_node` reads, 0.3 s (the health check, long
            # started, keeps its 5 s: this raylet sends no heartbeats).
            gcs.config.health_check_period_s = 0.1
            gcs.config.num_heartbeats_timeout = 3
            call = asyncio.ensure_future(
                gcs._call_node(NODE_ID, "Slow", {}, timeout=20))
            await held.wait()
            await asyncio.sleep(0.4)        # older than the grace now
            await gcs.node_conns[NODE_ID].close()
            await _wait_for(
                lambda: gcs.nodes[NODE_ID].suspect_recoveries >= 1,
                what="the raylet to re-register")
            assert not call.done(), call
            answer.set()
            assert (await asyncio.wait_for(call, 10))["ok"]
            await raylet.close()
        finally:
            await gcs.stop()

    run(main())


def test_gcs_restart_rehydrates_native_plane(tmp_path, monkeypatch):
    """Crash rehydration: a restarted GCS replays the persisted node
    and actor tables into a fresh native plane — the ALIVE actor is
    ALIVE natively, the in-flight PENDING one is re-driven (exactly one
    CreateActor) when its node re-registers."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")
    path = str(tmp_path / "gcs_state")

    async def phase1():
        gcs = GcsServer(persistence_path=path)
        host, port = await gcs.start()
        try:
            raylet, created, payloads = await _fake_raylet(host, port)
            driver = await rpc.connect_session(host, port, name="driver")
            assert (await driver.call("RegisterActor", {
                "actor_id": "re-alive", "spec": b"\x08alive",
                "max_restarts": 0}))["ok"]
            await asyncio.wait_for(created.wait(), 10)
            await raylet.call("ActorReady", {
                "actor_id": "re-alive", "address": ["127.0.0.1", 47002]})
            await _wait_for(
                lambda: gcs.actors["re-alive"]["state"] == ACTOR_ALIVE,
                what="actor ALIVE pre-restart")
            # Second actor: created at the raylet but NEVER ActorReady —
            # in-flight at "crash" time, restored as PENDING.
            assert (await driver.call("RegisterActor", {
                "actor_id": "re-pending", "spec": b"\x09pend",
                "max_restarts": 0}))["ok"]
            await _wait_for(lambda: len(payloads) >= 2,
                            what="second CreateActor")
            await driver.close()
            await raylet.close()
        finally:
            await gcs.stop()  # final flush + compact

    async def phase2():
        gcs = GcsServer(persistence_path=path)
        host, port = await gcs.start()
        try:
            plane = gcs._actor_plane
            assert plane is not None
            # Rehydrated straight from the snapshot, before any node
            # re-registered.
            assert plane.actor_state("re-alive") == "ALIVE"
            assert plane.actor_state("re-pending") == "PENDING"
            assert plane.actor_count() == 2

            raylet, created, payloads = await _fake_raylet(host, port)
            # Node re-registration re-drives ONLY the pending ladder.
            await asyncio.wait_for(created.wait(), 10)
            await asyncio.sleep(0.3)
            assert [p["actor_id"] for p in payloads] == ["re-pending"]
            assert payloads[0]["spec"] == b"\x09pend"
            await raylet.call("ActorReady", {
                "actor_id": "re-pending",
                "address": ["127.0.0.1", 47005]})
            await _wait_for(
                lambda: gcs.actors["re-pending"]["state"] == ACTOR_ALIVE,
                what="re-driven actor ALIVE")
            assert gcs.actors["re-alive"]["state"] == ACTOR_ALIVE
            await raylet.close()
        finally:
            await gcs.stop()

    run(phase1())
    run(phase2())


def test_full_stack_native_control(monkeypatch):
    """ray_tpu.init under RAY_TPU_NATIVE_CONTROL=1: tasks and actors
    (plain + named) behave exactly as under the Python control plane,
    and both daemons report an installed plane that saw the traffic."""
    monkeypatch.setenv("RAY_TPU_NATIVE_CONTROL", "1")
    from ray_tpu._private.config import Config

    cfg = Config()
    cfg.health_check_period_s = 0.2
    cfg.num_heartbeats_timeout = 5
    cfg.worker_lease_timeout_s = 10.0
    cfg.object_store_memory = 64 * 1024 * 1024
    ray_tpu.init(num_cpus=2, config=cfg)
    try:
        @ray_tpu.remote
        def double(x):
            return x * 2

        assert ray_tpu.get([double.remote(i) for i in range(8)]) == \
            [i * 2 for i in range(8)]

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get(c.inc.remote()) == 1
        assert ray_tpu.get(c.inc.remote()) == 2

        named = Counter.options(name="nc-named").remote()
        assert ray_tpu.get(named.inc.remote()) == 1

        # More plain tasks after workers exist: the idle-worker pool is
        # populated, so the lease plane gets grantable shapes.
        assert ray_tpu.get([double.remote(i) for i in range(8)]) == \
            [i * 2 for i in range(8)]

        cw = ray_tpu._private.api_internal.get_core_worker()
        status = cw._run(cw.gcs.call("GetClusterStatus", {}))
        nc = status["native_control"]
        assert nc is not None, "GCS actor plane not installed"
        # Two RegisterActors flowed through the plane's frame hook —
        # handled natively or routed, never invisible.
        assert nc["handled_total"] + nc["native_fallthrough_total"] >= 2
        assert nc["proto_errors"] == 0

        state = cw._run(cw.raylet.call("GetState", {}))
        rnc = state["native_control"]
        assert rnc is not None, "raylet lease plane not installed"
        assert rnc["handled_total"] + rnc["native_fallthrough_total"] >= 1
        assert rnc["proto_errors"] == 0
    finally:
        ray_tpu.shutdown()
