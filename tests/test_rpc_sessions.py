"""Resilient RPC session unit tests (PR 10).

A ResilientConnection is a stable session id over reconnecting sockets:
stamped requests replay across socket death and the server-side
(session_id, rseq) reply cache makes the replay at-most-once. These
tests pin the session layer's own contracts — reconnect, replay, dedup,
per-call deadlines, grace exhaustion, the grace_s=0 fast path — plus
the NetChaos fault injector's frame-level behavior (duplicate frames,
cuts, one-way blackholes) against a live RpcServer.
"""

import asyncio

import pytest

from ray_tpu._private import rpc
from ray_tpu.test_utils import NetChaos


def run(coro):
    return asyncio.run(coro)


def echo_server():
    return rpc.RpcServer({"Echo": lambda c, p: {"v": p["v"]}}, name="t")


def test_session_reconnects_after_server_side_close():
    async def main():
        server = echo_server()
        host, port = await server.start()
        try:
            sess = await rpc.connect_session(host, port, name="s",
                                             grace_s=10.0)
            assert (await sess.call("Echo", {"v": 1}))["v"] == 1
            before = sess.reconnects
            for conn in list(server.connections):
                await conn.close()
            # Same session object keeps answering over a fresh socket.
            assert (await sess.call("Echo", {"v": 2}, timeout=10))["v"] == 2
            assert sess.reconnects >= before + 1
            assert not sess.closed
            await sess.close()
        finally:
            await server.stop()

    run(main())


def test_duplicate_request_frames_execute_once():
    """dup=1.0 duplicates every frame on the wire; the reply cache must
    absorb the duplicate REQUESTs (at-most-once) and the client must
    tolerate duplicate RESPONSEs."""
    async def main():
        counter = {"n": 0}

        def bump(conn, payload):
            counter["n"] += 1
            return {"n": counter["n"]}

        server = rpc.RpcServer({"Bump": bump}, name="t")
        host, port = await server.start()
        chaos = NetChaos(seed=5).start()
        try:
            ph, pp = chaos.link("dup", host, port)
            sess = await rpc.connect_session(ph, pp, name="dup-sess",
                                             grace_s=5.0)
            deduped0 = rpc.session_stats()["deduped_requests_total"]
            chaos.set_faults("dup", dup=1.0)
            for i in range(10):
                assert (await sess.call("Bump", {}, timeout=10))["n"] == i + 1
            assert counter["n"] == 10
            assert chaos.stats("dup")["frames_duplicated"] >= 10
            assert rpc.session_stats()["deduped_requests_total"] > deduped0
            await sess.close()
        finally:
            await server.stop()
            chaos.stop()

    run(main())


def test_cut_midflight_replays_without_second_execution():
    """A socket cut while the handler is running: the replayed request
    must attach to the in-flight execution (or its cached reply), not
    run the handler a second time."""
    async def main():
        calls = {"n": 0}

        async def slow(conn, payload):
            calls["n"] += 1
            await asyncio.sleep(0.5)
            return {"n": calls["n"]}

        server = rpc.RpcServer({"Slow": slow}, name="t")
        host, port = await server.start()
        chaos = NetChaos(seed=9).start()
        try:
            ph, pp = chaos.link("cut", host, port)
            sess = await rpc.connect_session(ph, pp, name="cut-sess",
                                             grace_s=10.0)
            replayed0 = rpc.session_stats()["replayed_requests_total"]
            fut = asyncio.ensure_future(sess.call("Slow", {}, timeout=15))
            await asyncio.sleep(0.1)  # request is in flight server-side
            chaos.cut("cut")
            assert (await fut)["n"] == 1
            assert calls["n"] == 1, "replay re-executed a stamped request"
            assert rpc.session_stats()["replayed_requests_total"] > replayed0
            await sess.close()
        finally:
            await server.stop()
            chaos.stop()

    run(main())


def test_session_stamp_stripped_before_handler():
    async def main():
        seen = {}

        def grab(key):
            def h(conn, payload):
                seen[key] = dict(payload)
                return {"ok": True}
            return h

        server = rpc.RpcServer({"KVGet": grab("exempt"),
                                "Other": grab("stamped")}, name="t")
        host, port = await server.start()
        try:
            sess = await rpc.connect_session(host, port, name="s",
                                             grace_s=5.0)
            await sess.call("KVGet", {"k": 1})
            await sess.call("Other", {"k": 1})
            # Exempt methods are never stamped; stamped methods have the
            # reserved keys stripped by the dispatcher.
            for key in ("exempt", "stamped"):
                assert rpc._SID_KEY not in seen[key]
                assert rpc._RSEQ_KEY not in seen[key]
                assert seen[key]["k"] == 1
            # Only the stamped call opened a server-side session.
            assert rpc.session_stats()["server_sessions"] >= 1
            await sess.close()
        finally:
            await server.stop()

    run(main())


def test_call_timeout_leaves_session_usable():
    async def main():
        async def hang(conn, payload):
            await asyncio.sleep(30)

        server = rpc.RpcServer(
            {"Hang": hang, "Echo": lambda c, p: {"v": p["v"]}}, name="t")
        host, port = await server.start()
        try:
            sess = await rpc.connect_session(host, port, name="s",
                                             grace_s=5.0)
            with pytest.raises(asyncio.TimeoutError):
                await sess.call("Hang", {}, timeout=0.3)
            assert (await sess.call("Echo", {"v": 3}))["v"] == 3
            assert not sess.closed
            await sess.close()
        finally:
            await server.stop()

    run(main())


def test_grace_exhaustion_fails_session_and_fires_on_close():
    async def main():
        server = echo_server()
        host, port = await server.start()
        sess = await rpc.connect_session(host, port, name="s", grace_s=0.5)
        fired = []
        sess.on_close(lambda: fired.append(1))
        await server.stop()  # nothing listening: redial can never succeed
        with pytest.raises(rpc.ConnectionLost):
            await sess.call("Echo", {"v": 1}, timeout=20)
        # The failure may surface via this call or the eager background
        # redial; either way the session is closed and on_close fired
        # exactly once.
        for _ in range(50):
            if fired:
                break
            await asyncio.sleep(0.05)
        assert fired == [1]
        assert sess.closed
        with pytest.raises(rpc.ConnectionLost):
            await sess.call("Echo", {"v": 2})

    run(main())


def test_server_stop_returns_under_live_clients():
    """stop() under a plain connection and a session still connected
    returns promptly (asyncio.Server.wait_closed() waits for every
    accepted connection since Python 3.12), both clients see the close,
    and a second stop() does nothing."""
    async def main():
        server = echo_server()
        host, port = await server.start()
        plain = await rpc.connect(host, port, name="p")
        sess = await rpc.connect_session(host, port, name="s", grace_s=0.3)
        assert (await plain.call("Echo", {"v": 1}))["v"] == 1
        assert (await sess.call("Echo", {"v": 2}))["v"] == 2
        plain_closed, sess_closed = asyncio.Event(), asyncio.Event()
        plain.on_close(plain_closed.set)
        sess.on_close(sess_closed.set)
        await asyncio.wait_for(server.stop(), 2)
        assert not server.connections
        await asyncio.wait_for(plain_closed.wait(), 2)
        # The session redials a dead port until its grace runs out.
        await asyncio.wait_for(sess_closed.wait(), 5)
        assert plain.closed and sess.closed
        with pytest.raises(OSError):
            await rpc.connect(host, port, timeout=1)
        await asyncio.wait_for(server.stop(), 2)

    run(main())


def test_grace_zero_still_gets_one_redial_attempt():
    """grace_s=0 (pool-worker semantics: die with the peer) still makes
    a single fast redial attempt — an instantly-rebound listener keeps
    the session; a dead one fails it."""
    async def main():
        server = echo_server()
        host, port = await server.start()
        try:
            sess = await rpc.connect_session(host, port, name="s",
                                             grace_s=0.0)
            for conn in list(server.connections):
                await conn.close()
            assert (await sess.call("Echo", {"v": 1}, timeout=10))["v"] == 1
            await sess.close()
        finally:
            await server.stop()

    run(main())


def test_deliberate_close_does_not_fire_on_close():
    async def main():
        server = echo_server()
        host, port = await server.start()
        try:
            sess = await rpc.connect_session(host, port, name="s")
            fired = []
            sess.on_close(lambda: fired.append(1))
            await sess.close()
            assert fired == []
            assert sess.closed
        finally:
            await server.stop()

    run(main())


def test_on_reconnect_runs_before_next_call():
    async def main():
        order = []
        server = rpc.RpcServer(
            {"Echo": lambda c, p: order.append("call") or {}}, name="t")
        host, port = await server.start()
        try:
            async def handshake(conn):
                order.append("handshake")

            sess = await rpc.connect_session(host, port, name="s",
                                             grace_s=10.0,
                                             on_reconnect=handshake)
            await sess.call("Echo", {})
            for conn in list(server.connections):
                await conn.close()
            await sess.call("Echo", {}, timeout=10)
            assert order == ["call", "handshake", "call"]
            await sess.close()
        finally:
            await server.stop()

    run(main())


def test_dial_raises_after_deadline_on_dead_port():
    async def main():
        server = echo_server()
        host, port = await server.start()
        await server.stop()  # port now refuses connections
        with pytest.raises((OSError, asyncio.TimeoutError)):
            await rpc.dial(host, port, timeout=0.5)

    run(main())


def test_one_way_partition_times_out_then_heals():
    """A directional blackhole (sockets open, frames eaten) must look
    like silence — calls time out, the session stays up — and a heal
    restores service on the same session."""
    async def main():
        server = echo_server()
        host, port = await server.start()
        chaos = NetChaos(seed=13).start()
        try:
            ph, pp = chaos.link("bh", host, port)
            sess = await rpc.connect_session(ph, pp, name="bh-sess",
                                             grace_s=10.0)
            assert (await sess.call("Echo", {"v": 1}))["v"] == 1
            chaos.partition("bh", "c2s")
            with pytest.raises(asyncio.TimeoutError):
                await sess.call("Echo", {"v": 2}, timeout=0.5)
            assert not sess.closed
            assert chaos.stats("bh")["frames_blackholed"] >= 1
            chaos.heal("bh")
            assert (await sess.call("Echo", {"v": 3}, timeout=10))["v"] == 3
            await sess.close()
        finally:
            await server.stop()
            chaos.stop()

    run(main())


def test_accept_then_close_peer_does_not_spin_redials():
    """A peer that ACCEPTS and instantly closes (half-up proxy, load
    balancer with no healthy backend) looks like a successful reconnect.
    Without cross-cycle backoff memory the session re-dials at connect
    speed (observed: ~250 reconnects/s against a refusing NetChaos
    link). The streak detector must keep backing off across these fake
    successes — and the session must still recover once a real server
    is back on the port."""
    async def main():
        server = echo_server()
        host, port = await server.start()
        sess = await rpc.connect_session(host, port, name="s",
                                         grace_s=30.0)
        assert (await sess.call("Echo", {"v": 1}))["v"] == 1
        await server.stop()

        accepts = {"n": 0}

        async def accept_close(reader, writer):
            accepts["n"] += 1
            writer.close()

        sick = await asyncio.start_server(accept_close, host, port)
        await asyncio.sleep(1.5)  # let the redial loop run against it
        sick.close()
        await sick.wait_closed()
        # connect-speed spinning would be hundreds of accepts here.
        assert accepts["n"] <= 10, \
            f"redial loop spun {accepts['n']} times in 1.5s"
        assert not sess.closed, "session failed before grace expired"

        server2 = rpc.RpcServer({"Echo": lambda c, p: {"v": p["v"]}},
                                name="t2")
        await server2.start(host=host, port=port)
        try:
            assert (await sess.call("Echo", {"v": 2}, timeout=15))["v"] == 2
            await sess.close()
        finally:
            await server2.stop()

    run(main())


def test_a_cut_soon_after_a_retried_reconnect_does_not_fail_the_session():
    """Two outages close together (a link that flaps twice): the first
    costs the session at least one failed dial (the handshake meets a
    refusing link), the second cuts the new connection younger than
    _MIN_STABLE_S. The second is a continuation of the first's streak,
    with the streak's OWN start as its grace anchor: the session redials
    and answers. (The anchor was only taken when a streak began with a
    quick death, so this one read its deadline off 0.0 and failed at
    once, "flapping (accept-then-close) for 30s", with 29 s of grace
    left: a raylet exited on the second of two 0.5-s flaps.)"""
    async def main():
        server = echo_server()
        host, port = await server.start()
        chaos = NetChaos(seed=3).start()
        try:
            ph, pp = chaos.link("twice", host, port)

            async def handshake(conn):
                await conn.call("Echo", {"v": 0}, timeout=5)

            sess = await rpc.connect_session(ph, pp, name="twice",
                                             grace_s=30.0,
                                             on_reconnect=handshake)
            assert (await sess.call("Echo", {"v": 1}))["v"] == 1
            # The first connection HELD (a raylet's has, for hours): the
            # first outage begins a streak, it does not continue one.
            await asyncio.sleep(rpc._MIN_STABLE_S + 0.1)
            await asyncio.to_thread(chaos.flap, "twice", 0.3)
            assert (await sess.call("Echo", {"v": 2}, timeout=10))["v"] == 2
            assert chaos.stats("twice")["conns_refused"] >= 1
            chaos.cut("twice")      # (well inside _MIN_STABLE_S of it)
            assert (await sess.call("Echo", {"v": 3}, timeout=10))["v"] == 3
            assert not sess.closed and sess.reconnects >= 2
            await sess.close()
        finally:
            await server.stop()
            chaos.stop()

    run(main())


def test_netchaos_deterministic_per_seed():
    """Same seed, same per-direction rng draw sequence — the fault
    schedule replays exactly."""
    from ray_tpu.test_utils import _ChaosLink

    seqs = []
    for _ in range(2):
        lk = _ChaosLink("x", ("127.0.0.1", 1), 42)
        seqs.append([(lk.rng["c2s"].random(), lk.rng["s2c"].random())
                     for _ in range(32)])
    assert seqs[0] == seqs[1]
    other = _ChaosLink("y", ("127.0.0.1", 1), 42)
    assert [other.rng["c2s"].random() for _ in range(32)] != \
        [a for a, _ in seqs[0]]
