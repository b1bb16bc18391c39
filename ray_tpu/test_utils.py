"""Chaos-testing utilities.

Parity: reference _private/test_utils.py:1401 NodeKillerActor (random
raylet SIGKILL during workloads) + release/nightly_tests/setup_chaos.py.
The in-process `NodeKiller` thread kills worker raylets from a
`cluster_utils.Cluster` at an interval, optionally re-adding replacements,
while the test drives a workload — the assertion is that retries, actor
restarts, and lineage reconstruction keep the workload correct
(SURVEY.md §5 failure-detection inventory).
"""

from __future__ import annotations

import asyncio
import random
import threading
import time


class NodeKiller:
    """Kills random non-head nodes of a Cluster every `interval_s`.

    with NodeKiller(cluster, interval_s=0.5, respawn=True,
                    node_args={"num_cpus": 2}):
        ... run workload ...
    """

    def __init__(self, cluster, *, interval_s: float = 1.0,
                 respawn: bool = True, node_args: dict | None = None,
                 max_kills: int | None = None, seed: int | None = None):
        self.cluster = cluster
        self.interval_s = interval_s
        self.respawn = respawn
        self.node_args = node_args or {}
        self.max_kills = max_kills
        self.rng = random.Random(seed)
        self.kills = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def _victims(self):
        return [n for n in self.cluster._node.nodes
                if n is not self.cluster.head_node
                and n.proc.poll() is None]

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            if self.max_kills is not None and self.kills >= self.max_kills:
                return
            victims = self._victims()
            if not victims:
                continue
            node = self.rng.choice(victims)
            try:
                self.cluster.remove_node(node)
                self.kills += 1
            except Exception:
                continue
            if self.respawn:
                try:
                    self.cluster.add_node(**self.node_args)
                except Exception:
                    pass

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="node-killer")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class NodePreempter:
    """Graceful-preemption chaos: drain-with-deadline, then kill — the
    spot/maintenance reclamation model (NodeKiller's SIGKILL cousin;
    reference: autoscaler.proto DrainNode preceding reclaim). The
    assertion model inverts NodeKiller's: a PREEMPTED node's death must
    be a non-event — zero lineage reconstructions, zero client-visible
    actor errors (drain evacuated everything first).

    Deterministic use (what most tests want)::

        preempter = NodePreempter(cluster, deadline_s=10)
        result = preempter.preempt(node)   # drain → DRAINED → kill
        assert result["state"] == "DRAINED"

    Interval mode mirrors NodeKiller::

        with NodePreempter(cluster, interval_s=2.0, respawn=True,
                           node_args={"num_cpus": 2}) as p:
            ... workload ...
        assert p.preemptions >= 1

    Stochastic STEP schedule (elastic-train chaos, reproducible): a
    preemption every ~`step_interval` training steps with ±`step_jitter`
    relative jitter, gaps drawn from the seeded rng — the same seed
    replays the same schedule. `step_source` is a zero-arg callable
    returning the workload's current global step::

        p = NodePreempter(cluster, deadline_s=5, step_interval=20,
                          step_source=lambda: trainer_step(), seed=7,
                          respawn=True, node_args={"num_cpus": 2})
        with p:
            ... train ...
        assert p.preemptions >= 2
    """

    def __init__(self, cluster, *, deadline_s: float = 10.0,
                 reason: str = "preemption", interval_s: float | None = None,
                 respawn: bool = False, node_args: dict | None = None,
                 max_preemptions: int | None = None, seed: int | None = None,
                 step_interval: int | None = None,
                 step_jitter: float = 0.3, step_source=None):
        self.cluster = cluster
        self.deadline_s = deadline_s
        self.reason = reason
        self.interval_s = interval_s
        self.respawn = respawn
        self.node_args = node_args or {}
        self.max_preemptions = max_preemptions
        self.rng = random.Random(seed)
        self.preemptions = 0
        self.results: list[dict] = []
        self.step_interval = step_interval
        self.step_jitter = step_jitter
        self.step_source = step_source
        self.step_schedule: list[int] = []  # steps preemptions fired at
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def preempt(self, node, *, kill: bool = True) -> dict:
        """Drain one node with the configured deadline, wait for
        DRAINED, then (by default) kill it. Returns the drain response
        (its "state" is DRAINED on a clean evacuation)."""
        result = self.cluster.drain_node(
            node, deadline_s=self.deadline_s, reason=self.reason,
            wait=True)
        self.results.append(result)
        if kill:
            self.cluster.remove_node(node)
        self.preemptions += 1
        return result

    def _victims(self):
        return [n for n in self.cluster._node.nodes
                if n is not self.cluster.head_node
                and n.proc.poll() is None]

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            if self.max_preemptions is not None \
                    and self.preemptions >= self.max_preemptions:
                return
            victims = self._victims()
            if not victims:
                continue
            node = self.rng.choice(victims)
            try:
                self.preempt(node)
            except Exception:
                continue
            if self.respawn:
                try:
                    self.cluster.add_node(**self.node_args)
                except Exception:
                    pass

    def _next_gap(self) -> int:
        """Steps until the next preemption: step_interval ± jitter,
        drawn from the seeded rng (deterministic schedule per seed)."""
        lo = max(1, int(round(self.step_interval * (1 - self.step_jitter))))
        hi = max(lo, int(round(self.step_interval * (1 + self.step_jitter))))
        return self.rng.randint(lo, hi)

    def _step_loop(self):
        target = self._next_gap()
        while not self._stop.wait(0.05):
            if self.max_preemptions is not None \
                    and self.preemptions >= self.max_preemptions:
                return
            try:
                step = int(self.step_source())
            except Exception:
                continue
            if step < target:
                continue
            victims = self._victims()
            if not victims:
                continue
            node = self.rng.choice(victims)
            try:
                self.preempt(node)
                self.step_schedule.append(step)
            except Exception:
                continue
            if self.respawn:
                try:
                    self.cluster.add_node(**self.node_args)
                except Exception:
                    pass
            target = step + self._next_gap()

    def start(self):
        if self.step_interval is not None:
            assert self.step_source is not None, \
                "step schedule needs step_source (current-step callable)"
            self._thread = threading.Thread(target=self._step_loop,
                                            daemon=True,
                                            name="node-preempter")
        else:
            assert self.interval_s is not None, \
                "interval mode needs interval_s; use preempt() directly"
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="node-preempter")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class _ChaosLink:
    """One proxied TCP link (internal to NetChaos).

    Fault knobs are plain attributes read by the pump coroutines on
    every frame; writes from the test thread are atomic under the GIL,
    so no locking is needed for test purposes. Each direction gets its
    own seeded rng so the two pumps never interleave draws — the same
    seed replays the same drop/dup schedule per stream.
    """

    def __init__(self, name: str, upstream: tuple[str, int], seed):
        self.name = name
        self.upstream = upstream
        self.rng = {d: random.Random(f"{seed}:{name}:{d}")
                    for d in ("c2s", "s2c")}
        self.drop = 0.0       # P(silently drop a frame)
        self.delay_s = 0.0    # added one-way latency per frame
        self.dup = 0.0        # P(forward a frame twice)
        self.blackhole: set[str] = set()  # directions silently eaten
        self.refusing = False  # new connections rejected (link "down")
        self.server = None
        self.host: str | None = None
        self.port: int | None = None
        self.writers: list = []  # live writers, for cut()
        self.stats = {"conns": 0, "conns_refused": 0,
                      "frames_forwarded": 0, "frames_dropped": 0,
                      "frames_duplicated": 0, "frames_blackholed": 0}


def scale_chaos_schedule(seed: int, n_flaps: int) -> dict:
    """The scale-chaos gate's hostility, as a pure function of the
    seed: flap (offset, duration) pairs and the two spot-kill offsets,
    in wave-relative seconds. `bench.py --scale-chaos` records this in
    its artifact so a certification run can be replayed from its JSON
    alone."""
    rng = random.Random(seed)
    flaps = [(round(rng.uniform(0.05, 0.6), 3),
              round(rng.uniform(0.2, 0.45), 3))
             for _ in range(n_flaps)]
    kills = [round(rng.uniform(0.1, 0.5), 3) for _ in range(2)]
    return {"seed": seed, "flaps": flaps, "kills": kills}


class NetChaos:
    """Seeded, deterministic network fault injector: a frame-aware TCP
    proxy interposed on the repo's length-prefixed msgpack RPC links.

    Faults operate on WHOLE frames (4-byte BE length + body, the
    _private/rpc.py wire format), so injected drops/dups/partitions
    exercise the resilient-session layer (reconnect, replay, server-side
    dedup, SUSPECT-before-DEAD) rather than producing protocol garbage.
    Composable with NodeKiller/NodePreempter — proxy the control links,
    then kill/preempt through the same cluster.

    Usage::

        chaos = NetChaos(seed=7).start()
        ph, pp = chaos.link("n1-gcs", gcs_host, gcs_port)
        node = cluster.add_node(num_cpus=2, gcs_addr=(ph, pp))
        chaos.set_faults("n1-gcs", drop=0.05, delay_s=0.01, dup=0.02)
        chaos.partition("n1-gcs", "c2s")  # one-way: raylet->GCS eaten
        chaos.heal("n1-gcs")
        chaos.flap("n1-gcs", down_s=0.5)  # cut + refuse, then heal
        chaos.cut("n1-gcs")               # close live sockets once
        print(chaos.stats("n1-gcs"))
        chaos.stop()

    Fault vocabulary:
      - drop/delay_s/dup — per-frame probabilistic faults (seeded rng).
      - partition(direction=None) — silently eat frames one way ("c2s"
        client->server, "s2c" server->client) or both; sockets stay OPEN.
        This is the asymmetric partition SUSPECT exists for.
      - cut() — close every live proxied socket (clean connection loss).
      - flap(down_s) — refuse + cut for down_s, then heal: the
        transient outage that must be a non-event (no false DEAD).
    """

    def __init__(self, seed: int | None = None):
        self.seed = seed if seed is not None else random.randrange(2**31)
        self._links: dict[str, _ChaosLink] = {}
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None

    def start(self):
        started = threading.Event()

        def run():
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)
            self._loop.call_soon(started.set)
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="net-chaos")
        self._thread.start()
        if not started.wait(10.0):
            raise RuntimeError("NetChaos loop failed to start")
        return self

    def link(self, name: str, upstream_host: str,
             upstream_port: int) -> tuple[str, int]:
        """Open a proxy listener for `upstream`; returns (host, port)
        to hand to the client side (e.g. Cluster.add_node(gcs_addr=))."""
        assert self._loop is not None, "call start() first"
        assert name not in self._links, f"link {name!r} already exists"
        link = _ChaosLink(name, (upstream_host, upstream_port), self.seed)
        asyncio.run_coroutine_threadsafe(
            self._open(link), self._loop).result(10.0)
        self._links[name] = link
        return link.host, link.port

    async def _open(self, link: _ChaosLink):
        async def on_conn(reader, writer):
            if link.refusing:
                link.stats["conns_refused"] += 1
                writer.close()
                return
            try:
                up_reader, up_writer = await asyncio.open_connection(
                    *link.upstream)
            except OSError:
                link.stats["conns_refused"] += 1
                writer.close()
                return
            from ray_tpu._private.common import supervised_task

            link.stats["conns"] += 1
            link.writers += [writer, up_writer]
            pumps = [
                supervised_task(
                    self._pump(link, reader, up_writer, "c2s"),
                    name=f"chaos-{link.name}-c2s"),
                supervised_task(
                    self._pump(link, up_reader, writer, "s2c"),
                    name=f"chaos-{link.name}-s2c"),
            ]
            # One side dying kills the whole proxied conn, like a real
            # TCP reset would.
            await asyncio.wait(pumps, return_when=asyncio.FIRST_COMPLETED)
            for p in pumps:
                p.cancel()
            for w in (writer, up_writer):
                try:
                    w.close()
                except Exception:
                    pass
                if w in link.writers:
                    link.writers.remove(w)

        link.server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        link.host, link.port = link.server.sockets[0].getsockname()[:2]

    async def _pump(self, link: _ChaosLink, reader, writer, direction: str):
        rng = link.rng[direction]
        try:
            while True:
                header = await reader.readexactly(4)
                body = await reader.readexactly(int.from_bytes(header, "big"))
                frame = header + body
                if direction in link.blackhole:
                    link.stats["frames_blackholed"] += 1
                    continue
                if link.drop and rng.random() < link.drop:
                    link.stats["frames_dropped"] += 1
                    continue
                if link.delay_s:
                    await asyncio.sleep(link.delay_s)
                writer.write(frame)
                link.stats["frames_forwarded"] += 1
                if link.dup and rng.random() < link.dup:
                    # Replays the identical REQUEST frame — exercises
                    # the server-side (session_id, seq) reply cache.
                    writer.write(frame)
                    link.stats["frames_duplicated"] += 1
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass

    def set_faults(self, name: str, *, drop: float = 0.0,
                   delay_s: float = 0.0, dup: float = 0.0):
        link = self._links[name]
        link.drop, link.delay_s, link.dup = drop, delay_s, dup

    def partition(self, name: str, direction: str | None = None):
        """Silently eat frames — one way ("c2s"/"s2c") or both (None).
        Sockets stay open: neither side sees a connection error, only
        silence, so failure detection must come from heartbeat expiry."""
        link = self._links[name]
        link.blackhole |= {direction} if direction else {"c2s", "s2c"}

    def heal(self, name: str):
        """Lift partitions and connection refusal (probabilistic faults
        set via set_faults persist until reset explicitly)."""
        link = self._links[name]
        link.blackhole.clear()
        link.refusing = False

    def cut(self, name: str):
        """Close every live proxied socket on this link — both ends see
        a clean connection loss (the reconnect/replay trigger)."""
        link = self._links[name]

        def _close():
            for w in list(link.writers):
                try:
                    w.close()
                except Exception:
                    pass
            link.writers.clear()

        self._loop.call_soon_threadsafe(_close)

    def flap(self, name: str, down_s: float = 0.5):
        """Take the link fully down (refuse new conns + cut live ones)
        for `down_s`, then bring it back. Blocks the calling thread."""
        link = self._links[name]
        link.refusing = True
        self.cut(name)
        time.sleep(down_s)
        self.heal(name)

    def stats(self, name: str) -> dict:
        return dict(self._links[name].stats)

    def stop(self):
        if self._loop is None:
            return

        async def _shutdown():
            for link in self._links.values():
                if link.server is not None:
                    link.server.close()
                for w in list(link.writers):
                    try:
                        w.close()
                    except Exception:
                        pass
                link.writers.clear()
            # Every proxied connection has a handler and two pumps on this
            # loop, and closing their sockets only ASKS them to end: a loop
            # stopped before they did leaves them pending, and they are
            # then destroyed pending ("Task was destroyed but it is
            # pending!") whenever the collector finds the loop.
            rest = [t for t in asyncio.all_tasks()
                    if t is not asyncio.current_task()]
            for t in rest:
                t.cancel()
            await asyncio.gather(*rest, return_exceptions=True)

        loop = self._loop
        try:
            asyncio.run_coroutine_threadsafe(_shutdown(), loop).result(10.0)
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5)
            if not self._thread.is_alive():
                loop.close()
        self._loop = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def wait_for_condition(predicate, timeout: float = 30.0,
                       retry_interval_ms: float = 100.0) -> None:
    """Parity: reference _private/test_utils.py wait_for_condition."""
    deadline = time.monotonic() + timeout
    last_exc = None
    while time.monotonic() < deadline:
        try:
            if predicate():
                return
        except Exception as e:  # noqa: BLE001
            last_exc = e
        time.sleep(retry_interval_ms / 1000.0)
    msg = f"condition not met within {timeout}s"
    if last_exc is not None:
        msg += f" (last error: {last_exc})"
    raise TimeoutError(msg)
