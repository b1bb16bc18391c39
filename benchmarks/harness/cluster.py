"""Scaffolding copied from `chip_smoke.py` (which stays as it is): the
driver never initialises a JAX backend, a cell that cannot be scheduled
fails in seconds, and nobody leaves before the lease-holder's pid is gone
(a chip still held when the command exits does not answer the next one).
"""

from __future__ import annotations

import os
import time

from benchmarks.harness.loader import BenchmarkError

SCHEDULE_DEADLINE_S = 5.0
EXIT_DEADLINE_S = 60.0


def require_tpu_resource(chips: int) -> None:
    import ray_tpu
    from ray_tpu._private import accelerator

    deadline = time.monotonic() + SCHEDULE_DEADLINE_S
    while True:
        total = ray_tpu.cluster_resources()
        if total.get("TPU", 0) >= chips:
            return
        if time.monotonic() > deadline:
            raise BenchmarkError(
                f"no TPU device to lease: the cell needs TPU: {chips} and "
                f"this node offers {total} (detect_tpu_chip_count() = "
                f"{accelerator.detect_tpu_chip_count()})")
        time.sleep(0.2)


def wait_for_exit(pid: int) -> float:
    """Seconds until process `pid` (on this host) is gone."""
    t0 = time.monotonic()
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() - t0 > EXIT_DEADLINE_S:
            raise BenchmarkError(f"worker {pid} still alive after "
                                 f"{EXIT_DEADLINE_S:.0f}s; the chip is held")
        time.sleep(0.05)
    return time.monotonic() - t0


def device_report() -> dict:
    """Run INSIDE the lease-holder: what jax and the runtime say there."""
    import jax

    from ray_tpu._private import accelerator

    d = jax.devices()[0]
    return {"pid": os.getpid(), "platform": d.platform,
            "kind": d.device_kind, "count": len(jax.devices()),
            "pinned_platform": accelerator.pinned_platform(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "t_report": time.monotonic()}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (inside the lease-holder)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def memory_headroom() -> dict:
    """The fullest chip's allocator (inside the lease-holder): live bytes at
    their peak, the scratch reserved for programs, and the limit.  Where
    peak + reserved comes near the limit, loading a program can fail with
    RESOURCE_EXHAUSTED in one run and not the next (PERF.md, section 6)."""
    import jax

    stats = max(((d.memory_stats() or {}) for d in jax.devices()),
                key=lambda m: m.get("peak_bytes_in_use", 0))
    return {k: int(stats.get(k, 0)) for k in
            ("peak_bytes_in_use", "bytes_reserved", "bytes_limit")}


def check_lease_holder(who: dict, chips: int, platform: str) -> None:
    if who["pid"] == os.getpid():
        raise BenchmarkError("the cell ran in the driver process")
    if who["platform"] != platform or who["pinned_platform"] != platform:
        raise BenchmarkError(
            f"the lease-holder is on {who['platform']!r} (pinned "
            f"{who['pinned_platform']!r}); this run measures {platform!r}")
    # The CPU rehearsal's lease-holder sees the test's virtual devices.
    if platform == "tpu" and who["count"] != chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s) and the "
                             f"lease-holder sees {who['count']}")


def driver_stayed_off_jax() -> None:
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise BenchmarkError("the driver initialised a JAX backend: it must "
                             "stay off the chip")


def uncap_compile_cache() -> None:
    """Call first thing in a lease-holder.  A machine may cap jax's
    persistent cache (JAX_COMPILATION_CACHE_MAX_SIZE; 192 MiB on the chip
    tool's machines).  The train cell's executables pass that together,
    evict each other, and every run compiles again: 230 s of set-up in
    every run instead of about 60 (my chip runs, PR 23; the cache's own log
    showed the same key written and missed in the next process).  The
    directory stays the one the machine or the checkout gives."""
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts programs lowered or compiled in this process, by jax's own
    monitoring events; none may happen inside a measured window."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1
