"""Opt-out usage stats (parity: reference python/ray/_private/usage/ —
usage_lib.py collects cluster metadata on a schedule and reports it).

This build runs in egress-free environments, so the "report" sink is a
JSON file in the session directory instead of an HTTPS endpoint; the
collection schema (cluster metadata, library usage tags, counters) and
the RAY_TPU_USAGE_STATS_ENABLED=0 opt-out match the reference's shape.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_library_usages: set[str] = set()
_extra_tags: dict[str, str] = {}


def usage_stats_enabled() -> bool:
    return os.environ.get("RAY_TPU_USAGE_STATS_ENABLED", "1") not in (
        "0", "false", "False")


def record_library_usage(library: str) -> None:
    """Called on first use of data/train/tune/serve/rllib (reference:
    usage_lib.record_library_usage)."""
    with _lock:
        _library_usages.add(library)


def record_extra_usage_tag(key: str, value: str) -> None:
    with _lock:
        _extra_tags[key] = str(value)


def _collect(gcs_call=None) -> dict:
    import ray_tpu

    data = {
        "schema_version": "0.1",
        "source": "ray_tpu",
        "python_version": sys.version.split()[0],
        "os": sys.platform,
        "collected_at": time.time(),
        "libraries": sorted(_library_usages),
        "extra_tags": dict(_extra_tags),
    }
    # Passive only: NEVER import jax or initialize a backend from the
    # reporter. `jax.default_backend()` here used to spin up a PJRT
    # client inside every driver — a multi-second import racing user
    # work, and on a TPU host a driver that takes the chip its workers
    # lease (one process for each chip). Record what's already in the
    # process; accelerator inventory comes from the cluster resource view
    # below.
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        data["jax_version"] = getattr(jax_mod, "__version__", "unknown")
    try:
        nodes = ray_tpu.nodes()
        data["num_nodes"] = sum(1 for n in nodes if n.get("alive"))
        data["total_resources"] = ray_tpu.cluster_resources()
    except Exception:
        pass
    return data


class UsageStatsReporter:
    """Periodic collector writing usage_stats.json into the session dir."""

    def __init__(self, session_dir: str, interval_s: float = 300.0):
        self.path = os.path.join(session_dir, "usage_stats.json")
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        if not usage_stats_enabled():
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="usage-stats")
        self._thread.start()

    def _loop(self) -> None:
        while True:
            self.report_once()
            if self._stop.wait(self.interval_s):
                return

    def report_once(self) -> None:
        try:
            with open(self.path + ".tmp", "w") as f:
                json.dump(_collect(), f, indent=2, default=str)
            os.replace(self.path + ".tmp", self.path)
        except Exception:
            pass

    def stop(self) -> None:
        self._stop.set()
        # Join, don't just signal: a daemon thread still unwinding when
        # the interpreter finalizes gets pthread_exit'd mid-GIL-acquire,
        # which glibc turns into 'FATAL: exception not rethrown' + abort
        # (seen ~1-in-5 under load). Aim for dead-before-stop-returns;
        # if a report is wedged mid-RPC past the timeout, KEEP the
        # handle so a second stop() can re-join instead of losing track
        # of a live thread.
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=2.0)
            if t.is_alive():
                logger.warning(
                    "usage-stats reporter still alive after stop(): a "
                    "report is blocked; interpreter exit may race it")
                return
        self._thread = None
