"""TPU/accelerator autodetection for node resource specs.

Re-design of the reference's accelerator detection
(reference: python/ray/_private/accelerator.py — TPU chip count from
/dev/accel* at :155, version from GCE metadata/env at :177-212;
python/ray/util/accelerators/accelerators.py:9-11 TPU-V{2,3,4} constants;
TPU_VISIBLE_CHIPS isolation in ray_constants.py).

TPU is first-class here: detection also surfaces the pod-slice topology
(worker count, slice name) as node labels, so the scheduler can gang-place
onto ICI-connected hosts (STRICT_ICI placement groups).
"""

from __future__ import annotations

import glob
import os

TPU_RESOURCE = "TPU"

# accelerator_type constants (parity: util/accelerators/accelerators.py)
TPU_V2 = "TPU-V2"
TPU_V3 = "TPU-V3"
TPU_V4 = "TPU-V4"
TPU_V5E = "TPU-V5E"
TPU_V5P = "TPU-V5P"
TPU_V6E = "TPU-V6E"

# Environment overrides (TPU-VM images set these; tests set them too).
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"   # e.g. "v4-32"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_SLICE_NAME_ENV = "TPU_NAME"


def detect_tpu_chip_count() -> int:
    """Count local TPU chips (reference: accelerator.py:155 /dev/accel*)."""
    visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if visible is not None:
        return len([c for c in visible.split(",") if c.strip() != ""])
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    vfio = glob.glob("/dev/vfio/[0-9]*")
    if vfio:
        return len(vfio)
    return 0


def detect_tpu_version() -> str | None:
    """Map an accelerator-type string like 'v4-32' to TPU-V4 (reference:
    accelerator.py:177-212 reads GCE metadata; here env-only, metadata
    lookup is a provider concern in the autoscaler)."""
    acc_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV, "")
    if not acc_type:
        return None
    gen = acc_type.split("-")[0].lower()
    return {
        "v2": TPU_V2, "v3": TPU_V3, "v4": TPU_V4,
        "v5litepod": TPU_V5E, "v5e": TPU_V5E, "v5p": TPU_V5P, "v6e": TPU_V6E,
    }.get(gen)


def tpu_slice_labels() -> dict[str, str]:
    """Node labels describing the ICI slice this host belongs to.

    `tpu-slice`: slice identity — nodes sharing it are ICI-connected and
    live/die together (the gang-lease unit, SURVEY.md §7 hard parts).
    `tpu-worker-id`: this host's index within the slice.
    """
    labels = {}
    slice_name = os.environ.get(TPU_SLICE_NAME_ENV)
    if slice_name:
        labels["tpu-slice"] = slice_name
    # Generic provider-node identity (non-TPU clouds: the AWS provider's
    # user-data bootstrap sets it so the autoscaler can map the GCS node
    # back to the instance for idle-drain-terminate).
    node_name = os.environ.get("RAY_TPU_NODE_NAME")
    if node_name:
        labels["node-name"] = node_name
    worker_id = os.environ.get(TPU_WORKER_ID_ENV)
    if worker_id is not None:
        labels["tpu-worker-id"] = worker_id
    acc_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
    if acc_type:
        labels["tpu-accelerator-type"] = acc_type
    return labels


# ---------------------------------------------------------------------------
# Per-lease accelerator isolation for pool workers.
#
# Reference behavior: the raylet exports CUDA_VISIBLE_DEVICES /
# TPU_VISIBLE_CHIPS per lease so a worker that did not reserve an
# accelerator cannot touch it (ray_constants.py TPU_VISIBLE_CHIPS).
# JAX analog: the platform choice is fixed at first backend use, and a
# chip belongs to one process at a time.  So pool workers pin jax right
# after it is imported (import hook), or at the first task when the
# zygote pre-imported it: "cpu" unless the task being executed holds a
# TPU resource lease, the lease platform if it does.  A lease-holder
# that cannot get its platform fails; it never computes on the CPU under
# the chip's name.
# ---------------------------------------------------------------------------

# The one test seam: the platform a TPU lease-holder's jax is pinned to
# (tests run lease-holders on the virtual CPU devices).  Workers without
# a lease are pinned to "cpu" whatever this says.
LEASE_PLATFORM_ENV = "RAY_TPU_JAX_PLATFORM"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_current_task_has_tpu: bool = False
# Platform jax was actually pinned to in this process (None = not yet
# imported/pinned). Frozen after first jax import — jax cannot switch
# backends once initialized.
_pinned_platform: str | None = None
_lease_backend_verified: bool = False


def set_current_task_tpu(has_tpu: bool) -> None:
    global _current_task_has_tpu
    _current_task_has_tpu = has_tpu


def pinned_platform() -> str | None:
    return _pinned_platform


def lease_platform() -> str:
    return os.environ.get(LEASE_PLATFORM_ENV) or "tpu"


def compile_cache_dir() -> str:
    """Where the workers of this checkout keep jax's persistent compile
    cache: the directory JAX_COMPILATION_CACHE_DIR names, else one fixed
    path inside the checkout.  The path is part of the cache key, so it
    never contains a pid, a session id or a temporary name."""
    return os.environ.get(COMPILE_CACHE_ENV) \
        or os.path.join(_CHECKOUT, ".jax_cache")


def current_task_needs_fresh_worker() -> bool:
    """True when this worker's frozen jax pin can't serve the current
    task: the task holds a TPU lease but jax was pinned (by an earlier
    task without one) to another platform than a lease-holder gets.  The
    task must be retried on a fresh worker (whose first pin is the
    lease's)."""
    return _current_task_has_tpu and _pinned_platform is not None \
        and _pinned_platform != lease_platform()


def _pin_jax_platform(jax_module) -> None:
    global _pinned_platform
    plat = lease_platform() if _current_task_has_tpu else "cpu"
    jax_module.config.update("jax_platforms", plat)
    _pinned_platform = plat
    if _current_task_has_tpu and not os.environ.get(COMPILE_CACHE_ENV):
        # Lease-holders are the processes that compile for the chip.
        # With the variable set jax already uses that directory.
        jax_module.config.update("jax_compilation_cache_dir",
                                 compile_cache_dir())


def verify_lease_backend() -> None:
    """Once per lease-holder, as its backend comes up: the devices are
    the platform that was pinned, or the task fails.  Pinning "tpu"
    explicitly makes jax raise when the chip cannot be opened; this
    catches what is left — a backend that was initialized before the pin
    and so ignored it."""
    global _lease_backend_verified
    import sys

    if _lease_backend_verified or not _current_task_has_tpu:
        return
    jax_module = sys.modules.get("jax")
    if jax_module is None or _pinned_platform is None:
        return  # not imported yet: the import hook pins and verifies
    got = jax_module.devices()[0].platform
    if got != _pinned_platform:
        raise RuntimeError(
            f"task holds a TPU lease and jax was pinned to "
            f"{_pinned_platform!r}, but its devices are {got!r}: refusing "
            f"to run a lease-holder on another platform")
    _lease_backend_verified = True


def install_worker_jax_isolation() -> None:
    """Install the jax import hook (idempotent; pool workers only)."""
    import importlib.abc
    import importlib.machinery
    import sys

    if "jax" in sys.modules:
        # Pre-imported jax (site hooks, or a zygote-forked worker): no
        # backend is initialized yet, so the pin can — and must — wait
        # until the first task, when the TPU lease is actually known.
        # Pinning "cpu" here would freeze every such worker off the TPU.
        return
    if any(isinstance(f, _JaxIsolationFinder) for f in sys.meta_path):
        return
    sys.meta_path.insert(0, _JaxIsolationFinder())


def ensure_jax_pinned() -> None:
    """Task-time pin for workers whose jax was pre-imported (the import
    hook never fired). Safe to call repeatedly; first call wins, matching
    the freeze-on-first-import semantics of the hook path."""
    import sys

    if _pinned_platform is None and "jax" in sys.modules:
        _pin_jax_platform(sys.modules["jax"])


class _JaxIsolationFinder:
    """Meta-path finder that pins the jax platform right after the top-level
    `jax` package finishes importing (before any backend is initialized)."""

    _in_find = False

    def find_spec(self, name, path=None, target=None):
        if name != "jax" or _JaxIsolationFinder._in_find:
            return None
        import importlib.util

        _JaxIsolationFinder._in_find = True
        try:
            spec = importlib.util.find_spec("jax")
        finally:
            _JaxIsolationFinder._in_find = False
        if spec is None or spec.loader is None:
            return None
        spec.loader = _PinningLoader(spec.loader)
        return spec


class _PinningLoader:
    def __init__(self, inner):
        self._inner = inner

    def create_module(self, spec):
        return self._inner.create_module(spec)

    def exec_module(self, module):
        self._inner.exec_module(module)
        _pin_jax_platform(module)
        verify_lease_backend()

    def __getattr__(self, item):
        return getattr(self._inner, item)


def node_resources_and_labels() -> tuple[dict, dict]:
    """Auto-detected resource/label additions for this node."""
    resources: dict[str, float] = {}
    chips = detect_tpu_chip_count()
    if chips:
        resources[TPU_RESOURCE] = float(chips)
        version = detect_tpu_version()
        if version:
            resources[f"accelerator_type:{version}"] = 1.0
    return resources, tpu_slice_labels()
