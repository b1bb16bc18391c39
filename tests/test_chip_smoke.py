"""chip_smoke.py's phases at TINY widths on one CPU cluster, and the rules
a TPU lease-holder lives by (platform pin, compile cache).

The cluster is given `TPU` resources by hand, so these are tasks that
really hold a TPU lease; `RAY_TPU_JAX_PLATFORM=cpu` (conftest) is the seam
that tells the workers which platform such a lease-holder gets here.
"""

import dataclasses
import os
import sys
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402
import ray_tpu  # noqa: E402
from ray_tpu._private import accelerator  # noqa: E402
from ray_tpu.models.llama import TINY  # noqa: E402

TINY_FLASH = dataclasses.replace(TINY, attention="flash")


@pytest.fixture(scope="module")
def tpu_cluster():
    from tests.conftest import _fast_config

    ray_tpu.init(num_cpus=4, resources={"TPU": 4}, config=_fast_config())
    yield
    ray_tpu.shutdown()


def _lease_holder_checks(worker: dict):
    assert worker["pid"] != os.getpid()
    # The seam's platform, reached without the retire-and-retry loop
    # (the phase would have timed out in it), and the in-checkout cache.
    assert worker["pinned_platform"] == worker["platform"] == "cpu"
    assert worker["compile_cache_dir"] == accelerator.compile_cache_dir()


def test_serve_phase_tiny(tpu_cluster):
    rec = chip_smoke.serve_phase(
        TINY, engine_kwargs=dict(max_batch=4, max_len=128, page_size=16,
                                 decode_chunk=4),
        prompt_lengths=(3, 9, 14, 16), max_new=12)
    _lease_holder_checks(rec["worker"])
    # f32 on the CPU: the engine is bit-equal to Generator.
    assert [c["first_divergence"] for c in rec["comparison"]] == [None] * 4
    assert rec["device_plane"]["handoff_fallbacks"] == 0
    assert rec["device_plane"]["counters"]["in_process"] > 0
    assert not os.path.exists(f"/proc/{rec['worker']['pid']}")


def test_train_phase_tiny(tpu_cluster):
    rec = chip_smoke.train_phase(TINY_FLASH, seq=64, batch=2, steps=3)
    _lease_holder_checks(rec["worker"])
    assert len(rec["losses"]) == 3 and rec["losses"][-1] < rec["losses"][0]


def test_four_chip_phase_tiny(tpu_cluster):
    rec = chip_smoke.four_chip_phase(TINY_FLASH, deep_layers=3,
                                     compare_layers=1, batch=4, seq=64,
                                     steps=2)
    _lease_holder_checks(rec["worker"])
    held = rec["sharded"]["state_bytes_per_device_after_step_1"]
    assert len(held) == 4 and len(set(held.values())) == 1
    assert rec["first_loss_delta"] < 1e-4


def test_unschedulable_phase_fails_fast(tpu_cluster, monkeypatch):
    monkeypatch.setattr(chip_smoke, "SCHEDULE_DEADLINE_S", 0.5)
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU device"):
        chip_smoke.require_tpu_resource(64)


# ---- a lease-holder is on its platform or fails ---------------------------


class _FakeJax:
    """Stands in for the jax module at pin time: records config updates."""

    def __init__(self, fail=False):
        self.updates = {}
        self.config = types.SimpleNamespace(update=self._update)
        self._fail = fail

    def _update(self, key, value):
        if self._fail:
            raise RuntimeError("cannot pin")
        self.updates[key] = value


@pytest.fixture
def fresh_pin(monkeypatch):
    """accelerator's per-process pin state, as in a worker before its
    first task; the environment variables are the caller's to set."""
    monkeypatch.setattr(accelerator, "_pinned_platform", None)
    monkeypatch.setattr(accelerator, "_current_task_has_tpu", False)
    monkeypatch.setattr(accelerator, "_lease_backend_verified", False)
    monkeypatch.delenv(accelerator.COMPILE_CACHE_ENV, raising=False)
    return monkeypatch


@pytest.mark.parametrize("override,has_lease,want", [
    (None, True, "tpu"), (None, False, "cpu"),
    ("cpu", True, "cpu"), ("cpu", False, "cpu"), ("tpu", False, "cpu"),
])
def test_pin_platform(fresh_pin, override, has_lease, want):
    if override is None:
        fresh_pin.delenv(accelerator.LEASE_PLATFORM_ENV, raising=False)
    else:
        fresh_pin.setenv(accelerator.LEASE_PLATFORM_ENV, override)
    fake = _FakeJax()
    accelerator.set_current_task_tpu(has_lease)
    accelerator._pin_jax_platform(fake)
    assert fake.updates["jax_platforms"] == want
    assert accelerator.pinned_platform() == want
    # Whatever the seam says, the first pin serves the task it was made for.
    assert not accelerator.current_task_needs_fresh_worker()


def test_cpu_pinned_worker_cannot_serve_a_lease(fresh_pin):
    fresh_pin.delenv(accelerator.LEASE_PLATFORM_ENV, raising=False)
    accelerator._pin_jax_platform(_FakeJax())      # a task without a lease
    accelerator.set_current_task_tpu(True)         # then one with
    assert accelerator.current_task_needs_fresh_worker()
    # ... but under the seam a lease-holder gets "cpu": no retire loop.
    fresh_pin.setenv(accelerator.LEASE_PLATFORM_ENV, "cpu")
    assert not accelerator.current_task_needs_fresh_worker()


def test_pin_error_is_not_swallowed(fresh_pin):
    accelerator.set_current_task_tpu(True)
    with pytest.raises(RuntimeError, match="cannot pin"):
        accelerator._pin_jax_platform(_FakeJax(fail=True))
    assert accelerator.pinned_platform() is None


def test_lease_holder_on_another_platform_raises(fresh_pin):
    import jax

    assert jax.devices()[0].platform == "cpu"
    fresh_pin.setattr(accelerator, "_current_task_has_tpu", True)
    fresh_pin.setattr(accelerator, "_pinned_platform", "tpu")
    with pytest.raises(RuntimeError, match="refusing to run a lease-holder"):
        accelerator.verify_lease_backend()
    fresh_pin.setattr(accelerator, "_pinned_platform", "cpu")
    accelerator.verify_lease_backend()  # the pinned platform: passes, once
    assert accelerator._lease_backend_verified


# ---- a compile cache that can be placed from outside ----------------------


def test_compile_cache_env_wins(fresh_pin, tmp_path):
    fresh_pin.setenv(accelerator.COMPILE_CACHE_ENV, str(tmp_path))
    assert accelerator.compile_cache_dir() == str(tmp_path)
    fake = _FakeJax()
    accelerator.set_current_task_tpu(True)
    accelerator._pin_jax_platform(fake)
    # jax reads the variable itself: the program sets no directory in code.
    assert "jax_compilation_cache_dir" not in fake.updates


def test_compile_cache_default_is_one_fixed_path(fresh_pin):
    want = os.path.join(_REPO, ".jax_cache")
    assert accelerator.compile_cache_dir() == want
    # Nothing of this process or session in it: only the checkout's place.
    assert os.path.relpath(want, _REPO) == ".jax_cache"
    fake = _FakeJax()
    accelerator.set_current_task_tpu(True)
    accelerator._pin_jax_platform(fake)
    assert fake.updates["jax_compilation_cache_dir"] == want
    # Workers without a lease compile nothing for the chip: no cache set.
    fresh_pin.setattr(accelerator, "_pinned_platform", None)
    accelerator.set_current_task_tpu(False)
    fake = _FakeJax()
    accelerator._pin_jax_platform(fake)
    assert "jax_compilation_cache_dir" not in fake.updates
