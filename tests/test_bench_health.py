"""CLI smokes of bench.py's modes.  The three control-plane modes run on
the CPU by design; the device modes refuse to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.smoke
def test_bench_cli_serve_disagg_smoke():
    """`python bench.py --serve-disagg` is a device measurement: on a host
    without TPU chips it exits non-zero with a message that says so, and
    prints no metric line (no CPU stand-in).  The two-pool deployment
    itself is covered on the CPU by tests/test_serve_disagg.py."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_JAX_PLATFORM"] = "cpu"
    env.pop("TPU_VISIBLE_CHIPS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "--serve-disagg"],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO)
    assert r.returncode != 0
    assert "TPU" in r.stderr and "no CPU stand-in" in r.stderr
    assert '"metric"' not in r.stdout


@pytest.mark.smoke
def test_bench_cli_actor_churn_smoke():
    """`python bench.py --actor-churn` (ISSUE 18) drives the native
    control plane's RegisterActor->CreateActor->ActorReady ladder and
    the lease grant/return machine end-to-end and emits ONE JSON
    line. Small N; the artifact write is disabled
    so smoke runs never clobber a full-scale capture."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_JAX_PLATFORM"] = "cpu"
    env["RAY_TPU_BENCH_CHURN_N"] = "200"
    env["RAY_TPU_BENCH_CHURN_LAT_N"] = "50"
    env["RAY_TPU_BENCH_CHURN_TASK_S"] = "0.3"
    env["RAY_TPU_BENCH_CHURN_ARTIFACT"] = "0"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "--actor-churn"],
        capture_output=True, text=True, timeout=240, env=env, cwd=_REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "actor_churn_creations_per_s"
    extra = rec["extra"]
    assert "error" not in extra, extra
    # The acceptance floor (>=1000 creations/s) holds even at smoke
    # scale — the native ladder measures ~20k/s on a CPU container.
    assert rec["value"] >= 1000
    # Every actor ran the FULL native ladder (RegisterActor+ActorReady
    # both handled in C++), nothing fell through to Python.
    assert extra["native_handled_total"] == 2 * (
        extra["actors_created"] + extra["concurrent_churn_actors"])
    assert extra["native_fallthrough_total"] == 0
    assert extra["lease_grant_p99_ms"] >= extra["lease_grant_p50_ms"] > 0
    assert extra["tasks_per_s_under_churn"] > 0


@pytest.mark.smoke
def test_bench_cli_control_soak_smoke():
    """`python bench.py --control-soak` (ISSUE 19) at `make soak-smoke`
    scale: the default-on native control plane rides out NetChaos link
    flaps and a node preemption with zero lost and zero
    forked/duplicated creations, at least one suspect recovery, the
    grant/return cycle floor held, and the divergence breaker never
    tripped — the soak itself exits non-zero on any violation."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_JAX_PLATFORM"] = "cpu"
    env["RAY_TPU_SOAK_N"] = "40"
    env["RAY_TPU_SOAK_TASK_S"] = "0.5"
    env["RAY_TPU_SOAK_FLAPS"] = "1"
    env["RAY_TPU_SOAK_FLOOR"] = "2000"
    env["RAY_TPU_BENCH_SOAK_ARTIFACT"] = "0"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--control-soak"],
        capture_output=True, text=True, timeout=240, env=env, cwd=_REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "control_soak_cycles_per_s"
    extra = rec["extra"]
    assert "error" not in extra, extra
    assert extra["actors_alive"] == extra["actors_churned"]
    assert extra["lost"] == 0 and extra["forked"] == 0
    assert extra["suspect_recoveries"] >= 1
    assert extra["flaps"] >= 1
    assert rec["value"] >= extra["cycles_floor"]
    assert extra["divergence_trips_total"] == 0
    assert extra["native_degraded_total"] == 0


@pytest.mark.smoke
def test_bench_cli_scale_chaos_smoke():
    """`python bench.py --scale-chaos` (ISSUE 20) at `make scale-smoke`
    scale: a 16-sim-node, 2-tenant hostile run with NetChaos flaps,
    spot kills in both waves, and ONE mid-run GCS restart. The gate
    itself exits non-zero on any violation; here we additionally pin
    the certification envelope fields the artifact must carry."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_JAX_PLATFORM"] = "cpu"
    env["RAY_TPU_SCALE_NODES"] = "16"
    env["RAY_TPU_SCALE_TENANTS"] = "2"
    env["RAY_TPU_SCALE_N"] = "30"
    env["RAY_TPU_SCALE_BACKLOG"] = "1500"
    env["RAY_TPU_SCALE_LEASES"] = "600"
    env["RAY_TPU_BENCH_SCALE_ARTIFACT"] = "0"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--scale-chaos"],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "scale_chaos_lease_p99_ms"
    extra = rec["extra"]
    assert "error" not in extra, extra
    assert extra["sim_nodes"] == 16 and extra["tenants"] == 2
    assert extra["lost"] == 0 and extra["forked"] == 0
    assert extra["suspect_recoveries"] >= 1
    assert extra["spot_kills"] == 2
    rec_recovery = extra["recovery"]
    assert rec_recovery["recovering_observed"] and rec_recovery["recovered"]
    assert rec_recovery["first_grant_ms"] < rec_recovery["full_replay_ms"]
    assert rec_recovery["streamed_rows"] >= 1500
    fairness = extra["fairness"]
    assert fairness["starvation"] == 0
    assert fairness["min_ratio"] >= 0.5
    fanout = extra["fanout"]
    assert fanout["sent"] + fanout["native_batches"] > 0
    assert extra["divergence_trips_total"] == 0
    # Seed reproducibility: the schedule in the artifact is exactly
    # the pure function of the seed that test_utils exports, so a
    # certification run can be replayed from its JSON alone.
    from ray_tpu.test_utils import scale_chaos_schedule
    sched = extra["chaos_schedule"]
    expect = scale_chaos_schedule(sched["seed"], len(sched["flaps"]))
    assert sched == json.loads(json.dumps(expect))  # tuples -> lists


def test_scale_chaos_schedule_seed_reproducible():
    """Same seed, same hostility — byte-identical schedules; a
    different seed must actually move the chaos."""
    from ray_tpu.test_utils import scale_chaos_schedule
    a = scale_chaos_schedule(20, 4)
    b = scale_chaos_schedule(20, 4)
    assert a == b
    assert len(a["flaps"]) == 4 and len(a["kills"]) == 2
    for off, dur in a["flaps"]:
        assert 0.05 <= off <= 0.6 and 0.2 <= dur <= 0.45
    assert scale_chaos_schedule(21, 4) != a
