"""HyperBand / TPE searcher / ResourceChanging scheduler tests (parity:
reference tune/tests/test_trial_scheduler*.py, test_searchers.py)."""

import numpy as np
import pytest

from ray_tpu import tune
from ray_tpu.tune.schedulers import CONTINUE, STOP
from ray_tpu.tune.search import TPESearcher, _flatten, _unflatten


class _FakeTrial:
    def __init__(self, tid):
        self.trial_id = tid
        self.last_metric = None
        self.resources = None
        self.pending_resources = None


def test_hyperband_brackets_stagger_and_halve():
    sched = tune.HyperBandScheduler(metric="score", max_t=27,
                                    reduction_factor=3)
    trials = [_FakeTrial(f"t{i}") for i in range(6)]
    # Trials land in different brackets round-robin → different first
    # milestones (bracket 0 halves at t=1, bracket 1 first at t=3...).
    assert sched._bracket_of(trials[0]) != sched._bracket_of(trials[1])
    # Bracket-0 rung at t=1: first reporter sets the bar; a much worse
    # later report at the same rung stops.
    b0 = [t for t in trials if sched._bracket_of(t) == 0]
    assert sched.on_result(b0[0], 10.0, 1) == CONTINUE
    decisions = [sched.on_result(t, 0.1 * i, 1) for i, t in enumerate(b0[1:])]
    assert STOP in decisions
    # Reaching max_t always stops.
    assert sched.on_result(b0[0], 99.0, 27) == STOP


def test_flatten_roundtrip():
    d = {"a": 1, "b": {"c": 2, "d": {"e": 3}}}
    assert _unflatten(_flatten(d)) == d


def test_tpe_searcher_converges_toward_good_region():
    space = {"x": tune.uniform(-10, 10), "fixed": 7}
    s = TPESearcher(space, metric="score", mode="max", num_samples=40,
                    n_initial=10, seed=0)
    # Feed observations: score = -(x-3)^2 — optimum at x=3.
    for i in range(40):
        cfg = s.suggest(f"t{i}")
        if cfg is None:
            break
        assert cfg["fixed"] == 7
        x = cfg["x"]
        s.on_trial_complete(f"t{i}", cfg, -(x - 3.0) ** 2)
    late = [s.suggest(f"late{i}") for i in range(5)]
    # Suggestion budget exhausted → None.
    assert all(c is None for c in late)
    # The model-based suggestions should cluster near x=3 far better than
    # uniform(-10,10) would: check mean |x-3| of the last 10 suggestions.
    xs = [o[0]["x"] for o in s.observations[-10:]]
    assert np.mean(np.abs(np.array(xs) - 3.0)) < 4.0


def test_tpe_in_tuner_finds_minimum(ray_start_regular):
    def objective(config):
        from ray_tpu.train import session

        session.report({"loss": (config["lr"] - 0.01) ** 2})

    searcher = TPESearcher({"lr": tune.loguniform(1e-4, 1.0)},
                           metric="loss", mode="min", num_samples=6,
                           n_initial=3, seed=1)
    tuner = tune.Tuner(
        objective,
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    search_alg=searcher,
                                    max_concurrent_trials=3))
    results = tuner.fit()
    assert len(results) == 6
    best = results.get_best_result()
    assert best.metrics["loss"] < 0.05


def test_resource_changing_scheduler(ray_start_regular):
    """Trials start at 1 CPU; after 2 reports the allocator doubles them —
    the trial restarts from checkpoint with the new allocation."""

    def allocator(trial, metric_value, iteration):
        if iteration >= 2:
            return {"CPU": 2}
        return None

    def trainable(config):
        import os

        from ray_tpu.train import session

        for step in range(4):
            session.report({"step": step, "score": float(step)},
                           checkpoint={"step": step})

    sched = tune.ResourceChangingScheduler(
        resources_allocation_function=allocator)
    tuner = tune.Tuner(
        trainable,
        param_space={"a": tune.grid_search([1, 2])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched))
    results = tuner.fit()
    assert len(results) == 2
    assert not results.errors
    for r in results:
        assert r.metrics["score"] >= 0.0


def test_pg_per_trial_bundles(ray_start_regular):
    """A list of bundles as resources_per_trial reserves a placement
    group per trial (reference: tune PlacementGroupFactory); the trial
    actor runs in bundle 0 and the trainable receives the PG to place
    sub-workers into the rest."""

    def trainable(config):
        from ray_tpu.train import session

        pg = config["_trial_pg"]
        assert len(pg.bundle_specs) == 2

        import ray_tpu

        @ray_tpu.remote(num_cpus=1, placement_group=pg,
                        placement_group_bundle_index=1)
        def sub():
            return 7

        session.report({"sub": ray_tpu.get(sub.remote(), timeout=60)})

    tuner = tune.Tuner(
        trainable,
        param_space={"x": tune.grid_search([1, 2])},
        tune_config=tune.TuneConfig(metric="sub", mode="max",
                                    max_concurrent_trials=1),
        resources_per_trial=[{"CPU": 1}, {"CPU": 1}])
    results = tuner.fit()
    assert len(results) == 2 and not results.errors
    assert all(r.metrics["sub"] == 7 for r in results)
    # PGs are removed with their trials.
    from ray_tpu.util.state import list_placement_groups

    assert all(p.get("state") == "REMOVED"
               for p in list_placement_groups()) or not list_placement_groups()


def test_pb2_model_guided_perturbation():
    """PB2 unit: with history showing higher lr -> bigger improvement, the
    GP-UCB explore step proposes lr in the upper region of the bounds."""
    from ray_tpu.tune.schedulers import PB2

    class _T:
        def __init__(self, tid, lr):
            self.trial_id = tid
            self.config = {"lr": lr}

    sched = PB2(metric="reward", mode="max", perturbation_interval=2,
                hyperparam_bounds={"lr": [0.0, 1.0]}, seed=0)
    # Feed deltas: improvement proportional to lr.
    for step in range(6):
        for i, lr in enumerate([0.1, 0.5, 0.9]):
            t = _T(f"t{i}", lr)
            sched.on_result(t, metric_value=step * lr, iteration=step)
    new = [sched.perturb({"lr": 0.1})["lr"] for _ in range(5)]
    assert all(0.0 <= v <= 1.0 for v in new)
    assert np.mean(new) > 0.45, f"model should favor high lr, got {new}"


def test_pb2_in_tuner(ray_start_regular, tmp_path):
    def trainable(config):
        import os

        from ray_tpu.train import session
        from ray_tpu.train.checkpoint import Checkpoint

        w = 0.0
        if config.get("_checkpoint_path"):
            w = float(np.asarray(
                Checkpoint(config["_checkpoint_path"]).to_pytree()["w"]))
        for i in range(8):
            w += config["lr"]
            ck = Checkpoint.from_pytree(
                {"w": np.float64(w)},
                os.path.join(config["dir"],
                             f"pb2_{os.getpid()}_{i}"))
            session.report({"w": w}, checkpoint=ck)

    sched = tune.PB2(metric="w", mode="max", perturbation_interval=3,
                     hyperparam_bounds={"lr": [0.05, 1.0]},
                     quantile_fraction=0.5, seed=0)
    grid = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.05, 1.0]),
                     "dir": str(tmp_path)},
        tune_config=tune.TuneConfig(metric="w", mode="max", scheduler=sched,
                                    max_concurrent_trials=2),
    ).fit()
    assert grid.get_best_result().metrics["w"] >= 2.0
    assert len(grid) == 2


def test_bohb_factory_in_tuner(ray_start_regular):
    """BOHB = TPE searcher + HyperBand budgets driving one Tuner run."""
    from ray_tpu.tune.search import bohb

    def objective(config):
        from ray_tpu.train import session

        for i in range(8):
            session.report(
                {"loss": (config["lr"] - 0.01) ** 2 + 0.1 / (i + 1)})

    searcher, scheduler = bohb({"lr": tune.loguniform(1e-4, 1.0)},
                               metric="loss", mode="min", num_samples=4,
                               max_t=8, seed=2)
    results = tune.Tuner(
        objective,
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    search_alg=searcher,
                                    scheduler=scheduler,
                                    max_concurrent_trials=2)).fit()
    assert len(results) == 4
    assert results.get_best_result().metrics["loss"] < 0.3


def test_external_searcher_adapter(ray_start_regular):
    """Any ask/tell pair drives the Tuner through ExternalSearcher."""
    suggested, observed = [], []

    def ask():
        if len(suggested) >= 4:
            return None
        cfg = {"x": 0.25 * len(suggested)}
        suggested.append(cfg)
        return cfg

    def tell(config, value):
        observed.append((config["x"], value))

    def objective(config):
        from ray_tpu.train import session

        session.report({"score": -abs(config["x"] - 0.5)})

    searcher = tune.ExternalSearcher(ask, tell)
    results = tune.Tuner(
        objective,
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    search_alg=searcher,
                                    max_concurrent_trials=2)).fit()
    assert len(results) == 4 and len(observed) == 4
    assert results.get_best_result().config["x"] == 0.5
