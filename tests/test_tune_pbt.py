"""Population Based Training: exploit/explore with checkpoint exchange.

Mirrors ray: python/ray/tune/tests/test_trial_scheduler_pbt.py — unit
tests on the perturbation decision logic plus an e2e run where a
bad-hyperparameter trial must adopt a good trial's checkpoint+config and
catch up.
"""

import os
import time
import uuid

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.train import session as train_session
from ray_tpu.tune.schedulers import (
    CONTINUE,
    PB2,
    RESTART,
    PopulationBasedTraining,
    _gp_posterior,
)


class _FakeTrial:
    def __init__(self, trial_id, config, checkpoint=None):
        self.trial_id = trial_id
        self.config = config
        self.checkpoint = checkpoint


class TestPBTDecisions:
    def _pbt(self, **kw):
        kw.setdefault("metric", "score")
        kw.setdefault("mode", "max")
        kw.setdefault("perturbation_interval", 1)
        kw.setdefault("seed", 0)
        return PopulationBasedTraining(**kw)

    def test_top_trial_continues(self):
        pbt = self._pbt()
        trials = [
            _FakeTrial("a", {"lr": 1.0}, checkpoint="ck_a"),
            _FakeTrial("b", {"lr": 2.0}, checkpoint="ck_b"),
            _FakeTrial("c", {"lr": 3.0}, checkpoint="ck_c"),
            _FakeTrial("d", {"lr": 4.0}, checkpoint="ck_d"),
        ]
        pbt.set_trials(trials)
        for t, s in zip(trials, [10, 5, 3, 1]):
            assert (
                pbt.on_trial_result(
                    t.trial_id, {"score": s, "training_iteration": 1}
                )
                != RESTART
                or t.trial_id == "d"
            )

    def test_bottom_trial_exploits_top(self):
        pbt = self._pbt(hyperparam_mutations={"lr": [0.1, 1.0, 10.0]})
        trials = [
            _FakeTrial("good", {"lr": 1.0}, checkpoint="good_ck"),
            _FakeTrial("mid1", {"lr": 2.0}, checkpoint="m1"),
            _FakeTrial("mid2", {"lr": 3.0}, checkpoint="m2"),
            _FakeTrial("bad", {"lr": 99.0}, checkpoint="bad_ck"),
        ]
        pbt.set_trials(trials)
        pbt.on_trial_result("good", {"score": 100, "training_iteration": 1})
        pbt.on_trial_result("mid1", {"score": 50, "training_iteration": 1})
        pbt.on_trial_result("mid2", {"score": 40, "training_iteration": 1})
        decision = pbt.on_trial_result(
            "bad", {"score": 1, "training_iteration": 1}
        )
        assert decision == RESTART
        bad = trials[3]
        assert bad.checkpoint == "good_ck"  # exploited
        # explored: lr either perturbed from 1.0 (x1.2/x0.8) or resampled
        assert bad.config["lr"] != 99.0

    def test_no_restart_before_interval(self):
        pbt = self._pbt(perturbation_interval=5)
        trials = [
            _FakeTrial("a", {}, checkpoint="x"),
            _FakeTrial("b", {}, checkpoint="y"),
        ]
        pbt.set_trials(trials)
        pbt.on_trial_result("a", {"score": 10, "training_iteration": 2})
        d = pbt.on_trial_result("b", {"score": 1, "training_iteration": 2})
        assert d == CONTINUE  # iteration 2 < interval 5

    def test_no_exploit_without_checkpoint(self):
        pbt = self._pbt()
        trials = [
            _FakeTrial("a", {}, checkpoint=None),
            _FakeTrial("b", {}, checkpoint=None),
        ]
        pbt.set_trials(trials)
        pbt.on_trial_result("a", {"score": 10, "training_iteration": 1})
        d = pbt.on_trial_result("b", {"score": 1, "training_iteration": 1})
        assert d == CONTINUE


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _trainable(config):
    """Score grows by `rate` per iteration, accumulated in the checkpoint.
    A trial restarted from a better trial's checkpoint + mutated rate
    resumes from the donor's accumulated score."""
    sess = train_session.get_session()
    score = 0.0
    ck = sess.get_checkpoint()
    if ck is not None:
        score = float(ck.to_dict()["score"])
    for i in range(32):
        score += float(config["rate"])
        from ray_tpu.train.checkpoint import Checkpoint

        sess.report(
            {"score": score}, checkpoint=Checkpoint.from_dict({"score": score})
        )
        if i == 0:
            _wait_for_population(config["reported"])


def _wait_for_population(reported: str, size: int = 4):
    """After its first report (report() returns once the driver has taken
    it) a trial waits until every trial of the population has made one.
    PBT ranks a trial only against a population that has ALL reported,
    and 32 unpaced iterations are over in a fraction of the second or
    two by which one trial's worker starts after another's: without the
    wait a bad trial whose worker comes up first is through before it
    can be ranked.  A restarted trial adds a file and goes straight
    through."""
    open(os.path.join(reported, uuid.uuid4().hex), "w").close()
    deadline = time.monotonic() + 60
    while len(os.listdir(reported)) < size and time.monotonic() < deadline:
        time.sleep(0.01)


class TestPBTEndToEnd:
    def test_bad_trial_catches_up(self, cluster, tmp_path):
        from ray_tpu.train.config import RunConfig

        pbt = PopulationBasedTraining(
            perturbation_interval=4,
            quantile_fraction=0.25,
            resample_probability=0.0,
            hyperparam_mutations={"rate": [1.0, 5.0]},
            seed=7,
        )
        reported = tmp_path / "reported"
        reported.mkdir()
        tuner = tune.Tuner(
            _trainable,
            param_space={
                "rate": tune.grid_search([5.0, 4.0, 3.0, 0.01]),
                "reported": str(reported),
            },
            tune_config=tune.TuneConfig(
                metric="score", mode="max", scheduler=pbt
            ),
            run_config=RunConfig(
                name="pbt_test", storage_path=str(tmp_path)
            ),
        )
        grid = tuner.fit()
        assert not grid.errors
        assert pbt.num_perturbations >= 1, "PBT never perturbed"
        scores = sorted(
            r.metrics["score"] for r in grid if r.metrics
        )
        # the 0.01-rate trial would finish near 0.3 alone; having adopted
        # a winner's checkpoint + rate it must land far above that (32
        # iterations give 8 perturbation windows, so a loaded host that
        # reorders early reports still exploits well before the end)
        assert scores[0] > 10, scores


class TestPB2:
    """PB2: GP-UCB explore (ray: tune/schedulers/pb2.py role)."""

    def _pb2(self, **kw):
        kw.setdefault("metric", "score")
        kw.setdefault("mode", "max")
        kw.setdefault("perturbation_interval", 1)
        kw.setdefault("hyperparam_bounds", {"lr": (0.0, 1.0)})
        kw.setdefault("seed", 0)
        return PB2(**kw)

    def test_gp_posterior_recovers_optimum(self):
        """UCB argmax over a GP fit to y = 1 - (x - 0.6)^2 lands near
        0.6 — the numerics the scheduler rides on."""
        rng = np.random.default_rng(0)
        X = rng.random((40, 1))
        y = 1.0 - (X[:, 0] - 0.6) ** 2 + rng.normal(0, 0.01, 40)
        Xq = np.linspace(0, 1, 201)[:, None]
        mu, sigma = _gp_posterior(X, (y - y.mean()) / y.std(), Xq)
        best = float(Xq[int(np.argmax(mu + 0.1 * sigma)), 0])
        assert abs(best - 0.6) < 0.1, best
        assert sigma.shape == mu.shape and np.all(sigma >= 0)

    def test_cold_start_resamples_within_bounds(self):
        pb2 = self._pb2()
        trials = [
            _FakeTrial(i, {"lr": 0.9}, checkpoint=f"ck{i}")
            for i in "abcd"
        ]
        pb2.set_trials(trials)
        out = pb2._explore({"lr": 0.9})
        assert 0.0 <= out["lr"] <= 1.0

    def test_explore_moves_toward_observed_optimum(self):
        """Feed the population's reports where improvement peaks at
        lr=0.5: the GP explore must propose lr near 0.5, not a random
        or x1.2-perturbed value."""
        pb2 = self._pb2(perturbation_interval=100)  # collect only
        lrs = [0.05, 0.3, 0.5, 0.7, 0.95]
        trials = [
            _FakeTrial(f"t{i}", {"lr": lr}, checkpoint=f"ck{i}")
            for i, lr in enumerate(lrs)
        ]
        pb2.set_trials(trials)
        for step in range(1, 9):
            for t in trials:
                lr = t.config["lr"]
                gain = 1.0 - 4.0 * (lr - 0.5) ** 2  # best at 0.5
                pb2.on_trial_result(
                    t.trial_id,
                    {"score": step * gain, "training_iteration": step},
                )
        picks = [pb2._explore({"lr": 0.9})["lr"] for _ in range(5)]
        assert all(0.0 <= p <= 1.0 for p in picks)
        assert np.mean([abs(p - 0.5) for p in picks]) < 0.2, picks

    def test_int_hyperparams_stay_int(self):
        pb2 = self._pb2(hyperparam_bounds={"batch": (8.0, 128.0)})
        out = pb2._explore({"batch": 32})
        assert isinstance(out["batch"], int)
        assert 8 <= out["batch"] <= 128

    def test_bottom_trial_exploits_with_gp_explore(self):
        pb2 = self._pb2()
        trials = [
            _FakeTrial("good", {"lr": 0.5}, checkpoint="good_ck"),
            _FakeTrial("mid1", {"lr": 0.3}, checkpoint="m1"),
            _FakeTrial("mid2", {"lr": 0.7}, checkpoint="m2"),
            _FakeTrial("bad", {"lr": 0.99}, checkpoint="bad_ck"),
        ]
        pb2.set_trials(trials)
        for tid, s in (("good", 100), ("mid1", 50), ("mid2", 40)):
            pb2.on_trial_result(tid, {"score": s, "training_iteration": 1})
        decision = pb2.on_trial_result(
            "bad", {"score": 1, "training_iteration": 1}
        )
        assert decision == RESTART
        assert trials[3].checkpoint == "good_ck"
        assert 0.0 <= trials[3].config["lr"] <= 1.0

    def test_restart_resets_gp_observation_chain(self):
        """The score jump after exploiting a donor checkpoint must not
        be recorded as an improvement for the trial's OLD hyperparams."""
        pb2 = self._pb2()
        trials = [
            _FakeTrial("good", {"lr": 0.5}, checkpoint="good_ck"),
            _FakeTrial("mid1", {"lr": 0.3}, checkpoint="m1"),
            _FakeTrial("mid2", {"lr": 0.7}, checkpoint="m2"),
            _FakeTrial("bad", {"lr": 0.99}, checkpoint="bad_ck"),
        ]
        pb2.set_trials(trials)
        for tid, s in (("good", 100), ("mid1", 50), ("mid2", 40)):
            pb2.on_trial_result(tid, {"score": s, "training_iteration": 1})
        d = pb2.on_trial_result("bad", {"score": 1, "training_iteration": 1})
        assert d == RESTART
        n_before = len(pb2._y)
        # post-restart report: huge jump from the cloned weights
        pb2.on_trial_result("bad", {"score": 95, "training_iteration": 2})
        # no improvement row was attributed to the old lr=0.99
        assert len(pb2._y) == n_before
