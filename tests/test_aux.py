"""Tests for samplers, validators, events and trackers.

Mirrors reference unit tests: DownSamplerTest, DataValidators checks,
OptimizationStatesTracker/RandomEffectOptimizationTracker summaries.
"""

import numpy as np
import pytest

from photon_ml_tpu.data.validators import (
    DataValidationError,
    DataValidationType,
    validate_labeled_data,
)
from photon_ml_tpu.event import (
    EventEmitter,
    EventListener,
    PhotonOptimizationLogEvent,
    TrainingStartEvent,
)
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.sampler import (
    BinaryClassificationDownSampler,
    DefaultDownSampler,
    down_sampler_for,
)
from photon_ml_tpu.types import ConvergenceReason, TaskType


def _data(labels, weights=None, features=None, offsets=None):
    n = len(labels)
    x = np.ones((n, 2), np.float32) if features is None else np.asarray(features)
    return LabeledData.create(
        features=DenseFeatures(matrix=x),
        labels=np.asarray(labels, np.float32),
        weights=None if weights is None else np.asarray(weights, np.float32),
        offsets=None if offsets is None else np.asarray(offsets, np.float32),
    )


class TestDownSamplers:
    def test_default_preserves_expected_total_weight(self):
        labels = np.zeros(20000, np.float32)
        weights = np.ones(20000, np.float32)
        out = DefaultDownSampler(0.25).sample_weights(labels, weights, seed=1)
        kept = out > 0
        # survivors are re-scaled by 1/rate -> expected total weight unchanged
        assert np.isclose(kept.mean(), 0.25, atol=0.02)
        assert np.isclose(out.sum(), weights.sum(), rtol=0.05)
        assert np.allclose(out[kept], 4.0)

    def test_binary_keeps_all_positives(self):
        labels = np.array([1, 1, 0, 0, 0, 0, 0, 0] * 1000, np.float32)
        weights = np.full(labels.shape, 2.0, np.float32)
        out = BinaryClassificationDownSampler(0.5).sample_weights(
            labels, weights, seed=3
        )
        pos = labels >= 0.5
        assert np.allclose(out[pos], 2.0)  # positives untouched
        neg_kept = out[~pos] > 0
        assert np.isclose(neg_kept.mean(), 0.5, atol=0.03)
        assert np.allclose(out[~pos][neg_kept], 4.0)  # 2.0 / 0.5

    def test_factory_matches_task(self):
        assert isinstance(
            down_sampler_for(TaskType.LOGISTIC_REGRESSION, 0.5),
            BinaryClassificationDownSampler,
        )
        assert isinstance(
            down_sampler_for(TaskType.LINEAR_REGRESSION, 0.5), DefaultDownSampler
        )

    def test_invalid_rate_rejected(self):
        with pytest.raises(ValueError):
            DefaultDownSampler(1.0)
        with pytest.raises(ValueError):
            BinaryClassificationDownSampler(0.0)


class TestValidators:
    def test_clean_data_passes(self):
        validate_labeled_data(_data([0, 1, 0]), TaskType.LOGISTIC_REGRESSION)

    def test_nan_feature_rejected(self):
        d = _data([0, 1], features=np.array([[1, np.nan], [0, 1]], np.float32))
        with pytest.raises(DataValidationError, match="features contain NaN"):
            validate_labeled_data(d, TaskType.LOGISTIC_REGRESSION)

    def test_nonbinary_label_rejected_for_logistic(self):
        with pytest.raises(DataValidationError, match="must be 0 or 1"):
            validate_labeled_data(_data([0, 2]), TaskType.LOGISTIC_REGRESSION)

    def test_negative_label_rejected_for_poisson(self):
        with pytest.raises(DataValidationError, match="non-negative"):
            validate_labeled_data(_data([1, -1]), TaskType.POISSON_REGRESSION)

    def test_negative_weight_rejected(self):
        with pytest.raises(DataValidationError, match="negative"):
            validate_labeled_data(
                _data([0, 1], weights=[1, -1]), TaskType.LOGISTIC_REGRESSION
            )

    def test_multiple_failures_all_reported(self):
        d = _data(
            [5, 0],
            weights=[1, -1],
            features=np.array([[np.inf, 0], [0, 1]], np.float32),
        )
        with pytest.raises(DataValidationError) as err:
            validate_labeled_data(d, TaskType.LOGISTIC_REGRESSION)
        assert len(err.value.failures) == 3

    def test_padding_rows_exempt_from_label_checks(self):
        # weight-0 rows are padding; a junk label there must not fail
        validate_labeled_data(
            _data([0, 7], weights=[1, 0]), TaskType.LOGISTIC_REGRESSION
        )

    def test_disabled_mode_skips(self):
        validate_labeled_data(
            _data([0, 9]),
            TaskType.LOGISTIC_REGRESSION,
            mode=DataValidationType.VALIDATE_DISABLED,
        )

    def test_linear_regression_allows_any_finite_label(self):
        validate_labeled_data(_data([-3.5, 7.2]), TaskType.LINEAR_REGRESSION)


class _Recorder(EventListener):
    def __init__(self):
        self.events = []

    def on_event(self, event):
        self.events.append(event)


class _Exploder(EventListener):
    def on_event(self, event):
        raise RuntimeError("boom")


class TestEvents:
    def test_emit_reaches_all_listeners(self):
        em = EventEmitter()
        a, b = _Recorder(), _Recorder()
        em.register_listener(a)
        em.register_listener(b)
        ev = TrainingStartEvent(task="logistic_regression")
        em.send_event(ev)
        assert a.events == [ev] and b.events == [ev]

    def test_listener_exception_isolated(self):
        em = EventEmitter()
        rec = _Recorder()
        em.register_listener(_Exploder())
        em.register_listener(rec)
        em.send_event(
            PhotonOptimizationLogEvent(
                coordinate_id="fe",
                regularization_weight=1.0,
                objective_value=0.5,
                iterations=7,
                convergence_reason="FUNCTION_VALUES_CONVERGED",
            )
        )
        assert len(rec.events) == 1

    def test_register_by_class_name(self):
        em = EventEmitter()
        em.register_listener_class(f"{__name__}._Recorder")
        em.send_event(TrainingStartEvent(task="t"))
        assert len(em._listeners[0].events) == 1


class TestTrackers:
    def test_states_tracker_from_solve(self):
        import jax.numpy as jnp

        from photon_ml_tpu.losses.objective import make_glm_objective
        from photon_ml_tpu.losses.pointwise import LogisticLoss
        from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
        from photon_ml_tpu.opt.solve import solve
        from photon_ml_tpu.opt.tracking import OptimizationStatesTracker

        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        y = (x @ np.array([1.0, -1, 0.5, 0]) > 0).astype(np.float32)
        data = _data(y, features=x)
        obj = make_glm_objective(LogisticLoss)
        res = solve(
            obj,
            jnp.zeros(4),
            data,
            GlmOptimizationConfiguration(regularization_weight=0.1),
        )
        tr = OptimizationStatesTracker.from_result(res)
        assert tr.converged
        assert tr.values.shape[0] == tr.iterations + 1
        assert tr.values[-1] < tr.values[0]
        assert "reason=" in tr.to_summary_string()

    def test_random_effect_tracker_aggregates(self):
        import jax.numpy as jnp

        from photon_ml_tpu.opt.state import SolveResult
        from photon_ml_tpu.opt.tracking import RandomEffectOptimizationTracker

        def fake(reasons, iters):
            e = len(reasons)
            return SolveResult(
                w=jnp.zeros((e, 2)),
                value=jnp.ones(e),
                grad_norm=jnp.zeros(e),
                iterations=jnp.asarray(iters, jnp.int32),
                evaluations=jnp.asarray(iters, jnp.int32) + 1,
                reason=jnp.asarray(reasons, jnp.int32),
                value_history=jnp.zeros((e, 3)),
            )

        tr = RandomEffectOptimizationTracker.from_results(
            [fake([2, 2, 1], [3, 5, 100]), fake([3], [7])]
        )
        assert tr.num_entities == 4
        assert tr.reason_counts[ConvergenceReason.FUNCTION_VALUES_CONVERGED] == 2
        assert tr.reason_counts[ConvergenceReason.MAX_ITERATIONS] == 1
        assert tr.reason_counts[ConvergenceReason.GRADIENT_CONVERGED] == 1
        assert tr.iteration_stats["max"] == 100
        assert "entities" in tr.to_summary_string()
