"""Fixed-effect trainer tests: λ sweep warm start, normalization round-trip,
summary stats, and single-device vs 8-device-mesh equivalence (the analog of
the reference's NormalizationTest + OptimizerIntegTest on local[4] Spark).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.normalization import build_normalization_context
from photon_ml_tpu.ops import DenseFeatures, LabeledData
from photon_ml_tpu.ops.features import from_scipy_like
from photon_ml_tpu.opt import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.parallel import data_parallel_mesh, pad_batch_to_multiple, shard_batch
from photon_ml_tpu.stat import summarize
from photon_ml_tpu.types import NormalizationType, RegularizationType, TaskType


def _logreg(rng, n=256, d=8, intercept=True):
    X = rng.normal(size=(n, d)).astype(np.float32) * 2 + 0.5
    if intercept:
        X[:, -1] = 1.0
    w_true = rng.normal(size=d).astype(np.float32)
    p = 1 / (1 + np.exp(-(X @ w_true)))
    y = (rng.random(n) < p).astype(np.float32)
    return X, y


L2CFG = GlmOptimizationConfiguration(
    regularization=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def test_lambda_sweep_order_and_shrinkage(rng):
    X, y = _logreg(rng)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    lams = [0.1, 100.0, 10.0]
    fits = train_glm(data, TaskType.LOGISTIC_REGRESSION, L2CFG, regularization_weights=lams)
    # returned in requested order
    assert [f.regularization_weight for f in fits] == lams
    # heavier regularization => smaller coefficients
    norms = {f.regularization_weight: float(f.model.coefficients.l2_norm()) for f in fits}
    assert norms[100.0] < norms[10.0] < norms[0.1]


def test_normalization_returns_original_space_coefficients(rng):
    """Training with STANDARDIZATION must produce (near-)identical
    original-space models to training without normalization (the reference's
    NormalizationTest invariant: all normalization types reach the same
    optimum up to tolerance when unregularized)."""
    X, y = _logreg(rng, n=512, d=6)
    data_plain = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    summ = summarize(data_plain)
    norm = build_normalization_context(
        NormalizationType.STANDARDIZATION,
        summ.mean,
        summ.variance,
        summ.max_abs,
        intercept_index=5,
    )
    data_norm = LabeledData.create(
        DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y), norm=norm
    )
    cfg = GlmOptimizationConfiguration()  # unregularized LBFGS
    fit_plain = train_glm(data_plain, TaskType.LOGISTIC_REGRESSION, cfg)[0]
    fit_norm = train_glm(
        data_norm, TaskType.LOGISTIC_REGRESSION, cfg, intercept_index=5
    )[0]
    np.testing.assert_allclose(
        fit_norm.model.coefficients.means,
        fit_plain.model.coefficients.means,
        rtol=5e-2,
        atol=5e-3,
    )


def test_variances_inverse_hessian(rng):
    X, y = _logreg(rng, n=128, d=4, intercept=False)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    fit = train_glm(
        data, TaskType.LOGISTIC_REGRESSION, L2CFG, compute_variances=True
    )[0]
    v = fit.model.coefficients.variances
    assert v is not None and v.shape == (4,)
    assert float(jnp.min(v)) > 0


def test_summary_matches_numpy(rng):
    X = rng.normal(size=(64, 5)).astype(np.float32)
    X[rng.random((64, 5)) < 0.5] = 0.0
    w = rng.random(64).astype(np.float32) + 0.1
    data_dense = LabeledData.create(
        DenseFeatures(matrix=jnp.asarray(X)), jnp.zeros(64), weights=jnp.asarray(w)
    )
    rows, cols = np.nonzero(X)
    ell = from_scipy_like(rows, cols, X[rows, cols], X.shape)
    data_ell = LabeledData.create(ell, jnp.zeros(64), weights=jnp.asarray(w))

    for data in (data_dense, data_ell):
        s = summarize(data)
        wsum = w.sum()
        mean_np = (w[:, None] * X).sum(0) / wsum
        np.testing.assert_allclose(s.mean, mean_np, rtol=1e-4, atol=1e-5)
        var_np = ((w[:, None] * (X - mean_np) ** 2).sum(0)) / (wsum - 1)
        np.testing.assert_allclose(s.variance, var_np, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(s.max_val, X.max(0), rtol=1e-5)
        np.testing.assert_allclose(s.min_val, X.min(0), rtol=1e-5)
        np.testing.assert_allclose(s.max_abs, np.abs(X).max(0), rtol=1e-5)
        np.testing.assert_allclose(s.count, wsum, rtol=1e-5)


def test_pad_batch_is_noop_algebraically(rng):
    X, y = _logreg(rng, n=30, d=4, intercept=False)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    padded = pad_batch_to_multiple(data, 8)
    assert padded.num_rows == 32
    fit_a = train_glm(data, TaskType.LOGISTIC_REGRESSION, L2CFG)[0]
    fit_b = train_glm(padded, TaskType.LOGISTIC_REGRESSION, L2CFG)[0]
    np.testing.assert_allclose(
        fit_a.model.coefficients.means, fit_b.model.coefficients.means, rtol=1e-4, atol=1e-5
    )


def test_sharded_training_matches_single_device(rng):
    """The core distributed invariant: training over an 8-device mesh (batch
    sharded, XLA-inserted psums) must reproduce the single-device result.
    Replaces the reference's treeAggregate-vs-local equivalence testing."""
    assert len(jax.devices()) >= 8, "conftest must force 8 virtual devices"
    X, y = _logreg(rng, n=256, d=8)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    fit_single = train_glm(data, TaskType.LOGISTIC_REGRESSION, L2CFG)[0]

    mesh = data_parallel_mesh(8)
    data_sharded = shard_batch(data, mesh)
    fit_sharded = train_glm(data_sharded, TaskType.LOGISTIC_REGRESSION, L2CFG)[0]
    np.testing.assert_allclose(
        fit_sharded.model.coefficients.means,
        fit_single.model.coefficients.means,
        rtol=1e-3,
        atol=1e-4,
    )


def test_sharded_ell_training(rng):
    X, y = _logreg(rng, n=128, d=16, intercept=False)
    X[rng.random(X.shape) < 0.6] = 0.0
    rows, cols = np.nonzero(X)
    ell = from_scipy_like(rows, cols, X[rows, cols], X.shape)
    data = LabeledData.create(ell, jnp.asarray(y))
    fit_single = train_glm(data, TaskType.LOGISTIC_REGRESSION, L2CFG)[0]
    mesh = data_parallel_mesh(8)
    fit_sharded = train_glm(
        shard_batch(data, mesh), TaskType.LOGISTIC_REGRESSION, L2CFG
    )[0]
    np.testing.assert_allclose(
        fit_sharded.model.coefficients.means,
        fit_single.model.coefficients.means,
        rtol=1e-3,
        atol=1e-4,
    )


def test_zero_sweep_weight_disables_l1(rng):
    """regularization_weights=[0.0] with an L1 configuration must NOT apply
    the configuration's own weight (review finding)."""
    X, y = _logreg(rng, n=128, d=6, intercept=False)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    cfg_l1 = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L1),
        regularization_weight=5.0,
    )
    fit_zero = train_glm(
        data, TaskType.LOGISTIC_REGRESSION, cfg_l1, regularization_weights=[0.0]
    )[0]
    fit_plain = train_glm(
        data, TaskType.LOGISTIC_REGRESSION, GlmOptimizationConfiguration()
    )[0]
    np.testing.assert_allclose(
        fit_zero.model.coefficients.means,
        fit_plain.model.coefficients.means,
        rtol=1e-2,
        atol=1e-3,
    )


def test_warm_start_roundtrip_with_normalization(rng):
    """Feeding a returned (original-space) model back as initial_model with
    normalized data must start AT the optimum: 0-2 extra iterations."""
    X, y = _logreg(rng, n=256, d=6)
    data_plain = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    summ = summarize(data_plain)
    norm = build_normalization_context(
        NormalizationType.STANDARDIZATION, summ.mean, summ.variance, summ.max_abs, 5
    )
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y), norm=norm)
    fit1 = train_glm(data, TaskType.LOGISTIC_REGRESSION, L2CFG, intercept_index=5)[0]
    fit2 = train_glm(
        data,
        TaskType.LOGISTIC_REGRESSION,
        L2CFG,
        initial_model=fit1.model,
        intercept_index=5,
    )[0]
    assert int(fit2.result.iterations) <= 2
    np.testing.assert_allclose(
        fit2.model.coefficients.means, fit1.model.coefficients.means, rtol=1e-3, atol=1e-4
    )


def test_variances_transformed_to_original_space(rng):
    """Variances must scale by factor^2 when mapped back (delta method)."""
    X, y = _logreg(rng, n=256, d=4, intercept=False)
    X[:, 0] *= 10.0  # large-std feature: factor ~ 0.1
    data_plain = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    summ = summarize(data_plain)
    norm = build_normalization_context(
        NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
        summ.mean, summ.variance, summ.max_abs, None,
    )
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y), norm=norm)
    fit_n = train_glm(
        data, TaskType.LOGISTIC_REGRESSION, L2CFG, compute_variances=True
    )[0]
    fit_p = train_glm(
        data_plain, TaskType.LOGISTIC_REGRESSION, L2CFG, compute_variances=True
    )[0]
    # original-space variances from both paths should be on the same scale
    ratio = np.asarray(fit_n.model.coefficients.variances) / np.asarray(
        fit_p.model.coefficients.variances
    )
    assert np.all(ratio > 0.2) and np.all(ratio < 5.0), ratio


# -- evaluations counted where they happen (SolveResult.evaluations). The
# solvers' own suite (test_optimizers.py) is the slow lane; these are fast.

def _solve_by_name(name, objective, w0, data, l2, l1=0.0):
    from photon_ml_tpu.opt import lbfgs_solve, owlqn_solve, tron_solve

    if name == "owlqn":
        return owlqn_solve(objective, w0, data, jnp.float32(l2), jnp.float32(l1))
    solver = lbfgs_solve if name == "lbfgs" else tron_solve
    return solver(objective, w0, data, jnp.float32(l2))


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "tron"])
def test_evaluations_at_least_iterations_plus_one(rng, name):
    """Every iteration makes at least one value-and-gradient call, and the
    initial point one more; line-search retries come on top."""
    from photon_ml_tpu.losses import LogisticLoss, make_glm_objective

    X, y = _logreg(rng, d=6, intercept=False)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    res = _solve_by_name(
        name, make_glm_objective(LogisticLoss), jnp.zeros(6), data, l2=1.0, l1=2.0
    )
    iterations, evaluations = int(res.iterations), int(res.evaluations)
    assert res.evaluations.dtype == jnp.int32
    assert iterations >= 2
    assert evaluations >= iterations + 1
    if name == "tron":
        # one trial point an iteration, accepted or not
        assert evaluations == iterations + 1


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "tron"])
def test_evaluations_equal_iterations_plus_one_when_first_step_accepted(name):
    """0.5 * ||w - c||^2 with ||c|| = 1 from w = 0: the first trial step
    (t = 1 / ||g||) lands on the optimum, so the solve is the initial
    evaluation plus one per iteration and nothing else."""
    from photon_ml_tpu.losses import SquaredLoss, make_glm_objective

    c = jnp.asarray([0.5, -0.5, 0.5, -0.5, 0.0, 0.0], dtype=jnp.float32)
    data = LabeledData.create(DenseFeatures(matrix=jnp.eye(6, dtype=jnp.float32)), c)
    res = _solve_by_name(name, make_glm_objective(SquaredLoss), jnp.zeros(6), data, l2=0.0)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(c), atol=1e-6)
    assert int(res.iterations) == 1
    assert int(res.evaluations) == int(res.iterations) + 1
