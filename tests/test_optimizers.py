"""Optimizer tests, modeled on the reference's OptimizerTest/OptimizerIntegTest
(photon-lib src/test + src/integTest): drive each solver against known
objectives and check convergence invariants, cross-solver agreement, and
vmap batchability (the random-effect execution mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # excluded from the fast lane (pyproject markers)

from photon_ml_tpu.losses import (
    GlmObjective,
    LogisticLoss,
    SquaredLoss,
    make_glm_objective,
)
from photon_ml_tpu.ops import DenseFeatures, LabeledData
from photon_ml_tpu.opt import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
    lbfgs_solve,
    owlqn_solve,
    solve,
    tron_solve,
)
from photon_ml_tpu.types import ConvergenceReason, RegularizationType


def _linreg_problem(rng, n=64, d=8, noise=0.01):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    y = X @ w_true + noise * rng.normal(size=n).astype(np.float32)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    return data, w_true


def _logreg_problem(rng, n=256, d=6):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32) * 2
    p = 1 / (1 + np.exp(-(X @ w_true)))
    y = (rng.random(n) < p).astype(np.float32)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    return data, w_true


@pytest.mark.parametrize("solver", [lbfgs_solve, tron_solve])
def test_quadratic_exact_solution(rng, solver):
    """Least squares with tiny L2 has a closed-form optimum; both second-order
    capable solvers must find it."""
    data, w_true = _linreg_problem(rng)
    obj = make_glm_objective(SquaredLoss)
    l2 = jnp.float32(1e-3)
    res = solver(obj, jnp.zeros(8), data, l2)
    X = np.asarray(data.features.matrix)
    y = np.asarray(data.labels)
    w_exact = np.linalg.solve(X.T @ X + 1e-3 * np.eye(8), X.T @ y)
    np.testing.assert_allclose(res.w, w_exact, rtol=1e-3, atol=1e-3)
    assert int(res.reason) != ConvergenceReason.NOT_CONVERGED.value


@pytest.mark.parametrize("solver", [lbfgs_solve, tron_solve])
def test_logistic_converges_and_gradient_small(rng, solver):
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    res = solver(obj, jnp.zeros(6), data, jnp.float32(1.0))
    # gradient at the optimum must be tiny relative to the initial one
    _, g0 = obj.value_and_grad(jnp.zeros(6), data, jnp.float32(1.0))
    assert float(res.grad_norm) < 1e-3 * float(jnp.linalg.norm(g0))


def test_lbfgs_tron_agree(rng):
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    l2 = jnp.float32(0.5)
    r1 = lbfgs_solve(obj, jnp.zeros(6), data, l2)
    r2 = tron_solve(obj, jnp.zeros(6), data, l2)
    np.testing.assert_allclose(r1.w, r2.w, rtol=1e-2, atol=1e-3)
    np.testing.assert_allclose(r1.value, r2.value, rtol=1e-4)


def test_monotone_decrease(rng):
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    res = lbfgs_solve(obj, jnp.zeros(6), data, jnp.float32(0.1))
    h = np.asarray(res.value_history)
    h = h[~np.isnan(h)]
    assert len(h) >= 2
    assert np.all(np.diff(h) <= 1e-5), f"objective increased: {h}"


def test_owlqn_produces_sparse_solution(rng):
    """Strong L1 must zero out coefficients; weak L1 must fit well."""
    data, w_true = _linreg_problem(rng, n=128, d=10, noise=0.0)
    obj = make_glm_objective(SquaredLoss)
    strong = owlqn_solve(obj, jnp.zeros(10), data, jnp.float32(0.0), jnp.float32(500.0))
    weak = owlqn_solve(obj, jnp.zeros(10), data, jnp.float32(0.0), jnp.float32(1e-4))
    n_zero_strong = int(jnp.sum(jnp.abs(strong.w) < 1e-8))
    assert n_zero_strong >= 5, f"strong L1 left {10 - n_zero_strong} nonzeros"
    np.testing.assert_allclose(weak.w, w_true, rtol=1e-2, atol=1e-2)


def test_owlqn_matches_lbfgs_when_l1_zero(rng):
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    l2 = jnp.float32(0.5)
    r_owl = owlqn_solve(obj, jnp.zeros(6), data, l2, jnp.float32(0.0))
    r_lb = lbfgs_solve(obj, jnp.zeros(6), data, l2)
    np.testing.assert_allclose(r_owl.value, r_lb.value, rtol=1e-3)


def test_box_constraints_respected(rng):
    data, _ = _linreg_problem(rng)
    cfg = OptimizerConfig.lbfgs(constraint_lower=-0.1, constraint_upper=0.1)
    obj = make_glm_objective(SquaredLoss)
    res = lbfgs_solve(obj, jnp.zeros(8), data, jnp.float32(0.0), cfg)
    assert float(jnp.max(res.w)) <= 0.1 + 1e-6
    assert float(jnp.min(res.w)) >= -0.1 - 1e-6
    # and some coefficient should be AT the boundary (active constraint)
    assert float(jnp.max(jnp.abs(res.w))) > 0.1 - 1e-4


def test_bf16_history_reaches_same_optimum(rng):
    """bfloat16 s/y history (half the dominant memory term of huge-d
    solves, SCALING.md) must land on the same optimum within bf16 noise."""
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    l2 = jnp.float32(0.5)
    f32 = lbfgs_solve(obj, jnp.zeros(6), data, l2)
    cfg = OptimizerConfig.lbfgs(history_dtype="bfloat16")
    bf16 = lbfgs_solve(obj, jnp.zeros(6), data, l2, cfg)
    np.testing.assert_allclose(
        np.asarray(bf16.w), np.asarray(f32.w), rtol=5e-3, atol=5e-3
    )
    owl = owlqn_solve(obj, jnp.zeros(6), data, l2, jnp.float32(0.01), cfg)
    assert np.all(np.isfinite(np.asarray(owl.w)))

    with pytest.raises(ValueError, match="history_dtype"):
        OptimizerConfig.lbfgs(history_dtype="float64")


def test_owlqn_box_constraints(rng):
    """L1 + box compose (reference OWLQN.scala:46 passes the constraint map
    to LBFGS.scala:72's post-step projection): iterates stay in the box,
    some constraint binds, and an inactive box changes nothing."""
    data, _ = _linreg_problem(rng)
    obj = make_glm_objective(SquaredLoss)
    l1 = jnp.float32(0.05)
    cfg = OptimizerConfig.lbfgs(constraint_lower=-0.1, constraint_upper=0.1)
    res = owlqn_solve(obj, jnp.zeros(8), data, jnp.float32(0.0), l1, cfg)
    assert float(jnp.max(res.w)) <= 0.1 + 1e-6
    assert float(jnp.min(res.w)) >= -0.1 - 1e-6
    assert float(jnp.max(jnp.abs(res.w))) > 0.1 - 1e-4  # a bound binds

    wide = OptimizerConfig.lbfgs(constraint_lower=-100.0, constraint_upper=100.0)
    r_wide = owlqn_solve(obj, jnp.zeros(8), data, jnp.float32(0.0), l1, wide)
    r_free = owlqn_solve(obj, jnp.zeros(8), data, jnp.float32(0.0), l1)
    np.testing.assert_allclose(
        np.asarray(r_wide.w), np.asarray(r_free.w), atol=1e-5
    )


def test_vmap_batched_solves(rng):
    """vmap over independent problems == solving each separately — the
    random-effect execution mode (reference RandomEffectCoordinate's
    mapValues local solves)."""
    obj = make_glm_objective(SquaredLoss)
    n_prob, n, d = 5, 32, 4
    Xs = rng.normal(size=(n_prob, n, d)).astype(np.float32)
    ws = rng.normal(size=(n_prob, d)).astype(np.float32)
    ys = np.einsum("pnd,pd->pn", Xs, ws).astype(np.float32)
    datas = LabeledData.create(
        DenseFeatures(matrix=jnp.asarray(Xs)),
        jnp.asarray(ys),
        offsets=jnp.zeros((n_prob, n)),
        weights=jnp.ones((n_prob, n)),
    )
    l2 = jnp.float32(1e-3)
    batched = jax.vmap(lambda dd: lbfgs_solve(obj, jnp.zeros(d), dd, l2))(datas)
    for p in range(n_prob):
        single = lbfgs_solve(
            obj,
            jnp.zeros(d),
            jax.tree.map(lambda a: a[p], datas),
            l2,
        )
        np.testing.assert_allclose(batched.w[p], single.w, rtol=5e-2, atol=5e-3)
        np.testing.assert_allclose(batched.w[p], ws[p], rtol=5e-2, atol=5e-3)


def test_solve_dispatch(rng):
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    cfg_l1 = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.ELASTIC_NET, alpha=0.5),
        regularization_weight=1.0,
    )
    res = solve(obj, jnp.zeros(6), data, cfg_l1)
    assert res.w.shape == (6,)
    cfg_tron = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.tron(),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    res2 = solve(obj, jnp.zeros(6), data, cfg_tron)
    np.testing.assert_allclose(res2.grad_norm, 0.0, atol=5e-2)
    with pytest.raises(ValueError, match="TRON does not support L1"):
        solve(
            obj,
            jnp.zeros(6),
            data,
            GlmOptimizationConfiguration(
                optimizer_config=OptimizerConfig.tron(),
                regularization=RegularizationContext(RegularizationType.L1),
                regularization_weight=1.0,
            ),
        )


def test_warm_start_lambda_sweep_no_recompile(rng):
    """l2_weight is traced: two λ values must hit the same compiled program
    (the reference's warm-start sweep, ModelTraining.scala:160-206)."""
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    jitted = jax.jit(lambda w0, dd, l2: lbfgs_solve(obj, w0, dd, l2))
    r_high = jitted(jnp.zeros(6), data, jnp.float32(100.0))
    r_low = jitted(r_high.w, data, jnp.float32(0.1))
    assert jitted._cache_size() == 1
    assert float(r_low.value) < float(r_high.value)


# ---------------------------------------------------------------------------
# Reference OptimizerIntegTest.scala:120-200: convergence-state invariants
# over 100 random starts on the fake centroid objective (TestObjective.scala:
# f(w) = 0.5*||w - CENTROID||^2, CENTROID = 4.0), vmapped into one batched
# solve per optimizer instead of 100 sequential Spark jobs.
# ---------------------------------------------------------------------------

_CENTROID = 4.0


@pytest.mark.parametrize("name", ["lbfgs", "tron", "owlqn"])
def test_track_coefficients_history(rng, name):
    """OptimizerConfig.track_coefficients records the per-iteration w path
    (reference ModelTracker): last recorded iterate == final w, the path is
    finite up to `iterations`, NaN-padded after, and off by default."""
    data, _ = _logreg_problem(rng)
    obj = make_glm_objective(LogisticLoss)
    l2 = jnp.float32(0.1)
    cfg = OptimizerConfig(max_iterations=40, track_coefficients=True)
    if name == "lbfgs":
        res = lbfgs_solve(obj, jnp.zeros(6), data, l2, cfg)
        res_off = lbfgs_solve(obj, jnp.zeros(6), data, l2)
    elif name == "tron":
        res = tron_solve(obj, jnp.zeros(6), data, l2, cfg)
        res_off = tron_solve(obj, jnp.zeros(6), data, l2)
    else:
        res = owlqn_solve(obj, jnp.zeros(6), data, l2, jnp.float32(0.01), cfg)
        res_off = owlqn_solve(obj, jnp.zeros(6), data, l2, jnp.float32(0.01))
    assert res_off.w_history is None
    assert res.w_history is not None
    hist = np.asarray(res.w_history)
    iters = int(res.iterations)
    assert hist.shape == (41, 6)
    assert np.isfinite(hist[: iters + 1]).all()
    np.testing.assert_allclose(hist[iters], np.asarray(res.w), rtol=1e-6)
    if iters < 40:
        assert np.isnan(hist[iters + 1 :]).all()
    # the recorded start is the initial point
    np.testing.assert_allclose(hist[0], 0.0)


def test_track_models_through_train_glm(rng):
    """train_glm(track_models=True) yields per-iteration models whose last
    entry equals the fit model, mapped back through normalization."""
    from photon_ml_tpu.estimators.model_training import train_glm
    from photon_ml_tpu.normalization import build_normalization_context
    from photon_ml_tpu.stat.summary import summarize
    from photon_ml_tpu.types import NormalizationType, TaskType

    data, _ = _logreg_problem(rng)
    labeled = data
    summary = summarize(labeled)
    norm = build_normalization_context(
        NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
        mean=summary.mean,
        variance=summary.variance,
        max_magnitude=summary.max_abs,
        intercept_index=None,
    )
    labeled = labeled.replace(norm=norm)
    cfg = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig(max_iterations=30),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.1,
    )
    fit = train_glm(labeled, TaskType.LOGISTIC_REGRESSION, cfg,
                    track_models=True)[0]
    assert fit.tracked_models is not None
    assert len(fit.tracked_models) == int(fit.result.iterations) + 1
    np.testing.assert_allclose(
        np.asarray(fit.tracked_models[-1].coefficients.means),
        np.asarray(fit.model.coefficients.means),
        rtol=2e-4, atol=1e-6,
    )


def _centroid_objective():
    def value(w, data, l2):
        d = w - _CENTROID
        return 0.5 * jnp.dot(d, d)

    def value_and_grad(w, data, l2):
        d = w - _CENTROID
        return 0.5 * jnp.dot(d, d), d

    def hessian_vec(w, v, data, l2):
        return v

    def hessian_diag(w, data, l2):
        return jnp.ones_like(w)

    return GlmObjective(
        value=value,
        value_and_grad=value_and_grad,
        hessian_vec=hessian_vec,
        hessian_diag=hessian_diag,
        has_hessian=True,
    )


@pytest.mark.parametrize(
    "name,batched_solver",
    [
        (
            "lbfgs",
            lambda obj, cfg: jax.jit(jax.vmap(
                lambda w0: lbfgs_solve(obj, w0, jnp.zeros(1), jnp.float32(0.0), cfg)
            )),
        ),
        (
            "tron",
            lambda obj, cfg: jax.jit(jax.vmap(
                lambda w0: tron_solve(obj, w0, jnp.zeros(1), jnp.float32(0.0), cfg)
            )),
        ),
        (
            "owlqn",
            lambda obj, cfg: jax.jit(jax.vmap(
                lambda w0: owlqn_solve(
                    obj, w0, jnp.zeros(1), jnp.float32(0.0), jnp.float32(0.0), cfg
                )
            )),
        ),
    ],
)
def test_invariants_100_random_starts(rng, name, batched_solver):
    """Every start must converge to the centroid with a monotone value
    history and a reason consistent with its final state."""
    d, n_starts = 10, 100
    obj = _centroid_objective()
    cfg = (
        OptimizerConfig.tron(tolerance=1e-7, max_iterations=100)
        if name == "tron"
        else OptimizerConfig.lbfgs(tolerance=1e-7, max_iterations=200)
    )
    starts = jnp.asarray(rng.normal(size=(n_starts, d)).astype(np.float32) * 10)
    res = batched_solver(obj, cfg)(starts)

    reasons = np.asarray(res.reason)
    assert np.all(reasons != ConvergenceReason.NOT_CONVERGED.value)
    assert np.all(reasons != ConvergenceReason.MAX_ITERATIONS.value), (
        f"{name}: some starts hit max iterations: "
        f"{np.bincount(reasons, minlength=5)}"
    )
    # expected parameters (reference PARAMETER_TOLERANCE=1e-4, f64; f32 here)
    w = np.asarray(res.w)
    np.testing.assert_allclose(w, _CENTROID, atol=5e-3)

    # reason-consistent final state (OBJECTIVE/GRADIENT_TOLERANCE analogs)
    values = np.asarray(res.value)
    gnorms = np.asarray(res.grad_norm)
    f_conv = reasons == ConvergenceReason.FUNCTION_VALUES_CONVERGED.value
    g_conv = reasons == ConvergenceReason.GRADIENT_CONVERGED.value
    assert np.all(values[f_conv] < 1e-4)
    assert np.all(gnorms[g_conv] < 1e-2)

    # monotone non-increasing value history over the tracked prefix
    hist = np.asarray(res.value_history)  # [starts, max_iter+1], NaN padded
    valid = ~np.isnan(hist)
    diffs = np.diff(hist, axis=1)
    ok = np.isnan(diffs) | (diffs <= 1e-5)
    assert np.all(ok[valid[:, :-1] & valid[:, 1:]]), (
        f"{name}: objective increased somewhere in the tracked history"
    )


@pytest.mark.parametrize("task_name", ["LINEAR_REGRESSION", "LOGISTIC_REGRESSION"])
@pytest.mark.parametrize("solver_name", ["lbfgs", "tron", "owlqn"])
def test_solvers_survive_ill_conditioned_data(task_name, solver_name):
    """Reference OptimizerIntegTest drives each optimizer over deliberately
    ill-conditioned ("outlier") draws: the solve must stay finite and end
    with a valid convergence reason — never NaN coefficients or a crash."""
    from photon_ml_tpu.losses import make_glm_objective
    from photon_ml_tpu.losses.pointwise import loss_for_task
    from photon_ml_tpu.testing import draw_sample
    from photon_ml_tpu.types import TaskType

    task = TaskType[task_name]
    X, y, _ = draw_sample(task, n=256, d=8, regime="outlier", seed=11)
    data = LabeledData.create(
        DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y)
    )
    obj = make_glm_objective(loss_for_task(task))
    cfg = (
        OptimizerConfig.tron(max_iterations=20)
        if solver_name == "tron"
        else OptimizerConfig.lbfgs(max_iterations=50)
    )
    l2 = jnp.float32(1.0)
    if solver_name == "lbfgs":
        res = lbfgs_solve(obj, jnp.zeros(8), data, l2, cfg)
    elif solver_name == "tron":
        res = tron_solve(obj, jnp.zeros(8), data, l2, cfg)
    else:
        res = owlqn_solve(obj, jnp.zeros(8), data, l2, jnp.float32(0.1), cfg)
    w = np.asarray(res.w)
    assert np.all(np.isfinite(w)), f"{solver_name} produced non-finite w"
    assert np.isfinite(float(res.value))
    assert int(res.reason) in {r.value for r in ConvergenceReason}
    # the solve must improve on w=0
    f0 = float(obj.value(jnp.zeros(8), data, l2))
    assert float(res.value) <= f0 + 1e-6


@pytest.mark.parametrize(
    "name,width",
    [("lbfgs", 6), ("owlqn", 6), ("tron", 8), ("lbfgs", 1100), ("owlqn", 1100)],
    ids=["lbfgs", "owlqn", "tron", "lbfgs-tiled", "owlqn-tiled"],
)
def test_chunked_resume_matches_oneshot(rng, name, width):
    """init -> chunk(K) ... -> finalize must follow the EXACT trajectory of
    the uninterrupted solve: the chunk boundary only caps the while_loop's
    trip count, it never perturbs the carried state (L-BFGS history ring,
    TRON trust radius, OWL-QN pseudo-gradient bookkeeping). At 1,100
    coefficients a row of the history that crosses the boundary spans nine
    tiles and is padded to 1,152 (``opt/lbfgs.py:history_zeros``)."""
    from photon_ml_tpu.opt import solve, solve_chunk, solve_finalize, solve_init

    if name == "tron":
        data, _ = _linreg_problem(rng)
        obj = make_glm_objective(SquaredLoss)
        configuration = GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig.tron(),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=0.1,
        )
    else:
        data, _ = _logreg_problem(rng, d=width)
        obj = make_glm_objective(LogisticLoss)
        reg = RegularizationType.L1 if name == "owlqn" else RegularizationType.L2
        configuration = GlmOptimizationConfiguration(
            regularization=RegularizationContext(reg),
            regularization_weight=0.01 if name == "owlqn" else 0.1,
        )
    d = data.features.matrix.shape[1]
    assert d == width
    w0 = jnp.zeros(d)

    ref = solve(obj, w0, data, configuration)
    state = solve_init(obj, w0, data, configuration)
    if name != "tron":
        assert state.s_hist.shape[1:] == (-(-width // 128), 128)
    for _ in range(40):  # 40 chunks x 3 iters covers max_iterations=100
        state = solve_chunk(obj, state, data, configuration, num_iters=3)
    res = solve_finalize(state, configuration)

    np.testing.assert_allclose(np.asarray(res.w), np.asarray(ref.w),
                               rtol=0, atol=1e-6)
    assert int(res.iterations) == int(ref.iterations)
    assert int(res.reason) == int(ref.reason)
