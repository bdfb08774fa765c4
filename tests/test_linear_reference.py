"""The squared loss on normalized features, TRON's counts and the feature
statistics, held against the benchmark's plain reference
(``benchmarks/reference/linear.py``, loaded by path as ``benchmarks/run.py``
loads its layer metrics: float32 ``jax.numpy``, nothing of the program,
statistics in float64 on the host, a conjugate-gradient solve that is not
TRON). Small and seeded; the chip holds the same comparison at the cell's
size (``benchmarks/traffic/refit_norm.py``).
"""

import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.estimators.model_training import block_on_fit, train_glm
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import LogisticLoss, SquaredLoss
from photon_ml_tpu.normalization import build_normalization_context
from photon_ml_tpu.ops import LabeledData, fused_perm
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.sparse_perm import ColumnSplitFeatures
from photon_ml_tpu.opt import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.opt.solve import solve
from photon_ml_tpu.opt.tron import _truncated_cg, tron_chunk, tron_init
from photon_ml_tpu.stat.summary import _fused_stats, summarize
from photon_ml_tpu.telemetry import jit_trace_counts
from photon_ml_tpu.types import (
    ConvergenceReason,
    NormalizationType,
    RegularizationType,
    TaskType,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NORMALIZATIONS = (
    "SCALE_WITH_STANDARD_DEVIATION", "SCALE_WITH_MAX_MAGNITUDE", "STANDARDIZATION",
)
N, D, K = 384, 96, 6
INTERCEPT = D - 1
L2 = float(N)


@pytest.fixture(scope="module")
def linear():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_reference_linear", os.path.join(ROOT, "benchmarks", "reference", "linear.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def rows():
    """K scaled entries a row over the first D - 1 columns (scales two
    decades apart, a few repeated column ids in a row, which add up) and
    the intercept, an all-ones last column."""
    rng = np.random.default_rng(28)
    sigma = 10.0 ** rng.uniform(-1, 1, D - 1)
    cols = rng.integers(0, D - 1, (N, K))
    vals = rng.standard_normal((N, K)) * sigma[cols]
    w_true = rng.standard_normal(D - 1) * 0.3 / sigma
    labels = (vals * w_true[cols]).sum(-1) + 0.7 + 0.5 * rng.standard_normal(N)
    cols = np.concatenate([cols, np.full((N, 1), INTERCEPT)], axis=1).astype(np.int64)
    vals = np.concatenate([vals, np.ones((N, 1))], axis=1).astype(np.float32)
    return types.SimpleNamespace(cols=cols, vals=vals, labels=labels.astype(np.float32))


def _features(rows, engine):
    r = np.repeat(np.arange(N, dtype=np.int64), K + 1)
    c, v = rows.cols.reshape(-1), rows.vals.reshape(-1)
    if engine == "dense":
        matrix = np.zeros((N, D), np.float32)
        np.add.at(matrix, (r, c), v)
        return DenseFeatures(jnp.asarray(matrix))
    feats = fused_perm.from_coo(
        r, c, v, (N, D), size_floor=128 * 128, plan_cache="",
        col_split=2 if engine == "split" else 1,
        payload_dtype="bfloat16" if engine == "fused_bf16" else "float32",
    )
    assert isinstance(feats, ColumnSplitFeatures) == (engine == "split")
    assert INTERCEPT in np.asarray(feats.hot_cols)  # the dense column went to the hot side
    return feats


def _reference(linear, rows, kind, precision="float32"):
    config = {"fixed_effect": {"regularization_weight": L2, "normalization": kind}}
    problem = types.SimpleNamespace(n_cols=D, train=rows)
    return linear.LinearReference(config, problem, precision, intercept_index=INTERCEPT)


def _labeled(rows, engine, kind):
    """The program's data as ``cli/train_glm.py`` builds it: statistics,
    context, then the labeled data with the context in it."""
    feats = _features(rows, engine)
    plain = LabeledData.create(feats, jnp.asarray(rows.labels))
    summary = summarize(plain)
    norm = build_normalization_context(
        NormalizationType[kind], mean=summary.mean, variance=summary.variance,
        max_magnitude=summary.max_abs, intercept_index=INTERCEPT,
    )
    return plain.replace(norm=norm), summary


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-30)
    np.testing.assert_allclose(got / scale, want / scale, rtol=0, atol=rtol)


@pytest.mark.parametrize("what", ["value_and_grad", "hessian_vec"])
@pytest.mark.parametrize("engine", ["dense", "fused"])
@pytest.mark.parametrize("kind", NORMALIZATIONS)
def test_squared_loss_on_normalized_features_agrees_with_the_reference(
    linear, rows, kind, engine, what, interpret_kernels
):
    data, _ = _labeled(rows, engine, kind)
    ref = _reference(linear, rows, kind)
    objective = make_glm_objective(SquaredLoss)
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.2)
    v = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    if what == "value_and_grad":
        value, grad = objective.value_and_grad(w, data, jnp.float32(L2))
        want_value, want_grad = ref.value_and_grad(w)
        assert float(value) == pytest.approx(float(want_value), rel=2e-5)
        _close(grad, want_grad, 2e-5)
    else:
        _close(objective.hessian_vec(w, v, data, jnp.float32(L2)), ref.hessian_vec(v), 2e-5)


@pytest.mark.parametrize("what", ["mean", "variance", "max_abs", "factor"])
@pytest.mark.parametrize("engine", ["fused", "split"])
def test_summarize_agrees_with_the_reference_statistics(
    linear, rows, engine, what, interpret_kernels
):
    """``_fused_stats`` (one routed block) and ``_split_stats`` (two, the hot
    side folded in) against float64 sums on the host, zeros counted."""
    data, summary = _labeled(rows, engine, "SCALE_WITH_STANDARD_DEVIATION")
    ref = _reference(linear, rows, "SCALE_WITH_STANDARD_DEVIATION")
    if what == "factor":
        _close(data.norm.factor, ref.factor, 1e-5)
        assert float(data.norm.factor[INTERCEPT]) == 1.0
    else:
        _close(getattr(summary, what), ref.statistics[what], 1e-5)
    np.testing.assert_array_equal(
        np.asarray(summary.num_nonzeros), ref.statistics["nonzeros"])


def test_blocks_of_a_column_split_share_one_stats_program(linear, interpret_kernels):
    """Blocks whose spill sides differ in length are padded to the longest,
    so one compiled ``_fused_stats`` serves them all (on a v5e a new spill
    length cost 21 s of compiles: its three scatters sort their indices)."""
    rng = np.random.default_rng(7)
    n, d, k = 4096, 65536, 16
    cols = rng.integers(0, d, (n, k)).astype(np.int64)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    feats = fused_perm.from_coo(
        np.repeat(np.arange(n, dtype=np.int64), k), cols.reshape(-1), vals.reshape(-1), (n, d),
        max_hot_cols=0, plan_cache="",
    )
    spills = {b.spill_rows.shape[0] for b in feats.blocks if b.spill_rows is not None}
    assert isinstance(feats, ColumnSplitFeatures) and len(spills) > 1
    programs = _fused_stats._cache_size()
    summary = summarize(LabeledData.create(feats, jnp.zeros((n,), jnp.float32)))
    assert _fused_stats._cache_size() - programs == 1
    want = linear.column_statistics(cols, vals, d)
    for what in ("mean", "variance", "max_abs"):
        _close(getattr(summary, what), want[what], 1e-5)
    np.testing.assert_array_equal(np.asarray(summary.num_nonzeros), want["nonzeros"])


@pytest.mark.parametrize("engine", ["dense", "fused", "fused_bf16"])
@pytest.mark.parametrize("kind", NORMALIZATIONS)
def test_tron_fit_on_normalized_features_reaches_the_reference_optimum(
    linear, rows, kind, engine, interpret_kernels
):
    """``train_glm`` hands back the model in the original feature space. The
    bfloat16 payload is held to the optimum only as the exact objective
    scores its solution (relative 1e-4): its maps round at network entry."""
    data, _ = _labeled(rows, engine, kind)
    ref = _reference(linear, rows, kind)
    w_ref, info = ref.solve()
    assert info["relative_gradient"] < 1e-6
    fit = block_on_fit(train_glm(
        data, TaskType.LINEAR_REGRESSION, _tron(tolerance=1e-7, max_iterations=30),
        intercept_index=INTERCEPT,
    )[0])
    w = np.asarray(fit.model.coefficients.means)
    if engine == "fused_bf16":
        assert ref.objective(w) == pytest.approx(info["value"], rel=1e-4)
        return
    assert float(fit.result.value) == pytest.approx(info["value"], rel=1e-5)
    assert ref.objective(w) == pytest.approx(info["value"], rel=1e-5)
    assert np.linalg.norm(w - np.asarray(w_ref)) <= 2e-3 * np.linalg.norm(w_ref)
    assert ref.stationarity(w) < 1e-3  # where TRON stopped, not rounding
    assert int(fit.result.hessian_vecs) >= int(fit.result.iterations) > 0


def test_bfloat16_reference_is_the_control_not_the_reference(linear, rows):
    """The control rounds its operands: its factor is off by what bfloat16
    keeps of a value, hundreds of times what float32 statistics are off."""
    ref = _reference(linear, rows, "SCALE_WITH_STANDARD_DEVIATION")
    control = _reference(linear, rows, "SCALE_WITH_STANDARD_DEVIATION", "bfloat16")
    gap = np.abs(np.asarray(control.factor) / np.asarray(ref.factor) - 1.0).max()
    assert 1e-4 < gap < 2e-2


def _tron(**kw):
    return GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.tron(**kw),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=L2,
    )


def _logistic_data():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((256, 12)).astype(np.float32)
    w = rng.standard_normal(12).astype(np.float32)
    y = (rng.random(256) < 1.0 / (1.0 + np.exp(-x @ w))).astype(np.float32)
    return LabeledData.create(DenseFeatures(jnp.asarray(x)), jnp.asarray(y))


@pytest.mark.parametrize("solver", ["tron", "lbfgs", "owlqn"])
def test_hessian_vecs_are_the_cg_steps_and_zero_without_a_hessian(solver):
    data, objective = _logistic_data(), make_glm_objective(LogisticLoss)
    w0, l2 = jnp.zeros((12,), jnp.float32), jnp.float32(0.5)
    if solver == "tron":
        optimizer = OptimizerConfig.tron(tolerance=1e-6)
    else:
        optimizer = OptimizerConfig.lbfgs(max_iterations=20)
    kind = RegularizationType.ELASTIC_NET if solver == "owlqn" else RegularizationType.L2
    configuration = GlmOptimizationConfiguration(
        optimizer_config=optimizer,
        regularization=RegularizationContext(kind, alpha=0.5 if solver == "owlqn" else None),
        regularization_weight=1.0,
    )
    result = solve(objective, w0, data, configuration)
    assert int(result.iterations) > 1
    if solver != "tron":
        assert int(result.hessian_vecs) == 0 and int(result.rejected_steps) == 0
        return
    # TRON's outer loop one step at a time, each step's CG made by hand first
    state = tron_init(objective, w0, data, l2, optimizer)
    cg_steps = 0
    while int(state.reason) == ConvergenceReason.NOT_CONVERGED.value:
        at = state.w
        _, _, steps = _truncated_cg(
            lambda v: objective.hessian_vec(at, v, data, l2), state.g, state.delta,
            optimizer.max_cg_iterations, optimizer.cg_tolerance,
        )
        cg_steps += int(steps)
        state = tron_chunk(objective, state, data, l2, optimizer, num_iters=1)
    assert int(state.it) == int(result.iterations)
    assert int(result.hessian_vecs) == cg_steps > int(result.iterations)
    assert int(result.rejected_steps) == int(state.failures)
    assert int(result.evaluations) == int(result.iterations) + 1


def test_three_normalized_tron_fits_trace_the_solve_once(rows, interpret_kernels):
    data, _ = _labeled(rows, "fused", "SCALE_WITH_STANDARD_DEVIATION")
    jax.clear_caches()
    before = jit_trace_counts().get("glm_solve/tron", 0)
    fits = [
        block_on_fit(train_glm(data, TaskType.LINEAR_REGRESSION, _tron(),
                               intercept_index=INTERCEPT)[0])
        for _ in range(3)
    ]
    assert jit_trace_counts().get("glm_solve/tron", 0) - before == 1
    np.testing.assert_array_equal(
        np.asarray(fits[0].model.coefficients.means), np.asarray(fits[2].model.coefficients.means))
    jax.clear_caches()
