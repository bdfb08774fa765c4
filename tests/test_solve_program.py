"""The fixed effect's solve program is built once per (loss, configuration)
and kept across ``train_glm`` calls (ISSUE 27): equal calls trace, lower and
compile once; a new static key, shape or box presence builds once more; the
program is the one the old per-call ``jax.jit`` closure built, bit for bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.estimators.model_training import _solve_program, block_on_fit
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import loss_for_task
from photon_ml_tpu.ops import LabeledData, fused_perm
from photon_ml_tpu.ops.features import from_scipy_like
from photon_ml_tpu.opt import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.opt.solve import solve
from photon_ml_tpu.telemetry import (
    disable_tracing,
    enable_tracing,
    get_tracer,
    jit_trace_counts,
)
from photon_ml_tpu.types import RegularizationType, TaskType
from tests._tiny_glmix import _tiny_glmix, _tiny_glmix_estimator

TASK = TaskType.LOGISTIC_REGRESSION
SOLVERS = ("lbfgs", "owlqn", "tron")


def _configuration(solver: str, tolerance: float = 1e-6) -> GlmOptimizationConfiguration:
    """A fresh object at every call: equal to, never the same as, the last."""
    if solver == "tron":
        optimizer = OptimizerConfig.tron(max_iterations=5, tolerance=tolerance)
    else:
        optimizer = OptimizerConfig.lbfgs(max_iterations=8, tolerance=tolerance)
    kind = RegularizationType.ELASTIC_NET if solver == "owlqn" else RegularizationType.L2
    return GlmOptimizationConfiguration(
        optimizer_config=optimizer,
        regularization=RegularizationContext(kind, alpha=0.5 if solver == "owlqn" else None),
        regularization_weight=1.0,
    )


def _data(engine: str, n: int = 256, d: int = 40, seed: int = 7) -> LabeledData:
    rng = np.random.default_rng(seed)
    nnz = 8 * n
    rows, cols = rng.integers(0, n, nnz), rng.integers(0, d, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    if engine == "fused":
        features = fused_perm.from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128, plan_cache=""
        )
    else:
        features = from_scipy_like(rows, cols, vals, (n, d))
    return LabeledData.create(features, jnp.asarray(y))


@pytest.fixture
def clean_slate():
    """Nothing compiled by an earlier test of this process is left, so a
    test's first call is a first call."""
    jax.clear_caches()
    yield
    jax.clear_caches()


class _Watch:
    """Counts, call by call, the traces of ``glm_solve/<kind>`` (the
    program's own counter) and JAX's compile events for the solve (PR 26's
    compile spans, which are made of the ``jax.monitoring`` events)."""

    def __init__(self, kind: str):
        self.key = f"glm_solve/{kind}"
        self.tracer = enable_tracing(device_sync=False, clear=True)
        self.traces_before = self.traces()
        self.spans_before = 0

    def traces(self) -> int:
        return jit_trace_counts().get(self.key, 0)

    def since_last(self):
        """(traces so far, the solve's compile spans since the last look)."""
        spans = self.tracer.spans()
        new, self.spans_before = spans[self.spans_before:], len(spans)
        compiles = [
            s for s in new
            if s.name in ("jit/trace", "jit/lower", "jit/backend")
            and s.attrs["fun_name"] in ("glm_solve", "jit(glm_solve)")
        ]
        return self.traces() - self.traces_before, compiles


@pytest.fixture
def watch():
    watchers = []

    def start(kind):
        watchers.append(_Watch(kind))
        return watchers[-1]

    yield start
    disable_tracing()
    get_tracer().clear()


def _fit(data, solver, **kw):
    return block_on_fit(train_glm(data, TASK, _configuration(solver), **kw)[0])


def _old_program(configuration, use_l1, box=None):
    """What ``train_glm`` built at every call before ISSUE 27."""
    objective = make_glm_objective(loss_for_task(TASK))
    return jax.jit(
        lambda w0, dd, l2, l1: solve(
            objective, w0, dd, configuration,
            l2_weight=l2, l1_weight=l1 if use_l1 else 0.0, box=box,
        )
    )


def _assert_bitwise(result, expected):
    got, want = jax.tree.leaves(result), jax.tree.leaves(expected)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _weights(configuration, lam):
    reg = configuration.regularization
    return jnp.float32(reg.l2_weight(lam)), jnp.float32(reg.l1_weight(lam))


@pytest.mark.parametrize("engine", ["ell", "fused"])
@pytest.mark.parametrize("solver", SOLVERS)
def test_equal_calls_share_one_program(
    solver, engine, clean_slate, interpret_kernels, watch
):
    data = _data(engine)
    w = watch(solver)
    first = _fit(data, solver)
    traces, compiles = w.since_last()
    assert traces == 1
    assert {s.name for s in compiles} == {"jit/trace", "jit/lower", "jit/backend"}
    assert all("glm/train" in s.attrs["under"] for s in compiles)

    # fresh-but-equal configuration, replaced offsets, another λ, warm start
    moved = data.replace(offsets=jnp.full((data.num_rows,), 0.25, jnp.float32))
    second = _fit(
        moved, solver, regularization_weights=[3.0], initial_model=first.model
    )
    third = _fit(data, solver, initial_model=second.model)
    traces, compiles = w.since_last()
    assert traces == 1
    assert compiles == []
    assert not np.array_equal(np.asarray(second.result.w), np.asarray(first.result.w))

    configuration = _configuration(solver)
    old = _old_program(configuration, use_l1=solver == "owlqn")
    zeros = jnp.zeros((data.dim,), jnp.float32)
    _assert_bitwise(first.result, old(zeros, data, *_weights(configuration, 1.0)))
    _assert_bitwise(
        third.result, old(second.result.w, data, *_weights(configuration, 1.0))
    )


@pytest.mark.parametrize("change", ["tolerance", "shape", "box"])
@pytest.mark.parametrize("solver", SOLVERS)
def test_new_key_or_shape_builds_once_more(solver, change, clean_slate, watch):
    data = _data("ell")
    w = watch(solver)
    _fit(data, solver)
    assert w.since_last()[0] == 1
    for _ in range(2):  # the changed call builds once, its repeat not at all
        if change == "tolerance":
            block_on_fit(train_glm(data, TASK, _configuration(solver, tolerance=1e-4))[0])
        elif change == "shape":
            _fit(_data("ell", n=192), solver)
        else:
            box = (np.full(data.dim, -0.05, np.float32), np.full(data.dim, 0.05, np.float32))
            _fit(data, solver, box_constraints=box)
    assert w.since_last()[0] == 2
    _fit(data, solver)
    traces, compiles = w.since_last()
    if change == "tolerance":
        # another key: the one kept program made room for it (never two
        # keys' programs on the device), so the first key builds again
        assert _solve_program.cache_info().currsize == 1
        assert traces == 3
    else:
        # another specialization of the same key: the first is still there
        assert traces == 2 and compiles == []


@pytest.mark.parametrize("solver", SOLVERS)
def test_box_is_an_argument_and_clips_as_the_closed_over_one(solver, clean_slate, watch):
    data = _data("ell")
    configuration = _configuration(solver)
    zeros = jnp.zeros((data.dim,), jnp.float32)
    w = watch(solver)
    for bound in (0.05, 0.02):
        lower = np.full(data.dim, -bound, np.float32)
        upper = np.full(data.dim, bound, np.float32)
        upper[0] = np.inf
        fit = _fit(data, solver, box_constraints=(lower, upper))
        got = np.asarray(fit.result.w)
        assert (got >= lower).all() and (got[1:] <= upper[1:]).all()
        assert np.isclose(np.abs(got), bound).any()  # the box binds
        old = _old_program(configuration, solver == "owlqn", box=(lower, upper))
        _assert_bitwise(fit.result, old(zeros, data, *_weights(configuration, 1.0)))
    assert w.since_last()[0] == 1  # two boxes of one shape: one program


def test_clear_caches_frees_the_program_and_the_next_call_rebuilds_it(clean_slate, watch):
    """What the benchmark's ``release()`` relies on: the kept program is a
    plain jitted callable, not an ahead-of-time executable."""
    data = _data("ell")
    w = watch("lbfgs")
    first = _fit(data, "lbfgs")
    _fit(data, "lbfgs")
    assert w.since_last()[0] == 1
    jax.clear_caches()
    again = _fit(data, "lbfgs")
    traces, compiles = w.since_last()
    assert traces == 2
    assert {s.name for s in compiles} == {"jit/trace", "jit/lower", "jit/backend"}
    _assert_bitwise(again.result, first.result)


def test_variances_and_tracking_reuse_their_programs(clean_slate, watch):
    """``track_models`` changes the configuration (one more key); the
    Hessian diagonal is jitted over the objective's own function, which is
    the same object at every call."""
    data = _data("ell")
    w = watch("lbfgs")
    for _ in range(2):
        fit = train_glm(
            data, TASK, _configuration("lbfgs"), compute_variances=True, track_models=True
        )[0]
        assert fit.model.coefficients.variances is not None
        assert len(fit.tracked_models) == int(fit.result.iterations) + 1
    traces, _ = w.since_last()
    assert traces == 1
    hessians = [
        s for s in w.tracer.spans()
        if s.name == "jit/lower" and "hessian_diag" in s.attrs["fun_name"]
    ]
    assert len(hessians) == 1


@pytest.mark.parametrize(
    "solver,d,layout,direction",
    [("lbfgs", 40, "rows", "recursion"), ("lbfgs", 1100, "tiled", "gram"),
     ("owlqn", 40, "rows", "recursion"), ("owlqn", 1100, "tiled", "gram"),
     ("tron", 1100, "none", "none")],
)
def test_a_traced_solve_says_how_its_history_lies(solver, d, layout, direction, clean_slate, watch):
    """``glm/solve`` carries ``history_layout`` and ``direction`` beside the
    solver's counts: for L-BFGS and OWL-QN ``rows`` (``[m, d]``) under 128
    coefficients, whose direction is the two-loop ``recursion`` over its
    rows, and ``tiled`` (a row of the history to itself) from there up,
    whose direction comes from the carried ``gram`` matrix; ``none`` for
    TRON. ``descent_restarts`` is L-BFGS's count of directions that fell
    back to ``-g``: none on this smooth problem, and 0 from the others."""
    w = watch(solver)
    _fit(_data("ell", d=d), solver)
    (solved,) = [s for s in w.tracer.spans() if s.name == "glm/solve"]
    assert solved.attrs["history_layout"] == layout
    assert solved.attrs["direction"] == direction
    assert solved.attrs["descent_restarts"] == 0
    assert solved.attrs["evaluations"] >= solved.attrs["iterations"] + 1


@pytest.mark.parametrize("path", ["fit_multiple", "coordinate_descent"])
def test_the_normal_path_traces_the_fixed_effect_once(path, clean_slate, watch):
    """``fit_multiple`` over two equal configurations (as the benchmark's
    ``cd-fit`` traffic runs one fit after another) and one ``CoordinateDescent`` run of two outer
    iterations both reach ``train_glm`` twice with an equal configuration."""
    data = _tiny_glmix()
    w = watch("lbfgs")
    if path == "fit_multiple":
        fits = _tiny_glmix_estimator().fit_multiple(data, configs=[{}, {}], warm_start=True)
        assert len(fits) == 2
    else:
        _tiny_glmix_estimator(num_outer_iterations=2).fit(data)
    traces, compiles = w.since_last()
    solves = [s for s in w.tracer.spans() if s.name == "glm/solve"]
    assert len(solves) == 2
    assert traces == 1
    assert sorted(s.name for s in compiles) == ["jit/backend", "jit/lower", "jit/trace"]
    first_solve_end = min(s.start_s + s.duration_s for s in solves)
    assert all(s.start_s < first_solve_end for s in compiles)


def test_only_the_optimizer_settings_of_a_configuration_are_in_the_key(clean_slate, watch):
    """The weights are arguments of the program: a λ swept through the
    configuration (``fit_multiple``'s overrides) is not a new program."""
    data = _data("ell")
    w = watch("lbfgs")
    base = _configuration("lbfgs")
    first = block_on_fit(train_glm(data, TASK, base)[0])
    rebuilt = dataclasses.replace(
        base, optimizer_config=dataclasses.replace(base.optimizer_config)
    )
    assert rebuilt is not base and rebuilt == base
    block_on_fit(train_glm(data, TASK, rebuilt)[0])
    heavier = dataclasses.replace(base, regularization_weight=30.0)
    second = block_on_fit(train_glm(data, TASK, heavier)[0])
    traces, _ = w.since_last()
    assert traces == 1
    assert float(jnp.linalg.norm(second.result.w)) < float(jnp.linalg.norm(first.result.w))
    old = _old_program(heavier, use_l1=False)
    zeros = jnp.zeros((data.dim,), jnp.float32)
    _assert_bitwise(second.result, old(zeros, data, *_weights(heavier, 30.0)))
