"""The fixed effect's scores come from one jitted program, ``_fe_score``,
the same function object for the life of the process, with the features and
the weight vector as its arguments (ISSUE 29): a scoring no longer traces,
lowers and compiles the routed engine's Pallas kernels again; every engine,
coordinate object and ``fit_multiple`` configuration of one tree structure
and shapes dispatches what the first call compiled; the scores are the
eager ``features.matvec(w)``'s to float32 rounding, and the same bits on the
host plane and the device plane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.algorithm.coordinate import FixedEffectCoordinate, _fe_score
from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_ml_tpu.estimators.game import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    ParallelConfiguration,
)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops import LabeledData, fused_perm
from photon_ml_tpu.ops.features import DenseFeatures, from_scipy_like
from photon_ml_tpu.ops.sparse_perm import ColumnSplitFeatures
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
from photon_ml_tpu.telemetry import (
    disable_tracing,
    enable_tracing,
    get_tracer,
    jit_trace_counts,
    span,
)
from photon_ml_tpu.types import TaskType
from tests._tiny_glmix import _tiny_glmix, _tiny_glmix_estimator

TASK = TaskType.LOGISTIC_REGRESSION
ENGINES = ("dense", "ell", "routed", "split")
COMPILE_SPANS = ("jit/trace", "jit/lower", "jit/backend")


def _features(engine, n=300, d=90, seed=3):
    rng = np.random.default_rng(seed)
    rows, cols = rng.integers(0, n, 8 * n), rng.integers(0, d, 8 * n)
    vals = rng.standard_normal(8 * n).astype(np.float32)
    if engine == "dense":
        matrix = np.zeros((n, d), np.float32)
        np.add.at(matrix, (rows, cols), vals)
        return DenseFeatures(jnp.asarray(matrix))
    if engine == "ell":
        return from_scipy_like(rows, cols, vals, (n, d))
    if engine == "spill":  # skewed columns under a slot cap: split blocks with spill sides
        cols = np.minimum(cols * cols // d, d - 1)
    feats = fused_perm.from_coo(
        rows, cols, vals, (n, d), size_floor=128 * 128, plan_cache="",
        col_split=1 if engine == "routed" else 4,
        **(dict(kp_cap=8, max_hot_cols=0) if engine == "spill" else {}),
    )
    assert isinstance(feats, ColumnSplitFeatures) == (engine != "routed")
    if engine == "spill":
        assert any(block.spill_rows is not None for block in feats.blocks)
    return feats


def _coordinate(features, padded_rows=0, padded_cols=0) -> FixedEffectCoordinate:
    """``padded_*``: how many of the features' trailing rows and columns are
    a device grid's padding, trimmed at the coordinate's boundary."""
    return FixedEffectCoordinate(
        data=LabeledData.create(features, jnp.zeros((features.num_rows,), jnp.float32)),
        task=TASK,
        configuration=GlmOptimizationConfiguration(),
        num_real_rows=features.num_rows - padded_rows if padded_rows else None,
        num_real_cols=features.dim - padded_cols if padded_cols else None,
    )


def _model(d, seed) -> GeneralizedLinearModel:
    w = np.random.default_rng(seed).standard_normal(d).astype(np.float32)
    return GeneralizedLinearModel(coefficients=Coefficients(means=jnp.asarray(w)), task=TASK)


def _traces() -> int:
    return jit_trace_counts().get("fe_score", 0)


@pytest.fixture
def clean_slate():
    """Nothing compiled by an earlier test of this process is left, so a
    test's first scoring is a first scoring."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def tracer():
    yield enable_tracing(device_sync=False, clear=True)
    disable_tracing()
    get_tracer().clear()


def _compiles_under(tracer, parents):
    """The compile spans that JAX reported while one of ``parents`` was the
    calling thread's open span."""
    ids = {s.span_id for s in parents}
    return [s for s in tracer.spans() if s.name in COMPILE_SPANS and s.parent_id in ids]


def _compiles_in(tracer, name):
    return _compiles_under(tracer, [s for s in tracer.spans() if s.name == name])


# the eager dispatch rounds after every operation; one program may fuse a
# multiply into the sum or scatter-add that takes it, and add the blocks of a
# column split in one pass: for a row's sum of k products the two may differ
# by k roundings of the sum of the products' magnitudes (k <= 32 here)
ROUNDINGS = 32 * float(np.finfo(np.float32).eps)


@jax.jit
def _magnitudes(features, w):
    """|X| |w|: a plan's leaves are indices, which abs leaves as they are."""
    return jax.tree.map(jnp.abs, features).matvec(jnp.abs(w))


def _assert_the_matvec(scores, features, w, n):
    """The contract: every score is the eager ``features.matvec(w)``'s to
    float32 rounding, ``ROUNDINGS`` x (|X| |w|) of its row. The bits are
    often the eager ones too, and no test may count on it: ELL differs in a
    tenth of its rows on the CPU, a column split with a spill side in one row
    of thousands, the chip's 8 routed blocks by 1 - 2 units in the last place
    (chip, PR 29)."""
    eager = np.asarray(features.matvec(w))[:n]
    magnitudes = _magnitudes(features, w)
    assert (np.abs(scores - eager) <= ROUNDINGS * np.asarray(magnitudes)[:n]).all()
    assert np.abs(eager).max() > 1.0  # the scores are not all within the bound of 0


@pytest.mark.parametrize("padded", [False, True], ids=["whole", "padded"])
@pytest.mark.parametrize("engine", ENGINES + ("spill",))
def test_scores_are_the_matvec_on_both_planes(engine, padded, interpret_kernels):
    features = _features(engine)
    coordinate = _coordinate(features, padded_rows=44 if padded else 0,
                             padded_cols=10 if padded else 0)
    n = 256 if padded else 300
    for seed in range(3):
        model = _model(80 if padded else 90, seed)
        w = jnp.pad(model.coefficients.means, (0, 10)) if padded else model.coefficients.means
        on_device = coordinate.score_device(model)
        assert isinstance(on_device, jax.Array) and on_device.shape == (n,)
        on_host = coordinate.score(model)
        assert isinstance(on_host, np.ndarray)
        np.testing.assert_array_equal(on_host, np.asarray(on_device))
        _assert_the_matvec(on_host, features, w, n)


def test_a_grid_scores_through_the_same_program(rng, clean_slate):
    """2 x 2 grid features: ``num_real_rows`` trims the padded rows, the
    solve hands over a feature-sharded vector, both planes agree."""
    from tests.test_parallel_estimator import _coords, _glmix_data

    data = _glmix_data(rng)
    estimator = GameEstimator(
        task=TASK,
        coordinates={"global": _coords()["global"]},
        num_outer_iterations=1,
        parallel=ParallelConfiguration(n_data=2, n_feat=2, engine="benes"),
    )
    before = _traces()
    fit = estimator.fit(data)
    coordinate = estimator._build_coordinate(
        "global", estimator.coordinate_configs["global"], data
    )
    assert coordinate.num_real_rows == data.num_rows
    model = fit.model.models["global"]
    on_host = coordinate.score(model)
    assert on_host.shape == (data.num_rows,)
    np.testing.assert_array_equal(on_host, np.asarray(coordinate.score_device(model)))
    _assert_the_matvec(
        on_host, coordinate.data.features, coordinate._padded_w(model), data.num_rows
    )
    # the fit's own scoring (the solve's feature-sharded vector) and this
    # coordinate's (a vector padded on one device) are two layouts at most
    assert 1 <= _traces() - before <= 2


@pytest.mark.parametrize("engine", ENGINES)
def test_one_trace_for_every_model_and_coordinate_of_a_shape(
    engine, clean_slate, interpret_kernels, tracer
):
    before = _traces()
    first = _coordinate(_features(engine))
    with span("first-scoring"):
        first.score_device(_model(90, 0)).block_until_ready()
    assert _traces() - before == 1
    assert {s.name for s in _compiles_in(tracer, "first-scoring")} == set(COMPILE_SPANS)

    # other models, both planes, then another coordinate object over other
    # values of the same shapes (same tree structure: the plan's sizes are
    # the static part)
    second = _coordinate(_features(engine, seed=4))
    assert jax.tree.structure(second.data.features) == jax.tree.structure(first.data.features)
    with span("later-scorings"):
        for seed in range(1, 5):
            first.score_device(_model(90, seed))
            first.score(_model(90, seed))
        second.score_device(_model(90, 5))
    assert _traces() - before == 1
    assert _compiles_in(tracer, "later-scorings") == []

    # a new shape is one more program, and its repeat none
    wider = _coordinate(_features(engine, d=120))
    for seed in range(2):
        wider.score_device(_model(120, seed))
    assert _traces() - before == 2
    first.score_device(_model(90, 0))
    assert _traces() - before == 2


def test_two_calls_of_the_program_lower_the_routed_kernels_once(
    clean_slate, interpret_kernels, tracer
):
    features = _features("routed")
    w = _model(90, 0).coefficients.means
    with span("program"):
        _fe_score(features, w).block_until_ready()
        _fe_score(features, w).block_until_ready()
    assert sum(s.name == "jit/lower" for s in _compiles_in(tracer, "program")) == 1


def _routed_glmix_estimator(num_outer_iterations=1) -> GameEstimator:
    estimator = _tiny_glmix_estimator(num_outer_iterations)
    estimator.coordinate_configs["fixed"] = FixedEffectCoordinateConfiguration(
        "global", sparse_engine="fused"
    )
    return estimator


def _fixed_effect_scorings(tracer):
    """The spans that hold a fixed-effect scoring, in order of time:
    ``cd/initial_scores`` where models were handed in (every warm-started
    coordinate's) and the ``cd/score`` after the fixed effect's update."""
    return sorted(
        (
            s for s in tracer.spans()
            if (s.name == "cd/initial_scores" and s.attrs["coordinates"])
            or (s.name == "cd/score" and s.attrs["coordinate"] == "fixed")
        ),
        key=lambda s: s.start_s,
    )


@pytest.mark.parametrize(
    "path", ["fit_multiple", "two_fits", "outer_iterations", "two_descents"]
)
def test_a_fit_on_routed_features_compiles_the_scoring_once(
    path, clean_slate, interpret_kernels, tracer
):
    """``fit_multiple`` over two GLMix configurations (as the benchmark's
    ``cd-fit`` traffic runs one fit after another: a new
    ``CoordinateDescent`` each; here the second is warm-started), two estimators' fits on equal-shaped data, one fit of two
    outer iterations, and two bare ``CoordinateDescent`` objects over
    warm-started fixed effects of equal shapes: after the first scoring no
    other compiles anything."""
    before = _traces()
    if path == "two_descents":
        for seed in (0, 1):
            descent = CoordinateDescent(
                coordinates={"fixed": _coordinate(_features("routed", seed=seed))},
                update_order=["fixed"],
                num_rows=300,
            )
            result = descent.run(1, initial_models={"fixed": _model(90, seed)})
            assert set(result.models) == {"fixed"}
        expected = 4  # initial scores and score, twice
    elif path == "fit_multiple":
        fits = _routed_glmix_estimator().fit_multiple(
            _tiny_glmix(), configs=[{}, {}], warm_start=True
        )
        assert len(fits) == 2
        expected = 3  # score, then the second configuration's initial scores and score
    elif path == "two_fits":
        for seed in (0, 1):
            _routed_glmix_estimator().fit(_tiny_glmix(seed=seed))
        expected = 2
    else:
        _routed_glmix_estimator(num_outer_iterations=2).fit(_tiny_glmix())
        expected = 2
    assert len([s for s in tracer.spans() if s.name == "cd/run"]) == (
        1 if path == "outer_iterations" else 2
    )
    scorings = _fixed_effect_scorings(tracer)
    assert len(scorings) == expected
    assert _traces() - before == 1
    first = _compiles_under(tracer, scorings[:1])
    assert "_fe_score" in {s.attrs["fun_name"] for s in first}
    later = _compiles_under(tracer, scorings[1:])
    assert [(s.name, s.attrs["fun_name"], s.attrs["under"]) for s in later] == []


def test_clear_caches_frees_the_program_and_the_next_scoring_rebuilds_it(
    clean_slate, interpret_kernels, tracer
):
    """A plain jitted callable, never an ahead-of-time executable: what the
    benchmark's ``release()`` relies on."""
    coordinate = _coordinate(_features("routed"))
    before = _traces()
    first = np.asarray(coordinate.score_device(_model(90, 0)))
    coordinate.score_device(_model(90, 1))
    assert _traces() - before == 1
    jax.clear_caches()
    with span("after-clear"):
        again = np.asarray(coordinate.score_device(_model(90, 0)))
        coordinate.score_device(_model(90, 1))
    assert _traces() - before == 2
    assert sum(s.name == "jit/lower" for s in _compiles_in(tracer, "after-clear")) == 1
    np.testing.assert_array_equal(again, first)
