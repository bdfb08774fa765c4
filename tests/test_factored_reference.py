"""The full GAME ratings model (fixed effect + per-user + per-item random
effects + a user x item factored coordinate, squared loss, held-out RMSE)
through ``GameEstimator.fit_multiple``, held against the benchmark's plain
reference (``benchmarks/reference/game_mf.py``: float32 ``jax.numpy`` at
``highest``, nothing of the program, every block solved exactly: L-BFGS to
its optimum, normal equations, conjugate gradients row by row with no
Kronecker features). Small and seeded, solves run to tight tolerances; the
chip holds the same comparison at the cell's size
(``benchmarks/traffic/cd_fit_ratings.py``).

Also here: the factored coordinate on the device score plane (no row-length
transfer, the host path's model), its kept programs (nothing traces again
after the first fit, and the fixed effect's solve program is not evicted),
and its ``mf/*`` spans.
"""

import os
import sys

import numpy as np
import pytest

from photon_ml_tpu.algorithm.factored_random_effect import MFOptimizationConfiguration
from photon_ml_tpu.data.game_data import FeatureShard, GameData
from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_ml_tpu.estimators.game import (
    FactoredRandomEffectCoordinateConfiguration,
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_ml_tpu.opt.config import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.telemetry import disable_tracing, enable_tracing, jit_trace_counts
from photon_ml_tpu.types import RegularizationType, TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED, K, OUTER, FITS = 34, 3, 2, 3
ORDER = ["fixed", "per_user", "per_item", "user_item_mf"]
LEAVES = ("fixed", "per_user", "per_item", "latent", "matrix")


def _solver(max_iterations):
    return {"optimizer": "LBFGS", "regularization": "L2", "regularization_weight": 1.0,
            "max_iterations": max_iterations, "history_length": 10, "tolerance": 1e-10}


CONFIG = {
    "task": "LINEAR_REGRESSION", "pattern_seed": 7, "n_rows": 1536, "nnz_per_row": 8,
    "n_cols": 256, "held_out_rows": 256, "noise_scale": 0.5,
    "fixed_effect": {"true_model_scale": 0.3, **_solver(300)},
    "random_effects": {
        "per_user": {"n_entities": 24, "dim": 4, "popularity": "uniform",
                     "true_model_scale": 0.3, "num_buckets": 1, **_solver(100)},
        "per_item": {"n_entities": 16, "dim": 4, "popularity": "zipf", "zipf_exponent": 1.1,
                     "true_model_scale": 0.3, "active_cap": 128, "sample_seed": 0,
                     "num_buckets": 2, **_solver(100)},
    },
    "user_item_mf": {"entities": "per_user", "items": "per_item", "num_buckets": 1,
                     "latent_factors": K, "true_latent_factors": K, "alternations": 2,
                     "latent": _solver(100), "matrix": _solver(300)},
    "update_order": ORDER,
}


@pytest.fixture(scope="module")
def benchmarks_on_path():
    sys.path.insert(0, ROOT)
    yield
    sys.path.remove(ROOT)


@pytest.fixture(scope="module")
def problem(benchmarks_on_path):
    from benchmarks import datagen_ratings

    return datagen_ratings.make_problem(CONFIG, SEED)


@pytest.fixture(scope="module")
def reference(problem):
    from benchmarks.reference.game_mf import GameMfReference

    ref = GameMfReference(CONFIG, problem, SEED, "float32")
    return ref, ref.run(OUTER)


def _game_data(rows, n_cols, n_items) -> GameData:
    n, k = rows.cols.shape
    every_row = np.arange(n, dtype=np.int64)
    shards = {
        "global": FeatureShard(np.repeat(every_row, k), rows.cols.reshape(-1),
                               rows.vals.reshape(-1), n_cols),
        "item_id": FeatureShard(every_row, rows.entities["per_item"],
                                np.ones(n, np.float32), n_items),
    }
    for name, x in rows.entity_x.items():
        dim = x.shape[1]
        shards[name] = FeatureShard(np.repeat(every_row, dim),
                                    np.tile(np.arange(dim, dtype=np.int64), n),
                                    x.reshape(-1), dim)
    return GameData(labels=rows.labels, feature_shards=shards,
                    id_tags={f"{name}Id": ids for name, ids in rows.entities.items()})


def _optimizer(c):
    return GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.lbfgs(
            max_iterations=c["max_iterations"], tolerance=c["tolerance"],
            history_length=c["history_length"]),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=c["regularization_weight"],
    )


def _estimator(score_plane):
    coordinates = {"fixed": FixedEffectCoordinateConfiguration(
        "global", _optimizer(CONFIG["fixed_effect"]))}
    for name, re in CONFIG["random_effects"].items():
        coordinates[name] = RandomEffectCoordinateConfiguration(
            name,
            RandomEffectDataConfiguration(
                f"{name}Id", active_data_upper_bound=re.get("active_cap"),
                num_buckets=re["num_buckets"], seed=re.get("sample_seed", 0)),
            _optimizer(re),
        )
    mf = CONFIG["user_item_mf"]
    coordinates["user_item_mf"] = FactoredRandomEffectCoordinateConfiguration(
        "item_id", RandomEffectDataConfiguration("per_userId"),
        MFOptimizationConfiguration(K, mf["alternations"], seed=SEED),
        optimizer=_optimizer(mf["latent"]), matrix_optimizer=_optimizer(mf["matrix"]),
    )
    return GameEstimator(task=TaskType.LINEAR_REGRESSION, coordinates=coordinates,
                         update_order=ORDER, num_outer_iterations=OUTER,
                         score_plane=score_plane)


def _entity_table(model, n_entities):
    """[n_entities, dim] of a random-effect model whose projection is the
    identity or an index map over dense features."""
    table = np.zeros((n_entities, int(model.global_dim)), np.float32)
    for b, ids in enumerate(model.entity_ids):
        coef = np.asarray(model.coefficients[b])
        idx, valid = np.asarray(model.proj_indices[b]), np.asarray(model.proj_valid[b])
        rows = np.broadcast_to(np.asarray([int(i) for i in ids])[:, None], idx.shape)
        table[rows[valid], idx[valid]] = coef[valid]
    return table


def _leaves(models) -> dict:
    res = CONFIG["random_effects"]
    mf = models["user_item_mf"]
    return {
        "fixed": np.asarray(models["fixed"].coefficients.means),
        "per_user": _entity_table(models["per_user"], res["per_user"]["n_entities"]),
        "per_item": _entity_table(models["per_item"], res["per_item"]["n_entities"]),
        "latent": _entity_table(mf.latent, res["per_user"]["n_entities"]),
        "matrix": np.asarray(mf.projection_matrix),
    }


class _CountedConfigs:
    """``configs`` for ``fit_multiple``: FITS empty override maps, the jit
    trace counters read as each is asked for (so after every fit but the
    last)."""

    def __init__(self):
        self.counts = []

    def __bool__(self):
        return True

    def __len__(self):
        return FITS

    def __iter__(self):
        for _ in range(FITS):
            self.counts.append(jit_trace_counts())
            yield {}


@pytest.fixture(scope="module")
def fitted(problem):
    """Three fits from the zero model in one process on the device plane,
    traced; what every test below reads."""
    n_items = CONFIG["random_effects"]["per_item"]["n_entities"]
    train = _game_data(problem.train, problem.n_cols, n_items)
    held = _game_data(problem.held_out, problem.n_cols, n_items)
    estimator, configs = _estimator("device"), _CountedConfigs()
    tracer = enable_tracing(device_sync=True)
    try:
        fits = estimator.fit_multiple(train, validation_data=held, configs=configs,
                                      warm_start=False)
        spans = tracer.spans()
    finally:
        disable_tracing()
    configs.counts.append(jit_trace_counts())
    return {"fits": fits, "counts": configs.counts, "spans": spans, "train": train,
            "held": held, "transfers": estimator.last_transfer_stats.snapshot()}


def _numbers(fit, snaps, ref):
    """The program's fit against the reference's run, as the benchmark's
    ``cd_fit_ratings`` compares them."""
    objective = [v for _, v in fit["objective"]]
    rmse = [v for _, v in fit["validation"]]
    assert len(objective) == len(rmse) == len(snaps) == OUTER * len(ORDER)
    # the first RMSE minimum among the updates at which every coordinate has a model
    complete = len(ORDER) - 1
    picked = complete + int(np.argmin(rmse[complete:]))
    expect = snaps[picked].leaves()
    norms = {k: (float(np.linalg.norm(fit["leaves"][k])), float(np.linalg.norm(expect[k])))
             for k in LEAVES}
    scored = ref.evaluate(fit["leaves"])
    return {
        "loss_gap": max(abs(p - s.objective) / s.objective for p, s in zip(objective, snaps)),
        "rmse_gap": max(abs(p - s.rmse) for p, s in zip(rmse, snaps)),
        "change_gap": max(abs(p - r) / r for p, r in norms.values()),
        "scored_objective_gap": abs(objective[picked] - scored.objective) / scored.objective,
        "scored_rmse_gap": abs(rmse[picked] - scored.rmse),
    }


# Each limit with its reason. The program's L-BFGS ends on
# FUNCTION_VALUES_CONVERGED when its float32 objective stops moving, whatever
# the tolerance asked; that leaves a block's coefficients 1e-4 - 5e-4 from the
# optimum (read: every entity's ridge solve against its normal equations),
# where the objective is flat to second order. The next block is then solved
# against a slightly different residual, and the gaps add up through the eight
# updates. Readings on this seed beside each limit; the bfloat16 control reads
# over every one of them, by 3 (loss_gap, rmse_gap) to 300 times (scored_rmse_gap).
LIMITS = {
    "loss_gap": 2e-4,              # read 5.6e-5: where the solvers stop, as above
    "rmse_gap": 2e-4,              # read 5.3e-5: the same, on 256 held-out rows
    "change_gap": 3e-4,            # read 5.1e-5: norms of leaves 1e-4 - 5e-4 from their optimum
    # the same model scored twice in float32 (1,536 rows, five leaves): the
    # order of the sums alone, a few units in the last place
    "scored_objective_gap": 5e-6,  # read 1.3e-6
    "scored_rmse_gap": 5e-7,       # read 1.4e-9
}


def _program_fit(fitted, i=0):
    fit = fitted["fits"][i]
    return {"objective": fit.objective_history, "validation": fit.validation_history,
            "leaves": _leaves(fit.model.models)}


def test_full_game_fit_matches_the_plain_reference(fitted, reference):
    ref, snaps = reference
    numbers = _numbers(_program_fit(fitted), snaps, ref)
    for name, limit in LIMITS.items():
        assert numbers[name] <= limit, (name, numbers)
    # the model returned is the lowest-RMSE update's, a complete one
    fit = fitted["fits"][0]
    assert fit.validation_metric == min(v for _, v in fit.validation_history[len(ORDER) - 1:])


def test_the_reference_in_bfloat16_is_not_within_the_limits(problem, reference):
    """The control: the same reference with bfloat16 operands, put in the
    program's place, fails at least one limit."""
    from benchmarks.reference.game_mf import GameMfReference

    ref, snaps = reference
    low = GameMfReference(CONFIG, problem, SEED, "bfloat16").run(OUTER)
    complete = len(ORDER) - 1
    picked = complete + int(np.argmin([s.rmse for s in low[complete:]]))
    stand_in = {"objective": [(s.coordinate, s.objective) for s in low],
                "validation": [(s.coordinate, s.rmse) for s in low],
                "leaves": low[picked].leaves()}
    numbers = _numbers(stand_in, snaps, ref)
    assert any(numbers[name] > limit for name, limit in LIMITS.items()), numbers


def test_device_plane_gives_the_host_paths_model_with_no_row_transfer(fitted):
    t = fitted["transfers"]
    assert t["score_plane"] == "device"
    assert t["coordinate_updates"] == t["device_plane_updates"] == OUTER * len(ORDER)
    assert t["row_transfers_h2d"] == t["row_transfers_d2h"] == 0
    host = _estimator("host")
    fit = host.fit(fitted["train"], validation_data=fitted["held"])
    assert host.last_transfer_stats.snapshot()["row_transfers_d2h"] == OUTER * len(ORDER)
    device = fitted["fits"][0]
    # the same IEEE float32 adds build the offsets on either plane, and the
    # same kept programs solve: equal to rounding of the score plane's sums
    np.testing.assert_allclose([v for _, v in fit.objective_history],
                               [v for _, v in device.objective_history], rtol=1e-6)
    mine, theirs = _leaves(device.model.models), _leaves(fit.model.models)
    for name in LEAVES:
        np.testing.assert_allclose(mine[name], theirs[name], rtol=1e-4, atol=1e-5, err_msg=name)


def test_nothing_traces_again_after_the_first_fit(fitted):
    """Three fits in one process: the projection program, the matrix solve
    and the fixed effect's solve (one kept slot, which the matrix solve must
    not take) end where they stood after the first fit, and every fit
    reports what the first did."""
    before, after_first, after_second, after_third = fitted["counts"]
    watched = [k for k in after_third
               if k.startswith(("mf_project", "mf_matrix_solve", "glm_solve/", "re_chunk/",
                                "re_init/", "re_extract/", "fe_score"))]
    assert {"mf_project", "mf_matrix_solve/lbfgs", "glm_solve/lbfgs"} <= set(watched)
    for key in watched:
        assert after_first[key] == after_second[key] == after_third[key], (key, fitted["counts"])
    assert after_first["mf_project"] - before.get("mf_project", 0) == 1
    assert after_first["mf_matrix_solve/lbfgs"] - before.get("mf_matrix_solve/lbfgs", 0) == 1
    first = fitted["fits"][0].objective_history
    for fit in fitted["fits"][1:]:
        assert fit.objective_history == first


def test_mf_spans_nest_under_the_coordinate_and_carry_the_solvers_counts(fitted):
    spans = fitted["spans"]
    by_id = {s.span_id: s for s in spans}
    alternations = CONFIG["user_item_mf"]["alternations"]
    updates = [s for s in spans if s.name == "mf/update"]
    assert len(updates) == FITS * OUTER
    for s in updates:
        parent = by_id[s.parent_id]
        assert parent.name == "cd/coordinate" and parent.attrs["coordinate"] == "user_item_mf"
        assert s.attrs["alternations"] == alternations and s.attrs["latent_factors"] == K
    for name in ("mf/project", "mf/solve_latent", "mf/solve_matrix"):
        inner = [s for s in spans if s.name == name]
        assert len(inner) == FITS * OUTER * alternations, name
        assert all(by_id[s.parent_id].name == "mf/update" for s in inner), name
    n_items = CONFIG["random_effects"]["per_item"]["n_entities"]
    for s in spans:
        if s.name == "mf/solve_matrix":
            assert s.attrs["evaluations"] >= s.attrs["iterations"] >= 1
            assert s.attrs["coefficients"] == n_items * K
    # the matrix solve is no ``glm/solve``: those are the fixed effect's, two a fit
    assert len([s for s in spans if s.name == "glm/solve"]) == FITS * OUTER
    # a latent solve's rounds wait under it, and its compiles parent to the mf spans
    waits = [s for s in spans if s.name == "re/round_wait" and "mf/solve_latent" in s.path]
    assert waits
    compiles = [s for s in spans if s.name.startswith("jit/") and "mf/update" in s.path]
    assert compiles and all("mf/update" in s.attrs["under"] for s in compiles
                            if s.name != "jit/cache")
