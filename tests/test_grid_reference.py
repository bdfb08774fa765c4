"""The GLMix model on a 2 x 2 (data x feat) device grid, through
``GameEstimator(parallel=...)``, held against the benchmark's plain
reference (``benchmarks/reference/glmix_grid.py``: the whole problem on one
device, nothing of a mesh) and against the one-device fit, on the CPU's
forced devices. Small and seeded, solves run to tight tolerances; the chip
holds the same comparison at the cell's size
(``benchmarks/traffic/cd_fit_grid.py``).

Also here, what a grid adds and one chip cannot get wrong: the parts of a
sharded axis add up to the whole (margins over ``feat``, gradients over
``data``); a feat shard padded to whole rows of 128 keeps the solve free of
``collective-permute``; a ``device_sync`` span waits for every device of the
grid; a second fit traces nothing.
"""

import functools
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.game_data import FeatureShard, GameData
from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_ml_tpu.estimators.game import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    ParallelConfiguration,
    RandomEffectCoordinateConfiguration,
)
from photon_ml_tpu.opt.config import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.parallel.grid_features import (
    COLUMN_MULTIPLE,
    grid_from_coo,
    grid_mesh,
    shard_vector_data,
    shard_vector_feat,
)
from photon_ml_tpu.telemetry import disable_tracing, enable_tracing, jit_trace_counts, span
from photon_ml_tpu.telemetry.span import _BARRIER_DEVICES, barrier_over
from photon_ml_tpu.types import RegularizationType, TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED, OUTER = 36, 2
ORDER = ["fixed", "per_user", "per_item"]


def _solver(max_iterations):
    return {"optimizer": "LBFGS", "regularization": "L2", "regularization_weight": 1.0,
            "max_iterations": max_iterations, "history_length": 10, "tolerance": 1e-10}


CONFIG = {
    "task": "LOGISTIC_REGRESSION", "pattern_seed": 11, "n_rows": 1024, "nnz_per_row": 8,
    "n_cols": 512, "held_out_rows": 256,
    "grid": {"n_data": 2, "n_feat": 2},
    "fixed_effect": {"true_model_scale": 0.3, **_solver(300)},
    "random_effects": {
        "per_user": {"n_entities": 24, "dim": 4, "popularity": "uniform",
                     "true_model_scale": 0.3, "num_buckets": 1, **_solver(100)},
        "per_item": {"n_entities": 16, "dim": 4, "popularity": "zipf", "zipf_exponent": 1.1,
                     "true_model_scale": 0.3, "active_cap": 128, "sample_seed": 0,
                     "num_buckets": 2, **_solver(100)},
    },
    "update_order": ORDER,
}


@pytest.fixture(scope="module")
def benchmarks_on_path():
    sys.path.insert(0, ROOT)
    yield
    sys.path.remove(ROOT)


@pytest.fixture(scope="module")
def problem(benchmarks_on_path):
    from benchmarks import datagen

    return datagen.make_problem(CONFIG, SEED)


@pytest.fixture(scope="module")
def reference(problem):
    from benchmarks.reference.glmix_grid import GlmixGridReference

    ref = GlmixGridReference(CONFIG, problem, "float32")
    return ref, ref.run(OUTER)


def _game_data(rows, n_cols) -> GameData:
    n, k = rows.cols.shape
    every_row = np.arange(n, dtype=np.int64)
    shards = {"global": FeatureShard(np.repeat(every_row, k), rows.cols.reshape(-1),
                                     rows.vals.reshape(-1), n_cols)}
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


def _estimator(engine=None):
    """The configuration's estimator: on one device, or (``engine``) on the
    2 x 2 grid with that tile engine."""
    coordinates = {"fixed": FixedEffectCoordinateConfiguration(
        "global", _optimizer(CONFIG["fixed_effect"]), sparse_engine="ell")}
    for name, re in CONFIG["random_effects"].items():
        coordinates[name] = RandomEffectCoordinateConfiguration(
            name,
            RandomEffectDataConfiguration(
                f"{name}Id", active_data_upper_bound=re.get("active_cap"),
                num_buckets=re["num_buckets"], seed=re.get("sample_seed", 0)),
            _optimizer(re),
        )
    parallel = None if engine is None else ParallelConfiguration(2, 2, engine)
    return GameEstimator(task=TaskType.LOGISTIC_REGRESSION, coordinates=coordinates,
                         update_order=ORDER, num_outer_iterations=OUTER, parallel=parallel)


def _entity_table(model, n_entities):
    table = np.zeros((n_entities, int(model.global_dim)), np.float32)
    for b, ids in enumerate(model.entity_ids):
        real = len(ids)  # lanes past the entities pad the axis to the grid
        coef = np.asarray(model.coefficients[b])[:real]
        idx = np.asarray(model.proj_indices[b])[:real]
        valid = np.asarray(model.proj_valid[b])[:real]
        rows = np.broadcast_to(np.asarray([int(i) for i in ids])[:, None], idx.shape)
        table[rows[valid], idx[valid]] = coef[valid]
    return table


def _leaves(models) -> dict:
    res = CONFIG["random_effects"]
    return {"fixed": np.asarray(models["fixed"].coefficients.means),
            **{name: _entity_table(models[name], res[name]["n_entities"]) for name in res}}


@pytest.fixture(scope="module")
def data(problem):
    return (_game_data(problem.train, problem.n_cols),
            _game_data(problem.held_out, problem.n_cols))


@pytest.fixture(scope="module")
def one_device(data):
    train, held = data
    return _estimator().fit(train, validation_data=held)


@pytest.mark.parametrize("engine", ["ell", "fused"])
def test_a_grid_fit_is_the_reference_and_the_one_device_fit(
        engine, data, reference, one_device, interpret_kernels):
    """Six block updates on the 2 x 2 grid: the objective and the held-out
    AUC after each against exact block coordinate descent on the whole
    problem, the returned model against the reference's of the same update
    and against the model one device returns."""
    train, held = data
    ref, snaps = reference
    fit = _estimator(engine).fit(train, validation_data=held)
    objectives = [v for _, v in fit.objective_history]
    aucs = [v for _, v in fit.validation_history]
    assert len(objectives) == len(snaps) == OUTER * len(ORDER)
    for got, snap in zip(objectives, snaps):
        assert got == pytest.approx(snap.objective, rel=2e-5)
    for got, snap in zip(aucs, snaps):
        assert got == pytest.approx(snap.auc, abs=2e-4)
    assert objectives == pytest.approx([v for _, v in one_device.objective_history], rel=1e-5)

    mine, single = _leaves(fit.model.models), _leaves(one_device.model.models)
    picked = snaps[int(np.argmax([s.auc for s in snaps[len(ORDER) - 1:]])) + len(ORDER) - 1]
    exact = {"fixed": np.asarray(picked.fixed),
             **{k: np.asarray(v) for k, v in picked.random.items()}}
    for leaf in mine:
        scale = np.linalg.norm(exact[leaf])
        assert np.linalg.norm(mine[leaf] - exact[leaf]) < 2e-3 * scale, leaf
        assert np.linalg.norm(mine[leaf] - single[leaf]) < 1e-3 * scale, leaf
    # the objective the fit reports is the reference scorer's of that model
    scored = ref.evaluate(mine["fixed"], {k: v for k, v in mine.items() if k != "fixed"})
    returned = int(np.argmax(aucs[len(ORDER) - 1:])) + len(ORDER) - 1
    assert objectives[returned] == pytest.approx(scored.objective, rel=2e-6)


@pytest.fixture(scope="module")
def grid(problem):
    train = problem.train
    n, k = train.cols.shape
    mesh = grid_mesh(2, 2)
    gf = grid_from_coo(np.repeat(np.arange(n), k), train.cols.reshape(-1),
                       train.vals.reshape(-1), (n, problem.n_cols), mesh, engine="ell")
    assert (gf.num_rows, gf.dim) == (n, problem.n_cols)  # nothing to pad here
    return gf, mesh


def test_the_feat_shards_margins_add_up_to_the_whole_rows(problem, reference, grid, rng):
    """What the sum over ``feat`` adds: the margins of each feat shard's
    columns alone (the coefficients of the other shard at zero) are the
    reference's margins of those columns, and the two add up to its margins
    of the whole rows. One alone is what a lost sum computes."""
    from benchmarks.reference import solvers

    (ref, _), (gf, mesh) = reference, grid
    train, d = problem.train, problem.n_cols
    w = rng.standard_normal(d).astype(np.float32)
    whole = np.asarray(ref.features.matvec(jnp.asarray(w)))
    parts = []
    for shard in range(2):
        own = (np.arange(d) // (d // 2)) == shard
        part = np.asarray(gf.matvec(shard_vector_feat(jnp.asarray(np.where(own, w, 0)), mesh)))
        plain = solvers.SparseRows(train.cols, np.where(own[train.cols], train.vals, 0), d)
        np.testing.assert_allclose(part, np.asarray(plain.matvec(jnp.asarray(w))), atol=1e-5)
        parts.append(part)
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gf.matvec(shard_vector_feat(jnp.asarray(w), mesh))), whole, atol=1e-5)
    assert np.abs(parts[0] - whole).max() > 0.1  # one shard is not the row


def test_the_data_shards_gradients_add_up_to_every_rows(problem, reference, grid, rng):
    """What the sum over ``data`` adds: the gradient from each data shard's
    rows alone (the other shard's row factors at zero) and the two together
    against the reference's gradient from every row."""
    (ref, _), (gf, mesh) = reference, grid
    n = problem.train.n
    c = rng.standard_normal(n).astype(np.float32)
    whole = np.asarray(ref.features.rmatvec(jnp.asarray(c)))
    parts = []
    for shard in range(2):
        own = (np.arange(n) // (n // 2)) == shard
        parts.append(np.asarray(gf.rmatvec(
            shard_vector_data(jnp.asarray(np.where(own, c, 0)), mesh))))
        np.testing.assert_allclose(
            parts[-1], np.asarray(ref.features.rmatvec(jnp.asarray(np.where(own, c, 0)))),
            atol=1e-5)
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gf.rmatvec(shard_vector_data(jnp.asarray(c), mesh))), whole, atol=1e-5)
    assert np.abs(parts[0] - whole).max() > 0.1


@functools.lru_cache(maxsize=None)
def _solve_collectives(d):
    """(padded width, collective operations by kind) of an L-BFGS solve
    compiled for the 2 x 4 grid at ``d`` columns."""
    from photon_ml_tpu.losses.objective import make_glm_objective
    from photon_ml_tpu.losses.pointwise import LogisticLoss
    from photon_ml_tpu.ops.data import LabeledData
    from photon_ml_tpu.opt.solve import solve

    rng = np.random.default_rng(d)
    n, k = 256, 4
    mesh = grid_mesh(2, 4)
    gf = grid_from_coo(
        np.repeat(np.arange(n), k), rng.integers(0, d, n * k),
        rng.standard_normal(n * k).astype(np.float32), (n, d), mesh, engine="ell")
    labeled = LabeledData.create(
        gf, shard_vector_data(jnp.asarray((rng.random(n) < 0.5).astype(np.float32)), mesh))
    cfg = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.lbfgs(max_iterations=5), regularization_weight=1.0)
    objective = make_glm_objective(LogisticLoss)
    text = jax.jit(
        lambda w0, data: solve(objective, w0, data, cfg, l2_weight=jnp.float32(1.0))
    ).lower(gf.zero_coefficients(), labeled).compile().as_text()
    kinds = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter", "all-to-all")
    return gf.dim, {kind: len(re.findall(rf" {kind}(?:-start)?\(", text)) for kind in kinds}


@pytest.mark.parametrize("d", [4000, 4096])
def test_a_compiled_grid_solve_moves_no_halo(d):
    """On the 2 x 4 grid a feat shard is padded to whole rows of 128
    (d = 4,000 -> 4 x 1,024), so the history's ``[m, d/128, 128]`` view of a
    feat-sharded vector stays on its device: the compiled solve holds the
    sums over ``feat`` and ``data`` (``all-reduce``), as many as at a width
    that needs no pad, and neither a ``collective-permute`` of a row's halo
    nor an ``all-gather``."""
    width, collectives = _solve_collectives(d)
    assert width == 4096 and (width // 4) % COLUMN_MULTIPLE == 0
    assert collectives["all-reduce"] > 0
    assert collectives == {**_solve_collectives(4096)[1], "collective-permute": 0,
                           "all-gather": 0}


def _random_coo(n, d, k, seed=0):
    rng = np.random.default_rng(seed)
    return (np.repeat(np.arange(n), k), rng.integers(0, d, n * k),
            rng.standard_normal(n * k).astype(np.float32))


def test_a_tile_says_whether_its_plans_were_read_back(tmp_path):
    """``grid/build_tile``: one span a tile, its routed slots, and the
    ``cached`` attr of the tile's own ``route/plan`` children false on the
    build that routes and true on the next, which reads the plan files."""
    n, d = 64, 2048
    rows, cols, vals = _random_coo(n, d, 6)
    tracer = enable_tracing(device_sync=False)
    try:
        for _ in range(2):
            grid_from_coo(rows, cols, vals, (n, d), grid_mesh(2, 2), engine="benes",
                          plan_cache=str(tmp_path))
        grid_from_coo(rows, cols, vals, (n, d), grid_mesh(2, 2), engine="ell")
        spans = tracer.spans()
    finally:
        disable_tracing()
    tiles = [s for s in spans if s.name == "grid/build_tile"]
    read_back = {}  # a tile's span id -> `cached` of each of its plans
    for s in spans:
        if s.name == "route/plan":
            read_back.setdefault(s.parent_id, []).append(s.attrs["cached"])
    positions = [(dd, df) for dd in range(2) for df in range(2)]
    for build, cached in enumerate([False, True]):
        mine = tiles[4 * build: 4 * build + 4]
        assert sorted((t.attrs["dd"], t.attrs["df"]) for t in mine) == positions
        assert all(t.attrs["slots"] > 0 for t in mine)
        assert all(read_back[t.span_id] and set(read_back[t.span_id]) == {cached}
                   for t in mine)
    assert all(t.attrs["slots"] == 0 and t.span_id not in read_back for t in tiles[8:])


@pytest.mark.parametrize("n,k", [(64, 6), (512, 1)], ids=["counted", "sorted"])
def test_a_split_grid_pins_its_blocks_to_the_fullest_row(n, k):
    """A column split pins every block's row width to the most nonzeros any
    tile's row holds inside one block, whether the planner counts them in
    bins (few rows, many nonzeros) or by a sort (many rows, few)."""
    d, split = 2048, 2
    rows, cols, vals = _random_coo(n, d, k)
    grid = grid_from_coo(rows, cols, vals, (n, d), grid_mesh(2, 2), engine="benes",
                         kp_cap=None, col_split=split)
    held = np.zeros((n, d), bool)
    held[rows, cols] = True
    # rows by (data shard, row), columns by (feat shard, block, column)
    per_block = held.reshape(2, n // 2, 2, split, d // (2 * split)).sum(-1)
    assert {b.ell_values.shape[-1] for b in grid.shards.blocks} == {int(per_block.max())}


def test_a_device_sync_span_waits_for_every_device_of_the_grid():
    """Work dispatched to the grid's last device, the default device idle:
    the span closes only when that work has retired."""
    mesh = grid_mesh(2, 2)
    far = mesh.devices[1, 1]
    assert far != jax.devices()[0]

    @jax.jit
    def slow(x):
        return jax.lax.fori_loop(0, 300, lambda _, v: jnp.sin(v) + 1e-3, x)

    x = jax.device_put(jnp.ones((1 << 20,), jnp.float32), far)
    jax.block_until_ready(slow(x))  # compiled
    enable_tracing(device_sync=True)
    try:
        with barrier_over(mesh.devices.ravel().tolist()):
            with span("test/far_device", device_sync=True):
                t0 = time.perf_counter()
                out = slow(x)
                dispatched = time.perf_counter() - t0
                was_ready = out.is_ready()
            assert out.is_ready()
        assert _BARRIER_DEVICES.get() == ()  # the default device alone again
        jax.block_until_ready(out)
        # the dispatch returned before the work was done, so it is the
        # span's close that waited
        assert not was_ready or dispatched > 0.05
    finally:
        disable_tracing()


class _CountedConfigs:
    """``configs`` for ``fit_multiple``: two empty override maps, the jit
    trace counters read as each is asked for and once the last is done."""

    def __init__(self):
        self.counts = []

    def __bool__(self):
        return True

    def __len__(self):
        return 2

    def __iter__(self):
        for _ in range(2):
            self.counts.append(jit_trace_counts())
            yield {}


def test_a_second_grid_fit_traces_nothing(data):
    """Two fits from the zero model on one prepared grid: after the first,
    no program is traced again, and the first traces the fixed effect's
    solve once (the zero start is laid out as a warm start is) and the
    random effects' once a bucket shape."""
    train, held = data
    configs = _CountedConfigs()
    jax.clear_caches()
    fits = _estimator("ell").fit_multiple(train, validation_data=held, configs=configs,
                                          warm_start=False)
    configs.counts.append(jit_trace_counts())
    before, after_first, after_second = configs.counts

    def new(counts, program):
        return sum(v - before.get(k, 0) for k, v in counts.items() if k.startswith(program))

    assert after_second == after_first
    assert new(after_first, "glm_solve/") == 1
    assert new(after_first, "fe_score") == 1
    assert new(after_first, "re_oneshot/") == 3  # one per-user bucket, two per-item
    assert ([v for _, v in fits[0].objective_history]
            == [v for _, v in fits[1].objective_history])
