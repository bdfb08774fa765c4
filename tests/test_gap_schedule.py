"""Gap-guided block scheduling (DuHL) for stochastic streaming.

The load-bearing contract: with ``gap_schedule`` OFF (the default) the
stochastic visit order is bitwise-identical to the historical blind per-epoch
``rng.permutation`` trajectory — the scheduler must be impossible to
observe unless opted into. With it ON, the scheduler's invariants hold:
bootstrap epochs cover every block, stale scores decay, the exploration
floor refreshes every block within ``~1/explore`` epochs, and selected
blocks are grouped by part file so the decode LRU decodes each part file
at most once per epoch.
"""

import numpy as np
import pytest

from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    build_index_maps,
    write_training_examples,
)
from photon_ml_tpu.streaming import (
    BlockPrefetcher,
    GapScheduler,
    StreamingSource,
    group_by_part_file,
    solve_streaming_stochastic,
)

# Aligned layout on purpose: block_rows divides every file's rows, so no
# block straddles a file boundary and "one decode per file per epoch" is
# an exact guarantee (not just the expected case).
FILE_ROWS = (64, 64, 64)
N_ROWS = sum(FILE_ROWS)
D = 6
BLOCK_ROWS = 32  # 192 rows -> 6 blocks, 2 per file, none ragged

SHARDS = {
    "global": FeatureShardConfiguration(
        feature_bags=("features",), add_intercept=True
    ),
}


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    rng = np.random.default_rng(7)
    root = tmp_path_factory.mktemp("gapsched")
    X = rng.normal(size=(N_ROWS, D)).astype(np.float32)
    w = rng.normal(size=D).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-(X @ w))) > rng.random(N_ROWS)).astype(
        np.float32
    )
    paths = []
    row = 0
    for fi, n in enumerate(FILE_ROWS):
        recs = [
            {
                "uid": f"r{i}",
                "label": float(y[i]),
                "weight": 1.0,
                "features": [
                    ("g", str(j), float(X[i, j])) for j in range(D)
                ],
            }
            for i in range(row, row + n)
        ]
        p = str(root / f"part-{fi:05d}.avro")
        write_training_examples(p, recs)
        paths.append(p)
        row += n
    return {"paths": paths, "index_maps": build_index_maps(paths, SHARDS)}


@pytest.fixture()
def source(dataset):
    return StreamingSource.open(
        dataset["paths"], SHARDS, index_maps=dataset["index_maps"],
        block_rows=BLOCK_ROWS,
    )


@pytest.fixture()
def skewed_source(tmp_path):
    """Eight blocks over four files; blocks 0 and 4 hold a logistic signal,
    the others near-zero features under a constant label."""
    rng = np.random.default_rng(11)
    hard = (0, 4)
    n_blocks = 8
    n = n_blocks * BLOCK_ROWS
    w = 2.0 * rng.normal(size=D).astype(np.float32)
    X = (0.01 * rng.normal(size=(n, D))).astype(np.float32)
    y = np.ones(n, dtype=np.float32)
    for b in hard:
        rows = slice(b * BLOCK_ROWS, (b + 1) * BLOCK_ROWS)
        X[rows] = rng.normal(size=(BLOCK_ROWS, D))
        y[rows] = (
            1.0 / (1.0 + np.exp(-(X[rows] @ w))) > rng.random(BLOCK_ROWS)
        ).astype(np.float32)
    paths = []
    per_file = 2 * BLOCK_ROWS
    for fi in range(n // per_file):
        recs = [
            {
                "uid": f"r{i}",
                "label": float(y[i]),
                "weight": 1.0,
                "features": [
                    ("g", str(j), float(X[i, j])) for j in range(D)
                ],
            }
            for i in range(fi * per_file, (fi + 1) * per_file)
        ]
        p = str(tmp_path / f"part-{fi:05d}.avro")
        write_training_examples(p, recs)
        paths.append(p)
    source = StreamingSource.open(
        paths, SHARDS, index_maps=build_index_maps(paths, SHARDS),
        block_rows=BLOCK_ROWS,
    )
    return source, hard


# ------------------------------------------------------- scheduler unit
class TestGapScheduler:
    def test_ctor_validation(self):
        with pytest.raises(ValueError, match="num_blocks"):
            GapScheduler(0)
        with pytest.raises(ValueError, match="decay"):
            GapScheduler(4, decay=0.0)
        with pytest.raises(ValueError, match="decay"):
            GapScheduler(4, decay=1.5)
        with pytest.raises(ValueError, match="explore"):
            GapScheduler(4, explore=-0.1)
        with pytest.raises(ValueError, match="visit_fraction"):
            GapScheduler(4, visit_fraction=0.0)

    def test_bootstrap_epoch_visits_every_block(self):
        sched = GapScheduler(10, visit_fraction=0.3)
        order = sched.epoch_order()
        assert sorted(order.tolist()) == list(range(10))

    def test_visit_fraction_sizes_scheduled_epochs(self):
        sched = GapScheduler(10, visit_fraction=0.4, explore=0.1)
        first = sched.epoch_order()
        sched.update({int(b): 1.0 + int(b) for b in first})
        order = sched.epoch_order()
        # ceil(0.4 * 10) selected + 1 exploration pick
        assert order.size == 5
        # the four largest measured gaps are all in the visit set
        assert {9, 8, 7, 6} <= set(order.tolist())

    def test_unvisited_blocks_outrank_measured_ones(self):
        sched = GapScheduler(6, visit_fraction=0.5)
        first = sched.epoch_order()
        # feed back gaps for only half the visited blocks: the rest stay
        # at the +inf sentinel and must be re-selected next epoch
        sched.update({int(b): 5.0 for b in first[:3]})
        unmeasured = set(int(b) for b in first[3:])
        order = sched.epoch_order()
        assert unmeasured <= set(order.tolist())

    def test_decay_discounts_stale_scores(self):
        sched = GapScheduler(4, decay=0.5, visit_fraction=0.25, explore=0.0)
        sched.epoch_order()
        sched.update({0: 8.0, 1: 1.0, 2: 1.0, 3: 1.0})
        eff0 = sched.effective_scores()
        assert eff0[0] == 8.0  # age 0: undiscounted
        # three epochs without visiting block 0 -> score halves each epoch
        for _ in range(3):
            sched.update({})
        eff3 = sched.effective_scores()
        assert eff3[0] == pytest.approx(8.0 * 0.5 ** 3)

    def test_exploration_refreshes_stale_blocks(self):
        # Block 0 measures a tiny gap once; blocks 1..9 always measure
        # large gaps. Greedy-only scheduling would starve block 0 forever;
        # the epsilon floor must re-visit it within ~1/explore epochs.
        sched = GapScheduler(
            10, decay=1.0, explore=0.1, visit_fraction=0.5, seed=3
        )
        first = sched.epoch_order()
        sched.update({int(b): (0.001 if b == 0 else 10.0) for b in first})
        revisited_at = None
        for epoch in range(1, 21):
            order = sched.epoch_order()
            if 0 in order.tolist():
                revisited_at = epoch
                break
            sched.update({int(b): 10.0 for b in order})
        assert revisited_at is not None and revisited_at <= 12

    def test_update_rejects_out_of_range_blocks(self):
        sched = GapScheduler(4)
        with pytest.raises(IndexError, match="outside"):
            sched.update({4: 1.0})

    def test_drain_decisions_records_and_clears(self):
        sched = GapScheduler(5, visit_fraction=0.4)
        sched.epoch_order()
        sched.update({0: 1.0, 1: 2.0, 2: 3.0, 3: 4.0, 4: 5.0})
        sched.epoch_order()
        decisions = sched.drain_decisions()
        assert [d["epoch"] for d in decisions] == [0, 1]
        assert decisions[0]["visited"] == 5  # bootstrap covers everything
        assert decisions[1]["unvisited"] == 0
        assert decisions[1]["score_max"] == 5.0
        assert sched.drain_decisions() == []

    def test_gauges_exported(self):
        from photon_ml_tpu.telemetry import get_registry

        sched = GapScheduler(8)
        sched.epoch_order()
        gauges = get_registry().snapshot()["gauges"]
        assert gauges["stream.gap_sched.visited_blocks"]["last"] == 8.0
        assert "stream.gap_sched.visit_fraction" in gauges


# --------------------------------------------- part-file-aware ordering
class TestGroupByPartFile:
    def test_groups_same_file_blocks_adjacently(self, source):
        plan = source.plan
        # blocks 0,1 -> file 0; 2,3 -> file 1; 4,5 -> file 2
        got = group_by_part_file([5, 0, 3, 1, 4, 2], plan)
        assert got == [4, 5, 0, 1, 2, 3]
        # file order follows each file's highest-priority block; within a
        # file blocks ascend so the decode walk is monotone — and only the
        # given blocks appear (reordering never widens the visit set)
        assert group_by_part_file([2, 5, 3], plan) == [2, 3, 5]
        assert group_by_part_file([], plan) == []

    def test_one_decode_per_file_per_epoch(self, source):
        """The re-decode hazard fix: a grouped shuffled visit order must
        not decode any part file more than once per pass (aligned blocks,
        so the guarantee is exact, not amortized)."""
        plan = source.plan
        rng = np.random.default_rng(0)
        worst = rng.permutation(plan.num_blocks)  # interleaves files
        order = group_by_part_file(worst, plan)
        before = source.files_decoded
        for _ in BlockPrefetcher(
            source, shards=("global",), order=list(order)
        ):
            pass
        assert source.files_decoded - before <= len(plan.files)


# ------------------------------------------------- solver off/on paths
def _stochastic_fixture(source):
    import jax.numpy as jnp

    from photon_ml_tpu.losses.objective import make_glm_objective
    from photon_ml_tpu.losses.pointwise import LogisticLoss
    from photon_ml_tpu.opt import GlmOptimizationConfiguration
    from photon_ml_tpu.opt.config import RegularizationContext
    from photon_ml_tpu.types import RegularizationType

    cfg = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.5,
    )
    objective = make_glm_objective(LogisticLoss)
    dim = source.plan.shard_dims["global"]
    w0 = jnp.zeros((dim,), jnp.float32)
    return objective, cfg, w0


class TestSolverScheduling:
    def _run(self, source, scheduler, seed=5, epochs=4):
        objective, cfg, w0 = _stochastic_fixture(source)
        orders = []

        class _Shard:
            def __init__(self, blk):
                self.data = blk.data["global"]
                self.weight_sum = blk.weight_sum

        def make_blocks(order):
            orders.append(np.asarray(order).copy())

            def gen():
                for blk in BlockPrefetcher(
                    source, shards=("global",), order=list(order)
                ):
                    yield _Shard(blk)

            return gen()

        result = solve_streaming_stochastic(
            objective, w0, make_blocks,
            configuration=cfg,
            num_blocks=source.plan.num_blocks,
            total_weight=float(source.plan.total_rows),
            epochs=epochs, chunk_iters=2, blocks_per_update=2, seed=seed,
            scheduler=scheduler,
        )
        return result, orders

    def test_off_path_orders_are_the_blind_permutation(self, source):
        """gap_schedule off MUST reproduce the historical trajectory
        bitwise: per-epoch orders equal a fresh rng's permutation stream
        and the solved w is bit-for-bit deterministic across runs."""
        result_a, orders_a = self._run(source, scheduler=None, seed=5)
        rng = np.random.default_rng(5)
        for order in orders_a:
            np.testing.assert_array_equal(
                order, rng.permutation(source.plan.num_blocks)
            )
        result_b, orders_b = self._run(source, scheduler=None, seed=5)
        for oa, ob in zip(orders_a, orders_b):
            np.testing.assert_array_equal(oa, ob)
        np.testing.assert_array_equal(
            np.asarray(result_a.w), np.asarray(result_b.w)
        )

    def test_gap_path_bootstraps_then_schedules(self, source):
        n = source.plan.num_blocks
        sched = GapScheduler(
            n, plan=source.plan, visit_fraction=0.5, explore=0.0, seed=0
        )
        result, orders = self._run(source, scheduler=sched, epochs=3)
        # epoch 0 bootstraps every block; later epochs visit the
        # visit_fraction working set (3 of 6) plus the minimum single
        # exploration pick the floor guarantees even at explore=0
        assert sorted(orders[0].tolist()) == list(range(n))
        assert all(o.size == 4 for o in orders[1:])
        # the solver fed measured gaps back: nothing left unmeasured
        assert np.all(np.isfinite(sched.scores))
        assert np.asarray(result.w).shape == (source.plan.shard_dims["global"],)

    def test_gap_path_keeps_its_visits_on_the_blocks_with_signal(
        self, skewed_source
    ):
        """DuHL's point, in the scheduler's own currency (block visits):
        where two blocks in eight carry the signal and the rest are fitted
        by the bootstrap pass, every scheduled epoch visits both, and an
        easy block is visited less often than either."""
        source, hard = skewed_source
        n = source.plan.num_blocks
        sched = GapScheduler(
            n, plan=source.plan, visit_fraction=0.5, explore=0.0, seed=0
        )
        _, orders = self._run(source, scheduler=sched, epochs=6)
        assert sorted(orders[0].tolist()) == list(range(n))
        scheduled = orders[1:]
        assert all(o.size < n for o in scheduled)
        visits = np.zeros(n, dtype=int)
        for order in scheduled:
            assert set(hard) <= set(order.tolist()), (hard, order)
            visits[order] += 1
        easy = [b for b in range(n) if b not in hard]
        assert visits[easy].mean() < visits[list(hard)].min(), visits

    def test_gap_orders_are_file_grouped(self, source):
        sched = GapScheduler(source.plan.num_blocks, plan=source.plan, seed=1)
        _, orders = self._run(source, scheduler=sched, epochs=3)
        for order in orders:
            starts = [source.plan.spans(int(b))[0][0] for b in order]
            # each part file appears as one contiguous run
            runs = [f for i, f in enumerate(starts) if i == 0 or starts[i - 1] != f]
            assert len(runs) == len(set(runs)), (order, starts)


# ------------------------------------------------- coordinate/estimator
class TestCoordinateWiring:
    def test_gap_schedule_requires_stochastic_mode(self, source):
        from photon_ml_tpu.opt import (
            GlmOptimizationConfiguration,
            RegularizationContext,
        )
        from photon_ml_tpu.streaming.coordinate import (
            StreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        with pytest.raises(ValueError, match="stochastic"):
            StreamingFixedEffectCoordinate(
                source=source,
                shard_id="global",
                task=TaskType.LOGISTIC_REGRESSION,
                configuration=GlmOptimizationConfiguration(
                    regularization=RegularizationContext(
                        RegularizationType.L2
                    ),
                    regularization_weight=0.1,
                ),
                mode="full",
                gap_schedule=True,
            )

    def test_estimator_gap_schedule_end_to_end(self, source):
        from photon_ml_tpu.estimators.game import (
            FixedEffectCoordinateConfiguration,
            GameEstimator,
        )
        from photon_ml_tpu.opt import (
            GlmOptimizationConfiguration,
            RegularizationContext,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinates={
                "fixed": FixedEffectCoordinateConfiguration(
                    "global",
                    GlmOptimizationConfiguration(
                        regularization=RegularizationContext(
                            RegularizationType.L2
                        ),
                        regularization_weight=0.1,
                    ),
                )
            },
            update_order=["fixed"],
            num_outer_iterations=1,
        )
        fit = est.fit_streaming(
            source, mode="stochastic", stochastic_epochs=4,
            stochastic_chunk_iters=2, gap_schedule=True,
        )
        coord = fit.model  # smoke: the fit produced a scoreable model
        assert coord is not None
        from photon_ml_tpu.telemetry import get_registry

        gauges = get_registry().snapshot()["gauges"]
        assert "stream.gap_sched.visited_blocks" in gauges
