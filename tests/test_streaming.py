"""Out-of-core streaming training: block planning, the double-buffered
prefetcher, block-sharded solvers, and estimator/CLI parity.

Streamed full-batch training must match
the in-memory fit within 1e-3 on held-out metrics, with ZERO extra jit
retraces across blocks — every streamed program compiles exactly once per
(objective, shape), however many blocks, passes, and fits run.
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    build_index_maps,
    file_row_counts,
    iter_game_data,
    list_data_files,
    read_game_data,
    write_training_examples,
)
from photon_ml_tpu.streaming import (
    BlockPrefetcher,
    StreamingSource,
    reset_stream_trace_counts,
    solve_streaming,
    solve_streaming_stochastic,
    stream_trace_counts,
    streamed_objective_value,
)

FILE_ROWS = (250, 270, 180)  # uneven on purpose: blocks straddle files
N_ROWS = sum(FILE_ROWS)
D_GLOBAL = 12
D_USER = 4
N_USERS = 10
BLOCK_ROWS = 128  # 700 rows -> 6 blocks, final one ragged (60 real rows)

SHARDS = {
    "global": FeatureShardConfiguration(
        feature_bags=("features",), add_intercept=True
    ),
    "per_user": FeatureShardConfiguration(
        feature_bags=("userFeatures",), add_intercept=False
    ),
}


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """Synthetic GLMix logistic data over 3 uneven Avro part files."""
    rng = np.random.default_rng(11)
    root = tmp_path_factory.mktemp("stream")
    Xg = rng.normal(size=(N_ROWS, D_GLOBAL)).astype(np.float32)
    Xu = rng.normal(size=(N_ROWS, D_USER)).astype(np.float32)
    users = rng.integers(0, N_USERS, size=N_ROWS)
    wg = rng.normal(size=D_GLOBAL).astype(np.float32)
    wu = {u: rng.normal(size=D_USER).astype(np.float32) for u in range(N_USERS)}
    z = Xg @ wg + np.array(
        [Xu[i] @ wu[users[i]] for i in range(N_ROWS)], np.float32
    )
    y = (1.0 / (1.0 + np.exp(-z)) > rng.random(N_ROWS)).astype(np.float32)

    paths = []
    row = 0
    for fi, n in enumerate(FILE_ROWS):
        recs = []
        for i in range(row, row + n):
            recs.append({
                "uid": f"r{i}",
                "label": float(y[i]),
                "weight": 1.0 + (i % 2),  # non-trivial weights
                "features": [
                    ("g", str(j), float(Xg[i, j])) for j in range(D_GLOBAL)
                ],
                "userFeatures": [
                    ("u", str(j), float(Xu[i, j])) for j in range(D_USER)
                ],
                "metadataMap": {"userId": f"u{users[i]:02d}"},
            })
        p = str(root / f"part-{fi:05d}.avro")
        write_training_examples(p, recs)
        paths.append(p)
        row += n
    index_maps = build_index_maps(paths, SHARDS)
    return {"paths": paths, "index_maps": index_maps, "labels": y,
            "users": users, "root": str(root)}


@pytest.fixture(scope="module")
def source(dataset):
    return StreamingSource.open(
        dataset["paths"], SHARDS, index_maps=dataset["index_maps"],
        block_rows=BLOCK_ROWS, id_tags=("userId",),
    )


@pytest.fixture(scope="module")
def mem_data(dataset):
    data, _, _ = read_game_data(
        dataset["paths"], SHARDS, dataset["index_maps"], id_tags=("userId",)
    )
    return data


# --------------------------------------------------------------- satellite 3
class TestFileGranularReader:
    def test_list_data_files(self, dataset):
        files = list_data_files(dataset["root"])
        assert files == dataset["paths"]  # sorted part files of the dir
        assert list_data_files(dataset["paths"]) == dataset["paths"]

    def test_file_row_counts_framing_only(self, dataset):
        counts = file_row_counts(dataset["paths"])
        assert [n for _, n in counts] == list(FILE_ROWS)
        assert [p for p, _ in counts] == dataset["paths"]

    def test_iter_game_data_per_file(self, dataset, mem_data):
        rows_seen = 0
        for (path, data, uids), want in zip(
            iter_game_data(
                dataset["paths"], SHARDS, dataset["index_maps"],
                id_tags=("userId",),
            ),
            FILE_ROWS,
        ):
            assert data.num_rows == want
            assert len(uids) == want
            # stable column space: per-file dims match the global index
            assert data.feature_shards["global"].dim == (
                mem_data.feature_shards["global"].dim
            )
            np.testing.assert_array_equal(
                data.labels, mem_data.labels[rows_seen:rows_seen + want]
            )
            rows_seen += want
        assert rows_seen == N_ROWS

    def test_iter_game_data_requires_index_maps(self, dataset):
        with pytest.raises(ValueError, match="index_maps"):
            next(iter_game_data(dataset["paths"], SHARDS, None))


# ------------------------------------------------------------- block planning
class TestBlockPlan:
    def test_plan_shapes(self, source):
        plan = source.plan
        assert plan.total_rows == N_ROWS
        assert plan.num_blocks == 6  # ceil(700 / 128)
        assert plan.padded_rows == 6 * BLOCK_ROWS
        assert plan.shard_dims["global"] == D_GLOBAL + 1  # + intercept
        assert plan.shard_dims["per_user"] == D_USER
        # dense synthetic rows: width == row nnz (+ intercept)
        assert plan.shard_widths["global"] == D_GLOBAL + 1
        assert plan.shard_widths["per_user"] == D_USER

    def test_block_spans_cross_file_boundaries(self, source):
        plan = source.plan
        # block 1 is rows [128, 256): rows 128..249 from file 0, 250..255
        # from file 1 — one block stitched from two files
        spans = plan.spans(1)
        assert [(fi, hi - lo) for fi, lo, hi in spans] == [(0, 122), (1, 6)]
        # every row is covered exactly once across all blocks
        total = sum(
            hi - lo
            for b in range(plan.num_blocks)
            for _, lo, hi in plan.spans(b)
        )
        assert total == N_ROWS

    def test_ragged_final_block_padding(self, source, mem_data):
        plan = source.plan
        last = plan.num_blocks - 1
        blk = source.build_block(last)
        assert blk.num_real == N_ROWS - last * BLOCK_ROWS == 60
        # real rows carry the data; padding rows are weight-0 no-ops
        np.testing.assert_array_equal(
            blk.labels[:60], mem_data.labels[last * BLOCK_ROWS:]
        )
        assert (blk.weights[60:] == 0).all()
        assert (blk.labels[60:] == 0).all()
        vals, idx = blk.shards["global"]
        assert vals.shape == (BLOCK_ROWS, plan.shard_widths["global"])
        assert (vals[60:] == 0).all()

    def test_blocks_reassemble_dataset(self, source, mem_data):
        labels = np.concatenate([
            source.build_block(b).labels[:source.build_block(b).num_real]
            for b in range(source.plan.num_blocks)
        ])
        np.testing.assert_array_equal(labels, mem_data.labels)

    def test_id_tags_per_block(self, source, dataset):
        blk = source.build_block(0)
        want = [f"u{u:02d}" for u in dataset["users"][:BLOCK_ROWS]]
        assert list(blk.id_tags["userId"]) == want


# --------------------------------------------------------------- prefetcher
class TestPrefetcher:
    def test_order_and_shapes(self, source):
        got = [blk.index for blk in BlockPrefetcher(source, depth=2)]
        assert got == list(range(source.plan.num_blocks))

    def test_custom_order(self, source):
        order = [3, 0, 5, 1]
        pf = BlockPrefetcher(source, shards=("global",), order=order)
        got = [blk.index for blk in pf]
        assert got == order
        assert pf.stats.blocks == len(order)

    def test_sync_mode_exposes_decode(self, source):
        pf = BlockPrefetcher(source, depth=0)
        list(pf)
        assert pf.stats.decode_s > 0
        # synchronous decode hides nothing, and says so
        assert pf.stats.hide_ratio == 0.0

    def test_threaded_stats_accounting(self, source):
        pf = BlockPrefetcher(source, depth=2)
        n = len(list(pf))
        assert n == pf.stats.blocks == source.plan.num_blocks
        assert pf.stats.decode_s > 0
        assert pf.stats.stall_s >= 0
        assert 0.0 <= pf.stats.hide_ratio <= 1.0

    def test_worker_error_propagates(self, source, monkeypatch):
        def boom(*a, **k):
            raise RuntimeError("decode exploded")

        monkeypatch.setattr(source, "build_block", boom)
        with pytest.raises(RuntimeError, match="decode exploded"):
            list(BlockPrefetcher(source, depth=2))

    def test_weight_sum_is_real_rows_only(self, source, mem_data):
        pf = BlockPrefetcher(source, shards=("global",), depth=1)
        total = sum(blk.weight_sum for blk in pf)
        assert total == pytest.approx(float(np.sum(mem_data.weights)), rel=1e-6)

    def test_sync_decode_parallelism_is_serial(self, dataset):
        """depth=0 + a single worker: decode work == decode wall, so the
        reported parallelism sits at ~1.0 (and 0.0 with no decode at all)."""
        from photon_ml_tpu.streaming.prefetch import PrefetchStats

        assert PrefetchStats().decode_parallelism == 0.0
        src = StreamingSource.open(
            dataset["paths"], SHARDS, index_maps=dataset["index_maps"],
            block_rows=BLOCK_ROWS, id_tags=("userId",), decode_workers=0,
        )
        pf = BlockPrefetcher(src, depth=0)
        list(pf)
        assert pf.stats.decode_s > 0
        assert pf.stats.decode_parallelism == pytest.approx(1.0, abs=0.2)

    @pytest.mark.skipif(
        (os.cpu_count() or 1) < 2,
        reason="decode-pool overlap needs >= 2 CPUs",
    )
    def test_decode_pool_overlap(self, dataset):
        """Satellite contract: with a 2-worker decode pool over >= 2 cold
        part files, summed per-thread decode work exceeds decode wall clock
        — the pool genuinely overlapped — and PrefetchStats reports the
        achieved parallelism (``decode_parallelism``)."""
        src = StreamingSource.open(
            dataset["paths"], SHARDS, index_maps=dataset["index_maps"],
            block_rows=BLOCK_ROWS, id_tags=("userId",), decode_workers=2,
        )
        pf = BlockPrefetcher(src, depth=2)
        assert len(list(pf)) == src.plan.num_blocks
        assert pf.stats.decode_work_s > 0
        assert pf.stats.decode_parallelism > 1.0


# ---------------------------------------------------------- streamed solvers
def _fe_problem(source, mem_data):
    import jax.numpy as jnp

    from photon_ml_tpu.losses.objective import make_glm_objective
    from photon_ml_tpu.losses.pointwise import LogisticLoss
    from photon_ml_tpu.ops.data import LabeledData

    objective = make_glm_objective(LogisticLoss)
    data = LabeledData.create(
        mem_data.sparse_features("global", engine="ell"),
        jnp.asarray(mem_data.labels),
        weights=jnp.asarray(mem_data.weights),
    )
    dim = source.plan.shard_dims["global"]
    return objective, data, dim


def _make_blocks(source):
    def gen():
        for blk in BlockPrefetcher(source, shards=("global",), depth=2):
            yield blk.data["global"]
    return gen


class TestStreamedSolver:
    def test_full_batch_parity_and_zero_retrace(self, source, mem_data):
        import jax.numpy as jnp

        from photon_ml_tpu.opt import GlmOptimizationConfiguration
        from photon_ml_tpu.opt.config import RegularizationContext
        from photon_ml_tpu.opt.solve import solve
        from photon_ml_tpu.types import RegularizationType

        cfg = GlmOptimizationConfiguration(
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=0.5,
        )
        objective, data, dim = _fe_problem(source, mem_data)
        w0 = jnp.zeros((dim,), jnp.float32)
        ref = solve(objective, w0, data, cfg)

        reset_stream_trace_counts()
        got = solve_streaming(objective, w0, _make_blocks(source), cfg)
        traces1 = dict(stream_trace_counts())
        # identical optimum within float32 solver noise
        assert float(got.value) == pytest.approx(float(ref.value), rel=1e-4)
        np.testing.assert_allclose(
            np.asarray(got.w), np.asarray(ref.w), atol=2e-3
        )
        # a second solve (same objective, same shapes) retraces NOTHING
        got2 = solve_streaming(objective, w0, _make_blocks(source), cfg)
        traces2 = dict(stream_trace_counts())
        assert traces2 == traces1, (traces1, traces2)
        assert float(got2.value) == pytest.approx(float(got.value), rel=1e-6)
        # and every streamed program compiled exactly once
        assert all(v == 1 for v in traces1.values()), traces1

    def test_streamed_objective_value_matches(self, source, mem_data):
        import jax.numpy as jnp

        from photon_ml_tpu.opt import GlmOptimizationConfiguration

        objective, data, dim = _fe_problem(source, mem_data)
        w = jnp.asarray(
            np.random.default_rng(0).normal(size=dim).astype(np.float32)
        )
        l2 = 0.3
        ref, _ = objective.value_and_grad(w, data, l2)
        got = streamed_objective_value(
            objective, w, _make_blocks(source), dim, l2
        )
        assert float(got) == pytest.approx(float(ref), rel=1e-5)

    def test_tron_and_l1_rejected(self, source, mem_data):
        import jax.numpy as jnp

        from photon_ml_tpu.opt import GlmOptimizationConfiguration, OptimizerConfig
        from photon_ml_tpu.opt.config import OptimizerType, RegularizationContext
        from photon_ml_tpu.types import RegularizationType

        objective, _, dim = _fe_problem(source, mem_data)
        w0 = jnp.zeros((dim,), jnp.float32)
        tron = GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig(optimizer=OptimizerType.TRON),
        )
        with pytest.raises(ValueError, match="TRON"):
            solve_streaming(objective, w0, _make_blocks(source), tron)
        l1 = GlmOptimizationConfiguration(
            regularization=RegularizationContext(RegularizationType.L1),
            regularization_weight=0.5,
        )
        with pytest.raises(ValueError, match="L1"):
            solve_streaming(objective, w0, _make_blocks(source), l1)

    def test_stochastic_mode_converges_close(self, source, mem_data):
        import jax.numpy as jnp

        from photon_ml_tpu.opt import GlmOptimizationConfiguration
        from photon_ml_tpu.opt.config import RegularizationContext
        from photon_ml_tpu.opt.solve import solve
        from photon_ml_tpu.types import RegularizationType

        cfg = GlmOptimizationConfiguration(
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=0.5,
        )
        objective, data, dim = _fe_problem(source, mem_data)
        w0 = jnp.zeros((dim,), jnp.float32)
        ref = solve(objective, w0, data, cfg)

        class _Shard:
            def __init__(self, blk):
                self.data = blk.data["global"]
                self.weight_sum = blk.weight_sum

        class _Blocks:
            def __init__(self, order):
                self.order = order

            def __iter__(self):
                for blk in BlockPrefetcher(
                    source, shards=("global",), order=list(self.order)
                ):
                    yield _Shard(blk)

        total_weight = float(np.sum(mem_data.weights))
        got = solve_streaming_stochastic(
            objective, w0,
            make_blocks_ordered=lambda order: _Blocks(order),
            configuration=cfg,
            num_blocks=source.plan.num_blocks,
            total_weight=total_weight,
            epochs=20, chunk_iters=8, blocks_per_update=3, seed=3,
        )
        # stochastic passes land NEAR the full-batch optimum: the gate is
        # the full-batch objective evaluated at the stochastic solution
        f_star = float(ref.value)
        f0 = float(streamed_objective_value(
            objective, w0, _make_blocks(source), dim, 0.5
        ))
        f_got = float(streamed_objective_value(
            objective, got.w, _make_blocks(source), dim, 0.5
        ))
        assert f_got <= f_star * 1.05, (f_got, f_star)
        # and it actually descended: >85% of the achievable improvement
        assert f_got <= f_star + 0.15 * (f0 - f_star), (f_got, f_star, f0)


# ----------------------------------------------------- estimator + CLI parity
def _auc(scores, labels):
    order = np.argsort(scores)
    ranks = np.empty(len(scores)); ranks[order] = np.arange(1, len(scores) + 1)
    pos = labels > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


class TestStreamingEstimator:
    def _estimator(self, with_re):
        from photon_ml_tpu.data import RandomEffectDataConfiguration
        from photon_ml_tpu.estimators.game import (
            FixedEffectCoordinateConfiguration,
            GameEstimator,
            RandomEffectCoordinateConfiguration,
        )
        from photon_ml_tpu.opt import (
            GlmOptimizationConfiguration,
            RegularizationContext,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        l2 = lambda lam: GlmOptimizationConfiguration(  # noqa: E731
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=lam,
        )
        coords = {"fixed": FixedEffectCoordinateConfiguration("global", l2(0.1))}
        if with_re:
            coords["per-user"] = RandomEffectCoordinateConfiguration(
                "per_user",
                data=RandomEffectDataConfiguration("userId", num_buckets=2),
                optimizer=l2(1.0),
            )
        return GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinates=coords,
            update_order=list(coords),
            num_outer_iterations=2 if with_re else 1,
        )

    @pytest.mark.parametrize("with_re", [False, True])
    def test_fit_streaming_matches_fit(self, source, mem_data, with_re):
        fit_mem = self._estimator(with_re).fit(mem_data, mem_data)
        fit_st = self._estimator(with_re).fit_streaming(
            source, validation_data=mem_data
        )
        sc_mem = np.asarray(fit_mem.model.score(mem_data))
        sc_st = np.asarray(fit_st.model.score(mem_data))
        auc_mem = _auc(sc_mem, mem_data.labels)
        auc_st = _auc(sc_st, mem_data.labels)
        assert abs(auc_mem - auc_st) < 1e-3, (auc_mem, auc_st)

    def test_second_fit_retraces_nothing(self, source, mem_data):
        self._estimator(True).fit_streaming(source)  # warm every program
        before = dict(stream_trace_counts())
        self._estimator(True).fit_streaming(source)
        after = dict(stream_trace_counts())
        assert after == before, {
            k: after[k] - before.get(k, 0)
            for k in after if after[k] != before.get(k, 0)
        }

    def test_stochastic_estimator_auc_parity(self, source, mem_data):
        """The optional stochastic mode is gated on held-out AUC parity
        with the in-memory fit. The gate is 1e-2 (vs 1e-3 for full-batch
        streaming, which is algebraically exact): stochastic block passes
        trade a bounded accuracy slack for fixed-memory epochs, and this
        test pins that slack so regressions surface."""
        fit_mem = self._estimator(False).fit(mem_data, mem_data)
        fit_st = self._estimator(False).fit_streaming(
            source, mode="stochastic", stochastic_epochs=20,
            stochastic_chunk_iters=8, blocks_per_update=3,
        )
        auc_mem = _auc(
            np.asarray(fit_mem.model.score(mem_data)), mem_data.labels
        )
        auc_st = _auc(
            np.asarray(fit_st.model.score(mem_data)), mem_data.labels
        )
        assert abs(auc_mem - auc_st) < 1e-2, (auc_mem, auc_st)

    def test_incompatible_modes_raise(self, source):
        est = self._estimator(False)
        est.compute_variance = True
        with pytest.raises(ValueError, match="variance"):
            est.fit_streaming(source)
        with pytest.raises(ValueError, match="mode"):
            self._estimator(False).fit_streaming(source, mode="minibatch")


# --------------------------------------------------- golden fixture (slow)
@pytest.mark.slow
class TestGoldenFixtureStreaming:
    """Streaming parity on the committed ratings fixture: the
    streamed trainer over the fixture split into blocks must land within
    1e-3 RMSE of the in-memory trainer, with zero extra retraces across
    blocks (same LBFGS config both arms; TRON cannot stream)."""

    HERE = os.path.join(os.path.dirname(__file__), "fixtures", "ratings")

    def _run(self, tmp_path, tag, extra):
        import json

        from photon_ml_tpu.cli.train_game import parse_args, run

        cfg = {
            "feature_shards": {
                "global": {"feature_bags": ["features"], "add_intercept": True},
                "per_user": {
                    "feature_bags": ["userFeatures"], "add_intercept": False,
                },
            },
            "coordinates": {
                "fixed": {
                    "type": "fixed",
                    "feature_shard": "global",
                    "optimizer": {
                        "optimizer": "LBFGS",
                        "regularization": "L2",
                        "regularization_weight": 10.0,
                    },
                },
                "per_user": {
                    "type": "random",
                    "feature_shard": "per_user",
                    "random_effect_type": "userId",
                    "optimizer": {
                        "regularization": "L2",
                        "regularization_weight": 1.0,
                    },
                },
            },
            "update_order": ["fixed", "per_user"],
        }
        cfg_path = tmp_path / f"game-{tag}.json"
        cfg_path.write_text(json.dumps(cfg))
        return run(parse_args([
            "--train-data-dirs", os.path.join(self.HERE, "train"),
            "--validation-data-dirs", os.path.join(self.HERE, "test"),
            "--coordinate-config", str(cfg_path),
            "--task", "LINEAR_REGRESSION",
            "--output-dir", str(tmp_path / f"out-{tag}"),
            "--evaluator", "RMSE",
            "--num-outer-iterations", "2",
            *extra,
        ]))

    def test_streamed_parity_and_zero_retraces(self, tmp_path):
        fit_mem = self._run(tmp_path, "mem", [])
        reset_stream_trace_counts()
        # explicit cache dir: the default would land next to the committed
        # fixture files; run 2 over identical inputs must hit it warm
        cache = ["--block-cache-dir", str(tmp_path / "blkcache")]
        fit_st = self._run(tmp_path, "st", [
            "--streaming", "--block-rows", "512", "--prefetch-depth", "2",
            *cache,
        ])
        traces1 = dict(stream_trace_counts())
        assert abs(fit_mem.validation_metric - fit_st.validation_metric) < 1e-3, (
            fit_mem.validation_metric, fit_st.validation_metric,
        )
        # every streamed program compiled exactly once over all blocks
        assert traces1 and all(v == 1 for v in traces1.values()), traces1
        # a second streamed run over the same shapes compiles nothing new,
        # and a cache-warm run lands on the identical metric
        fit_st2 = self._run(tmp_path, "st2", [
            "--streaming", "--block-rows", "512", "--prefetch-depth", "2",
            *cache,
        ])
        assert dict(stream_trace_counts()) == traces1
        assert fit_st2.validation_metric == pytest.approx(
            fit_st.validation_metric, abs=1e-6
        )
