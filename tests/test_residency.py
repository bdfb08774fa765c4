"""Hierarchical device residency (streaming/residency.py).

The residency-disabled
path must stay bitwise identical to the historical streamed solver with
zero extra jit traces, and the enabled path must cut warm-pass H2D bytes
while leaving the solve trajectory untouched (identical visit order —
residency changes transfer volume, never arithmetic).
"""

import numpy as np
import pytest

from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    build_index_maps,
    read_game_data,
    write_training_examples,
)
from photon_ml_tpu.streaming import (
    GapScheduler,
    ResidencyManager,
    StreamingSource,
    residency_hierarchy,
    stream_trace_counts,
)
from photon_ml_tpu.telemetry import get_registry

FILE_ROWS = (250, 270, 180)
N_ROWS = sum(FILE_ROWS)
D_GLOBAL = 12
BLOCK_ROWS = 128  # 700 rows -> 6 blocks, final one ragged

SHARDS = {
    "global": FeatureShardConfiguration(
        feature_bags=("features",), add_intercept=True
    ),
}


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    rng = np.random.default_rng(23)
    root = tmp_path_factory.mktemp("residency")
    X = rng.normal(size=(N_ROWS, D_GLOBAL)).astype(np.float32)
    w = rng.normal(size=D_GLOBAL).astype(np.float32)
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
                "weight": 1.0 + (i % 2),
                "features": [
                    ("g", str(j), float(X[i, j])) for j in range(D_GLOBAL)
                ],
            }
            for i in range(row, row + n)
        ]
        p = str(root / f"part-{fi:05d}.avro")
        write_training_examples(p, recs)
        paths.append(p)
        row += n
    index_maps = build_index_maps(paths, SHARDS)
    return {"paths": paths, "index_maps": index_maps}


@pytest.fixture(scope="module")
def source(dataset):
    return StreamingSource.open(
        dataset["paths"], SHARDS, index_maps=dataset["index_maps"],
        block_rows=BLOCK_ROWS,
    )


@pytest.fixture(scope="module")
def mem_data(dataset):
    data, _, _ = read_game_data(
        dataset["paths"], SHARDS, dataset["index_maps"]
    )
    return data


def _coordinate(source, **kw):
    from photon_ml_tpu.opt import (
        GlmOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.streaming.coordinate import (
        StreamingFixedEffectCoordinate,
    )
    from photon_ml_tpu.types import RegularizationType, TaskType

    cfg = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.5,
    )
    return StreamingFixedEffectCoordinate(
        source=source,
        shard_id="global",
        task=TaskType.LOGISTIC_REGRESSION,
        configuration=cfg,
        **kw,
    )


# ------------------------------------------------------------ manager unit
class TestResidencyManager:
    def test_budget_math(self):
        # byte budget divides by the uniform per-block upload size; the
        # tighter of blocks/bytes wins
        m = ResidencyManager(10, block_bytes=100, max_blocks=8, max_bytes=450)
        assert m.capacity == 4
        m = ResidencyManager(10, block_bytes=100, max_blocks=3, max_bytes=450)
        assert m.capacity == 3
        m = ResidencyManager(4, block_bytes=100, max_blocks=64)
        assert m.capacity == 4  # never more than the plan has
        with pytest.raises(ValueError, match="admits no blocks"):
            ResidencyManager(10, block_bytes=100, max_bytes=99)

    def test_bootstrap_then_gap_pinning(self):
        m = ResidencyManager(6, block_bytes=10, max_blocks=2)
        # bootstrap: first-come admission up to capacity
        assert m.offer(0, "e0") and m.offer(1, "e1")
        assert not m.offer(2, "e2")  # budget full
        assert m.resident_indices() == [0, 1]
        assert m.get(0) == "e0" and m.get(3) is None
        # measured gaps say blocks 4 and 5 matter: repin evicts 0 and 1
        m.update_gaps({0: 0.1, 1: 0.2, 2: 0.3, 3: 0.4, 4: 5.0, 5: 6.0})
        target = m.repin()
        assert target == [5, 4]
        assert m.resident_indices() == []  # evicted; re-pinned on visit
        assert not m.offer(2, "e2")  # not in target
        assert m.offer(5, "e5")
        assert m.resident_indices() == [5]
        assert m.stats.evicted_blocks == 2

    def test_repin_deterministic_under_fixed_gap_trajectory(self):
        trajectory = [
            {i: g for i, g in enumerate([3.0, 1.0, 4.0, 1.0, 5.0, 9.0])},
            {0: 0.5, 2: 8.0, 4: 0.5},
            {1: 7.0, 3: 7.0, 5: 0.1},  # exact tie -> stable index order
        ]
        runs = []
        for _ in range(2):
            m = ResidencyManager(6, block_bytes=10, max_blocks=3)
            targets = []
            for gaps in trajectory:
                m.update_gaps(gaps)
                targets.append(m.repin())
            runs.append(targets)
        assert runs[0] == runs[1]
        # ties broke by block index (stable argsort), deterministically
        assert runs[0][-1][0] == 1

    def test_gap_decay_evicts_stale_blocks(self):
        m = ResidencyManager(4, block_bytes=10, max_blocks=2, decay=0.5)
        m.update_gaps({0: 10.0, 1: 1.0, 2: 1.0, 3: 1.0})
        assert m.repin() == [0, 1]
        assert m.offer(0, "e0")
        # block 0 never re-measured: 10 * 0.5^age decays below the fresh
        # measurements and the pin flips
        for _ in range(4):
            m.update_gaps({1: 1.0, 2: 1.0, 3: 1.0})
        assert m.repin() == [1, 2]
        assert not m.is_resident(0)

    def test_mark_failed_evicts_and_excludes(self):
        m = ResidencyManager(4, block_bytes=10, max_blocks=2)
        assert m.offer(0, "e0")
        m.mark_failed([0])
        assert not m.is_resident(0)
        assert not m.offer(0, "e0")  # permanently excluded
        m.update_gaps({0: 99.0, 1: 1.0, 2: 2.0, 3: 3.0})
        assert 0 not in m.repin()  # even on a huge measured gap
        actions = [(d["action"], d["block"]) for d in m.drain_decisions()]
        assert ("evict", 0) in actions

    def test_decision_records_carry_score_and_byte_delta(self):
        m = ResidencyManager(4, block_bytes=10, max_blocks=2)
        m.offer(1, "e1")
        m.update_gaps({0: 1.0, 1: 0.1, 2: 2.0, 3: 3.0})
        m.repin()  # 1 falls out of the target -> evict
        recs = m.drain_decisions()
        pin = next(r for r in recs if r["action"] == "pin")
        ev = next(r for r in recs if r["action"] == "evict")
        assert pin["block"] == 1 and pin["byte_delta"] == 10
        assert pin["gap_score"] == -1.0  # bootstrap pin: no measurement
        assert ev["block"] == 1 and ev["byte_delta"] == -10
        assert ev["gap_score"] == pytest.approx(0.1)
        assert m.drain_decisions() == []  # drained

    def test_gap_scheduler_mark_failed_evicts_resident_block(self):
        sched = GapScheduler(6, seed=0)
        m = ResidencyManager(6, block_bytes=10, max_blocks=3)
        sched.attach_residency(m)
        assert m.offer(2, "e2")
        sched.mark_failed([2])
        assert not m.is_resident(2)
        assert bool(m.excluded[2]) and bool(sched.excluded[2])

    def test_gap_scheduler_update_drives_repin(self):
        sched = GapScheduler(4, seed=0)
        m = ResidencyManager(4, block_bytes=10, max_blocks=2)
        sched.attach_residency(m)
        sched.update({0: 1.0, 1: 9.0, 2: 8.0, 3: 0.5})
        # the scheduler's epoch-end feedback doubled as the repin signal
        assert m.epoch == 1
        assert m.offer(1, "e1") and not m.offer(0, "e0")


# ---------------------------------------------------------- streamed solve
class TestResidencyStreaming:
    def _fit_w(self, source, **kw):
        coord = _coordinate(source, **kw)
        model = coord.update_model(None, np.zeros(N_ROWS, np.float32))
        return coord, np.asarray(model.coefficients.means)

    def test_disabled_path_bitwise_and_zero_retrace(self, source):
        _, w_plain = self._fit_w(source)
        before = dict(stream_trace_counts())
        _, w_off = self._fit_w(source, resident_blocks=0)
        after = dict(stream_trace_counts())
        # residency off: the historical streamed path, bit for bit, and
        # not a single new jit trace
        np.testing.assert_array_equal(w_plain, w_off)
        assert after == before, {
            k: after[k] - before.get(k, 0)
            for k in after if after[k] != before.get(k, 0)
        }

    def test_enabled_matches_probe_path_bitwise(self, source):
        # residency serves identical device arrays in identical order; the
        # only program difference vs a probe-enabled solve is NONE — so the
        # trajectories must agree bit for bit
        _, w_probe = self._fit_w(source, collect_block_stats=True)
        coord, w_res = self._fit_w(source, resident_blocks=3)
        np.testing.assert_array_equal(w_probe, w_res)
        assert coord._residency.stats.hbm_hit_blocks > 0

    def test_resident_set_cuts_h2d_bytes(self, source):
        reg = get_registry()
        b0 = reg.counter_value("stream.h2d_bytes")
        coord, _ = self._fit_w(source, collect_block_stats=True)
        plain_bytes = reg.counter_value("stream.h2d_bytes") - b0
        passes = coord.last_solve_info.passes

        b1 = reg.counter_value("stream.h2d_bytes")
        coord_r, _ = self._fit_w(source, resident_blocks=4)
        res_bytes = reg.counter_value("stream.h2d_bytes") - b1
        passes_r = coord_r.last_solve_info.passes

        assert passes == passes_r  # same trajectory, same pass count
        # pass 1 uploads everything; every later pass skips the residents
        block_bytes = source.block_upload_bytes(("global",))
        num_blocks = source.plan.num_blocks
        assert plain_bytes == passes * num_blocks * block_bytes
        # exact conservation: every byte not re-uploaded was served from the
        # resident set (repin churn may re-upload a block once after an
        # eviction, so the saving is counted from actual HBM hits)
        mstats = coord_r._residency.stats
        assert plain_bytes - res_bytes == mstats.hbm_hit_bytes
        assert mstats.hbm_hit_bytes == mstats.hbm_hit_blocks * block_bytes
        # ...and the saving is substantial: at least 4 resident blocks per
        # pass once pinned, minus one pass of slack for bootstrap + churn
        assert mstats.hbm_hit_blocks >= (passes - 2) * 4
        stats = coord_r.last_prefetch_stats
        assert stats.resident_hit_blocks == 4
        assert stats.resident_hit_bytes == 4 * block_bytes

    def test_resident_buffers_survive_the_donation_seam(self, source):
        # acc_vg donates ONLY the f/g accumulators (argnums 2,3) — a pinned
        # block's arrays must stay alive across passes and solves
        coord, _ = self._fit_w(source, resident_blocks=3)
        entries = list(coord._residency._entries.values())
        assert entries
        for blk in entries:
            feats = blk.data["global"].features
            assert not feats.values.is_deleted()
            assert not feats.indices.is_deleted()
            np.asarray(feats.values)  # still materializable
        # and a second solve through the same pinned arrays still works
        model2 = coord.update_model(None, np.zeros(N_ROWS, np.float32))
        assert np.isfinite(np.asarray(model2.coefficients.means)).all()

    def test_resident_set_follows_gap_probe(self, source):
        coord, _ = self._fit_w(source, resident_blocks=2)
        mgr = coord._residency
        # after the solve the set equals the top-capacity blocks by
        # staleness-decayed measured gap — chosen, not static
        eff = mgr.effective_scores()
        want = sorted(np.argsort(-eff, kind="stable")[:2].tolist())
        assert sorted(mgr._target) == want
        assert (mgr.scores >= 0).all()  # every block was measured

    def test_residency_decisions_drain_for_the_ledger(self, source):
        from photon_ml_tpu.telemetry.validate import _PROGRESS_SCHEMAS

        coord, _ = self._fit_w(source, resident_blocks=2)
        decisions = coord.last_residency_decisions
        assert decisions and any(d["action"] == "pin" for d in decisions)
        required = set(_PROGRESS_SCHEMAS["residency"]) - {
            "outer", "coordinate"
        }
        for d in decisions:
            assert required <= set(d)

    def test_byte_budget_and_validation(self, source):
        block_bytes = source.block_upload_bytes(("global",))
        coord = _coordinate(source, resident_bytes=2 * block_bytes + 1)
        assert coord._residency.capacity == 2
        with pytest.raises(ValueError, match="admits no blocks"):
            _coordinate(source, resident_bytes=block_bytes - 1)
        with pytest.raises(ValueError, match="gap_schedule"):
            _coordinate(source, mode="stochastic", resident_blocks=2)

    def test_stochastic_residency_with_gap_schedule(self, source, mem_data):
        coord = _coordinate(
            source, mode="stochastic", gap_schedule=True, resident_blocks=2,
            epochs=8, chunk_iters=4,
        )
        model = coord.update_model(None, np.zeros(N_ROWS, np.float32))
        assert np.isfinite(np.asarray(model.coefficients.means)).all()
        mgr = coord._residency
        # epochs repinned through the scheduler's gap feedback
        assert mgr.stats.repins >= 1
        assert mgr.resident_blocks <= 2

    def test_hierarchy_accounting(self, source):
        coord, _ = self._fit_w(source, resident_blocks=3)
        levels = residency_hierarchy(source, coord._residency)
        assert set(levels) == {"disk", "ram", "hbm"}
        assert levels["hbm"]["hit_blocks"] > 0
        assert levels["hbm"]["saved_bytes"] == (
            levels["hbm"]["hit_blocks"]
            * source.block_upload_bytes(("global",))
        )
        # the decoded-file LRU (RAM level) served repeat visits
        assert levels["ram"]["file_cache_hits"] > 0
        assert levels["ram"]["files_decoded"] >= len(FILE_ROWS)


# --------------------------------------------------------------- estimator
class TestResidencyEstimator:
    def _estimator(self):
        from photon_ml_tpu.estimators.game import (
            FixedEffectCoordinateConfiguration,
            GameEstimator,
        )
        from photon_ml_tpu.opt import (
            GlmOptimizationConfiguration,
            RegularizationContext,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        cfg = GlmOptimizationConfiguration(
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=0.1,
        )
        return GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinates={
                "fixed": FixedEffectCoordinateConfiguration("global", cfg)
            },
            update_order=["fixed"],
            num_outer_iterations=1,
        )

    def test_fit_streaming_resident_auc_parity(self, source, mem_data):
        def auc(scores):
            order = np.argsort(scores)
            ranks = np.empty(len(scores))
            ranks[order] = np.arange(1, len(scores) + 1)
            pos = mem_data.labels > 0.5
            n_pos, n_neg = int(pos.sum()), int((~pos).sum())
            return (
                ranks[pos].sum() - n_pos * (n_pos + 1) / 2
            ) / (n_pos * n_neg)

        fit_plain = self._estimator().fit_streaming(source)
        fit_res = self._estimator().fit_streaming(source, resident_blocks=4)
        a_plain = auc(np.asarray(fit_plain.model.score(mem_data)))
        a_res = auc(np.asarray(fit_res.model.score(mem_data)))
        assert abs(a_plain - a_res) < 1e-6, (a_plain, a_res)

    def test_fit_streaming_validates_stochastic_residency(self, source):
        with pytest.raises(ValueError, match="gap_schedule"):
            self._estimator().fit_streaming(
                source, mode="stochastic", resident_blocks=2
            )
