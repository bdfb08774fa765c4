"""Fused Benes execution (ops/fused_perm.py) vs the stage-by-stage engine.

Ground truth is dense numpy algebra on the same COO triplets; the fused
Pallas kernels run through the interpreter on CPU (the same 8-virtual-device
harness as everything else), exercising descend/base/ascend tiles and all
four prologue/epilogue fusions.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # excluded from the fast lane (pyproject markers)

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops import fused_perm
from photon_ml_tpu.ops.fused_perm import (
    Broadcast,
    FusedBenesFeatures,
    MulBroadcast,
    MulReduce,
    Reduce,
    from_coo,
    fused_execute,
    parse_plan,
    unfused_execute,
)


def _random_coo(rng, n, d, nnz):
    rows = rng.integers(0, n, nnz)
    cols = rng.integers(0, d, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    dense = np.zeros((n, d), dtype=np.float32)
    np.add.at(dense, (rows, cols), vals)
    return rows, cols, vals, dense


def _check_against_dense(feats, dense, rng, atol=1e-4, rtol=1e-7):
    n, d = dense.shape
    w = rng.standard_normal(d).astype(np.float32)
    c = rng.standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(feats.matvec(jnp.asarray(w))), dense @ w, atol=atol, rtol=rtol
    )
    np.testing.assert_allclose(
        np.asarray(feats.rmatvec(jnp.asarray(c))), dense.T @ c, atol=atol,
        rtol=rtol,
    )
    np.testing.assert_allclose(
        np.asarray(feats.rmatvec_sq(jnp.asarray(c))), (dense * dense).T @ c,
        atol=atol, rtol=rtol,
    )
    np.testing.assert_allclose(
        np.asarray(feats.row_norms_sq()), (dense * dense).sum(1), atol=atol,
        rtol=rtol,
    )


class TestTileCap:
    """PHOTON_FUSED_TILE_U raises the kernel block height (the dispatch-
    overhead A/B knob for the hardware session); the descend/ascend tiles
    must stay exact wherever the raised u actually binds. from_coo shapes
    below the 128^3 ladder step always have R1 <= 8 (a cap never binds
    there), so the u-sensitive tiling is driven at the kernel level with
    shapes where R1 = 16/64."""

    @pytest.mark.parametrize("cap,B,R", [("32", 2, 2048), ("64", 1, 8192)])
    def test_descend_ascend_roundtrip_at_raised_u(
        self, rng, interpret_kernels, monkeypatch, cap, B, R
    ):
        import jax.numpy as jnp

        monkeypatch.setenv("PHOTON_FUSED_TILE_U", cap)
        R1 = R // 128
        u = fused_perm._tile_rows(R1)
        assert u > 8, (cap, R1, u)  # the raised cap must actually bind
        S = B * R * 128
        x = rng.standard_normal(S).astype(np.float32)
        # identity lane shuffle: the kernel's output is then exactly the
        # documented enter relayout (view [B,R,128], swap last two axes)
        ident = np.tile(np.arange(128, dtype=np.int8), (B * R, 1))
        v3 = fused_perm._descend_call(
            jnp.asarray(x).reshape(B * R, 128), jnp.asarray(ident),
            B, R, pro=None, interpret=True,
        )
        got = np.asarray(v3).reshape(B * 128 * R1, 128)
        expected = x.reshape(B, R, 128).transpose(0, 2, 1).reshape(
            B * 128 * R1, 128
        )
        np.testing.assert_array_equal(got, expected)
        # ascend with the identity shuffle inverts the relayout exactly
        back = fused_perm._ascend_call(
            v3.reshape(B * 128, R1, 128), jnp.asarray(ident),
            B, R, epi=None, interpret=True,
        )
        np.testing.assert_array_equal(
            np.asarray(back).reshape(-1), x
        )

    def test_full_engine_exact_with_cap_set(self, rng, interpret_kernels,
                                            monkeypatch):
        # end-to-end guard at from_coo scale (R1 <= 8 here, so this checks
        # the cap is a safe no-op on small plans + the base-block scaling)
        monkeypatch.setenv("PHOTON_FUSED_TILE_U", "64")
        n, d, nnz = 4096, 512, 24000
        rows, cols, vals, dense = _random_coo(rng, n, d, nnz)
        feats = from_coo(rows, cols, vals, (n, d), max_hot_cols=0,
                         plan_cache="")
        _check_against_dense(feats, dense, rng)

    def test_tile_rows_growth(self, monkeypatch):
        monkeypatch.setenv("PHOTON_FUSED_TILE_U", "64")
        assert fused_perm._tile_rows(8) == 8
        assert fused_perm._tile_rows(16) == 16
        assert fused_perm._tile_rows(128) == 64
        assert fused_perm._tile_rows(4) == 4  # below-8 plans keep u = R1
        monkeypatch.delenv("PHOTON_FUSED_TILE_U")
        assert fused_perm._tile_rows(128) == 8  # default unchanged

    def test_malformed_cap_falls_back(self, monkeypatch):
        monkeypatch.setenv("PHOTON_FUSED_TILE_U", "not-a-number")
        assert fused_perm._tile_cap() == 8
        monkeypatch.setenv("PHOTON_FUSED_TILE_U", "12")  # not a power of two
        assert fused_perm._tile_cap() == 8


class TestUnfusedFallback:
    """CPU default path (pallas unavailable): unfused XLA execution."""

    def test_matches_dense(self, rng):
        rows, cols, vals, dense = _random_coo(rng, n=64, d=40, nnz=500)
        feats = from_coo(rows, cols, vals, (64, 40), max_hot_cols=0)
        assert not feats._fused_ok() or fused_perm._INTERPRET is False
        _check_against_dense(feats, dense, rng)

    def test_hot_split(self, rng):
        rows, cols, vals, dense = _random_coo(rng, n=128, d=30, nnz=600)
        # every row touches column 0: a hot (intercept-like) column
        rows = np.concatenate([rows, np.arange(128)])
        cols = np.concatenate([cols, np.zeros(128, dtype=cols.dtype)])
        ones = np.ones(128, dtype=np.float32)
        vals = np.concatenate([vals, ones])
        np.add.at(dense, (np.arange(128), 0), ones)
        feats = from_coo(rows, cols, vals, (128, 30), hot_col_threshold=100)
        assert feats.hot_matrix is not None
        _check_against_dense(feats, dense, rng)

    def test_kp_above_128(self, rng):
        # one column with degree > 128 and the hot split disabled: KP = 512
        # (kp_cap=None + col_split=1 keep the big slot group this test
        # exercises; the auto layout would legitimately spill/split instead)
        n, d = 300, 12
        rows = np.arange(n)
        cols = np.full(n, 3)
        vals = rng.standard_normal(n).astype(np.float32)
        dense = np.zeros((n, d), dtype=np.float32)
        dense[rows, cols] = vals
        feats = from_coo(rows, cols, vals, (n, d), max_hot_cols=0,
                         kp_cap=None, col_split=1)
        assert feats.csc_k == 512
        _check_against_dense(feats, dense, rng)

    def test_kp_above_128_auto_layout_stays_exact(self, rng):
        # a heavy column under the default auto layout: it spills and/or
        # the columns split, and results stay exact. (Sized so that d*KP
        # clears MIN_FUSED_SIZE: below that floor a cap buys nothing and the
        # planner rightly keeps the flat layout.)
        from photon_ml_tpu.ops.sparse_perm import ColumnSplitFeatures

        n, d = 3000, 120
        rows = np.arange(n)
        cols = np.full(n, 3)
        vals = rng.standard_normal(n).astype(np.float32)
        dense = np.zeros((n, d), dtype=np.float32)
        dense[rows, cols] = vals
        feats = from_coo(rows, cols, vals, (n, d), max_hot_cols=0)
        assert (
            isinstance(feats, ColumnSplitFeatures)
            or feats.spill_rows is not None
        )
        _check_against_dense(feats, dense, rng)

    def test_empty(self):
        feats = from_coo([], [], [], (8, 8), max_hot_cols=0)
        z = np.asarray(feats.matvec(jnp.ones(8, jnp.float32)))
        np.testing.assert_allclose(z, np.zeros(8))

    def test_powers_of_two_groups(self, rng):
        rows, cols, vals, _ = _random_coo(rng, n=64, d=40, nnz=500)
        feats = from_coo(rows, cols, vals, (64, 40), max_hot_cols=0)
        assert feats.ell_k & (feats.ell_k - 1) == 0
        assert feats.csc_k & (feats.csc_k - 1) == 0


class TestFusedKernels:
    """Pallas kernels through the interpreter; sizes force >=1 recursion."""

    def test_single_level_all_maps(self, rng, interpret_kernels):
        # S >= 128^2 so the plan has exactly one descend/ascend level
        n, d = 1024, 600
        rows, cols, vals, dense = _random_coo(rng, n, d, 6000)
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128
        )
        assert len(parse_plan(feats.plan).descents) >= 1
        assert feats._fused_ok()
        _check_against_dense(feats, dense, rng)

    def test_single_level_hot_split(self, rng, interpret_kernels):
        n, d = 2048, 300
        rows, cols, vals, dense = _random_coo(rng, n, d, 8000)
        rows = np.concatenate([rows, np.arange(n)])
        cols = np.concatenate([cols, np.zeros(n, dtype=cols.dtype)])
        ones = np.ones(n, dtype=np.float32)
        vals = np.concatenate([vals, ones])
        np.add.at(dense, (np.arange(n), 0), ones)
        feats = from_coo(rows, cols, vals, (n, d), hot_col_threshold=n // 2)
        assert feats.hot_matrix is not None
        _check_against_dense(feats, dense, rng)

    @pytest.mark.parametrize("c", [2, 4, 8])
    def test_sublane_base_rows(self, rng, interpret_kernels, c):
        # S = c*128^2 makes the innermost base kernel's sublane stage use
        # rows=c (the vectorized per-lane row movement), not the rows=1
        # identity the other sizes hit
        n, d = 512, 300
        rows, cols, vals, dense = _random_coo(rng, n, d, 4000)
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0,
            size_floor=c * 128 * 128,
        )
        parsed = parse_plan(feats.plan)
        assert parsed.base[2] == c
        _check_against_dense(feats, dense, rng)

    def test_two_level_plan(self, rng, interpret_kernels):
        # size_floor pushes S to 128^3: two descents, sublane base, two ascents
        n, d = 512, 256
        rows, cols, vals, dense = _random_coo(rng, n, d, 3000)
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 ** 3
        )
        assert len(parse_plan(feats.plan).descents) == 2
        _check_against_dense(feats, dense, rng)

    def test_kp_above_128_fused(self, rng, interpret_kernels):
        n, d = 200, 64
        extra_rows = np.arange(n)
        extra_cols = np.full(n, 5)
        rows, cols, vals, dense = _random_coo(rng, n, d, 1500)
        ev = rng.standard_normal(n).astype(np.float32)
        np.add.at(dense, (extra_rows, extra_cols), ev)
        rows = np.concatenate([rows, extra_rows])
        cols = np.concatenate([cols, extra_cols])
        vals = np.concatenate([vals, ev])
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128
        )
        assert feats.csc_k >= 256
        _check_against_dense(feats, dense, rng)

    def test_k_above_128_fused(self, rng, interpret_kernels):
        # one row with >128 nnz and no hot split: K = 256 exercises the
        # group>LANES branches of MulBroadcast (rmatvec prologue) and
        # MulReduce (matvec epilogue)
        n, d = 64, 256
        rows, cols, vals, dense = _random_coo(rng, n, d, 800)
        extra_cols = rng.permutation(d)[:200]
        extra_rows = np.full(200, 7)
        ev = rng.standard_normal(200).astype(np.float32)
        np.add.at(dense, (extra_rows, extra_cols), ev)
        rows = np.concatenate([rows, extra_rows])
        cols = np.concatenate([cols, extra_cols])
        vals = np.concatenate([vals, ev])
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128
        )
        assert feats.ell_k >= 256
        _check_against_dense(feats, dense, rng)

    def test_fused_equals_unfused_execute(self, rng, interpret_kernels):
        n, d = 512, 512
        rows, cols, vals, _ = _random_coo(rng, n, d, 4000)
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128
        )
        S, K, KP = feats.size, feats.ell_k, feats.csc_k
        w = jnp.asarray(rng.standard_normal(S // KP).astype(np.float32))
        c = jnp.asarray(rng.standard_normal(S // K).astype(np.float32))
        for dplan, pro, epi in [
            (feats.plan_inv, Broadcast(w, KP), MulReduce(feats.ell_flat, K)),
            (feats.plan, MulBroadcast(feats.ell_flat, c, K), Reduce(KP)),
            (feats.plan, MulBroadcast(feats.ell_flat, c, K, transform="sq"), Reduce(KP)),
            (feats.plan, MulBroadcast(feats.ell_flat, c, K, transform="abs"), Reduce(KP)),
            (feats.plan, MulBroadcast(feats.ell_flat, c, K, transform="nnz"), Reduce(KP)),
        ]:
            got = np.asarray(fused_execute(dplan, pro, epi, interpret=True))
            want = np.asarray(unfused_execute(dplan, pro, epi))
            np.testing.assert_allclose(got, want, atol=1e-4)


class TestPropertyBased:
    def test_random_problem_shapes(self, interpret_kernels):
        """Property test across random sparsity patterns, shapes, paddings,
        and hot-split settings: the fused engine must match dense algebra
        for every (matvec, rmatvec, rmatvec_sq, row_norms_sq)."""
        hypothesis = pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=12, deadline=None)
        @given(
            n=st.integers(8, 600),
            d=st.integers(4, 500),
            nnz=st.integers(0, 3000),
            floor_pow=st.sampled_from([0, 128 * 128, 2 * 128 * 128]),
            hot=st.sampled_from([0, 64]),
            seed=st.integers(0, 2**31 - 1),
        )
        def check(n, d, nnz, floor_pow, hot, seed):
            rng = np.random.default_rng(seed)
            rows, cols, vals, dense = _random_coo(rng, n, d, nnz)
            feats = from_coo(
                rows, cols, vals, (n, d),
                max_hot_cols=hot, size_floor=floor_pow,
            )
            # high-degree draws accumulate hundreds of fp32 terms; rtol
            # covers ordering differences that scale with the sums
            _check_against_dense(feats, dense, rng, atol=5e-4, rtol=1e-4)

        check()


class TestSummaryStats:
    def test_matches_ell_engine(self, rng, interpret_kernels):
        from photon_ml_tpu.ops.data import LabeledData
        from photon_ml_tpu.ops.features import from_scipy_like
        from photon_ml_tpu.stat.summary import summarize

        n, d = 512, 300
        rows, cols, vals, dense = _random_coo(rng, n, d, 4000)
        # hot column so the hot-side min/max fold is exercised too
        rows = np.concatenate([rows, np.arange(n)])
        cols = np.concatenate([cols, np.zeros(n, dtype=cols.dtype)])
        ones = np.ones(n, dtype=np.float32)
        vals = np.concatenate([vals, ones])
        np.add.at(dense, (np.arange(n), 0), ones)
        weights = rng.random(n).astype(np.float32) + 0.5

        fused = from_coo(
            rows, cols, vals, (n, d), hot_col_threshold=n // 2,
            size_floor=128 * 128,
        )
        ell = from_scipy_like(rows, cols, vals, (n, d))
        y = jnp.zeros(n, jnp.float32)
        w = jnp.asarray(weights)
        s_f = summarize(LabeledData.create(fused, y, weights=w))
        s_e = summarize(LabeledData.create(ell, y, weights=w))
        for field in ("mean", "variance", "num_nonzeros", "max_abs",
                      "min_val", "max_val", "mean_abs"):
            np.testing.assert_allclose(
                np.asarray(getattr(s_f, field)),
                np.asarray(getattr(s_e, field)),
                rtol=1e-5, atol=1e-3, err_msg=field,
            )


class TestSlotGroupLimit:
    # which engine "auto" means, and that a failing kernel raises, are pinned
    # in the fast lane: tests/test_startup.py
    def test_fused_rejects_oversized_slot_groups(self):
        """A row/column with more than LANES*LANES nonzeros cannot tile the
        fused prologue/epilogue (the operand BlockSpec height LANES*u//q
        would silently hit zero); assemble must fail loudly, not lower to
        an obscure Mosaic error."""
        from photon_ml_tpu.ops import fused_perm as fp

        nnz = fp.MAX_FUSED_GROUP * 2  # one row, 2*16384 distinct columns
        rows = np.zeros(nnz, np.int64)
        cols = np.arange(nnz, dtype=np.int64)
        vals = np.ones(nnz, np.float32)
        with pytest.raises(fp.FusedGroupTooLarge, match="slot group K="):
            fp.from_coo(
                rows, cols, vals, (1, nnz), max_hot_cols=0, plan_cache=""
            )


class TestValidators:
    def test_validate_labeled_data_fused_engine(self, rng, interpret_kernels):
        from photon_ml_tpu.data.validators import (
            DataValidationError,
            validate_labeled_data,
        )
        from photon_ml_tpu.ops.data import LabeledData
        from photon_ml_tpu.types import TaskType

        n, d = 256, 128
        rows, cols, vals, _ = _random_coo(rng, n, d, 1500)
        # hot column so the concatenated hot side is validated too
        rows = np.concatenate([rows, np.arange(n)])
        cols = np.concatenate([cols, np.zeros(n, dtype=cols.dtype)])
        vals = np.concatenate([vals, np.ones(n, dtype=np.float32)])
        feats = from_coo(rows, cols, vals, (n, d), hot_col_threshold=n // 2)
        y = jnp.asarray((rng.random(n) > 0.5).astype(np.float32))
        validate_labeled_data(
            LabeledData.create(feats, y), TaskType.LOGISTIC_REGRESSION
        )  # clean data passes

        bad = np.array(vals)
        bad[7] = np.nan
        feats_bad = from_coo(rows, cols, bad, (n, d), hot_col_threshold=n // 2)
        with pytest.raises(DataValidationError):
            validate_labeled_data(
                LabeledData.create(feats_bad, y), TaskType.LOGISTIC_REGRESSION
            )


class TestGridFused:
    def test_grid_fused_matches_ell_grid(self, rng, interpret_kernels):
        import jax
        from photon_ml_tpu.parallel.grid_features import (
            grid_from_coo,
            grid_mesh,
            shard_vector_data,
            shard_vector_feat,
        )

        n, d = 256, 192
        rows, cols, vals, dense = _random_coo(rng, n, d, 2000)
        mesh = grid_mesh(2, 4)
        w = rng.standard_normal(d).astype(np.float32)
        c = rng.standard_normal(n).astype(np.float32)

        outs = {}
        for engine in ("ell", "fused"):
            gf = grid_from_coo(rows, cols, vals, (n, d), mesh, engine=engine)
            wp = np.zeros(gf.dim, np.float32)
            wp[:d] = w
            cp = np.zeros(gf.num_rows, np.float32)
            cp[:n] = c
            z = np.asarray(gf.matvec(shard_vector_feat(jnp.asarray(wp), mesh)))
            g = np.asarray(gf.rmatvec(shard_vector_data(jnp.asarray(cp), mesh)))
            outs[engine] = (z[:n], g[:d])

        np.testing.assert_allclose(outs["fused"][0], dense @ w, atol=1e-4)
        np.testing.assert_allclose(outs["fused"][1], dense.T @ c, atol=1e-4)
        np.testing.assert_allclose(outs["fused"][0], outs["ell"][0], atol=1e-4)
        np.testing.assert_allclose(outs["fused"][1], outs["ell"][1], atol=1e-4)


class TestEstimatorFused:
    def test_game_estimator_fused_engine(self, rng):
        from photon_ml_tpu.data.game_data import FeatureShard, GameData
        from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
        from photon_ml_tpu.estimators.game import (
            FixedEffectCoordinateConfiguration,
            GameEstimator,
            RandomEffectCoordinateConfiguration,
        )
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
        )
        from photon_ml_tpu.types import TaskType

        n, d, k = 400, 64, 4
        rows = np.repeat(np.arange(n), k)
        cols = rng.integers(0, d, n * k)
        vals = rng.standard_normal(n * k).astype(np.float32)
        dense = np.zeros((n, d), np.float32)
        np.add.at(dense, (rows, cols), vals)
        w_true = (rng.standard_normal(d) * 0.5).astype(np.float32)
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-dense @ w_true))).astype(
            np.float32
        )
        users = [f"u{i % 10}" for i in range(n)]
        data = GameData(
            labels=y,
            feature_shards={"g": FeatureShard(rows=rows, cols=cols, vals=vals, dim=d)},
            id_tags={"userId": users},
            offsets=np.zeros(n, np.float32),
            weights=np.ones(n, np.float32),
        )
        opt = GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig.lbfgs(max_iterations=25),
            regularization_weight=1.0,
        )

        fits = {}
        for engine in ("ell", "fused"):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinates={
                    "global": FixedEffectCoordinateConfiguration(
                        feature_shard="g", optimizer=opt, sparse_engine=engine
                    ),
                    "per-user": RandomEffectCoordinateConfiguration(
                        feature_shard="g",
                        data=RandomEffectDataConfiguration(
                            random_effect_type="userId"
                        ),
                        optimizer=opt,
                    ),
                },
                num_outer_iterations=1,
            )
            fits[engine] = est.fit(data)
        w_e = np.asarray(fits["ell"].model.models["global"].coefficients.means)
        w_f = np.asarray(fits["fused"].model.models["global"].coefficients.means)
        np.testing.assert_allclose(w_f, w_e, atol=5e-3)


class TestInSolver:
    """The fused engine as a drop-in FeatureMatrix in an actual GLM solve."""

    def test_lbfgs_matches_ell(self, rng, interpret_kernels):
        from photon_ml_tpu.losses.objective import make_glm_objective
        from photon_ml_tpu.losses.pointwise import LogisticLoss
        from photon_ml_tpu.ops.data import LabeledData
        from photon_ml_tpu.ops.features import from_scipy_like
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
        )
        from photon_ml_tpu.opt.solve import solve

        n, d = 512, 200
        rows, cols, vals, dense = _random_coo(rng, n, d, 4000)
        w_true = rng.standard_normal(d).astype(np.float32) * 0.5
        z = dense @ w_true
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)

        objective = make_glm_objective(LogisticLoss)
        cfg = GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig.lbfgs(max_iterations=30),
            regularization_weight=1.0,
        )
        l2 = jnp.float32(1.0)

        ell = from_scipy_like(rows, cols, vals, (n, d))
        res_ell = solve(
            objective, jnp.zeros(d, jnp.float32),
            LabeledData.create(ell, jnp.asarray(y)), cfg, l2_weight=l2,
        )
        fused = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128
        )
        res_fused = solve(
            objective, jnp.zeros(d, jnp.float32),
            LabeledData.create(fused, jnp.asarray(y)), cfg, l2_weight=l2,
        )
        np.testing.assert_allclose(
            np.asarray(res_fused.w), np.asarray(res_ell.w), atol=5e-3
        )

    @pytest.mark.parametrize("optimizer", ["tron", "owlqn"])
    def test_tron_owlqn_match_ell(self, rng, interpret_kernels, optimizer):
        """TRON drives Hessian-vector products (matvec + rmatvec on the
        direction) and OWL-QN the L1 pseudo-gradient through the fused maps."""
        from photon_ml_tpu.losses.objective import make_glm_objective
        from photon_ml_tpu.losses.pointwise import LogisticLoss
        from photon_ml_tpu.ops.data import LabeledData
        from photon_ml_tpu.ops.features import from_scipy_like
        from photon_ml_tpu.opt.config import (
            GlmOptimizationConfiguration,
            OptimizerConfig,
        )
        from photon_ml_tpu.opt.solve import solve

        n, d = 512, 160
        rows, cols, vals, dense = _random_coo(rng, n, d, 3500)
        w_true = rng.standard_normal(d).astype(np.float32) * 0.5
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-(dense @ w_true)))).astype(
            np.float32
        )
        if optimizer == "tron":
            cfg = GlmOptimizationConfiguration(
                optimizer_config=OptimizerConfig.tron(max_iterations=12),
                regularization_weight=1.0,
            )
            l1 = 0.0
        else:
            cfg = GlmOptimizationConfiguration(
                optimizer_config=OptimizerConfig.lbfgs(max_iterations=30),
                regularization_weight=1.0,
            )
            l1 = 0.5
        objective = make_glm_objective(LogisticLoss)
        l2 = jnp.float32(1.0)
        l1_arg = jnp.float32(l1) if l1 else None

        ell = from_scipy_like(rows, cols, vals, (n, d))
        res_ell = solve(
            objective, jnp.zeros(d, jnp.float32),
            LabeledData.create(ell, jnp.asarray(y)), cfg,
            l2_weight=l2, l1_weight=l1_arg,
        )
        fused = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128
        )
        res_fused = solve(
            objective, jnp.zeros(d, jnp.float32),
            LabeledData.create(fused, jnp.asarray(y)), cfg,
            l2_weight=l2, l1_weight=l1_arg,
        )
        np.testing.assert_allclose(
            np.asarray(res_fused.w), np.asarray(res_ell.w), atol=5e-3
        )
        if l1:
            # OWL-QN must produce an actually-sparse solution on both engines
            assert (np.abs(np.asarray(res_fused.w)) < 1e-8).any()


class TestBf16Payload:
    def test_bf16_kernels_interpret(self, rng, interpret_kernels):
        """The fused kernels' bf16 load/store + f32 in-VMEM shuffle paths,
        via the Pallas interpreter."""
        n, d = 1024, 600
        rows, cols, vals, dense = _random_coo(rng, n, d, 6000)
        feats = from_coo(
            rows, cols, vals, (n, d), max_hot_cols=0, size_floor=128 * 128,
            kp_cap=None, col_split=1, payload_dtype="bfloat16",
        )
        assert feats._fused_ok()
        w = rng.standard_normal(d).astype(np.float32)
        c = rng.standard_normal(n).astype(np.float32)
        z_ref, g_ref = dense @ w, dense.T @ c
        z = np.asarray(feats.matvec(jnp.asarray(w)))
        g = np.asarray(feats.rmatvec(jnp.asarray(c)))
        assert np.abs(z - z_ref).max() / (np.abs(z_ref).max() + 1e-6) < 2e-2
        assert np.abs(g - g_ref).max() / (np.abs(g_ref).max() + 1e-6) < 2e-2

    def test_bf16_payload_close_and_f32_exact(self, rng):
        """payload_dtype='bfloat16' halves the permuted intermediates: the
        maps stay within bf16 entry-rounding error (~2^-8 relative) while
        the default f32 path is untouched."""
        rows, cols, vals, dense = _random_coo(rng, n=256, d=512, nnz=4096)
        w = rng.standard_normal(512).astype(np.float32)
        c = rng.standard_normal(256).astype(np.float32)
        fb = from_coo(rows, cols, vals, (256, 512), max_hot_cols=0,
                      kp_cap=None, col_split=1, payload_dtype="bfloat16")
        z = np.asarray(fb.matvec(jnp.asarray(w)))
        g = np.asarray(fb.rmatvec(jnp.asarray(c)))
        z_ref, g_ref = dense @ w, dense.T @ c
        scale_z = np.abs(z_ref).max() + 1e-6
        scale_g = np.abs(g_ref).max() + 1e-6
        assert np.abs(z - z_ref).max() / scale_z < 2e-2
        assert np.abs(g - g_ref).max() / scale_g < 2e-2
        # f32 default still exact
        f32 = from_coo(rows, cols, vals, (256, 512), max_hot_cols=0,
                       kp_cap=None, col_split=1)
        np.testing.assert_allclose(
            np.asarray(f32.matvec(jnp.asarray(w))), z_ref, atol=2e-4
        )

    def test_bf16_payload_through_auto_layout(self, rng):
        """bf16 payload composes with the KP-cap/column-split planner."""
        from photon_ml_tpu.ops.sparse_perm import ColumnSplitFeatures

        n, d, k = 512, 8192, 8
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        cols = rng.integers(0, d, n * k).astype(np.int64)
        vals = rng.standard_normal(n * k).astype(np.float32)
        dense = np.zeros((n, d), np.float32)
        np.add.at(dense, (rows, cols), vals)
        f = from_coo(rows, cols, vals, (n, d), max_hot_cols=0,
                     payload_dtype="bfloat16")
        w = rng.standard_normal(d).astype(np.float32)
        z = np.asarray(f.matvec(jnp.asarray(w)))
        z_ref = dense @ w
        assert np.abs(z - z_ref).max() / (np.abs(z_ref).max() + 1e-6) < 2e-2
        if isinstance(f, ColumnSplitFeatures):
            for blk in f.blocks:
                assert getattr(blk, "payload_dtype", "float32") == "bfloat16"
