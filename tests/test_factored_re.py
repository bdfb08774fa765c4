"""Factored random-effect (MF) coordinate tests.

Mirrors reference FactoredRandomEffectCoordinateTest /
MatrixFactorizationModelTest: kron-feature linear maps against explicit
materialization, alternating training recovering low-rank per-entity
structure, and GameEstimator integration.
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # excluded from the fast lane (pyproject markers)

from photon_ml_tpu.algorithm.factored_random_effect import (
    FactoredRandomEffectCoordinate,
    KronFeatures,
    MFOptimizationConfiguration,
    _latent_dataset,
    build_kron_tiles,
)
from photon_ml_tpu.data.game_data import FeatureShard, GameData
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.estimators.game import (
    FactoredRandomEffectCoordinateConfiguration,
    FixedEffectCoordinateConfiguration,
    GameEstimator,
)
from photon_ml_tpu.models.matrix_factorization import MatrixFactorizationModel
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration
from photon_ml_tpu.types import TaskType


def _low_rank_data(n=800, d=20, entities=10, k_true=2, seed=0, noise=0.2):
    """Per-entity coefficients w_e = B v_e with a shared low-rank B."""
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((d, k_true)).astype(np.float32)
    V = rng.standard_normal((entities, k_true)).astype(np.float32)
    X = (rng.standard_normal((n, d)) * (rng.random((n, d)) < 0.5)).astype(np.float32)
    e_of = np.arange(n) % entities
    z = np.einsum("nd,nd->n", X, (B @ V.T).T[e_of])
    y = (z + noise * rng.standard_normal(n) > 0).astype(np.float32)
    rows, cols = np.nonzero(X)
    return X, rows, cols, X[rows, cols], y, e_of


def _dataset(seed=0, **kw):
    X, rows, cols, vals, y, e_of = _low_rank_data(seed=seed, **kw)
    ids = np.array([f"e{e}" for e in e_of])
    ds = build_random_effect_dataset(
        entity_ids=ids,
        feature_rows=rows,
        feature_cols=cols,
        feature_vals=vals,
        global_dim=X.shape[1],
        labels=y,
        config=RandomEffectDataConfiguration(random_effect_type="e"),
    )
    return ds, X, y, ids


class TestKronFeatures:
    def _explicit(self, ds, latents, d, k):
        """Materialize the [n, d*k] kron design matrix row-block by row-block."""
        mats = []
        for b, bucket in enumerate(ds.buckets):
            Xb = np.asarray(bucket.X)
            pidx = np.asarray(bucket.proj_indices)
            v = np.asarray(latents[b])
            E, S, D = Xb.shape
            out = np.zeros((E * S, d * k), dtype=np.float32)
            for e in range(E):
                xg = np.zeros((S, d), np.float32)
                for j in range(D):
                    xg[:, pidx[e, j]] += Xb[e, :, j]
                out[e * S : (e + 1) * S] = np.einsum(
                    "sd,k->sdk", xg, v[e]
                ).reshape(S, d * k)
            mats.append(out)
        return np.concatenate(mats)

    def test_linear_maps_match_explicit(self):
        ds, X, y, ids = _dataset(n=120, entities=4)
        d = X.shape[1]
        k = 3
        rng = np.random.default_rng(1)
        latents = [
            jnp.asarray(rng.standard_normal((b.num_entities, k)).astype(np.float32))
            for b in ds.buckets
        ]
        tiles = build_kron_tiles(
            [np.asarray(b.proj_indices) for b in ds.buckets],
            [np.asarray(b.proj_valid) for b in ds.buckets],
        )
        feats = KronFeatures.build([b.X for b in ds.buckets], latents, tiles, d, k)
        M = self._explicit(ds, latents, d, k)
        w = rng.standard_normal(d * k).astype(np.float32)
        c = rng.standard_normal(M.shape[0]).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(feats.matvec(jnp.asarray(w))), M @ w, rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(feats.rmatvec(jnp.asarray(c))), M.T @ c, rtol=2e-4, atol=2e-3
        )
        np.testing.assert_allclose(
            np.asarray(feats.rmatvec_sq(jnp.asarray(c))),
            (M * M).T @ c,
            rtol=2e-4,
            atol=2e-3,
        )
        np.testing.assert_allclose(
            np.asarray(feats.row_norms_sq()),
            np.sum(M * M, axis=1),
            rtol=2e-4,
            atol=2e-4,
        )


class TestFactoredCoordinate:
    def test_alternating_training_fits(self):
        ds, X, y, ids = _dataset()
        coord = FactoredRandomEffectCoordinate(
            dataset=ds,
            task=TaskType.LOGISTIC_REGRESSION,
            re_configuration=GlmOptimizationConfiguration(regularization_weight=0.1),
            matrix_configuration=GlmOptimizationConfiguration(regularization_weight=0.1),
            mf_configuration=MFOptimizationConfiguration(
                num_latent_factors=4, num_iterations=2
            ),
            base_offsets=np.zeros(len(y), np.float32),
        )
        model = coord.update_model(None, np.zeros(len(y), np.float32))
        scores = coord.score(model)
        acc = float(np.mean((scores > 0) == (y > 0.5)))
        assert acc > 0.85, acc
        # warm-started second update improves or holds
        model2 = coord.update_model(model, np.zeros(len(y), np.float32))
        acc2 = float(np.mean((coord.score(model2) > 0) == (y > 0.5)))
        assert acc2 > 0.85

    def test_random_projected_dataset_rejected(self):
        from photon_ml_tpu.projector import ProjectorType

        X, rows, cols, vals, y, e_of = _low_rank_data(n=60, entities=3)
        ids = np.array([f"e{e}" for e in e_of])
        ds = build_random_effect_dataset(
            entity_ids=ids, feature_rows=rows, feature_cols=cols,
            feature_vals=vals, global_dim=X.shape[1], labels=y,
            config=RandomEffectDataConfiguration(
                random_effect_type="e",
                projector=ProjectorType.RANDOM,
                projected_dim=4,
            ),
        )
        with pytest.raises(ValueError, match="INDEX_MAP or"):
            FactoredRandomEffectCoordinate(
                dataset=ds,
                task=TaskType.LOGISTIC_REGRESSION,
                re_configuration=GlmOptimizationConfiguration(),
                matrix_configuration=GlmOptimizationConfiguration(),
                mf_configuration=MFOptimizationConfiguration(num_latent_factors=2),
                base_offsets=np.zeros(len(y), np.float32),
            )

    def test_latent_dataset_projection(self):
        ds, X, y, ids = _dataset(n=60, entities=3)
        d = X.shape[1]
        B = jnp.asarray(
            np.random.default_rng(0).standard_normal((d, 2)).astype(np.float32)
        )
        lds = _latent_dataset(ds, B)
        b0, l0 = ds.buckets[0], lds.buckets[0]
        Bg = np.asarray(B)[np.asarray(b0.proj_indices)]
        expected = np.einsum("esd,edk->esk", np.asarray(b0.X), Bg)
        np.testing.assert_allclose(np.asarray(l0.X), expected, rtol=1e-4, atol=1e-5)

    def test_model_export(self):
        ds, X, y, ids = _dataset(n=200, entities=5)
        coord = FactoredRandomEffectCoordinate(
            dataset=ds,
            task=TaskType.LOGISTIC_REGRESSION,
            re_configuration=GlmOptimizationConfiguration(regularization_weight=1.0),
            matrix_configuration=GlmOptimizationConfiguration(regularization_weight=1.0),
            mf_configuration=MFOptimizationConfiguration(num_latent_factors=2),
            base_offsets=np.zeros(len(y), np.float32),
        )
        model = coord.update_model(None, np.zeros(len(y), np.float32))
        w = model.coefficients_for("e0")
        assert w is not None and len(w) == X.shape[1]
        assert model.coefficients_for("unseen") is None


class TestGameWithFactoredCoordinate:
    def test_fe_plus_factored_re(self):
        X, rows, cols, vals, y, e_of = _low_rank_data(n=600, entities=8, seed=3)
        ids = np.array([f"e{e}" for e in e_of])
        data = GameData(
            labels=y,
            feature_shards={
                "global": FeatureShard(
                    rows=rows, cols=cols, vals=vals, dim=X.shape[1]
                )
            },
            id_tags={"e": ids},
        )
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinates={
                "fixed": FixedEffectCoordinateConfiguration(
                    feature_shard="global",
                    optimizer=GlmOptimizationConfiguration(regularization_weight=1.0),
                ),
                "factored": FactoredRandomEffectCoordinateConfiguration(
                    feature_shard="global",
                    data=RandomEffectDataConfiguration(random_effect_type="e"),
                    mf=MFOptimizationConfiguration(num_latent_factors=3),
                    optimizer=GlmOptimizationConfiguration(regularization_weight=0.5),
                ),
            },
            num_outer_iterations=2,
        )
        fit = est.fit(data, validation_data=data)
        assert fit.validation_metric is not None
        assert fit.validation_metric > 0.85  # AUC on train-as-validation
        # scoring via GameModel covers the factored path
        scores = fit.model.score(data)
        assert scores.shape == (len(y),)


class TestMatrixFactorizationModel:
    def _model(self):
        return MatrixFactorizationModel(
            row_effect_type="user",
            col_effect_type="item",
            row_factors=np.array([[1.0, 2.0], [0.5, -1.0]], np.float32),
            col_factors=np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]], np.float32),
            row_index={"u0": 0, "u1": 1},
            col_index={"i0": 0, "i1": 1, "i2": 2},
        )

    def test_score(self):
        m = self._model()
        assert m.score("u0", "i0") == 1.0
        assert m.score("u0", "i2") == 3.0
        assert m.score("u9", "i0") == 0.0  # unseen -> 0

    def test_score_batch(self):
        m = self._model()
        out = m.score_batch(["u0", "u1", "zz"], ["i1", "i2", "i0"])
        np.testing.assert_allclose(out, [2.0, -0.5, 0.0])

    def test_latent_dim_mismatch_rejected(self):
        with pytest.raises(ValueError, match="latent dimension"):
            MatrixFactorizationModel(
                row_effect_type="u",
                col_effect_type="i",
                row_factors=np.zeros((1, 2), np.float32),
                col_factors=np.zeros((1, 3), np.float32),
                row_index={},
                col_index={},
            )
