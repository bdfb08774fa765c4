"""A GLMix small enough for a unit test: a sparse fixed effect and one
per-user random effect (18 entities, over the adaptive driver's
``min_lanes`` of 8). Shared by the tests that go through ``GameEstimator``."""

import numpy as np


def _tiny_glmix(seed=0, n_users=18, rows_per_user=12, d_fe=6, d_re=3):
    from photon_ml_tpu.data.game_data import FeatureShard, GameData

    rng = np.random.default_rng(seed)
    n = n_users * rows_per_user
    Xg = rng.normal(size=(n, d_fe)).astype(np.float32)
    Xu = rng.normal(size=(n, d_re)).astype(np.float32)
    users = np.repeat([f"u{i:03d}" for i in range(n_users)], rows_per_user)
    z = Xg @ rng.normal(size=d_fe) + (Xu * rng.normal(size=(n_users, d_re))[
        np.repeat(np.arange(n_users), rows_per_user)]).sum(-1)
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)

    def coo(X):
        rows, cols = np.nonzero(X)
        return FeatureShard(rows=rows, cols=cols, vals=X[rows, cols], dim=X.shape[1])

    return GameData(
        labels=y,
        feature_shards={"global": coo(Xg), "per_user": coo(Xu)},
        id_tags={"userId": users},
    )


def _tiny_glmix_estimator(num_outer_iterations=1):
    from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_ml_tpu.estimators.game import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_ml_tpu.types import TaskType

    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={
            "fixed": FixedEffectCoordinateConfiguration("global"),
            # 18 entities, over the adaptive driver's min_lanes of 8: rounds
            "per_user": RandomEffectCoordinateConfiguration(
                feature_shard="per_user",
                data=RandomEffectDataConfiguration(random_effect_type="userId"),
            ),
        },
        num_outer_iterations=num_outer_iterations,
    )
