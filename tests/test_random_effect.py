"""Random-effect engine tests (reference RandomEffectCoordinateTest /
RandomEffectDataSetTest / LocalDataSetTest analogs): grouping/projection
correctness, vmap'd solves vs per-entity direct solves, caps, feature
selection, passive data, scoring alignment."""

import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # excluded from the fast lane (pyproject markers)

from photon_ml_tpu.data import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.estimators.random_effect import (
    score_random_effects,
    train_random_effects,
)
from photon_ml_tpu.losses import SquaredLoss, make_glm_objective
from photon_ml_tpu.ops import DenseFeatures, LabeledData
from photon_ml_tpu.opt import GlmOptimizationConfiguration, RegularizationContext
from photon_ml_tpu.types import RegularizationType, TaskType

L2CFG = GlmOptimizationConfiguration(
    regularization=RegularizationContext(RegularizationType.L2),
    regularization_weight=0.1,
)


def _make_re_problem(rng, n_entities=12, samples_per_entity=(5, 40), global_dim=50):
    """Synthetic per-entity linear models over a sparse global feature space."""
    rows, cols, vals = [], [], []
    entity_ids, labels = [], []
    w_true = {}
    r = 0
    for e in range(n_entities):
        eid = f"user{e:03d}"
        n_e = int(rng.integers(*samples_per_entity))
        # each entity observes a small random slice of the global space
        feats = np.sort(rng.choice(global_dim, size=int(rng.integers(3, 8)), replace=False))
        w_e = rng.normal(size=len(feats)).astype(np.float32)
        w_true[eid] = dict(zip(feats.tolist(), w_e.tolist()))
        for _ in range(n_e):
            x = rng.normal(size=len(feats)).astype(np.float32)
            y = float(x @ w_e)
            for c, v in zip(feats, x):
                rows.append(r)
                cols.append(c)
                vals.append(v)
            entity_ids.append(eid)
            labels.append(y)
            r += 1
    return entity_ids, np.array(rows), np.array(cols), np.array(vals), np.array(labels), w_true


def test_grouping_and_projection_roundtrip(rng):
    ids, rows, cols, vals, labels, _ = _make_re_problem(rng)
    cfg = RandomEffectDataConfiguration(random_effect_type="userId", num_buckets=3)
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)
    assert ds.num_entities == 12
    # every sample lands exactly once (weights > 0 once across buckets)
    seen = np.zeros(len(ids), dtype=int)
    for b in ds.buckets:
        wt = np.asarray(b.weights)
        pos = np.asarray(b.sample_pos)
        seen[pos[wt > 0]] += 1
    np.testing.assert_array_equal(seen, 1)
    # local features reproduce the original rows
    X_orig = np.zeros((len(ids), 50), dtype=np.float32)
    X_orig[rows, cols] = vals
    for b in ds.buckets:
        X = np.asarray(b.X)
        pidx = np.asarray(b.proj_indices)
        wt = np.asarray(b.weights)
        pos = np.asarray(b.sample_pos)
        for e in range(b.num_entities):
            for s in range(b.max_samples):
                if wt[e, s] > 0:
                    x_glob = np.zeros(50, dtype=np.float32)
                    np.add.at(x_glob, pidx[e], X[e, s])
                    np.testing.assert_allclose(x_glob, X_orig[pos[e, s]], rtol=1e-6)


def test_vmap_solves_match_per_entity_training(rng):
    """The batched RE solve must match training each entity separately with
    the plain FE trainer on its local data."""
    ids, rows, cols, vals, labels, w_true = _make_re_problem(rng, n_entities=8)
    cfg = RandomEffectDataConfiguration(random_effect_type="userId", num_buckets=2)
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)
    model, results = train_random_effects(ds, TaskType.LINEAR_REGRESSION, L2CFG)

    for b, bucket in enumerate(ds.buckets):
        for e in range(bucket.num_entities):
            wt = np.asarray(bucket.weights[e])
            m = wt > 0
            data_e = LabeledData.create(
                DenseFeatures(matrix=bucket.X[e][m]),
                bucket.labels[e][m],
            )
            fit = train_glm(data_e, TaskType.LINEAR_REGRESSION, L2CFG)[0]
            np.testing.assert_allclose(
                model.coefficients[b][e][: fit.model.dim],
                fit.model.coefficients.means,
                rtol=2e-2,
                atol=2e-3,
            )


def test_recovers_per_entity_truth_and_export(rng):
    ids, rows, cols, vals, labels, w_true = _make_re_problem(
        rng, n_entities=10, samples_per_entity=(30, 60)
    )
    cfg = RandomEffectDataConfiguration(random_effect_type="userId", num_buckets=2)
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)
    tiny = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1e-4,
    )
    model, _ = train_random_effects(ds, TaskType.LINEAR_REGRESSION, tiny)
    for eid, truth in w_true.items():
        got = model.coefficients_for(eid)
        assert got is not None
        for feat, val in truth.items():
            assert abs(got[feat] - val) < 0.05, (eid, feat, got[feat], val)


def test_active_cap_and_passive_scoring(rng):
    ids, rows, cols, vals, labels, _ = _make_re_problem(
        rng, n_entities=6, samples_per_entity=(20, 30)
    )
    cfg = RandomEffectDataConfiguration(
        random_effect_type="userId", active_data_upper_bound=10, num_buckets=1, seed=1
    )
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)
    b = ds.buckets[0]
    assert b.max_samples == 10
    # passive rows exist and cover the overflow
    n_active = int((np.asarray(b.weights) > 0).sum())
    p = ds.passive[0]
    assert p is not None
    assert n_active + p.X.shape[0] == len(ids)

    model, _ = train_random_effects(ds, TaskType.LINEAR_REGRESSION, L2CFG)
    scores = score_random_effects(model, ds)
    assert scores.shape == (len(ids),)
    # passive scores = dot of projected features with entity coefficients
    X_orig = np.zeros((len(ids), 50), dtype=np.float32)
    X_orig[rows, cols] = vals
    ppos = np.asarray(p.sample_pos)
    for k in range(min(5, len(ppos))):
        r = ppos[k]
        eid = ids[r]
        w_map = model.coefficients_for(eid)
        expected = sum(X_orig[r, f] * w for f, w in w_map.items())
        np.testing.assert_allclose(scores[r], expected, rtol=1e-4, atol=1e-5)


def test_feature_selection_caps_local_dim(rng):
    ids, rows, cols, vals, labels, _ = _make_re_problem(rng, n_entities=6)
    cfg = RandomEffectDataConfiguration(
        random_effect_type="userId", max_local_features=3, num_buckets=1
    )
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)
    assert ds.buckets[0].local_dim <= 3
    # selected features should be informative: model still correlates with y
    model, _ = train_random_effects(ds, TaskType.LINEAR_REGRESSION, L2CFG)
    scores = score_random_effects(model, ds)
    corr = np.corrcoef(scores, labels)[0, 1]
    assert corr > 0.5, corr


def test_update_offsets_residual_trick(rng):
    ids, rows, cols, vals, labels, _ = _make_re_problem(rng, n_entities=4)
    cfg = RandomEffectDataConfiguration(random_effect_type="userId", num_buckets=1)
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)
    residual = rng.normal(size=len(ids)).astype(np.float32)
    ds2 = ds.update_offsets(residual)
    b = ds2.buckets[0]
    wt = np.asarray(b.weights)
    pos = np.asarray(b.sample_pos)
    off = np.asarray(b.offsets)
    m = wt > 0
    np.testing.assert_allclose(off[m], residual[pos[m]], rtol=1e-6)
    # padding rows keep offset 0
    assert np.all(off[~m] == 0.0)


def test_adaptive_driver_matches_oneshot_across_buckets(rng):
    """End-to-end over multiple size buckets: the convergence-adaptive driver
    (chunked rounds over tiles of live lanes, on by default) and the forced one-shot
    lockstep path must produce the same exported per-entity rows."""
    import dataclasses

    from photon_ml_tpu.opt import AdaptiveSolveConfig

    ids, rows, cols, vals, labels, _ = _make_re_problem(rng, n_entities=24)
    cfg = RandomEffectDataConfiguration(random_effect_type="userId", num_buckets=3)
    ds = build_random_effect_dataset(ids, rows, cols, vals, 50, labels, cfg)

    cfg_ad = dataclasses.replace(
        L2CFG, adaptive=AdaptiveSolveConfig(enabled=True, chunk_iters=4, min_lanes=2)
    )
    cfg_os = dataclasses.replace(L2CFG, adaptive=AdaptiveSolveConfig(enabled=False))
    stats = []
    m_ad, _ = train_random_effects(
        ds, TaskType.LINEAR_REGRESSION, cfg_ad, stats_out=stats
    )
    m_os, _ = train_random_effects(ds, TaskType.LINEAR_REGRESSION, cfg_os)

    rows_ad = {str(e): c for e, c in m_ad.items()}
    rows_os = {str(e): c for e, c in m_os.items()}
    assert set(rows_ad) == set(rows_os)
    for eid in rows_ad:
        for k in set(rows_ad[eid]) | set(rows_os[eid]):
            assert abs(rows_ad[eid].get(k, 0.0) - rows_os[eid].get(k, 0.0)) <= 1e-5
    # one SolverStats per bucket, each fully converged
    assert len(stats) == len(ds.buckets)
    assert all(s.converged == s.num_entities for s in stats)
