"""The random-effect build's two ways to each of its host steps give the
same dataset, to the bit: keys found through a table of flags and indices
(short key spans) or by sorting and binary search (long ones), and feature
rows taken as given (row-major input) or sorted first."""

import numpy as np
import pytest

from photon_ml_tpu.data import random_effect
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    _pack_random_effect_dataset,
)
from photon_ml_tpu.projector import ProjectorType

BUCKET_FIELDS = ("X", "labels", "offsets", "weights", "sample_pos", "proj_indices", "proj_valid")
PASSIVE_FIELDS = ("X", "entity_index", "sample_pos")


def _problem(seed, n=1500, n_entities=120, global_dim=10, per_row=6, shuffled=False,
             repeats=True):
    rng = np.random.default_rng(seed)
    ids = np.array([f"m{i}" for i in rng.zipf(1.4, n) % n_entities])
    counts = rng.integers(0, per_row + 1, n)
    rows = np.repeat(np.arange(n), counts)
    cols = rng.integers(0, global_dim, rows.size)
    if repeats:
        cols[::9] = cols[::9] % 2  # repeated (row, column) pairs: the last one wins
    else:
        cols = (np.arange(rows.size) - np.repeat(np.cumsum(counts) - counts, counts)) * 3 % 7
    vals = rng.standard_normal(rows.size).astype(np.float32)
    labels = (rng.random(n) < 0.5).astype(np.float32)
    offsets = rng.standard_normal(n).astype(np.float32)
    weights = (rng.random(n) + 0.5).astype(np.float32)
    if shuffled:
        p = rng.permutation(rows.size)
        rows, cols, vals = rows[p], cols[p], vals[p]
    return ids, rows, cols, vals, global_dim, labels, offsets, weights


def _pack(problem, config):
    ids, rows, cols, vals, dim, labels, offsets, weights = problem
    return _pack_random_effect_dataset(ids, rows, cols, vals, dim, labels, config, offsets, weights)


def _assert_same(a, b):
    assert len(a.buckets) == len(b.buckets)
    for x, y in zip(a.buckets, b.buckets):
        for f in BUCKET_FIELDS:
            u, v = getattr(x, f), getattr(y, f)
            assert u.dtype == v.dtype and u.shape == v.shape and np.array_equal(u, v), f
    for x, y in zip(a.passive, b.passive):
        assert (x is None) == (y is None)
        if x is not None:
            for f in PASSIVE_FIELDS:
                assert np.array_equal(getattr(x, f), getattr(y, f)), f
    assert a.entity_ids == b.entity_ids and a.entity_to_loc == b.entity_to_loc
    assert np.array_equal(a.row_gather, b.row_gather)


CONFIGS = {
    "index_map": dict(num_buckets=4),
    "cap_and_passive": dict(num_buckets=3, active_data_upper_bound=5, passive_data_lower_bound=2),
    "feature_ratio": dict(num_buckets=4, features_to_samples_ratio=0.5),
    "max_local_features": dict(num_buckets=2, max_local_features=3, active_data_upper_bound=8),
    "identity": dict(num_buckets=4, projector=ProjectorType.IDENTITY),
    "random_projection": dict(num_buckets=3, projector=ProjectorType.RANDOM, projected_dim=5,
                              active_data_upper_bound=6),
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_key_tables_match_sorting(name, monkeypatch):
    config = RandomEffectDataConfiguration("memberId", seed=3, **CONFIGS[name])
    problem = _problem(11)
    dense_span, tables = random_effect._dense_span, []

    def noted(keys, span, count):
        tables.append(dense_span(keys, span, count))
        return tables[-1]

    monkeypatch.setattr(random_effect, "_dense_span", noted)
    by_table = _pack(problem, config)
    # an index-map build finds its keys through the tables here
    assert all(tables) and len(tables) == (0 if "projector" in CONFIGS[name] else 2)
    monkeypatch.setattr(random_effect, "_dense_span", lambda keys, span, count: False)
    _assert_same(by_table, _pack(problem, config))


@pytest.mark.parametrize("name", ["index_map", "cap_and_passive", "identity"])
def test_row_major_input_matches_shuffled_input(name):
    """Without repeated (row, column) pairs the order of the nonzeros
    within a row is not seen."""
    config = RandomEffectDataConfiguration("memberId", seed=5, **CONFIGS[name])
    _assert_same(_pack(_problem(7, repeats=False), config),
                 _pack(_problem(7, shuffled=True, repeats=False), config))


@pytest.mark.parametrize("widths", ["one_width", "mixed"])
def test_expand_nnz_matches_a_loop(widths):
    """Every nonzero of the chosen rows, in row order: (the row's place in
    the choice, its flat index), against a loop over the rows."""
    rng = np.random.default_rng(2)
    n = 300
    counts = np.full(n, 4) if widths == "one_width" else rng.integers(0, 7, n)
    row_end = np.cumsum(counts)
    row_start = row_end - counts
    chosen = rng.permutation(n)[:200]
    rep, flat = random_effect._expand_nnz(chosen, row_start, row_end)
    want = [(k, i) for k, r in enumerate(chosen) for i in range(row_start[r], row_end[r])]
    assert rep.tolist() == [k for k, _ in want] and flat.tolist() == [i for _, i in want]
