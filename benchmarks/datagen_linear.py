"""Seeded linear-regression problems on the benchmark's shared column
pattern, as plain numpy on the host (``datagen.make_problem`` has no
generator for ``LINEAR_REGRESSION``).

The two seeds are ``datagen.py``'s. The column ids come from
``pattern_seed`` by the same first call ``datagen.make_problem`` makes, so a
configuration with the same ``n_rows``, ``nnz_per_row``, ``n_cols`` and
``pattern_seed`` has bit for bit the same matrix pattern as the other
configurations and is served by the routed plan they cached. ``--seed``
draws everything else: column ``j``'s scale ``sigma_j = 10^U(lo, hi)``, its
values ``sigma_j * N(0, 1)``, the true coefficients ``N(0, (scale /
sigma_j)^2)`` and the labels ``x . w_true + N(0, noise^2)``. Column scales
decades apart are what make a normalization factor show in a comparison.
"""

from __future__ import annotations

import numpy as np

from benchmarks.datagen import Problem, Rows


def make_problem(config: dict, seed: int) -> Problem:
    fe = config["fixed_effect"]
    if config["task"] != "LINEAR_REGRESSION":
        raise ValueError(f"datagen_linear draws LINEAR_REGRESSION, not {config['task']!r}")
    n, k, n_cols = int(config["n_rows"]), int(config["nnz_per_row"]), int(config["n_cols"])
    pattern = np.random.default_rng(int(config["pattern_seed"]))
    rng = np.random.default_rng(int(seed))

    cols = pattern.integers(0, n_cols, (n, k)).astype(np.int64)
    lo, hi = fe["column_scale_log10_range"]
    sigma = (10.0 ** rng.uniform(lo, hi, n_cols)).astype(np.float32)
    w_true = (rng.standard_normal(n_cols) * fe["true_model_scale"]).astype(np.float32) / sigma
    vals = rng.standard_normal((n, k)).astype(np.float32) * sigma[cols]
    noise = rng.standard_normal(n) * fe["noise_scale"]
    labels = (vals * w_true[cols]).sum(-1) + noise
    return Problem(
        n_cols=n_cols,
        train=Rows(cols, vals, labels.astype(np.float32), {}, {}),
        held_out=None,
        entity_counts={},
    )
