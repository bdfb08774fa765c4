"""Seeded ratings problems for the full GAME model (fixed effect + per-user
and per-item random effects + a user x item factorization) on the
benchmark's shared pattern, as plain numpy on the host
(``datagen.make_problem`` has no generator for ``LINEAR_REGRESSION`` and none
with a factored term).

The two seeds are ``datagen.py``'s, and the pattern's draws are made by the
same calls in the same order: the fixed-effect column ids first, then every
random effect's entity of each row, for the training rows and then for the
held-out rows. So a configuration with ``glmix-1b-chip``'s ``n_rows``,
``nnz_per_row``, ``n_cols``, ``pattern_seed`` and random effects has bit for
bit its matrix pattern (and is served by the routed plan it cached) and its
users and items. ``--seed`` draws everything else: the true fixed and
random-effect models, the true user and item factors ``N(0, 1/k)`` in
``latent_factors`` dimensions, the feature values and the labels

    y = x.w + xu.theta_user + xi.theta_item + U[user].V[item] + N(0, noise^2).

The factored coordinate's feature shard is not stored: it is one nonzero of
value 1 a row at the row's item id (``Rows.entities[<items>]``), which the
driver and the reference each build from the ids.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.datagen import Problem, Rows, _zipf


@dataclasses.dataclass
class RatingsProblem(Problem):
    """``datagen.Problem`` and the seed it was drawn from: the factored
    coordinate's projection matrix starts from a draw of that seed, in the
    program (``mf.seed``) and in the reference alike."""

    seed: int = 0


def make_problem(config: dict, seed: int) -> RatingsProblem:
    if config["task"] != "LINEAR_REGRESSION":
        raise ValueError(f"datagen_ratings draws LINEAR_REGRESSION, not {config['task']!r}")
    fe, res, mf = config["fixed_effect"], config["random_effects"], config["user_item_mf"]
    n, k, n_cols = int(config["n_rows"]), int(config["nnz_per_row"]), int(config["n_cols"])
    n_held = int(config["held_out_rows"])
    pattern = np.random.default_rng(int(config["pattern_seed"]))
    rng = np.random.default_rng(int(seed))

    w_true = (rng.standard_normal(n_cols) * fe["true_model_scale"]).astype(np.float32)
    true_re, popularity = {}, {}
    for name, re in res.items():
        true_re[name] = (
            rng.standard_normal((re["n_entities"], re["dim"])) * re["true_model_scale"]
        ).astype(np.float32)
        if re["popularity"] == "zipf":
            popularity[name] = _zipf(re["n_entities"], re["zipf_exponent"])
        elif re["popularity"] != "uniform":
            raise ValueError(f"unknown popularity {re['popularity']!r}")
    users, items = mf["entities"], mf["items"]
    factors = int(mf["true_latent_factors"])
    scale = 1.0 / np.sqrt(factors)
    user_factors = (rng.standard_normal((res[users]["n_entities"], factors)) * scale).astype(np.float32)
    item_factors = (rng.standard_normal((res[items]["n_entities"], factors)) * scale).astype(np.float32)

    def draw(rows: int) -> Rows:
        cols = pattern.integers(0, n_cols, (rows, k)).astype(np.int64)
        vals = rng.standard_normal((rows, k)).astype(np.float32)
        z = (vals * w_true[cols]).sum(-1)
        entities, entity_x = {}, {}
        for name, re in res.items():
            if name in popularity:
                ids = pattern.choice(re["n_entities"], rows, p=popularity[name])
            else:
                ids = pattern.integers(0, re["n_entities"], rows)
            x = rng.standard_normal((rows, re["dim"])).astype(np.float32)
            z = z + (x * true_re[name][ids]).sum(-1)
            entities[name], entity_x[name] = ids.astype(np.int64), x
        z = z + (user_factors[entities[users]] * item_factors[entities[items]]).sum(-1)
        labels = z + rng.standard_normal(rows) * float(config["noise_scale"])
        return Rows(cols, vals, labels.astype(np.float32), entities, entity_x)

    train = draw(n)
    held = draw(n_held)
    return RatingsProblem(
        n_cols=n_cols, train=train, held_out=held,
        entity_counts={name: re["n_entities"] for name, re in res.items()},
        seed=int(seed),
    )
