"""Seeded problems, as plain numpy on the host.

Two seeds. ``pattern_seed`` (from the configuration file) draws the data
set's pattern: the column ids of the fixed-effect matrix and the entity of
every row. ``--seed`` draws everything else: the true model, the values, the
random-effect features and the labels, of training and held-out rows alike.
The routed plan is a function of the column pattern alone and costs minutes
of host time per new pattern, and the random-effect bucket shapes (so the
programs compiled for them) are a function of the entity assignment alone.
With the pattern fixed the plan cache and the compile cache in the checkout
serve every run and every seed after a cell's first, as a retrain on a stable
feature space and population is served; and every seed gives the same sizes,
so no seed changes the work.

The arrays here are handed both to the program (wrapped in its ``GameData``
by the traffic driver) and to the plain reference, which takes nothing else.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass
class Rows:
    """One set of rows: a sparse fixed-effect shard of ``k`` nonzeros a row
    plus, for GLMix, dense per-entity features and entity ids."""

    cols: np.ndarray                 # [n, k] int64 column ids
    vals: np.ndarray                 # [n, k] float32
    labels: np.ndarray               # [n] float32
    entities: Dict[str, np.ndarray]  # coordinate name -> [n] int64 entity id
    entity_x: Dict[str, np.ndarray]  # coordinate name -> [n, dim] float32

    @property
    def n(self) -> int:
        return self.cols.shape[0]


    def first_half(self) -> "Rows":
        """The first half of the rows: what the ``half_batch`` stand-in is
        given where the reference keeps all of them."""
        h = self.n // 2
        return Rows(
            self.cols[:h], self.vals[:h], self.labels[:h],
            {k: v[:h] for k, v in self.entities.items()},
            {k: v[:h] for k, v in self.entity_x.items()},
        )


@dataclasses.dataclass
class Problem:
    n_cols: int
    train: Rows
    held_out: Optional[Rows]
    entity_counts: Dict[str, int]


def _zipf(n_items: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n_items + 1) ** exponent
    return p / p.sum()


def make_problem(config: dict, seed: int) -> Problem:
    """The configuration's data set for ``seed``. ``config`` is the parsed
    configuration file (see configs/)."""
    fe = config["fixed_effect"]
    n, k, n_cols = int(config["n_rows"]), int(config["nnz_per_row"]), int(config["n_cols"])
    n_held = int(config.get("held_out_rows", 0))
    pattern = np.random.default_rng(int(config["pattern_seed"]))
    rng = np.random.default_rng(int(seed))
    task = config["task"]

    w_true = (rng.standard_normal(n_cols) * fe["true_model_scale"]).astype(np.float32)
    res = config.get("random_effects", {})
    true_re, popularity = {}, {}
    for name, re in res.items():
        true_re[name] = (
            rng.standard_normal((re["n_entities"], re["dim"])) * re["true_model_scale"]
        ).astype(np.float32)
        if re["popularity"] == "zipf":
            popularity[name] = _zipf(re["n_entities"], re["zipf_exponent"])
        elif re["popularity"] != "uniform":
            raise ValueError(f"unknown popularity {re['popularity']!r}")

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
        if task == "LOGISTIC_REGRESSION":
            labels = rng.random(rows) < 1.0 / (1.0 + np.exp(-z))
        elif task == "POISSON_REGRESSION":
            labels = rng.poisson(np.exp(z))
        else:
            raise ValueError(f"no generator for task {task!r}")
        return Rows(cols, vals, labels.astype(np.float32), entities, entity_x)

    train = draw(n)
    held = draw(n_held) if n_held else None
    return Problem(
        n_cols=n_cols,
        train=train,
        held_out=held,
        entity_counts={name: re["n_entities"] for name, re in res.items()},
    )
