"""Seeded problems for a per-member random effect at a deployment's entity
count: every member has at least one row, and the rows a member has follow
a discrete power law (``configs/glmix-members-1m.json``).

``datagen.make_problem`` draws a row's entity by ``pattern.choice``, which
gives no entity a row for certain; this generator lays the members out
first and the rows after. The two seeds split the work as ``datagen.py``
says: ``pattern_seed`` draws the pattern (the rows a member has, the order
of the rows, the item of every row, the fixed-effect column ids), ``--seed``
everything else (the true model, the values, the features, the labels).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.datagen import Problem, Rows, _zipf


def power_law(low: int, high: int, exponent: float) -> np.ndarray:
    """P(k) for k in [low, high], proportional to k ** -exponent."""
    k = np.arange(low, high + 1, dtype=np.float64)
    p = k ** -float(exponent)
    return p / p.sum()


def member_rows(re: dict, n_rows: int, pattern: np.random.Generator) -> np.ndarray:
    """Rows a member has, one draw from the power law a member, trimmed to
    ``n_rows`` in all: the draw's excess is taken a row at a time from
    members drawn in proportion to their rows, never a member's last row."""
    low, high = re["rows_per_member"]
    n = int(re["n_entities"])
    counts = low + pattern.choice(high - low + 1, n, p=power_law(low, high, re["rows_exponent"]))
    excess = int(counts.sum()) - n_rows
    if excess < 0:
        raise ValueError(
            f"pattern_seed draws {counts.sum()} rows for {n} members, under n_rows "
            f"{n_rows}: the trim cannot add rows"
        )
    owner = np.repeat(np.arange(n), counts)
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    spare = np.ones(owner.size, bool)
    spare[first] = False
    taken = pattern.choice(np.flatnonzero(spare), excess, replace=False)
    return counts - np.bincount(owner[taken], minlength=n)


def make_problem(config: dict, seed: int) -> Problem:
    """The configuration's data set for ``seed``: the fixed effect as
    ``datagen.make_problem`` draws it, the per-member coordinate laid out by
    ``member_rows``, the other random effects' entities Zipf or uniform."""
    fe = config["fixed_effect"]
    n, k, n_cols = int(config["n_rows"]), int(config["nnz_per_row"]), int(config["n_cols"])
    n_held = int(config.get("held_out_rows", 0))
    pattern = np.random.default_rng(int(config["pattern_seed"]))
    rng = np.random.default_rng(int(seed))
    if config["task"] != "LOGISTIC_REGRESSION":
        raise ValueError("the member generator is written for the logistic task")

    res: Dict[str, dict] = config["random_effects"]
    members = [name for name, re in res.items() if re["popularity"] == "power_law"]
    if len(members) != 1:
        raise ValueError(f"one power-law coordinate expected, got {members}")
    (member,) = members
    counts = member_rows(res[member], n, pattern)
    train_ids = {member: pattern.permutation(np.repeat(np.arange(counts.size), counts))}
    held_ids = {member: train_ids[member][pattern.integers(0, n, n_held)]}
    for name, re in res.items():
        if name == member:
            continue
        if re["popularity"] == "zipf":
            p = _zipf(re["n_entities"], re["zipf_exponent"])
            train_ids[name] = pattern.choice(re["n_entities"], n, p=p)
            held_ids[name] = pattern.choice(re["n_entities"], n_held, p=p)
        elif re["popularity"] == "uniform":
            train_ids[name] = pattern.integers(0, re["n_entities"], n)
            held_ids[name] = pattern.integers(0, re["n_entities"], n_held)
        else:
            raise ValueError(f"unknown popularity {re['popularity']!r}")
    cols = {"train": pattern.integers(0, n_cols, (n, k)),
            "held": pattern.integers(0, n_cols, (n_held, k))}

    w_true = (rng.standard_normal(n_cols) * fe["true_model_scale"]).astype(np.float32)
    true_re = {
        name: (rng.standard_normal((re["n_entities"], re["dim"])) * re["true_model_scale"]
               ).astype(np.float32)
        for name, re in res.items()
    }

    def draw(col_ids: np.ndarray, ids: Dict[str, np.ndarray]) -> Rows:
        rows = col_ids.shape[0]
        vals = rng.standard_normal((rows, k)).astype(np.float32)
        z = (vals * w_true[col_ids]).sum(-1)
        entity_x = {}
        for name, re in res.items():
            x = rng.standard_normal((rows, re["dim"])).astype(np.float32)
            z = z + (x * true_re[name][ids[name]]).sum(-1)
            entity_x[name] = x
        labels = rng.random(rows) < 1.0 / (1.0 + np.exp(-z))
        return Rows(col_ids.astype(np.int64), vals, labels.astype(np.float32),
                    {name: v.astype(np.int64) for name, v in ids.items()}, entity_x)

    train = draw(cols["train"], train_ids)
    held = draw(cols["held"], held_ids) if n_held else None
    return Problem(
        n_cols=n_cols, train=train, held_out=held,
        entity_counts={name: re["n_entities"] for name, re in res.items()},
    )
