"""Traffic ``cd-fit-members``: ``cd-fit``'s whole fits from the zero model,
one after another on one prepared data set, on a data set whose per-member
coordinate holds a deployment's share of members (``datagen_members.py``).

The step, the entry (``GameEstimator.fit_multiple(warm_start=False)``), the
warm-up and the comparison's arithmetic are ``cd_fit``'s. What this module
changes is the data and the plain reference: ``reference/glmix_members.py``
solves the members in width classes, where ``reference/glmix.py`` would pad
every member to the largest one.

``STAND_INS`` are ``cd_fit``'s four, each put in the program's place and
compared as the program is (``control_numbers``).
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmarks import compare, datagen, datagen_members
from benchmarks.traffic import cd_fit

make_problem = datagen_members.make_problem
STAND_INS = cd_fit.STAND_INS


class Driver(cd_fit.Driver):
    def prepare(self) -> None:
        from photon_ml_tpu.data.game_data import FeatureShard, GameData

        t0 = time.perf_counter()
        self.problem = make_problem(self.config, self.seed)
        self.log(f"data build {time.perf_counter() - t0:.1f}s (host, seeded)")

        def game_data(rows: datagen.Rows) -> GameData:
            n, k = rows.cols.shape
            every_row = np.arange(n, dtype=np.int64)
            shards = {
                "global": FeatureShard(
                    np.repeat(every_row, k), rows.cols.reshape(-1), rows.vals.reshape(-1),
                    self.problem.n_cols,
                )
            }
            for name, x in rows.entity_x.items():
                dim = x.shape[1]
                shards[name] = FeatureShard(
                    np.repeat(every_row, dim), np.tile(np.arange(dim, dtype=np.int64), n),
                    x.reshape(-1), dim,
                )
            return GameData(
                labels=rows.labels, feature_shards=shards,
                id_tags={f"{name}Id": ids for name, ids in rows.entities.items()},
            )

        self.train = game_data(self.problem.train)
        self.held_out = game_data(self.problem.held_out)
        self.estimator = self._estimator()

    def check(self) -> Dict[str, float]:
        self.kept_reference = reference_run(self.config, self.params, self.problem, self.log)
        return cd_fit._numbers(self.config, *self.kept_reference, self.histories, self.models,
                               self.log)


def reference_run(config: dict, params: dict, problem, log=None):
    """(the float32 reference, its run of one fit)."""
    from benchmarks.reference.glmix_members import GlmixMembersReference

    ref = GlmixMembersReference(config, problem, "float32")
    return ref, ref.run(int(params["outer_iterations"]), log=log)


def control_numbers(config: dict, problem, reference, reference_snaps, stand_in: str = "bfloat16",
                    log=None) -> Dict[str, float]:
    """``cd_fit.control_numbers`` with this cell's reference: "bfloat16"
    (the control), "half_batch", "unchanged" and "carried_over", each put in
    the program's place and compared as the program is."""
    from benchmarks.reference.glmix import Snapshot
    from benchmarks.reference.glmix_members import GlmixMembersReference

    per = len(config["update_order"])
    outer = len(reference_snaps) // per
    runs = None
    if stand_in == "bfloat16":
        low = GlmixMembersReference(config, problem, "bfloat16").run(outer, log=log)
    elif stand_in == "half_batch":
        halved = datagen.Problem(problem.n_cols, problem.train.first_half(),
                                 problem.held_out, problem.entity_counts)
        low = GlmixMembersReference(config, halved, "float32").run(outer, log=log)
    elif stand_in == "unchanged":
        stuck = reference_snaps[per - 1]
        low = list(reference_snaps[:per]) + [
            Snapshot(s.step, s.coordinate, stuck.objective, stuck.auc, stuck.fixed, stuck.random)
            for s in reference_snaps[per:]
        ]
    elif stand_in == "carried_over":
        first = list(reference_snaps)
        returned = first[compare.picked_update([s.auc for s in first], per - 1)]
        runs = [first, reference.run(outer, log=log, start=returned)]
    else:
        raise ValueError(f"unknown stand-in {stand_in!r}")
    histories, models = [], {}
    for i, mine in enumerate(runs or [low, low]):
        hist = {"objective": [m.objective for m in mine], "validation": [m.auc for m in mine]}
        histories.append(hist)
        picked = mine[compare.picked_update(hist["validation"], per - 1)]
        models[i] = {"fixed": np.asarray(picked.fixed),
                     **{k: np.asarray(v) for k, v in picked.random.items()}}
    return cd_fit._numbers(config, reference, reference_snaps, histories, models)
