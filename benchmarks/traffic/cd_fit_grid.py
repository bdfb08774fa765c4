"""Traffic ``cd-fit-grid``: ``cd_fit``'s whole GLMix fits, on a (data x
feat) grid of chips.

``cd_fit``'s step, entry, counters and comparison to the letter; the one
difference is the estimator, which is handed the ``parallel=`` that the
configuration's ``grid`` key states: the fixed effect's tiles routed one a
device, margins summed over ``feat`` and gradients over ``data``, ``w``, the
gradient and the L-BFGS history feat-sharded for the whole solve, the
random effects' entity blocks spread over every device.

The reference (``reference/glmix_grid.py``) knows nothing of a grid: it fits
the whole problem on one device. Two stand-ins are this cell's own, each what
a lost collective computes: ``one_feat_shard`` and ``one_data_shard``.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Dict

import numpy as np

from benchmarks import compare, datagen
from benchmarks.traffic import cd_fit

try:
    # what a device grid needs of the program and a tree before it lacks
    # (feat shards padded to whole rows of 128; such a tree's spans also
    # wait for one device of the mesh). Such a tree does not run this cell.
    from photon_ml_tpu.parallel.grid_features import COLUMN_MULTIPLE  # noqa: F401
except ImportError as e:
    sys.stderr.write(f"cd_fit_grid: this program cannot run a device grid's cell: {e}\n")
    raise SystemExit(2)  # run.py's EXIT_BAD_WORKLOAD

make_problem = datagen.make_problem
STAND_INS = cd_fit.STAND_INS + ("one_feat_shard", "one_data_shard")


class Driver(cd_fit.Driver):
    def _estimator(self):
        """``cd_fit``'s estimator, built again with the grid."""
        from photon_ml_tpu.estimators.game import ParallelConfiguration

        flat = super()._estimator()
        grid = self.config["grid"]
        return type(flat)(
            task=flat.task,
            coordinates=flat.coordinate_configs,
            update_order=flat.update_order,
            num_outer_iterations=flat.num_outer_iterations,
            emitter=flat.emitter,
            parallel=ParallelConfiguration(
                n_data=int(grid["n_data"]), n_feat=int(grid["n_feat"]), engine=grid["engine"]
            ),
        )

    def _host_model(self, models: dict) -> dict:
        """``cd_fit``'s host copies; a bucket's lanes past its entities pad
        the entity axis to the grid and hold no model."""
        trimmed = dict(models)
        for name in self.config["random_effects"]:
            m = models[name]
            real = [len(ids) for ids in m.entity_ids]
            trimmed[name] = dataclasses.replace(m, **{
                leaf: [np.asarray(a)[:n] for a, n in zip(getattr(m, leaf), real)]
                for leaf in ("coefficients", "proj_indices", "proj_valid")
            })
        return super()._host_model(trimmed)

    def check(self) -> Dict[str, float]:
        # kept for control_numbers (calibrate.py)
        self.kept_reference = reference_run(self.config, self.params, self.problem, self.log)
        return cd_fit._numbers(self.config, *self.kept_reference, self.histories, self.models,
                               self.log)


def reference_run(config: dict, params: dict, problem, log=None):
    """(the float32 reference, its run of one fit)."""
    from benchmarks.reference.glmix_grid import GlmixGridReference

    ref = GlmixGridReference(config, problem, "float32")
    return ref, ref.run(int(params["outer_iterations"]), log=log)


def control_numbers(config: dict, problem, reference, reference_snaps, stand_in: str = "bfloat16",
                    log=None) -> Dict[str, float]:
    """``cd_fit.control_numbers``, with the two stand-ins of a grid: the
    reference whose fixed-effect solves see what one shard of an axis holds
    (``reference/glmix_grid.py``), put in the program's place."""
    if stand_in not in ("one_feat_shard", "one_data_shard"):
        return cd_fit.control_numbers(config, problem, reference, reference_snaps, stand_in, log)
    from benchmarks.reference.glmix_grid import GlmixGridReference

    per = len(config["update_order"])
    lost = GlmixGridReference(config, problem, "float32", lost_sum=stand_in).run(
        len(reference_snaps) // per, log=log)
    hist = {"objective": [m.objective for m in lost], "validation": [m.auc for m in lost]}
    picked = lost[compare.picked_update(hist["validation"], per - 1)]
    model = {"fixed": np.asarray(picked.fixed),
             **{k: np.asarray(v) for k, v in picked.random.items()}}
    return cd_fit._numbers(config, reference, reference_snaps, [hist, hist], {0: model, 1: model})
