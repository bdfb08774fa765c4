"""Plain reference for GLMix training on a device grid: ``glmix.py``'s exact
block coordinate descent on the whole problem, on one device. It knows
nothing of a mesh, a shard or a collective: every margin is the whole row's,
every gradient every row's, which is what the program's sums over ``feat``
and over ``data`` have to add up to.

``lost_sum`` plants what a grid can get wrong and one chip cannot, in the
fixed-effect solves alone (the scores, objectives and AUCs read after an
update stay the whole problem's):

- "one_feat_shard": margins from the first feat shard's columns only (the
  first ``n_cols / n_feat``), what a lost sum over ``feat`` hands the loss:
  the other shards' columns never enter a margin, so their coefficients
  stay at zero;
- "one_data_shard": the loss and its gradient from the first data shard's
  rows only (the first ``n_rows / n_data``), what a lost sum over ``data``
  hands the optimizer.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import solvers
from benchmarks.reference.glmix import GlmixReference

LOST_SUMS = ("one_feat_shard", "one_data_shard")


class GlmixGridReference(GlmixReference):
    def __init__(self, config: dict, problem, precision: str = "float32",
                 lost_sum: Optional[str] = None):
        super().__init__(config, problem, precision)
        if lost_sum is not None and lost_sum not in LOST_SUMS:
            raise ValueError(f"unknown lost sum {lost_sum!r}; have {LOST_SUMS}")
        self.lost_sum = lost_sum
        grid, train = config["grid"], problem.train
        self.solve_features, self.solve_rows = self.features, None
        if lost_sum == "one_feat_shard":
            shard = -(-problem.n_cols // int(grid["n_feat"]))
            self.solve_features = solvers.SparseRows(
                train.cols, np.where(train.cols < shard, train.vals, np.float32(0.0)),
                problem.n_cols, precision,
            )
        elif lost_sum == "one_data_shard":
            shard = -(-train.n // int(grid["n_data"]))
            self.solve_rows = jnp.asarray(np.arange(train.n) < shard, jnp.float32)

    def _solve_fixed(self, w0, offsets):
        """``GlmixReference._solve_fixed`` over the rows and columns the
        solve is left with."""
        if self.lost_sum is None:
            return super()._solve_fixed(w0, offsets)
        l2 = jnp.float32(self.fe_l2)
        labels, feats = self.labels, self.solve_features
        rows = self.solve_rows if self.solve_rows is not None else jnp.ones_like(labels)

        @jax.jit
        def pointwise(z, w):
            zz = z + offsets
            value = jnp.sum(rows * solvers.logistic_value(zz, labels)) + 0.5 * l2 * jnp.vdot(w, w)
            return value, rows * solvers.logistic_d1(zz, labels)

        def value_and_grad(w):
            value, d1 = pointwise(feats.matvec(w), w)
            return value, feats.rmatvec(d1) + l2 * w

        w, info = solvers.minimize_lbfgs(
            value_and_grad, w0, gradient_scale=self.fe_gradient_scale
        )
        self.fe_gradient_scale = max(self.fe_gradient_scale, info["gradient_norm_start"])
        return w, info
