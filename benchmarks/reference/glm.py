"""Plain reference for one sparse GLM fit with elastic-net regularisation
and a box: the minimiser of

    sum_i loss(x_i . w, y_i) + 0.5 * l2 * |w|^2 + l1 * |w|_1,  lo <= w <= hi

by orthant-wise L-BFGS in float32 ``jax.numpy`` (solvers.py). Takes the
seeded rows and the configuration's numbers, nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import solvers


class GlmReference:
    def __init__(self, config: dict, problem, precision: str = "float32"):
        fe = config["fixed_effect"]
        self.value_fn, self.d1_fn = solvers.LOSSES[config["task"]]
        weight = float(fe["regularization_weight"])
        alpha = float(fe["elastic_net_alpha"])
        self.l1, self.l2 = alpha * weight, (1.0 - alpha) * weight
        self.lo, self.hi = fe.get("constraint_lower"), fe.get("constraint_upper")
        self.n_cols = problem.n_cols
        self.features = solvers.SparseRows(
            problem.train.cols, problem.train.vals, problem.n_cols, precision
        )
        self.labels = jnp.asarray(problem.train.labels)
        l2, labels, value_fn, d1_fn = jnp.float32(self.l2), self.labels, self.value_fn, self.d1_fn

        @jax.jit
        def pointwise(z, w):
            return jnp.sum(value_fn(z, labels)) + 0.5 * l2 * jnp.vdot(w, w), d1_fn(z, labels)

        self._pointwise = pointwise

    def value_and_grad(self, w):
        """The smooth part (loss + L2) and its gradient."""
        value, d1 = self._pointwise(self.features.matvec(w), w)
        return value, self.features.rmatvec(d1) + jnp.float32(self.l2) * w

    def objective(self, w) -> float:
        w = jnp.asarray(w)
        value, _ = self._pointwise(self.features.matvec(w), w)
        return float(value) + self.l1 * float(jnp.sum(jnp.abs(w)))

    def solve(self, log=None):
        w0 = jnp.zeros((self.n_cols,), jnp.float32)
        w, info = solvers.minimize_owlqn(
            self.value_and_grad, w0, self.l1, self.lo, self.hi
        )
        if log is not None:
            log(f"reference[{self.features.precision}] fit: {info}")
        return w, info

    def conditions(self, w) -> Dict[str, float]:
        """How far ``w`` is from meeting the box and the orthant (optimality)
        conditions, by the reference's own gradient: the largest step beyond
        the box, and the norm of the pseudo-gradient at ``w`` against its
        norm at the zero model."""
        w = jnp.asarray(w)
        _, g = self.value_and_grad(w)
        pg = solvers.pseudo_gradient(w, g, self.l1, self.lo, self.hi)
        _, g0 = self.value_and_grad(jnp.zeros_like(w))
        pg0 = solvers.pseudo_gradient(jnp.zeros_like(w), g0, self.l1, self.lo, self.hi)
        beyond = 0.0
        if self.lo is not None:
            beyond = max(beyond, float(jnp.max(self.lo - w)))
        if self.hi is not None:
            beyond = max(beyond, float(jnp.max(w - self.hi)))
        return {
            "box_violation": max(beyond, 0.0),
            "stationarity": float(jnp.linalg.norm(pg)) / max(float(jnp.linalg.norm(pg0)), 1e-30),
        }
