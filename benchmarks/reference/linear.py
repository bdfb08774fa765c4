"""Plain reference for one sparse linear regression on normalized features
with an L2 penalty: the minimiser of

    0.5 * sum_i (z_i - y_i)^2 + 0.5 * l2 * |w|^2,
    z_i = x_i . (factor .* w) - shift . (factor .* w)

in float32 ``jax.numpy`` at ``highest`` matmul precision, gather and
scatter-add in row blocks (solvers.SparseRows). Takes the seeded rows and the
configuration's numbers, nothing of the program. The transform
``x -> (x - shift) .* factor`` is never applied to the data: it is folded
into effective coefficients, as the reference implementation describes it
(NormalizationContext.scala:39, ValueAndGradientAggregator.scala:35-79); the
model handed back is in the original feature space (``w_orig = factor .* w``,
the intercept less ``shift . w_orig``; NormalizationContext.scala:71-82).

Departures from that description, each because this is the plain side:

- the column statistics are accumulated in float64 on the host
  (``np.bincount``), not by an online float summarizer: mean and unbiased
  variance over all rows, implicit zeros counted, unit row weights;
- the solver is not TRON. The objective is a strongly convex quadratic, so
  its optimum solves the normal equations ``(A'A + l2 I) w = A'y`` with ``A``
  the normalized map; plain conjugate gradients, driven from the host one
  Hessian-vector product at a time, no trust region and no truncation;
- CG's recurrence drifts from the true gradient in float32, so when it
  reports the tolerance met the gradient is computed anew and CG starts
  again from there, until the true gradient meets it or stops improving.

``precision`` "bfloat16" is the control: every product of a feature value
with a coefficient, a row factor or itself takes bfloat16 operands, sums
staying wide.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import solvers


def _at_highest(method):
    """Run (and so trace) ``method`` at ``highest`` matmul precision: on a TPU
    a float32 dot is otherwise computed from bfloat16 passes."""
    @functools.wraps(method)
    def call(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return method(*args, **kwargs)
    return call


NORMALIZATIONS = ("NONE", "SCALE_WITH_STANDARD_DEVIATION", "SCALE_WITH_MAX_MAGNITUDE",
                  "STANDARDIZATION")


def _coalesced(cols: np.ndarray, vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(cols, vals)`` with a row's entries of one column summed into one of
    them and the others set to 0: the matrix entry is their sum, which the
    linear maps give by themselves and a sum of squares does not."""
    order = np.argsort(cols, axis=1, kind="stable")
    cols = np.take_along_axis(cols, order, axis=1)
    vals = np.take_along_axis(vals, order, axis=1).astype(np.float64)
    same = cols[:, 1:] == cols[:, :-1]
    for j in np.flatnonzero(same.any(axis=0)):
        rows = np.flatnonzero(same[:, j])
        vals[rows, j + 1] += vals[rows, j]
        vals[rows, j] = 0.0
    return cols, vals


def column_statistics(cols: np.ndarray, vals: np.ndarray, n_cols: int,
                      precision: str = "float32") -> Dict[str, np.ndarray]:
    """Per column over all ``n`` rows, zeros counted, in float64: ``mean``,
    unbiased ``variance``, ``max_abs`` and ``nonzeros``."""
    n = cols.shape[0]
    vals = np.asarray(solvers.operand(jnp.asarray(vals), precision).astype(jnp.float32))
    cols, vals = _coalesced(cols, vals)
    flat, v = cols.reshape(-1), vals.reshape(-1)
    mean = np.bincount(flat, weights=v, minlength=n_cols) / n
    s2 = np.bincount(flat, weights=v * v, minlength=n_cols)
    max_abs = np.zeros((n_cols,), np.float64)
    np.maximum.at(max_abs, flat, np.abs(v))
    return {
        "mean": mean,
        "variance": np.maximum(s2 - n * mean * mean, 0.0) / max(n - 1, 1),
        "max_abs": max_abs,
        "nonzeros": np.bincount(flat, weights=(v != 0), minlength=n_cols),
    }


def normalization_of(statistics: Dict[str, np.ndarray], kind: str,
                     intercept_index: Optional[int]) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """(factor, shift) in float32. A column that never varies keeps factor
    1; the intercept's slot has factor 1 and shift 0."""
    if kind not in NORMALIZATIONS:
        raise ValueError(f"unknown normalization {kind!r}; have {NORMALIZATIONS}")
    n_cols = statistics["mean"].shape[0]
    factor, shift = np.ones((n_cols,), np.float64), None
    if kind == "SCALE_WITH_MAX_MAGNITUDE":
        scale = statistics["max_abs"]
    elif kind != "NONE":
        scale = np.sqrt(statistics["variance"])
    if kind != "NONE":
        np.divide(1.0, scale, out=factor, where=scale > 0)
    if kind == "STANDARDIZATION":
        if intercept_index is None:
            raise ValueError("STANDARDIZATION needs an intercept column")
        shift = statistics["mean"].copy()
    if intercept_index is not None:
        factor[intercept_index] = 1.0
        if shift is not None:
            shift[intercept_index] = 0.0
    return factor.astype(np.float32), None if shift is None else shift.astype(np.float32)


class LinearReference:
    """``normalization`` overrides the configuration's (the ``unnormalized``
    stand-in asks for "NONE"); ``intercept_index`` names the all-ones column
    where the data has one."""

    def __init__(self, config: dict, problem, precision: str = "float32",
                 normalization: Optional[str] = None, intercept_index: Optional[int] = None):
        fe = config["fixed_effect"]
        rows = problem.train
        self.l2 = float(fe["regularization_weight"])
        self.n_cols, self.intercept_index = problem.n_cols, intercept_index
        self.features = solvers.SparseRows(rows.cols, rows.vals, problem.n_cols, precision)
        self.labels = jnp.asarray(rows.labels)
        self.statistics = column_statistics(rows.cols, rows.vals, problem.n_cols, precision)
        factor, shift = normalization_of(
            self.statistics, normalization or fe["normalization"], intercept_index)
        self.factor = jnp.asarray(factor)
        self.shift = None if shift is None else jnp.asarray(shift)
        l2, labels, factor, shift = jnp.float32(self.l2), self.labels, self.factor, self.shift

        def centred(z, ew):
            return z if shift is None else z - jnp.vdot(shift, ew)

        def to_gradient(raw, total, w):
            if shift is not None:
                raw = raw - shift * total
            return factor * raw + l2 * w

        self._effective = jax.jit(lambda w: factor * w)
        self._residual = jax.jit(lambda z, ew: centred(z, ew) - labels)
        self._centred = jax.jit(centred)
        self._value = jax.jit(lambda r, w: 0.5 * jnp.vdot(r, r) + 0.5 * l2 * jnp.vdot(w, w))
        self._to_gradient = jax.jit(lambda raw, r, w: to_gradient(raw, jnp.sum(r), w))

    # -- the objective in the normalized space ---------------------------------
    @_at_highest
    def value_and_grad(self, w):
        ew = self._effective(w)
        r = self._residual(self.features.matvec(ew), ew)
        return self._value(r, w), self._to_gradient(self.features.rmatvec(r), r, w)

    @_at_highest
    def hessian_vec(self, v):
        """The (constant) Hessian ``A'A + l2 I`` applied to ``v``."""
        ev = self._effective(v)
        zv = self._centred(self.features.matvec(ev), ev)
        return self._to_gradient(self.features.rmatvec(zv), zv, v)

    # -- between the spaces ------------------------------------------------------
    @_at_highest
    def to_original(self, w):
        w_orig = self.factor * jnp.asarray(w)
        if self.shift is not None:
            w_orig = w_orig.at[self.intercept_index].add(-jnp.vdot(self.shift, w_orig))
        return w_orig

    @_at_highest
    def to_normalized(self, w_orig):
        w = jnp.asarray(w_orig)
        if self.shift is not None:
            w = w.at[self.intercept_index].add(jnp.vdot(self.shift, w))
        return w / self.factor

    # -- of a model in the original space ---------------------------------------
    def objective(self, w_orig) -> float:
        return float(self.value_and_grad(self.to_normalized(w_orig))[0])

    def gradient(self, w_orig):
        """The normalized-space gradient at the original-space model."""
        return self.value_and_grad(self.to_normalized(w_orig))[1]

    @_at_highest
    def stationarity(self, w_orig) -> float:
        """The gradient norm at ``w_orig`` over the gradient norm at zero."""
        zero = jnp.zeros((self.n_cols,), jnp.float32)
        at_zero = float(jnp.linalg.norm(self.value_and_grad(zero)[1]))
        return float(jnp.linalg.norm(self.gradient(w_orig))) / max(at_zero, 1e-30)

    @_at_highest
    def solve(self, log=None, tolerance: float = 1e-7, max_iterations: int = 400):
        """(original-space coefficients, info) of the optimum: CG from the
        zero model until the true gradient norm is ``tolerance`` of its norm
        there."""
        vdot, axpy = solvers._vdot, solvers._axpy
        w = jnp.zeros((self.n_cols,), jnp.float32)
        value, g = self.value_and_grad(w)
        g0_norm = float(jnp.linalg.norm(g))
        relative, iterations, restarts = 1.0, 0, 0
        while g0_norm > 0:
            r = -g
            d, rtr = r, float(vdot(r, r))
            while iterations < max_iterations and rtr > 0:
                hd = self.hessian_vec(d)
                alpha = rtr / float(vdot(d, hd))
                w, r = axpy(alpha, d, w), axpy(-alpha, hd, r)
                rtr_new = float(vdot(r, r))
                iterations += 1
                if rtr_new ** 0.5 <= tolerance * g0_norm:
                    break
                d, rtr = axpy(rtr_new / rtr, d, r), rtr_new
            value, g = self.value_and_grad(w)
            before, relative = relative, float(jnp.linalg.norm(g)) / g0_norm
            if relative <= tolerance or iterations >= max_iterations or relative > 0.5 * before:
                break
            restarts += 1
        info = {"value": float(value), "iterations": iterations, "restarts": restarts,
                "relative_gradient": relative}
        if log is not None:
            log(f"reference[{self.features.precision}] fit: {info}")
        return self.to_original(w), info
