"""Solver result containers and convergence bookkeeping.

Reference parity: optimization/Optimizer.scala (convergence checks :131-145,
abs tolerances derived from the initial state :68-71) and
OptimizationStatesTracker.scala:31 (per-iteration value history ring buffer,
surfaced in logs and ModelTracker). Device-side: the history is a fixed
[max_iterations+1] array padded with NaN, and the convergence reason is an
int32 code (types.ConvergenceReason).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.types import ConvergenceReason


@struct.dataclass
class SolveResult:
    """Outcome of one optimizer run. All fields are device arrays so the
    whole result can be vmap'd (one lane per random-effect entity)."""

    w: jax.Array              # [d] final coefficients
    value: jax.Array          # scalar final objective (incl. L2; incl. L1 for OWL-QN)
    grad_norm: jax.Array      # scalar ||grad|| (pseudo-gradient for OWL-QN)
    iterations: jax.Array     # int32 number of outer iterations performed
    # int32 objective.value_and_grad calls, counted in the loop carry where
    # they happen: the initial point, every line-search trial, every
    # recomputation after a box projection
    evaluations: jax.Array
    reason: jax.Array         # int32 ConvergenceReason code
    value_history: jax.Array  # [max_iterations+1] objective per iteration, NaN-padded
    # [max_iterations+1, d] per-iteration coefficients, NaN-padded — only
    # when OptimizerConfig.track_coefficients (reference ModelTracker /
    # OptimizationStatesTracker keeps per-iteration coefficients)
    w_history: Optional[jax.Array] = None
    # int32 objective.hessian_vec calls (TRON's CG steps, summed over the
    # outer iterations) and int32 trust-region steps rejected, counted in
    # the loop carry; 0 from L-BFGS and OWL-QN, which make neither
    hessian_vecs: Optional[jax.Array] = None
    rejected_steps: Optional[jax.Array] = None

    def converged(self) -> jax.Array:
        return self.reason != ConvergenceReason.NOT_CONVERGED.value

    def reason_enum(self) -> ConvergenceReason:
        return ConvergenceReason(int(self.reason))


def function_values_converged(f_prev: jax.Array, f: jax.Array, abs_tol: jax.Array) -> jax.Array:
    """|f_prev - f| <= abs_tol (reference Optimizer.scala:131-138)."""
    return jnp.abs(f_prev - f) <= abs_tol


def gradient_converged(grad_norm: jax.Array, abs_tol: jax.Array) -> jax.Array:
    """||g|| <= abs_tol (reference Optimizer.scala:140-145)."""
    return grad_norm <= abs_tol


def absolute_tolerances(f0: jax.Array, g0_norm: jax.Array, rel_tol: float):
    """Derive absolute tolerances from the initial state
    (reference Optimizer.scala:68-71: relative tolerance times the magnitude
    of the zero-model loss / gradient, floored to avoid degenerate zeros)."""
    abs_f_tol = rel_tol * jnp.maximum(jnp.abs(f0), 1e-15)
    abs_g_tol = rel_tol * jnp.maximum(g0_norm, 1e-15)
    return abs_f_tol, abs_g_tol
