"""TRON: trust-region Newton method, fully on device.

Reference parity: optimization/TRON.scala:80 (itself a port of LIBLINEAR's
tron.cpp): outer trust-region loop (:148-250) with truncated conjugate-gradient
inner solves over Hessian-vector products (:275-335), eta/sigma trust-radius
constants (:97-98), maxNumImprovementFailures=5, defaults maxIter=15,
≤20 CG iterations, tol=1e-5 (:253-259).

In the reference every CG step paid a Spark treeAggregate for its
Hessian-vector product (HessianVectorAggregator.scala:145); here each Hv is a
fused XLA computation (or a psum'd sharded one), and the entire outer loop is
one ``lax.while_loop`` program.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.losses.objective import GlmObjective
from photon_ml_tpu.opt.config import OptimizerConfig
from photon_ml_tpu.opt.lbfgs import _project_box, resolve_box
from photon_ml_tpu.opt.state import (
    SolveResult,
    absolute_tolerances,
    function_values_converged,
    gradient_converged,
)
from photon_ml_tpu.types import ConvergenceReason

# Trust-region update constants (reference TRON.scala:97-98 / LIBLINEAR).
ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0


class _CgState(NamedTuple):
    s: jax.Array
    r: jax.Array
    d: jax.Array
    rtr: jax.Array
    it: jax.Array
    done: jax.Array


def _truncated_cg(hess_vec, g, delta, max_cg: int, cg_tol: float):
    """Steihaug truncated CG: approximately solve H s = -g with ||s|| <= delta.

    Returns (s, r, iterations) where r is the final residual -g - H s (used
    for the predicted-reduction formula, reference TRON.scala:275-335) and
    ``iterations`` the int32 count of CG steps made: one ``hess_vec`` each.
    """
    r0 = -g
    stop_norm = cg_tol * jnp.linalg.norm(g)
    init = _CgState(
        s=jnp.zeros_like(g),
        r=r0,
        d=r0,
        rtr=jnp.dot(r0, r0),
        it=jnp.int32(0),
        done=jnp.sqrt(jnp.dot(r0, r0)) <= stop_norm,
    )

    def cond(c: _CgState):
        return (~c.done) & (c.it < max_cg)

    def body(c: _CgState) -> _CgState:
        hd = hess_vec(c.d)
        dhd = jnp.dot(c.d, hd)
        alpha = c.rtr / jnp.where(dhd <= 0, 1e-30, dhd)
        s_try = c.s + alpha * c.d

        # Negative curvature or boundary hit: move to the trust-region edge
        # along d and stop.
        hit = (dhd <= 0) | (jnp.linalg.norm(s_try) > delta)
        std = jnp.dot(c.s, c.d)
        dd = jnp.dot(c.d, c.d)
        ss = jnp.dot(c.s, c.s)
        rad = jnp.sqrt(jnp.maximum(std * std + dd * (delta * delta - ss), 0.0))
        tau = (-std + rad) / jnp.maximum(dd, 1e-30)
        s_edge = c.s + tau * c.d
        r_edge = c.r - tau * hd

        s_new = jnp.where(hit, s_edge, s_try)
        r_new = jnp.where(hit, r_edge, c.r - alpha * hd)
        rtr_new = jnp.dot(r_new, r_new)
        converged = jnp.sqrt(rtr_new) <= stop_norm
        beta = rtr_new / jnp.maximum(c.rtr, 1e-30)
        d_new = jnp.where(hit | converged, c.d, r_new + beta * c.d)
        return _CgState(
            s=s_new,
            r=r_new,
            d=d_new,
            rtr=rtr_new,
            it=c.it + 1,
            done=hit | converged,
        )

    out = jax.lax.while_loop(cond, body, init)
    return out.s, out.r, out.it


class _TronState(NamedTuple):
    """Resumable TRON loop state: carries the trust radius and init-derived
    tolerances so chunked execution (``tron_chunk`` every K iterations)
    follows the one-shot trajectory exactly."""

    w: jax.Array
    f: jax.Array
    g: jax.Array
    delta: jax.Array
    it: jax.Array
    evals: jax.Array      # int32 objective.value_and_grad calls so far
    hvs: jax.Array        # int32 objective.hessian_vec calls so far (the CG steps, summed)
    failures: jax.Array   # int32 trust-region steps rejected so far
    reason: jax.Array
    history: jax.Array
    w_hist: jax.Array     # [max_iter+1, d] coefficients (or [0] when off)
    abs_f_tol: jax.Array
    abs_g_tol: jax.Array


def tron_init(
    objective: GlmObjective,
    w0: jax.Array,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig.tron(),
) -> _TronState:
    if not objective.has_hessian:
        raise ValueError(
            "TRON requires a twice-differentiable objective; smoothed hinge "
            "is first-order only (use LBFGS, reference OptimizerFactory.scala)"
        )
    max_iter = config.max_iterations
    dtype = w0.dtype

    f0, g0 = objective.value_and_grad(w0, data, l2_weight)
    g0_norm = jnp.linalg.norm(g0)
    abs_f_tol, abs_g_tol = absolute_tolerances(f0, g0_norm, config.tolerance)

    history0 = jnp.full((max_iter + 1,), jnp.nan, dtype=dtype).at[0].set(f0)
    w_hist0 = (
        jnp.full((max_iter + 1,) + w0.shape, jnp.nan, dtype=dtype).at[0].set(w0)
        if config.track_coefficients
        else jnp.zeros((0,), dtype=dtype)
    )
    return _TronState(
        w=w0,
        f=f0,
        g=g0,
        delta=g0_norm,  # initial radius = ||g0|| (reference TRON.scala:112)
        it=jnp.int32(0),
        evals=jnp.int32(1),
        hvs=jnp.int32(0),
        failures=jnp.int32(0),
        reason=jnp.where(
            g0_norm <= abs_g_tol,
            jnp.int32(ConvergenceReason.GRADIENT_CONVERGED.value),
            jnp.int32(ConvergenceReason.NOT_CONVERGED.value),
        ),
        history=history0,
        w_hist=w_hist0,
        abs_f_tol=abs_f_tol,
        abs_g_tol=abs_g_tol,
    )


def tron_chunk(
    objective: GlmObjective,
    state: _TronState,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig.tron(),
    box=None,
    num_iters=None,
) -> _TronState:
    """Advance by at most ``num_iters`` outer iterations (None = to the
    end); same chunking contract as ``lbfgs_chunk``."""
    max_iter = config.max_iterations
    box_lo, box_hi, has_box = resolve_box(box, config)
    it_stop = None if num_iters is None else state.it + jnp.int32(num_iters)

    def cond(s: _TronState):
        c = (s.reason == ConvergenceReason.NOT_CONVERGED.value) & (s.it < max_iter)
        if it_stop is not None:
            c = c & (s.it < it_stop)
        return c

    def body(s: _TronState) -> _TronState:
        hv = lambda v: objective.hessian_vec(s.w, v, data, l2_weight)
        step, resid, cg_steps = _truncated_cg(
            hv, s.g, s.delta, config.max_cg_iterations, config.cg_tolerance
        )
        w_try = s.w + step
        if has_box:
            w_try = _project_box(w_try, box_lo, box_hi)
            step = w_try - s.w
        f_try, g_try = objective.value_and_grad(w_try, data, l2_weight)

        gs = jnp.dot(s.g, step)
        prered = -0.5 * (gs - jnp.dot(step, resid))
        actred = s.f - f_try
        snorm = jnp.linalg.norm(step)

        # Trust-radius update (reference TRON.scala:200-240 / LIBLINEAR).
        denom = f_try - s.f - gs
        alpha = jnp.where(
            -actred <= gs,
            SIGMA3,
            jnp.maximum(SIGMA1, -0.5 * (gs / jnp.where(jnp.abs(denom) < 1e-30, 1e-30, denom))),
        )
        delta = jnp.where(
            actred < ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, SIGMA1) * snorm, SIGMA2 * s.delta),
            jnp.where(
                actred < ETA1 * prered,
                jnp.maximum(SIGMA1 * s.delta, jnp.minimum(alpha * snorm, SIGMA2 * s.delta)),
                jnp.where(
                    actred < ETA2 * prered,
                    jnp.maximum(SIGMA1 * s.delta, jnp.minimum(alpha * snorm, SIGMA3 * s.delta)),
                    jnp.maximum(s.delta, jnp.minimum(alpha * snorm, SIGMA3 * s.delta)),
                ),
            ),
        )

        accept = actred > ETA0 * prered
        failures = jnp.where(accept, s.failures, s.failures + 1)
        w_new = jnp.where(accept, w_try, s.w)
        f_new = jnp.where(accept, f_try, s.f)
        g_new = jnp.where(accept, g_try, s.g)

        it = s.it + 1
        g_conv = gradient_converged(jnp.linalg.norm(g_new), s.abs_g_tol)
        f_conv = accept & function_values_converged(s.f, f_new, s.abs_f_tol)
        too_many_failures = failures >= config.max_improvement_failures
        degenerate = (prered <= 0) & (actred <= 0)
        reason = jnp.where(
            g_conv,
            ConvergenceReason.GRADIENT_CONVERGED.value,
            jnp.where(
                f_conv,
                ConvergenceReason.FUNCTION_VALUES_CONVERGED.value,
                jnp.where(
                    too_many_failures | degenerate,
                    ConvergenceReason.OBJECTIVE_NOT_IMPROVING.value,
                    jnp.where(
                        it >= max_iter,
                        ConvergenceReason.MAX_ITERATIONS.value,
                        ConvergenceReason.NOT_CONVERGED.value,
                    ),
                ),
            ),
        ).astype(jnp.int32)

        return _TronState(
            w=w_new,
            f=f_new,
            g=g_new,
            delta=delta,
            it=it,
            evals=s.evals + 1,
            hvs=s.hvs + cg_steps,
            failures=failures,
            reason=reason,
            history=s.history.at[it].set(f_new),
            w_hist=(
                s.w_hist.at[it].set(w_new)
                if config.track_coefficients
                else s.w_hist
            ),
            abs_f_tol=s.abs_f_tol,
            abs_g_tol=s.abs_g_tol,
        )

    return jax.lax.while_loop(cond, body, state)


def tron_finalize(
    state: _TronState, config: OptimizerConfig = OptimizerConfig.tron()
) -> SolveResult:
    """Convert a (fully run) loop state into the public SolveResult."""
    reason = jnp.where(
        state.reason == ConvergenceReason.NOT_CONVERGED.value,
        jnp.int32(ConvergenceReason.MAX_ITERATIONS.value),
        state.reason,
    )
    return SolveResult(
        w=state.w,
        value=state.f,
        grad_norm=jnp.linalg.norm(state.g),
        iterations=state.it,
        evaluations=state.evals,
        hessian_vecs=state.hvs,
        rejected_steps=state.failures,
        descent_restarts=jnp.zeros_like(state.it),
        reason=reason,
        value_history=state.history,
        w_history=state.w_hist if config.track_coefficients else None,
    )


def tron_solve(
    objective: GlmObjective,
    w0: jax.Array,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig.tron(),
    box=None,
) -> SolveResult:
    state = tron_init(objective, w0, data, l2_weight, config)
    state = tron_chunk(objective, state, data, l2_weight, config, box=box)
    return tron_finalize(state, config)
