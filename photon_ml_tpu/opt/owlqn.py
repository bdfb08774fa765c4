"""OWL-QN: Orthant-Wise Limited-memory Quasi-Newton for L1 / elastic net.

Reference parity: optimization/OWLQN.scala:40, which wrapped
``breeze.optimize.OWLQN``; the L1 weight is applied at the optimizer level —
never inside the smooth objective (the L2 part of elastic net stays in the
objective). Algorithm follows Andrew & Gao (2007):

- pseudo-gradient: subgradient of f(w) + l1*||w||_1 choosing the orthant of
  steepest descent at w_j = 0
- two-loop direction computed from SMOOTH gradient history, then aligned
  (projected) against the pseudo-gradient
- line search over orthant-projected points pi(w + t*d; xi) with a
  backtracking sufficient-decrease condition on F = f + l1*||w||_1
  (Breeze's OWLQN uses the same backtracking scheme)

Box constraints compose with L1 exactly as in the reference: OWLQN.scala:46
passes the constraint map up to LBFGS.scala:72, which projects the iterate
into the box after each accepted step; here the projected point's value and
gradient are recomputed so the curvature pairs stay consistent.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.losses.objective import GlmObjective
from photon_ml_tpu.opt.config import OptimizerConfig
from photon_ml_tpu.opt.lbfgs import (
    _project_box,
    history_zeros,
    resolve_box,
    resolve_history_dtype,
    two_loop_direction,
    update_history,
)
from photon_ml_tpu.opt.state import (
    SolveResult,
    absolute_tolerances,
    function_values_converged,
    gradient_converged,
)
from photon_ml_tpu.types import ConvergenceReason


def pseudo_gradient(w: jax.Array, g: jax.Array, l1: jax.Array) -> jax.Array:
    """Subgradient of f + l1*|w|_1 with steepest-descent tie-breaking at 0."""
    at_zero = w == 0
    pg_nonzero = g + l1 * jnp.sign(w)
    # at w_j = 0 the subdifferential is [g - l1, g + l1]; the minimal-norm
    # element is 0 if the interval contains 0, else the closest endpoint.
    pg_zero = jnp.where(g + l1 < 0, g + l1, jnp.where(g - l1 > 0, g - l1, 0.0))
    return jnp.where(at_zero, pg_zero, pg_nonzero)


def _project_orthant(w: jax.Array, xi: jax.Array) -> jax.Array:
    """pi(w; xi): zero out coordinates that left the orthant xi."""
    return jnp.where(jnp.sign(w) == xi, w, 0.0)


class _OwlqnState(NamedTuple):
    """Resumable OWL-QN loop state (see _LbfgsState, also for the shape of
    ``s_hist`` / ``y_hist``): carries the L1 weight and the init-derived
    tolerances so chunked execution — ``owlqn_chunk`` every K iterations —
    follows the one-shot trajectory exactly."""

    w: jax.Array
    f: jax.Array          # smooth f (no L1)
    g: jax.Array          # smooth gradient
    F: jax.Array          # f + l1*|w|_1
    s_hist: jax.Array
    y_hist: jax.Array
    rho: jax.Array
    count: jax.Array
    it: jax.Array
    evals: jax.Array      # int32 objective.value_and_grad calls so far
    reason: jax.Array
    history: jax.Array
    w_hist: jax.Array     # [max_iter+1, d] coefficients (or [0] when off)
    l1: jax.Array         # scalar L1 weight (traced)
    abs_f_tol: jax.Array
    abs_g_tol: jax.Array


def owlqn_init(
    objective: GlmObjective,
    w0: jax.Array,
    data,
    l2_weight: jax.Array,
    l1_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
) -> _OwlqnState:
    m = config.history_length
    max_iter = config.max_iterations
    dim = w0.shape[-1]
    dtype = w0.dtype
    l1 = jnp.asarray(l1_weight, dtype=dtype)

    f0, g0 = objective.value_and_grad(w0, data, l2_weight)
    F0 = f0 + l1 * jnp.sum(jnp.abs(w0))
    pg0 = pseudo_gradient(w0, g0, l1)
    pg0_norm = jnp.linalg.norm(pg0)
    abs_f_tol, abs_g_tol = absolute_tolerances(F0, pg0_norm, config.tolerance)

    hdtype = resolve_history_dtype(config, dtype)
    history0 = jnp.full((max_iter + 1,), jnp.nan, dtype=dtype).at[0].set(F0)
    w_hist0 = (
        jnp.full((max_iter + 1, dim), jnp.nan, dtype=dtype).at[0].set(w0)
        if config.track_coefficients
        else jnp.zeros((0,), dtype=dtype)
    )
    return _OwlqnState(
        w=w0,
        f=f0,
        g=g0,
        F=F0,
        s_hist=history_zeros(m, dim, hdtype),
        y_hist=history_zeros(m, dim, hdtype),
        rho=jnp.zeros((m,), dtype=dtype),
        count=jnp.int32(0),
        it=jnp.int32(0),
        evals=jnp.int32(1),
        reason=jnp.int32(ConvergenceReason.NOT_CONVERGED.value),
        history=history0,
        w_hist=w_hist0,
        l1=l1,
        abs_f_tol=abs_f_tol,
        abs_g_tol=abs_g_tol,
    )


def owlqn_chunk(
    objective: GlmObjective,
    state: _OwlqnState,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    box=None,
    num_iters=None,
) -> _OwlqnState:
    """Advance by at most ``num_iters`` outer iterations (None = to the
    end); same chunking contract as ``lbfgs_chunk``."""
    box_lo, box_hi, has_box = resolve_box(box, config)
    max_iter = config.max_iterations
    dtype = state.w.dtype
    l1 = state.l1
    it_stop = None if num_iters is None else state.it + jnp.int32(num_iters)

    GAMMA = 1e-4  # sufficient-decrease constant (Andrew & Gao use 1e-4)
    BACKTRACK = 0.5

    def cond(s: _OwlqnState):
        c = (s.reason == ConvergenceReason.NOT_CONVERGED.value) & (s.it < max_iter)
        if it_stop is not None:
            c = c & (s.it < it_stop)
        return c

    def body(s: _OwlqnState) -> _OwlqnState:
        pg = pseudo_gradient(s.w, s.g, l1)
        d = two_loop_direction(pg, s.s_hist, s.y_hist, s.rho, s.count)
        # align direction with -pg (zero disagreeing coordinates)
        d = jnp.where(d * pg < 0, d, 0.0)
        # orthant to search in: sign(w), or sign(-pg) where w = 0
        xi = jnp.where(s.w != 0, jnp.sign(s.w), jnp.sign(-pg))

        t0 = jnp.where(s.count == 0, 1.0 / jnp.maximum(jnp.linalg.norm(d), 1e-12), 1.0)

        class _LS(NamedTuple):
            t: jax.Array
            i: jax.Array
            w_t: jax.Array
            f_t: jax.Array
            g_t: jax.Array
            F_t: jax.Array
            ok: jax.Array

        def ls_cond(c: _LS):
            return (~c.ok) & (c.i < config.max_line_search_iterations)

        def ls_body(c: _LS) -> _LS:
            w_t = _project_orthant(s.w + c.t * d, xi)
            f_t, g_t = objective.value_and_grad(w_t, data, l2_weight)
            F_t = f_t + l1 * jnp.sum(jnp.abs(w_t))
            # sufficient decrease vs directional derivative of F along the
            # PROJECTED step (Andrew & Gao eq. for the projected path)
            ok = F_t <= s.F + GAMMA * jnp.dot(pg, w_t - s.w)
            return _LS(
                t=jnp.where(ok, c.t, c.t * BACKTRACK),
                i=c.i + 1,
                w_t=w_t,
                f_t=f_t,
                g_t=g_t,
                F_t=F_t,
                ok=ok,
            )

        ls0 = _LS(
            t=t0.astype(dtype),
            i=jnp.int32(0),
            w_t=s.w,
            f_t=s.f,
            g_t=s.g,
            F_t=s.F,
            ok=jnp.bool_(False),
        )
        ls = jax.lax.while_loop(ls_cond, ls_body, ls0)
        evals = s.evals + ls.i

        w_new = jnp.where(ls.ok, ls.w_t, s.w)
        f_new = jnp.where(ls.ok, ls.f_t, s.f)
        g_new = jnp.where(ls.ok, ls.g_t, s.g)
        F_new = jnp.where(ls.ok, ls.F_t, s.F)
        if has_box:
            # post-step projection (reference LBFGS.scala:72, inherited by
            # OWLQN); recompute at the projected point so curvature pairs
            # and convergence checks see the true state — but only when the
            # projection actually clipped something (bounds inactive or a
            # failed line search leave w unchanged, and the line-search
            # f/g are already exact there)
            w_proj = _project_box(w_new, box_lo, box_hi)
            clipped = jnp.any(w_proj != w_new)

            def _recompute(_):
                f_p, g_p = objective.value_and_grad(w_proj, data, l2_weight)
                return f_p, g_p, f_p + l1 * jnp.sum(jnp.abs(w_proj))

            def _reuse(_):
                return f_new, g_new, F_new

            f_new, g_new, F_new = jax.lax.cond(clipped, _recompute, _reuse, None)
            evals = evals + clipped.astype(jnp.int32)
            w_new = w_proj

        s_vec = w_new - s.w
        y_vec = g_new - s.g
        s_hist, y_hist, rho, count = update_history(
            s.s_hist, s.y_hist, s.rho, s.count, s_vec, y_vec
        )

        it = s.it + 1
        pg_new = pseudo_gradient(w_new, g_new, l1)
        g_conv = gradient_converged(jnp.linalg.norm(pg_new), s.abs_g_tol)
        f_conv = ls.ok & function_values_converged(s.F, F_new, s.abs_f_tol)
        no_step = ~ls.ok
        reason = jnp.where(
            g_conv,
            ConvergenceReason.GRADIENT_CONVERGED.value,
            jnp.where(
                f_conv,
                ConvergenceReason.FUNCTION_VALUES_CONVERGED.value,
                jnp.where(
                    no_step,
                    ConvergenceReason.OBJECTIVE_NOT_IMPROVING.value,
                    jnp.where(
                        it >= max_iter,
                        ConvergenceReason.MAX_ITERATIONS.value,
                        ConvergenceReason.NOT_CONVERGED.value,
                    ),
                ),
            ),
        ).astype(jnp.int32)

        return _OwlqnState(
            w=w_new,
            f=f_new,
            g=g_new,
            F=F_new,
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            count=count,
            it=it,
            evals=evals,
            reason=reason,
            history=s.history.at[it].set(F_new),
            w_hist=(
                s.w_hist.at[it].set(w_new)
                if config.track_coefficients
                else s.w_hist
            ),
            l1=s.l1,
            abs_f_tol=s.abs_f_tol,
            abs_g_tol=s.abs_g_tol,
        )

    return jax.lax.while_loop(cond, body, state)


def owlqn_finalize(
    state: _OwlqnState, config: OptimizerConfig = OptimizerConfig()
) -> SolveResult:
    """Convert a (fully run) loop state into the public SolveResult."""
    reason = jnp.where(
        state.reason == ConvergenceReason.NOT_CONVERGED.value,
        jnp.int32(ConvergenceReason.MAX_ITERATIONS.value),
        state.reason,
    )
    pg_final = pseudo_gradient(state.w, state.g, state.l1)
    return SolveResult(
        w=state.w,
        value=state.F,
        grad_norm=jnp.linalg.norm(pg_final),
        iterations=state.it,
        evaluations=state.evals,
        hessian_vecs=jnp.zeros_like(state.it),
        rejected_steps=jnp.zeros_like(state.it),
        reason=reason,
        value_history=state.history,
        w_history=state.w_hist if config.track_coefficients else None,
    )


def owlqn_solve(
    objective: GlmObjective,
    w0: jax.Array,
    data,
    l2_weight: jax.Array,
    l1_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    box=None,
) -> SolveResult:
    state = owlqn_init(objective, w0, data, l2_weight, l1_weight, config)
    state = owlqn_chunk(objective, state, data, l2_weight, config, box=box)
    return owlqn_finalize(state, config)
