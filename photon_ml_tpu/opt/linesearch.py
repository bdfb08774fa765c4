"""Strong-Wolfe line search as a single ``lax.while_loop`` state machine.

Replaces Breeze's StrongWolfeLineSearch (used by the reference's LBFGS,
LBFGS.scala:59-106). Standard bracket-then-zoom (Nocedal & Wright alg. 3.5/3.6)
with bisection zoom; c1=1e-4, c2=0.9. Each trial evaluates value-and-gradient
once; the gradient at the accepted point is carried out so the caller does not
re-evaluate.

The whole search is branch-free XLA control flow: one while_loop whose state
includes a ``stage`` flag (0 = bracketing, 1 = zoom) — safe under jit, vmap,
and shard_map.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

C1 = 1e-4
C2 = 0.9


class LineSearchResult(NamedTuple):
    t: jax.Array        # accepted step
    f: jax.Array        # phi(t)
    g: jax.Array        # full gradient at w + t*d
    success: jax.Array  # bool: Wolfe conditions met
    evaluations: jax.Array  # int32 eval_step calls made


def strong_wolfe_search(
    eval_step: Callable[[jax.Array], Tuple[jax.Array, jax.Array, jax.Array]],
    f0: jax.Array,
    g0: jax.Array,
    dphi0: jax.Array,
    t_init: jax.Array,
    max_iters: int = 25,
) -> LineSearchResult:
    """eval_step(t) -> (phi(t), grad_at_point [d], dphi(t)).

    ``g0`` is the full gradient at t=0 (the caller already has it); it seeds
    the carried gradient buffers so no evaluation is spent on shape probing.
    Returns the accepted step with its value/gradient. When the search cannot
    satisfy Wolfe within ``max_iters`` evaluations it returns the best
    sufficient-decrease point seen (success=False if none found; the t=0
    point with its g0 is the last resort so the caller can detect a null step).

    The search also stops, with the same fallback, when a trial whose slope
    is still steeper than ``C2 * dphi0`` becomes the bracket's upper end (its
    value is not below the bracket's lower end, or fails sufficient
    decrease). On a convex objective, which a GLM's is, that can only be
    rounding: the value at such a step lies at least ``C2 |dphi0| t`` below
    ``f0``. Every slope left inside the bracket is steeper still, so no
    point in it can meet the curvature condition, and the bisections that
    remain could only read rounding. A search that starts at the rounding
    floor of a converged solve would otherwise spend all ``max_iters`` of
    its evaluations there, as many or as few as rounding draws.
    """

    class _S(NamedTuple):
        stage: jax.Array    # 0 bracket, 1 zoom, 2 done
        i: jax.Array
        t: jax.Array        # current trial
        t_lo: jax.Array
        f_lo: jax.Array
        d_lo: jax.Array
        t_hi: jax.Array
        f_hi: jax.Array
        # best sufficient-decrease point seen (fallback)
        t_best: jax.Array
        f_best: jax.Array
        g_best: jax.Array
        has_best: jax.Array
        # accepted point
        t_acc: jax.Array
        f_acc: jax.Array
        g_acc: jax.Array
        success: jax.Array
        # no point left in the bracket can meet the curvature condition
        hopeless: jax.Array

    zero = jnp.zeros_like(t_init)
    init = _S(
        stage=jnp.int32(0),
        i=jnp.int32(0),
        t=t_init,
        t_lo=zero,
        f_lo=f0,
        d_lo=dphi0,
        t_hi=zero,
        f_hi=f0,
        t_best=zero,
        f_best=f0,
        g_best=g0,
        has_best=jnp.bool_(False),
        t_acc=zero,
        f_acc=f0,
        g_acc=g0,
        success=jnp.bool_(False),
        hopeless=jnp.bool_(False),
    )

    def cond(s: _S):
        return (s.stage != 2) & (s.i < max_iters) & ~s.hopeless

    def body(s: _S) -> _S:
        f_t, g_t, d_t = eval_step(s.t)
        armijo_fail = (f_t > f0 + C1 * s.t * dphi0) | ((s.i > 0) & (f_t >= s.f_lo) & (s.stage == 0))
        wolfe_ok = (~armijo_fail) & (jnp.abs(d_t) <= -C2 * dphi0)
        # in the zoom, t becomes the upper end unless it improves on the lower
        shrink_hi = armijo_fail | (f_t >= s.f_lo)

        # track best sufficient-decrease point as a fallback
        suff = f_t <= f0 + C1 * s.t * dphi0
        better = suff & ((~s.has_best) | (f_t < s.f_best))
        t_best = jnp.where(better, s.t, s.t_best)
        f_best = jnp.where(better, f_t, s.f_best)
        g_best = jnp.where(better, g_t, s.g_best)
        has_best = s.has_best | suff

        def bracket_step():
            # returns (stage, t, t_lo, f_lo, d_lo, t_hi, f_hi, accept)
            enter_zoom_hi = armijo_fail
            enter_zoom_swap = (~armijo_fail) & (~wolfe_ok) & (d_t >= 0)
            stage = jnp.where(wolfe_ok, 2, jnp.where(enter_zoom_hi | enter_zoom_swap, 1, 0))
            # zoom brackets
            t_lo = jnp.where(enter_zoom_hi, s.t_lo, jnp.where(enter_zoom_swap, s.t, s.t))
            f_lo = jnp.where(enter_zoom_hi, s.f_lo, jnp.where(enter_zoom_swap, f_t, f_t))
            d_lo = jnp.where(enter_zoom_hi, s.d_lo, jnp.where(enter_zoom_swap, d_t, d_t))
            t_hi = jnp.where(enter_zoom_hi, s.t, jnp.where(enter_zoom_swap, s.t_lo, s.t_hi))
            f_hi = jnp.where(enter_zoom_hi, f_t, jnp.where(enter_zoom_swap, s.f_lo, s.f_hi))
            # next trial: midpoint if zooming, expand if still bracketing
            t_next = jnp.where(stage == 1, 0.5 * (t_lo + t_hi), s.t * 2.0)
            return stage, t_next, t_lo, f_lo, d_lo, t_hi, f_hi

        def zoom_step():
            stage = jnp.where(wolfe_ok, 2, jnp.int32(1))
            # if new lo, possibly swap hi to old lo when derivative points past
            swap = (~shrink_hi) & (d_t * (s.t_hi - s.t_lo) >= 0)
            t_hi = jnp.where(shrink_hi, s.t, jnp.where(swap, s.t_lo, s.t_hi))
            f_hi = jnp.where(shrink_hi, f_t, jnp.where(swap, s.f_lo, s.f_hi))
            t_lo = jnp.where(shrink_hi, s.t_lo, s.t)
            f_lo = jnp.where(shrink_hi, s.f_lo, f_t)
            d_lo = jnp.where(shrink_hi, s.d_lo, d_t)
            t_next = 0.5 * (t_lo + t_hi)
            return stage, t_next, t_lo, f_lo, d_lo, t_hi, f_hi

        b = bracket_step()
        z = zoom_step()
        in_zoom = s.stage == 1
        stage = jnp.where(in_zoom, z[0], b[0])
        t_next = jnp.where(in_zoom, z[1], b[1])
        t_lo = jnp.where(in_zoom, z[2], b[2])
        f_lo = jnp.where(in_zoom, z[3], b[3])
        d_lo = jnp.where(in_zoom, z[4], b[4])
        t_hi = jnp.where(in_zoom, z[5], b[5])
        f_hi = jnp.where(in_zoom, z[6], b[6])

        accepted = stage == 2
        # t became the bracket's upper end while its slope is still steeper
        # than the curvature condition allows (see the docstring)
        upper = jnp.where(in_zoom, shrink_hi, armijo_fail)
        hopeless = (~accepted) & upper & (d_t < C2 * dphi0)
        return _S(
            stage=stage,
            i=s.i + 1,
            t=t_next,
            t_lo=t_lo,
            f_lo=f_lo,
            d_lo=d_lo,
            t_hi=t_hi,
            f_hi=f_hi,
            t_best=t_best,
            f_best=f_best,
            g_best=g_best,
            has_best=has_best,
            t_acc=jnp.where(accepted, s.t, s.t_acc),
            f_acc=jnp.where(accepted, f_t, s.f_acc),
            g_acc=jnp.where(accepted, g_t, s.g_acc),
            success=s.success | accepted,
            hopeless=hopeless,
        )

    o = jax.lax.while_loop(cond, body, init)

    # Fallback: best sufficient-decrease point seen (t=0 state if none).
    use_acc = o.success
    return LineSearchResult(
        t=jnp.where(use_acc, o.t_acc, jnp.where(o.has_best, o.t_best, 0.0)),
        f=jnp.where(use_acc, o.f_acc, jnp.where(o.has_best, o.f_best, f0)),
        g=jnp.where(use_acc, o.g_acc, o.g_best),
        success=use_acc | o.has_best,
        evaluations=o.i,
    )
