"""The strong-Wolfe line search (``opt/linesearch.py``) on one-dimensional
functions written here: an ordinary search accepts what it accepted before,
and a search that starts at the rounding floor of a converged objective
stops once a trial whose slope is still too steep for the curvature
condition reads no decrease, where it used to bisect through all of its
evaluations.

Not in ``test_optimizers.py`` because that module is marked ``slow`` as a
whole and the tier-1 run deselects it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.opt.linesearch import C1, C2, strong_wolfe_search

MAX_ITERS = 25
F0 = 3870.27392578125  # the scale of the objective where a fit's last search ran out
ULP = float(np.spacing(np.float32(F0)))


def _quadratic(f0, dphi0, curvature):
    """phi(t) = f0 + dphi0 t + curvature t^2 / 2, its gradient standing in
    for the full one (a [1] vector)."""

    def eval_step(t):
        f = f0 + dphi0 * t + 0.5 * curvature * t * t
        slope = dphi0 + curvature * t
        return f, jnp.reshape(slope, (1,)), slope

    return eval_step


def _floor(f0, dphi0, above):
    """A converged objective as float32 sees it: the predicted decrease is a
    few last places, and every trial reads ``f0`` plus ``above(t)`` of them;
    the slope is the quadratic's with its minimum at t = 1."""

    def eval_step(t):
        f = jnp.float32(f0) + above(t) * jnp.float32(ULP)
        slope = dphi0 * (1.0 - t)
        return f, jnp.reshape(slope, (1,)), slope

    return eval_step


def _search(eval_step, f0, dphi0, t_init=1.0):
    f0, dphi0 = jnp.float32(f0), jnp.float32(dphi0)
    return strong_wolfe_search(
        eval_step, f0, jnp.zeros((1,), jnp.float32), dphi0, jnp.float32(t_init), MAX_ITERS
    )


@pytest.mark.parametrize(
    "f0, dphi0, curvature, t_accepted, evaluations",
    [
        (1.0, -1.0, 1.0 / 0.3, 0.5, 2),    # overshoot, one bisection
        (1.0, -1.0, 0.04, 4.0, 3),         # undershoot, two expansions
        (F0, -4.0, 4.0, 1.0, 1),           # the quasi-Newton step at once
        (F0, -1e-2, 1e-2 / 0.3, 0.5, 2),   # a small step far above the floor
    ],
)
def test_an_ordinary_search_accepts_the_wolfe_point(f0, dphi0, curvature, t_accepted, evaluations):
    ls = _search(_quadratic(f0, dphi0, curvature), f0, dphi0)
    assert bool(ls.success)
    assert float(ls.t) == t_accepted
    assert int(ls.evaluations) == evaluations
    f_t = f0 + dphi0 * t_accepted + 0.5 * curvature * t_accepted**2
    assert f_t <= f0 + C1 * t_accepted * dphi0
    assert abs(dphi0 + curvature * t_accepted) <= 0.9 * abs(dphi0)


def _evaluations_to_the_floor():
    """Trials of the bisection t = 1, 1/2, 1/4, ... down to the first whose
    slope, ``dphi0 (1 - t)``, is steeper than ``C2 dphi0``: the search's
    stop, counted independently of its code."""
    k = 0
    while 1.0 - 2.0**-k <= C2:
        k += 1
    return k + 1


@pytest.mark.parametrize("dphi0", [-9.4e-4, -4e-3, -3e-2])
def test_a_search_on_the_rounding_floor_stops_with_no_step(dphi0):
    """Every trial reads a last place above f0: no sufficient decrease
    anywhere, so the search hands back t = 0 and failure, as it did after
    all of its evaluations, within the bisections that the floor allows."""
    ls = _search(_floor(F0, dphi0, lambda t: jnp.where(t > 0, 1.0, 0.0)), F0, dphi0)
    assert not bool(ls.success)
    assert float(ls.t) == 0.0
    assert float(ls.f) == np.float32(F0)
    assert int(ls.evaluations) == _evaluations_to_the_floor() < MAX_ITERS


def test_a_search_on_the_floor_keeps_its_sufficient_decrease_point():
    """Trials from t = 0.1 up read a last place high, the smaller ones read
    f0, where the slope is still too steep for the curvature condition: the
    first of those is sufficient decrease (f0 + C1 t dphi0 rounds to f0) and
    ends the search, which hands it back."""
    dphi0 = -3e-2
    ls = _search(_floor(F0, dphi0, lambda t: jnp.where(t >= 0.1, 1.0, 0.0)), F0, dphi0)
    assert bool(ls.success)
    assert float(ls.t) == 0.0625
    assert float(ls.f) == np.float32(F0)
    assert int(ls.evaluations) == _evaluations_to_the_floor()


def test_lanes_stop_each_at_its_own_floor():
    """Under vmap (the random effects' lanes) a lane on the floor stops at
    its own count, and an ordinary lane beside it accepts as it would alone."""
    f0 = jnp.asarray([F0, 1.0], jnp.float32)
    dphi0 = jnp.asarray([-9.4e-4, -1.0], jnp.float32)
    curvature = jnp.asarray([9.4e-4, 1.0 / 0.3], jnp.float32)
    floor = jnp.asarray([1.0, 0.0], jnp.float32)

    def one(f0, dphi0, curvature, floor):
        def eval_step(t):
            f = jnp.where(
                floor > 0,
                f0 + jnp.where(t > 0, ULP, 0.0),
                f0 + dphi0 * t + 0.5 * curvature * t * t,
            )
            slope = dphi0 + curvature * t
            return f, jnp.reshape(slope, (1,)), slope

        return strong_wolfe_search(
            eval_step, f0, jnp.zeros((1,), jnp.float32), dphi0, jnp.float32(1.0), MAX_ITERS
        )

    ls = jax.vmap(one)(f0, dphi0, curvature, floor)
    assert [bool(x) for x in ls.success] == [False, True]
    assert [float(x) for x in ls.t] == [0.0, 0.5]
    assert [int(x) for x in ls.evaluations] == [_evaluations_to_the_floor(), 2]
