"""The L-BFGS / OWL-QN curvature history as ``opt/lbfgs.py`` lays it out
(``[m, ceil(d/128), 128]``, a row padded to a multiple of 128) against a
float64 NumPy two-loop written here, which shares nothing with ``opt/``.

Not in ``test_optimizers.py`` because that module is marked ``slow`` as a
whole and the tier-1 run deselects it; these cases are small and count there.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.opt.lbfgs import (
    history_zeros,
    two_loop_direction,
    update_history,
)

M = 6
# history width -> the shape of one row
ROW_SHAPES = {16: (1, 128), 1024: (8, 128), 1152: (9, 128), 1300: (11, 128)}
# pairs inserted before the direction is taken
COUNTS = {"empty": 0, "one": 1, "m-1": M - 1, "m": M, "wrapped": M + 7}


@functools.lru_cache(maxsize=None)
def _programs(vmapped: bool):
    direction, update = two_loop_direction, update_history
    if vmapped:
        direction, update = jax.vmap(direction), jax.vmap(update)
    return jax.jit(direction), jax.jit(update)


def _pair(rng, d):
    """A pair with s.y > 0 (y = A s for a positive diagonal A)."""
    s = rng.normal(size=d).astype(np.float32)
    y = (s * rng.uniform(0.5, 2.0, size=d)).astype(np.float32)
    return s, y


def _stored(v, hdtype):
    """What a row of storage dtype ``hdtype`` keeps of ``v``, in float64."""
    return np.asarray(jnp.asarray(v).astype(hdtype).astype(jnp.float32), np.float64)


class _Ring:
    """The reference: a ring of the last ``M`` accepted pairs and the plain
    two-loop recursion (Nocedal & Wright, algorithm 7.4) in float64."""

    def __init__(self, hdtype):
        self.hdtype = hdtype
        self.pairs = []

    def insert(self, s, y):
        rho = 1.0 / float(np.dot(s.astype(np.float64), y.astype(np.float64)))
        self.pairs = (self.pairs + [(_stored(s, self.hdtype), _stored(y, self.hdtype), rho)])[-M:]

    def direction(self, g):
        q = g.astype(np.float64)
        alphas = []
        for s, y, rho in reversed(self.pairs):
            a = rho * np.dot(s, q)
            q = q - a * y
            alphas.append(a)
        if self.pairs:
            s, y, _ = self.pairs[-1]
            q = q * (np.dot(s, y) / np.dot(y, y))
        for (s, y, rho), a in zip(self.pairs, reversed(alphas)):
            q = q + (a - rho * np.dot(y, q)) * s
        return -q


def _filled(rng, d, hdtype, count):
    """A history after ``count`` accepted inserts, and its reference."""
    _, update = _programs(False)
    state = (
        history_zeros(M, d, hdtype),
        history_zeros(M, d, hdtype),
        jnp.zeros((M,), jnp.float32),
        jnp.int32(0),
    )
    ring = _Ring(hdtype)
    for _ in range(count):
        s, y = _pair(rng, d)
        state = update(*state, jnp.asarray(s), jnp.asarray(y))
        ring.insert(s, y)
    assert int(state[3]) == count
    return state, ring


def _assert_direction(got, ring, g):
    want = ring.direction(g)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), want, rtol=2e-4, atol=2e-5 * np.abs(want).max()
    )


@pytest.mark.parametrize("hdtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d", list(ROW_SHAPES))
@pytest.mark.parametrize("case", list(COUNTS) + ["rejected", "vmapped"])
def test_history_matches_float64_two_loop(rng, case, d, hdtype):
    direction, update = _programs(False)
    g = rng.normal(size=d).astype(np.float32)

    if case == "vmapped":
        # two lanes of one program whose rings stand at different counts
        lanes = [_filled(rng, d, hdtype, c) for c in (2, M + 3)]
        batched = jax.tree.map(lambda *xs: jnp.stack(xs), *[st for st, _ in lanes])
        s, y = _pair(rng, d)
        pair = jnp.stack([jnp.asarray(s)] * 2), jnp.stack([jnp.asarray(y)] * 2)
        vdirection, vupdate = _programs(True)
        batched = vupdate(*batched, *pair)
        np.testing.assert_array_equal(np.asarray(batched[3]), [3, M + 4])
        got = vdirection(jnp.stack([jnp.asarray(g)] * 2), *batched)
        for lane, (_, ring) in enumerate(lanes):
            ring.insert(s, y)
            _assert_direction(got[lane], ring, g)
        return

    count = COUNTS.get(case, M + 2)
    state, ring = _filled(rng, d, hdtype, count)
    s_hist, y_hist = state[:2]
    assert s_hist.shape == y_hist.shape == (M,) + ROW_SHAPES[d]
    assert s_hist.dtype == hdtype
    # a row's padding past d stays zero, so it adds nothing to a dot product
    flat = np.asarray(s_hist.astype(jnp.float32)).reshape(M, -1)
    assert not flat[:, d:].any()
    assert flat[: min(count, M), :d].any(axis=1).all()

    if case == "rejected":
        # s.y < 0: the curvature guard keeps the pair out, and every buffer
        # is bit for bit what it was
        s, y = _pair(rng, d)
        after = update(*state, jnp.asarray(s), jnp.asarray(-y))
        for before, now in zip(state, after):
            np.testing.assert_array_equal(
                np.asarray(before.astype(jnp.float32)), np.asarray(now.astype(jnp.float32))
            )
        state = after

    _assert_direction(direction(jnp.asarray(g), *state), ring, g)
