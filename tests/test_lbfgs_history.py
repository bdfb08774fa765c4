"""The L-BFGS / OWL-QN curvature history as ``opt/lbfgs.py`` lays it out
(``[m, d]`` under 128 coefficients, else ``[m, ceil(d/128), 128]``, a row
padded to a multiple of 128) and the direction taken from it (the two-loop
recursion over a ``[m, d]`` history, the carried Gram matrix of a tiled
one) against a float64 NumPy two-loop written here, which shares nothing
with ``opt/``.

Not in ``test_optimizers.py`` because that module is marked ``slow`` as a
whole and the tier-1 run deselects it; these cases are small and count there.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import LogisticLoss
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.opt.config import (
    GlmOptimizationConfiguration,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.opt.lbfgs import (
    gram_zeros,
    history_zeros,
    two_loop_direction,
    update_history,
)
from photon_ml_tpu.opt.owlqn import pseudo_gradient
from photon_ml_tpu.opt.solve import solve, solve_chunk, solve_finalize, solve_init

M = 6
# history width -> the shape of one row
ROW_SHAPES = {16: (16,), 1024: (8, 128), 1152: (9, 128), 1300: (11, 128)}
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
    """The reference: a ring of the last ``M`` accepted pairs as the rows
    store them and the plain two-loop recursion (Nocedal & Wright,
    algorithm 7.4) in float64."""

    def __init__(self, hdtype):
        self.hdtype = hdtype
        self.pairs = []

    def insert(self, s, y):
        s, y = _stored(s, self.hdtype), _stored(y, self.hdtype)
        self.pairs = (self.pairs + [(s, y, 1.0 / float(np.dot(s, y)))])[-M:]

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


def _empty(d, hdtype, v):
    s_hist = history_zeros(M, d, hdtype)
    return (s_hist, history_zeros(M, d, hdtype), jnp.zeros((M,), jnp.float32),
            jnp.int32(0), gram_zeros(s_hist, jnp.asarray(v)))


def _filled(rng, d, hdtype, count, v):
    """A history after ``count`` accepted inserts, each with ``v`` as the
    vector the next direction is taken from, and its reference."""
    _, update = _programs(False)
    state = _empty(d, hdtype, v)
    ring = _Ring(hdtype)
    for _ in range(count):
        s, y = _pair(rng, d)
        state = update(*state, jnp.asarray(s), jnp.asarray(y), jnp.asarray(v))
        ring.insert(s, y)
    assert int(state[3]) == count
    return state, ring


def _assert_direction(got, ring, g):
    d, dv = got
    want = ring.direction(g)
    np.testing.assert_allclose(
        np.asarray(d, np.float64), want, rtol=2e-4, atol=2e-5 * np.abs(want).max()
    )
    # d . v, as the Gram form reads it from the coefficients
    np.testing.assert_allclose(float(dv), float(np.dot(want, g)), rtol=2e-4)


@pytest.mark.parametrize("hdtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d", list(ROW_SHAPES))
@pytest.mark.parametrize("case", list(COUNTS) + ["rejected", "vmapped", "pseudo_gradient"])
def test_history_matches_float64_two_loop(rng, case, d, hdtype):
    direction, update = _programs(False)
    g = rng.normal(size=d).astype(np.float32)

    if case == "pseudo_gradient":
        # OWL-QN's v: the pseudo-gradient at a point with coordinates at zero,
        # some of them inside [g - l1, g + l1] (so exactly 0 there)
        w = rng.normal(size=d).astype(np.float32) * (rng.random(d) < 0.5)
        g = np.asarray(pseudo_gradient(jnp.asarray(w), jnp.asarray(g), jnp.float32(0.7)))
        assert (g == 0).any()

    if case == "vmapped":
        # two lanes of one program whose rings stand at different counts
        lanes = [_filled(rng, d, hdtype, c, g) for c in (2, M + 3)]
        batched = jax.tree.map(lambda *xs: jnp.stack(xs), *[st for st, _ in lanes])
        s, y = _pair(rng, d)
        two = lambda v: jnp.stack([jnp.asarray(v)] * 2)  # noqa: E731
        vdirection, vupdate = _programs(True)
        batched = vupdate(*batched, two(s), two(y), two(g))
        np.testing.assert_array_equal(np.asarray(batched[3]), [3, M + 4])
        got = vdirection(two(g), *batched)
        for lane, (_, ring) in enumerate(lanes):
            ring.insert(s, y)
            _assert_direction((got[0][lane], got[1][lane]), ring, g)
        return

    count = COUNTS.get(case, M + 2)
    state, ring = _filled(rng, d, hdtype, count, g)
    s_hist, y_hist = state[:2]
    assert s_hist.shape == y_hist.shape == (M,) + ROW_SHAPES[d]
    assert s_hist.dtype == hdtype
    # a row's padding past d stays zero, so it adds nothing to a dot product
    flat = np.asarray(s_hist.astype(jnp.float32)).reshape(M, -1)
    assert not flat[:, d:].any()
    assert flat[: min(count, M), :d].any(axis=1).all()
    # a [m, d] history carries no Gram matrix; a tiled one (2m+1)^2
    assert state[4].shape == ((0, 0) if d < 128 else (2 * M + 1,) * 2)

    if case == "rejected":
        # s.y < 0: the curvature guard keeps the pair out, and the rows, the
        # ring's counts and the pairs' products are bit for bit what they
        # were; v's products are taken again
        s, y = _pair(rng, d)
        after = update(*state, jnp.asarray(s), jnp.asarray(-y), jnp.asarray(g))
        for before, now in zip(state[:4], after[:4]):
            np.testing.assert_array_equal(
                np.asarray(before.astype(jnp.float32)), np.asarray(now.astype(jnp.float32))
            )
        np.testing.assert_array_equal(np.asarray(state[4])[:-1, :-1], np.asarray(after[4])[:-1, :-1])
        np.testing.assert_allclose(np.asarray(state[4]), np.asarray(after[4]), rtol=1e-5)
        state = after

    _assert_direction(direction(jnp.asarray(g), *state), ring, g)


@pytest.mark.parametrize("hdtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [1152, 1300])
def test_the_carried_gram_matrix_is_the_history_s(rng, d, hdtype):
    """``M + 7`` inserts, every third of them rejected by the curvature guard,
    each with a new ``v``: the Gram matrix carried one row and column at a
    time equals the one recomputed in float64 from the rows as stored and
    the last ``v``, to float32 rounding."""
    _, update = _programs(False)
    v = rng.normal(size=d).astype(np.float32)
    state = _empty(d, hdtype, v)
    for i in range(M + 7):
        s, y = _pair(rng, d)
        v = rng.normal(size=d).astype(np.float32)
        state = update(*state, jnp.asarray(s), jnp.asarray(-y if i % 3 == 2 else y), jnp.asarray(v))
    assert int(state[3]) == M + 7 - (M + 7) // 3

    rows = lambda h: np.asarray(h.astype(jnp.float32), np.float64).reshape(M, -1)[:, :d]  # noqa: E731
    basis = np.concatenate([rows(state[0]), rows(state[1]), v.astype(np.float64)[None]])
    want = basis @ basis.T
    scale = np.sqrt(np.outer(np.diag(want), np.diag(want)))
    np.testing.assert_array_less(np.abs(np.asarray(state[4], np.float64) - want), 1e-6 * scale)


def test_a_chunked_solve_is_the_unsplit_solve_bit_for_bit(rng):
    """L-BFGS and OWL-QN at 1,100 coefficients (a tiled history, the Gram
    form): a solve run in chunks of 3 iterations, the Gram matrix carried
    between them with the rest of the state, returns what the unsplit solve
    does, bit for bit."""
    n, d = 512, 1100
    X = (rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.02)).astype(np.float32)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ rng.normal(size=d)))).astype(np.float32)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    obj = make_glm_objective(LogisticLoss)
    for reg, weight in ((RegularizationType.L2, 0.1), (RegularizationType.L1, 0.01)):
        cfg = GlmOptimizationConfiguration(
            regularization=RegularizationContext(reg), regularization_weight=weight
        )
        whole = jax.jit(lambda w0: solve(obj, w0, data, cfg))(jnp.zeros(d))
        state = jax.jit(lambda w0: solve_init(obj, w0, data, cfg))(jnp.zeros(d))
        assert state.gram.shape == (2 * 10 + 1,) * 2
        chunk = jax.jit(lambda st: solve_chunk(obj, st, data, cfg, num_iters=3))
        for _ in range(34):  # 34 chunks x 3 iterations cover max_iterations=100
            state = chunk(state)
        split = jax.jit(lambda st: solve_finalize(st, cfg))(state)
        assert int(whole.iterations) > 10  # the ring wrapped
        assert int(split.iterations) == int(whole.iterations)
        np.testing.assert_array_equal(np.asarray(split.w), np.asarray(whole.w))
        np.testing.assert_array_equal(np.asarray(split.value_history), np.asarray(whole.value_history))


@pytest.mark.parametrize("d", sorted(ROW_SHAPES))
def test_a_recursion_over_a_row_of_lanes_is_written_out(d):
    """No direction holds a loop for the chip to run step by step: a history
    under a row of lanes (the vmapped per-entity solves, 16 coefficients)
    has its recursion over the rows written out, and a wider one (the fixed
    effect's) runs its recursion on the Gram matrix's coefficients, written
    out too."""
    hist = jax.ShapeDtypeStruct((M,) + ROW_SHAPES[d], jnp.float32)
    vec = jax.ShapeDtypeStruct((d,), jnp.float32)
    rho = jax.ShapeDtypeStruct((M,), jnp.float32)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    gram = jax.eval_shape(gram_zeros, hist, vec)
    text = jax.jit(two_loop_direction).lower(vec, hist, hist, rho, count, gram).as_text()
    assert "stablehlo.while" not in text


def test_a_narrow_tiled_history_is_written_out_too():
    """The one-row ring ``[m, 1, 128]`` of a 128-wide solve is a tiled
    history: its direction comes from the Gram matrix, with no loop left."""
    hist = jax.ShapeDtypeStruct((M, 1, 128), jnp.float32)
    vec = jax.ShapeDtypeStruct((128,), jnp.float32)
    rho = jax.ShapeDtypeStruct((M,), jnp.float32)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    gram = jax.ShapeDtypeStruct((2 * M + 1, 2 * M + 1), jnp.float32)
    text = jax.jit(two_loop_direction).lower(vec, hist, hist, rho, count, gram).as_text()
    assert "stablehlo.while" not in text


@pytest.mark.parametrize("d", [16, 200], ids=["rows", "tiled"])
def test_a_direction_that_does_not_descend_restarts_from_minus_g(rng, d):
    """A history whose one pair has negative curvature (``y = -s``, which the
    guard would never admit) turns ``-H g`` uphill: L-BFGS takes ``-g``
    instead and counts the restart in ``SolveResult.descent_restarts``."""
    n = 64
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    data = LabeledData.create(DenseFeatures(matrix=jnp.asarray(X)), jnp.asarray(y))
    obj = make_glm_objective(LogisticLoss)
    cfg = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2), regularization_weight=0.1
    )
    state = solve_init(obj, jnp.zeros(d), data, cfg)
    g = np.asarray(state.g, np.float64)
    s_hist = np.zeros(state.s_hist.shape, np.float32)
    s_hist.reshape(s_hist.shape[0], -1)[0, :d] = g
    if d >= 128:
        m = state.rho.shape[0]
        basis = np.zeros((2 * m + 1, d))
        basis[0], basis[m], basis[2 * m] = g, -g, g
        state = state._replace(gram=jnp.asarray(basis @ basis.T, jnp.float32))
    state = state._replace(
        s_hist=jnp.asarray(s_hist), y_hist=-jnp.asarray(s_hist),
        rho=state.rho.at[0].set(-1.0 / float(g @ g)), count=jnp.int32(1),
    )
    assert state.descent_restarts.dtype == jnp.int32
    after = solve_finalize(solve_chunk(obj, state, data, cfg, num_iters=1), cfg)
    assert int(after.descent_restarts) == 1
    assert float(after.value) < float(state.f)  # the step along -g went down


def test_a_member_lane_grows_by_the_counter_alone():
    """The state of one vmapped per-entity L-BFGS lane at d = 16 (a ``rows``
    history): 1,880 bytes of the fields it had before the restart counter,
    and the counter's 4; its Gram matrix is ``[0, 0]``."""
    E, d, n = 8, 16, 4
    data = LabeledData(features=DenseFeatures(matrix=jnp.zeros((E, n, d))), labels=jnp.zeros((E, n)),
                       offsets=jnp.zeros((E, n)), weights=jnp.ones((E, n)), norm=None)
    obj = make_glm_objective(LogisticLoss)
    cfg = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2), regularization_weight=0.1
    )
    state = jax.eval_shape(jax.vmap(lambda w0, dd: solve_init(obj, w0, dd, cfg)),
                           jnp.zeros((E, d)), data)
    assert state.gram.shape == (E, 0, 0)
    lane = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(state)) // E
    assert lane == 1880 + 4
