"""L-BFGS as a fully on-device ``lax.while_loop`` program.

Reference parity: optimization/LBFGS.scala:39 — which delegated to
``breeze.optimize.LBFGS`` on the Spark driver, with one cluster job per
objective evaluation. Here the whole solve (two-loop recursion, strong-Wolfe
line search, convergence checks) is one XLA program: no host round-trips,
vmap-able so millions of per-entity random-effect solves batch into one
kernel launch.

Defaults match the reference (maxIter=100, m=10, tol=1e-7,
LBFGS.scala:147-152). Box constraints are applied by projection after each
accepted step (LBFGS.scala:72).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.losses.objective import GlmObjective
from photon_ml_tpu.opt.config import OptimizerConfig
from photon_ml_tpu.opt.linesearch import strong_wolfe_search
from photon_ml_tpu.opt.state import (
    SolveResult,
    absolute_tolerances,
    function_values_converged,
    gradient_converged,
)
from photon_ml_tpu.types import ConvergenceReason


_LANES = 128


def history_layout(dim: int) -> str:
    """How ``history_zeros`` lays out the s/y buffers of a solve of width
    ``dim``: ``rows`` (``[m, d]``) under one row of lanes, ``tiled``
    (``[m, ceil(d/128), 128]``) from there up. What a ``glm/solve`` span of
    an L-BFGS or OWL-QN solve reports as its ``history_layout`` (TRON keeps
    no history and reports "none")."""
    return "rows" if dim < _LANES else "tiled"


def direction_form(layout: str) -> str:
    """How the quasi-Newton direction of a solve whose history lies as
    ``layout`` (``history_layout``, or "none" for TRON) is formed: ``gram``
    from the carried Gram matrix (``gram_zeros``), ``recursion`` by the
    two-loop over the rows, ``none`` without a history. What a ``glm/solve``
    span reports as its ``direction``."""
    return {"none": "none", "rows": "recursion", "tiled": "gram"}[layout]


def history_zeros(m: int, dim: int, dtype) -> jax.Array:
    """An empty s/y ring buffer for a solve of static width ``dim``; the
    shape alone decides the layout (``history_layout``).

    From 128 coefficients up, ``[m, ceil(dim/128), 128]``, rows zero-padded
    to a multiple of 128 (zeros add nothing to a dot product). As ``[m, d]``
    float32 the TPU tiled a wide buffer (8, 128): eight rows interleaved in
    one tile and ten rows padded to sixteen, so reading one row moved eight
    and the buffer took 1.6x its data. With a row to itself a row is
    contiguous, nothing pads, and its flattening to a vector is a bitcast.

    Under 128 coefficients (the vmapped per-entity solves), ``[m, d]``:
    vmapped to ``[E, m, d]`` the chip lays the entity axis minor, in lanes,
    so nothing pads (640 bytes an entity at ``m = 10``, ``d = 16``), where
    ``[E, m, 1, 128]`` padded every row to 128 lanes (5,120 bytes, eight
    times the data; ``tests/test_tpu_compile.py``). The buffers' rank is
    what ``gram_zeros``, ``two_loop_direction`` and ``update_history`` go
    by."""
    if history_layout(dim) == "rows":
        return jnp.zeros((m, dim), dtype=dtype)
    return jnp.zeros((m, -(-dim // _LANES), _LANES), dtype=dtype)


def gram_zeros(s_hist: jax.Array, v: jax.Array) -> jax.Array:
    """The inner products a solve carries beside its empty history
    ``s_hist`` (``history_zeros``), ``v`` being the vector whose direction
    its first iteration takes: ``g`` for L-BFGS, the pseudo-gradient for
    OWL-QN.

    A ``tiled`` history carries the symmetric ``[2m+1, 2m+1]`` Gram matrix
    of the basis ``[S; Y; v]`` (index ``i`` is row ``i`` of ``s_hist``,
    ``m + i`` row ``i`` of ``y_hist``, ``2m`` the vector), in the working
    dtype, which ``update_history`` keeps one row and column at a time.
    ``two_loop_direction`` then runs the recursion on ``2m+1`` coefficients
    and reads the history once (the vector-free L-BFGS of Chen, Wang and
    Zhou, "Large-scale L-BFGS using MapReduce", NIPS 2014). Before any pair
    only ``v.v`` is set. A ``rows`` history (the vmapped per-entity solves)
    carries ``[0, 0]``: its recursion reads its narrow rows, and a lane's
    state does not grow."""
    if s_hist.ndim == 2:
        return jnp.zeros((0, 0), v.dtype)
    k = 2 * s_hist.shape[0] + 1
    return jnp.zeros((k, k), v.dtype).at[-1, -1].set(jnp.sum(v * v))


def _as_history_row(vec: jax.Array, hist: jax.Array, dtype=None) -> jax.Array:
    """``vec`` [dim] zero-padded and shaped as a row of ``hist``, in ``dtype``
    (the buffer's own storage dtype by default)."""
    pad = math.prod(hist.shape[1:]) - vec.shape[-1]
    dtype = hist.dtype if dtype is None else dtype
    return jnp.pad(vec.astype(dtype), (0, pad)).reshape(hist.shape[1:])


class _LbfgsState(NamedTuple):
    """Resumable L-BFGS loop state: everything the next outer iteration
    needs, including the absolute tolerances derived from the initial point
    (so a solve can be split into chunks — ``lbfgs_chunk`` — and each chunk
    continues exactly where the previous one stopped).

    ``s_hist`` / ``y_hist`` are what ``history_zeros`` makes for ``d``:
    ``[m, d]`` under 128 coefficients, else ``[m, ceil(d/128), 128]``, so
    that a row is contiguous in HBM; ``gram`` is what ``gram_zeros`` makes
    beside them, with ``v = g``."""

    w: jax.Array          # [d]
    f: jax.Array
    g: jax.Array          # [d]
    s_hist: jax.Array     # steps ring buffer (history_zeros)
    y_hist: jax.Array     # gradient-diff ring buffer (history_zeros)
    rho: jax.Array        # [m] 1/(s.y)
    count: jax.Array      # int32 number of valid history pairs
    gram: jax.Array       # [2m+1, 2m+1] products of [S; Y; g] ([0, 0] for rows)
    it: jax.Array         # int32 outer iteration
    evals: jax.Array      # int32 objective.value_and_grad calls so far
    descent_restarts: jax.Array  # int32 iterations that fell back to -g
    reason: jax.Array     # int32 ConvergenceReason
    history: jax.Array    # [max_iter+1] objective values
    w_hist: jax.Array     # [max_iter+1, d] coefficients (or [0] when off)
    abs_f_tol: jax.Array  # scalar, derived from f0 at init
    abs_g_tol: jax.Array  # scalar, derived from ||g0|| at init


def _tile_dots(rows: jax.Array, x: jax.Array) -> jax.Array:
    """``rows[..., T, 128] . x[T, 128]`` over the last two axes, a vector-unit
    multiply-reduce in the working dtype (never a contraction that the chip
    could run as bfloat16 passes). Summed over the tiles first and the lanes
    last, so that the TPU compiler fuses every such sum over the same rows
    into one pass that reads each row once."""
    return jnp.sum(jnp.sum(rows * x, axis=-2), axis=-1)


def _recursion(g, s_hist, y_hist, rho, count) -> jax.Array:
    """The two-loop recursion over a ``rows`` history: newest pair in row 0
    and the older ones below it, so the recursion reads row ``i`` at its
    ``i``-th step whatever ``count`` is (vmapped over entities whose counts
    differ, a read is a slice where a ring's per-lane slot was a gather,
    which the chip answered by laying every lane's buffer out again, padded
    to 128 lanes). Row ``i`` is valid iff ``i < count``; invalid rows have
    rho=0, so their updates are algebraic no-ops (alpha = rho*(s.q) = 0).
    Its 2m steps are written out: each is then a fused operation or two,
    where a loop step was about ten launches, every iteration of every
    tile."""
    m = rho.shape[0]
    wd = g.dtype

    def bwd(i, carry):
        q, alphas = carry
        r = jnp.where(i < count, rho[i], 0.0)
        a = r * jnp.dot(s_hist[i].astype(wd), q)
        return q - a * y_hist[i].astype(wd), alphas.at[i].set(a)

    q, alphas = jax.lax.fori_loop(0, m, bwd, (g, jnp.zeros_like(rho)), unroll=True)

    # initial Hessian scaling gamma = (s.y)/(y.y) of the newest valid pair
    s_new = s_hist[0].astype(wd)
    y_new = y_hist[0].astype(wd)
    sy = jnp.dot(s_new, y_new)
    yy = jnp.dot(y_new, y_new)
    gamma = jnp.where((count > 0) & (yy > 0), sy / jnp.maximum(yy, 1e-30), 1.0)

    def fwd(i, r_vec):
        idx = m - 1 - i  # oldest first among the last m
        valid = idx < count
        r = jnp.where(valid, rho[idx], 0.0)
        beta = r * jnp.dot(y_hist[idx].astype(wd), r_vec)
        return r_vec + jnp.where(valid, (alphas[idx] - beta), 0.0) * s_hist[idx].astype(wd)

    return -jax.lax.fori_loop(0, m, fwd, gamma * q, unroll=True)


def _gram_coefficients(gram, rho, count):
    """The two-loop recursion run on the coefficients of ``q`` over the basis
    ``[S; Y; v]`` (``gram_zeros``), from ``q = v``: each inner product it
    needs is a row of the Gram matrix against the coefficients. The ring's
    slots are taken newest first (slot ``(count - 1 - i) mod m`` at step
    ``i``), and the last ``count`` of them are valid; an invalid slot has
    rho=0 and adds nothing. Returns the coefficients of ``H v`` on the basis
    as it is stored, and ``v . H v``."""
    m = rho.shape[0]
    slots = jnp.mod(count - 1 - jnp.arange(m), m)
    basis = jnp.concatenate([slots, m + slots, jnp.array([2 * m])])
    ordered = gram[basis][:, basis]  # newest first
    r = jnp.where(jnp.arange(m) < count, rho[slots], 0.0)

    c = jnp.zeros((2 * m + 1,), gram.dtype).at[2 * m].set(1.0)
    alphas = []
    for i in range(m):
        a = r[i] * jnp.sum(ordered[i] * c)
        c = c.at[m + i].add(-a)
        alphas.append(a)
    sy, yy = ordered[0, m], ordered[m, m]
    c = c * jnp.where((count > 0) & (yy > 0), sy / jnp.maximum(yy, 1e-30), 1.0)
    for i in reversed(range(m)):
        beta = r[i] * jnp.sum(ordered[m + i] * c)
        c = c.at[i].add(alphas[i] - beta)
    return jnp.zeros_like(c).at[basis].set(c), jnp.sum(ordered[2 * m] * c)


def two_loop_direction(
    v: jax.Array,
    s_hist: jax.Array,
    y_hist: jax.Array,
    rho: jax.Array,
    count: jax.Array,
    gram: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """The quasi-Newton direction ``d = -H v`` over a masked history of the
    last ``m`` pairs, and ``d . v``.

    A ``tiled`` history takes it from the carried Gram matrix: the recursion
    on ``2m+1`` coefficients, then one pass ``d = -(sum_i a_i s_i + sum_i
    b_i y_i + c v)`` that reads each row once (2m + 2 vectors of traffic,
    where the row recursion moved about 10m). ``d . v`` comes from the
    coefficients. A ``rows`` history runs the two-loop recursion over its
    rows (``_recursion``). History may be stored bf16
    (config.history_dtype); rows are cast to the working dtype on read."""
    if s_hist.ndim == 2:
        d = _recursion(v, s_hist, y_hist, rho, count)
        return d, jnp.dot(d, v)
    m, dim, wd = rho.shape[0], v.shape[-1], v.dtype
    coef, vhv = _gram_coefficients(gram, rho, count)
    # written out as one sum of 2m+1 scaled rows, so that it is one pass
    # (two reductions over the rows' axis were two, and a third to add
    # them); the padding is zero in every row and in v, so it stays zero
    hv = coef[2 * m] * _as_history_row(v, s_hist, wd)
    for i in range(m):
        hv = hv + coef[i] * s_hist[i].astype(wd) + coef[m + i] * y_hist[i].astype(wd)
    if math.prod(s_hist.shape[1:]) > dim:
        # cut once the sum is written: a cut fused into the sum split it
        hv = jax.lax.optimization_barrier(hv)
    return -hv.reshape(-1)[:dim], -vhv


def resolve_history_dtype(config: OptimizerConfig, working_dtype) -> jnp.dtype:
    """The storage dtype for s/y ring buffers (config.history_dtype or the
    working dtype) — shared by L-BFGS and OWL-QN."""
    return jnp.dtype(config.history_dtype) if config.history_dtype else working_dtype


def _gram_pass(s_hist, y_hist, count, gram, s_vec, y_vec, v_next):
    """The end of an iteration's one pass over a ``tiled`` history: the pair
    as its rows will hold it and ``v_next``, each against every row of ``S``
    and ``Y`` as they stand and against each other, in one fusion that
    reads each row and each vector once. Returns the curvature guard, ``1 /
    (s.y)`` and the Gram matrix with the pair at slot ``count mod m`` if it
    is accepted, and ``v_next``'s row and column either way."""
    m, wd = s_hist.shape[0], gram.dtype
    s_r, y_r = (_as_history_row(x, s_hist).astype(wd) for x in (s_vec, y_vec))
    v_t = _as_history_row(v_next, s_hist, wd)
    # S and Y each with two rows more, [S; s; v] and [Y; y; y], padded and
    # selected inside the fusion that reads them: so the three vectors'
    # products with each other come out of the same pass (a concatenation
    # was written out, and products of their own read the vectors again)
    row = jax.lax.broadcasted_iota(jnp.int32, (m + 2, 1, 1), 0)
    grown = lambda hist: jnp.pad(hist.astype(wd), ((0, 2), (0, 0), (0, 0)))  # noqa: E731
    s_ext = jnp.where(row == m, s_r, jnp.where(row == m + 1, v_t, grown(s_hist)))
    y_ext = jnp.where(row >= m, y_r, grown(y_hist))

    def line(x):
        """``x`` against ``[S; Y; v_next]`` as they stand, and ``x.s``,
        ``x.y``."""
        a, b = _tile_dots(s_ext, x), _tile_dots(y_ext, x)
        return jnp.concatenate([a[:m], b[:m], a[m + 1 :]]), a[m], b[m]

    s_line, ss, sy = line(s_r)
    y_line, _, yy = line(y_r)
    v_old, vs, vy = line(v_t)
    good_pair = sy > 1e-10 * jnp.maximum(yy, 1e-30)

    slot = jnp.mod(count, m)

    def put_line(mat, i, vals):
        return mat.at[i, :].set(vals).at[:, i].set(vals)

    def with_pair(vals, with_s, with_y):
        return vals.at[slot].set(with_s).at[m + slot].set(with_y)

    paired = put_line(gram, slot, with_pair(s_line, ss, sy))
    paired = put_line(paired, m + slot, with_pair(y_line, sy, yy))
    gram = put_line(
        jnp.where(good_pair, paired, gram), 2 * m,
        jnp.where(good_pair, with_pair(v_old, vs, vy), v_old),
    )
    return good_pair, 1.0 / jnp.maximum(sy, 1e-30), gram


def update_history(
    s_hist, y_hist, rho, count, gram, s_vec, y_vec, v_next
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Curvature-guarded insert (skip when s.y too small), casting the pair
    to the buffers' storage dtype, and the Gram matrix brought up to the
    history and to ``v_next``, the vector the next direction is taken from
    — shared by L-BFGS and OWL-QN so their history handling cannot diverge.
    The guard and ``rho = 1 / (s.y)`` are of the pair as the rows hold it,
    so that a bfloat16 history's update keeps the secant equation of the
    pair it stores.

    Into a ``tiled`` history's ring the pair goes at slot ``count mod m``.
    One pass reads ``S`` and ``Y`` once and the pair and ``v_next`` once,
    for ``s``, ``y`` and ``v_next`` against every row and each other (the
    guard's ``s.y`` and ``y.y`` among them); the slot's row and column of
    the Gram matrix then take the new pair's products and the last row and
    column ``v_next``'s. A rejected pair leaves every row and the pairs'
    products as they were, and changes only ``v_next``'s. The guard selects
    on the row, not on the buffer: a rejected pair rewrites one row with
    itself, an accepted one is an in-place dynamic-update-slice.

    A ``rows`` history (newest first, see ``_recursion``) shifts down by a
    row and takes the pair as row 0: ``m`` narrow rows moved, and no slot
    that differs from lane to lane. It carries no Gram matrix."""
    m, wd = rho.shape[0], rho.dtype
    if s_hist.ndim == 2:
        s_row, y_row = _as_history_row(s_vec, s_hist), _as_history_row(y_vec, y_hist)
        s_r, y_r = s_row.astype(wd), y_row.astype(wd)
        sy = jnp.dot(s_r, y_r)
        good_pair = sy > 1e-10 * jnp.maximum(jnp.dot(y_r, y_r), 1e-30)
        inv_sy = 1.0 / jnp.maximum(sy, 1e-30)

        def shift(hist, row):
            return jnp.where(good_pair, jnp.concatenate([row[None], hist[:-1]]), hist)

        s_hist = shift(s_hist, s_row)
        y_hist = shift(y_hist, y_row)
        rho = shift(rho, inv_sy)
        return s_hist, y_hist, rho, jnp.where(good_pair, count + 1, count), gram

    good_pair, inv_sy, gram = _gram_pass(s_hist, y_hist, count, gram, s_vec, y_vec, v_next)
    slot = jnp.mod(count, m)

    def put(hist, vec):
        row = jnp.where(good_pair, _as_history_row(vec, hist), hist[slot])
        return hist.at[slot].set(row)

    s_hist = put(s_hist, s_vec)
    y_hist = put(y_hist, y_vec)
    rho = jnp.where(good_pair, rho.at[slot].set(inv_sy), rho)
    return s_hist, y_hist, rho, jnp.where(good_pair, count + 1, count), gram


def _project_box(w: jax.Array, lower, upper) -> jax.Array:
    if lower is not None:
        w = jnp.maximum(w, lower)
    if upper is not None:
        w = jnp.minimum(w, upper)
    return w


def resolve_box(box, config: OptimizerConfig):
    """(lower, upper, has_box) from a per-coefficient ``box`` override or
    the config's scalar bounds — shared by all three solvers."""
    lo, hi = box if box is not None else (
        config.constraint_lower, config.constraint_upper
    )
    return lo, hi, lo is not None or hi is not None


def lbfgs_init(
    objective: GlmObjective,
    w0: jax.Array,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
) -> _LbfgsState:
    """Evaluate the initial point and build the resumable loop state
    (absolute tolerances included — reference Optimizer.scala:68-71)."""
    m = config.history_length
    max_iter = config.max_iterations
    dim = w0.shape[-1]
    dtype = w0.dtype

    f0, g0 = objective.value_and_grad(w0, data, l2_weight)
    g0_norm = jnp.linalg.norm(g0)
    abs_f_tol, abs_g_tol = absolute_tolerances(f0, g0_norm, config.tolerance)

    hdtype = resolve_history_dtype(config, dtype)
    history0 = jnp.full((max_iter + 1,), jnp.nan, dtype=dtype).at[0].set(f0)
    w_hist0 = (
        jnp.full((max_iter + 1, dim), jnp.nan, dtype=dtype).at[0].set(w0)
        if config.track_coefficients
        else jnp.zeros((0,), dtype=dtype)
    )
    s_hist = history_zeros(m, dim, hdtype)
    return _LbfgsState(
        w=w0,
        f=f0,
        g=g0,
        s_hist=s_hist,
        y_hist=history_zeros(m, dim, hdtype),
        rho=jnp.zeros((m,), dtype=dtype),
        count=jnp.int32(0),
        gram=gram_zeros(s_hist, g0),
        it=jnp.int32(0),
        evals=jnp.int32(1),
        descent_restarts=jnp.int32(0),
        reason=jnp.int32(ConvergenceReason.NOT_CONVERGED.value),
        history=history0,
        w_hist=w_hist0,
        abs_f_tol=abs_f_tol,
        abs_g_tol=abs_g_tol,
    )


def lbfgs_chunk(
    objective: GlmObjective,
    state: _LbfgsState,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    box: Optional[Tuple] = None,
    num_iters: Optional[int] = None,
) -> _LbfgsState:
    """Advance the solve by at most ``num_iters`` outer iterations (None =
    run to convergence/max_iterations). The full solver state — curvature
    ring buffers, step counts, tolerances — is carried in ``state``, so
    chunked execution follows EXACTLY the same per-iterate trajectory as one
    uninterrupted ``while_loop``; only the program boundaries differ. This
    is what lets the random-effect driver pull converged lanes out of a
    vmapped batch every K iterations (estimators/random_effect.py)."""
    max_iter = config.max_iterations
    dtype = state.w.dtype
    box_lo, box_hi, has_box = resolve_box(box, config)
    it_stop = None if num_iters is None else state.it + jnp.int32(num_iters)

    def cond(s: _LbfgsState):
        c = (s.reason == ConvergenceReason.NOT_CONVERGED.value) & (s.it < max_iter)
        if it_stop is not None:
            c = c & (s.it < it_stop)
        return c

    def body(s: _LbfgsState) -> _LbfgsState:
        d, dphi0 = two_loop_direction(s.g, s.s_hist, s.y_hist, s.rho, s.count, s.gram)
        # Safeguard: if not a descent direction (can happen after box
        # projection perturbs the quasi-Newton pairs), restart with -g.
        bad = dphi0 >= 0
        d = jnp.where(bad, -s.g, d)
        dphi0 = jnp.where(bad, -jnp.dot(s.g, s.g), dphi0)

        def eval_step(t):
            w_t = s.w + t * d
            f_t, g_t = objective.value_and_grad(w_t, data, l2_weight)
            return f_t, g_t, jnp.dot(g_t, d)

        # First iteration: t ~ 1/||g|| (Breeze's firstStepSize heuristic);
        # afterwards the natural quasi-Newton step t=1.
        t_init = jnp.where(
            s.count == 0, 1.0 / jnp.maximum(jnp.linalg.norm(d), 1e-12), 1.0
        ).astype(dtype)
        ls = strong_wolfe_search(
            eval_step, s.f, s.g, dphi0, t_init, config.max_line_search_iterations
        )
        evals = s.evals + ls.evaluations

        w_new = s.w + ls.t * d
        w_new = _project_box(w_new, box_lo, box_hi)
        # Projection may have changed the point; recompute f/g only if a box
        # is configured (static branch — no cost otherwise).
        if has_box:
            f_new, g_new = objective.value_and_grad(w_new, data, l2_weight)
            evals = evals + 1
        else:
            f_new, g_new = ls.f, ls.g

        s_vec = w_new - s.w
        y_vec = g_new - s.g
        s_hist, y_hist, rho, count, gram = update_history(
            s.s_hist, s.y_hist, s.rho, s.count, s.gram, s_vec, y_vec, g_new
        )

        it = s.it + 1
        # Convergence checks (reference Optimizer.scala:131-145). A failed
        # line search that produced no movement terminates with
        # OBJECTIVE_NOT_IMPROVING — f_conv is gated on success so a stalled
        # search is never misreported as converged.
        no_step = (~ls.success) | (ls.t <= 0)
        f_conv = ls.success & function_values_converged(s.f, f_new, s.abs_f_tol)
        g_conv = gradient_converged(jnp.linalg.norm(g_new), s.abs_g_tol)
        reason = jnp.where(
            g_conv,
            ConvergenceReason.GRADIENT_CONVERGED.value,
            jnp.where(
                no_step,
                ConvergenceReason.OBJECTIVE_NOT_IMPROVING.value,
                jnp.where(
                    f_conv,
                    ConvergenceReason.FUNCTION_VALUES_CONVERGED.value,
                    jnp.where(
                        it >= max_iter,
                        ConvergenceReason.MAX_ITERATIONS.value,
                        ConvergenceReason.NOT_CONVERGED.value,
                    ),
                ),
            ),
        ).astype(jnp.int32)

        return _LbfgsState(
            w=w_new,
            f=f_new,
            g=g_new,
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            count=count,
            gram=gram,
            it=it,
            evals=evals,
            descent_restarts=s.descent_restarts + bad.astype(jnp.int32),
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


def lbfgs_finalize(
    state: _LbfgsState, config: OptimizerConfig = OptimizerConfig()
) -> SolveResult:
    """Turn a finished (or exhausted) loop state into a SolveResult. A state
    still marked NOT_CONVERGED is reported as MAX_ITERATIONS — callers only
    finalize once the iteration budget is spent."""
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
        hessian_vecs=jnp.zeros_like(state.it),
        rejected_steps=jnp.zeros_like(state.it),
        descent_restarts=state.descent_restarts,
        reason=reason,
        value_history=state.history,
        w_history=state.w_hist if config.track_coefficients else None,
    )


def lbfgs_solve(
    objective: GlmObjective,
    w0: jax.Array,
    data,
    l2_weight: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    box: Optional[Tuple] = None,
) -> SolveResult:
    """Minimize objective over w starting from w0. Pure function of its
    inputs; jit/vmap/shard_map-safe.

    ``box`` = (lower, upper) per-coefficient arrays (either side may be
    None) — the reference's per-feature constraint map
    (GLMSuite.createConstraintFeatureMap); scalar bounds come from the
    config."""
    state = lbfgs_init(objective, w0, data, l2_weight, config)
    state = lbfgs_chunk(objective, state, data, l2_weight, config, box=box)
    return lbfgs_finalize(state, config)
