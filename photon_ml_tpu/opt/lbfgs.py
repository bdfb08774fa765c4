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


# What a ``glm/solve`` span of an L-BFGS or OWL-QN solve reports as its
# ``history_layout`` (TRON keeps no history and reports "none").
HISTORY_LAYOUT = "tiled"
_LANES = 128


def history_zeros(m: int, dim: int, dtype) -> jax.Array:
    """An empty s/y ring buffer for a solve of static width ``dim``:
    ``[m, ceil(dim/128), 128]``, rows zero-padded to a multiple of 128 (zeros
    add nothing to a dot product).

    As ``[m, d]`` float32 the TPU tiled the buffer (8, 128): eight rows
    interleaved in one tile and ten rows padded to sixteen, so reading one
    row moved eight and the buffer took 1.6x its data. With a row to itself a
    row is contiguous, nothing pads, and its flattening to a vector is a
    bitcast. The vmapped per-entity solves (16 or 32 coefficients) get the
    same layout: on the chip they run faster on it than on ``[m, d]``, at
    eight times the bytes of a 16-wide history (PERF.md section 6, PR 35).
    The shape is known here and to the three helpers below, through which
    ``two_loop_direction`` and ``update_history`` go."""
    return jnp.zeros((m, -(-dim // _LANES), _LANES), dtype=dtype)


def _row_width(hist: jax.Array) -> int:
    """Elements in one row of a history buffer, its padding included."""
    return math.prod(hist.shape[1:])


def _history_row(hist: jax.Array, idx, dtype) -> jax.Array:
    """Row ``idx`` of a history buffer as a flat vector of the working dtype,
    its zero padding (if any) included: a bitcast of the row's tiles, where
    a ``[:d]`` slice would write the row out first."""
    return hist[idx].reshape(-1).astype(dtype)


def _as_history_row(vec: jax.Array, hist: jax.Array) -> jax.Array:
    """``vec`` [dim] in the storage dtype and row shape of ``hist``."""
    pad = _row_width(hist) - vec.shape[-1]
    return jnp.pad(vec.astype(hist.dtype), (0, pad)).reshape(hist.shape[1:])


class _LbfgsState(NamedTuple):
    """Resumable L-BFGS loop state: everything the next outer iteration
    needs, including the absolute tolerances derived from the initial point
    (so a solve can be split into chunks — ``lbfgs_chunk`` — and each chunk
    continues exactly where the previous one stopped).

    ``s_hist`` / ``y_hist`` are what ``history_zeros`` makes for ``d``:
    ``[m, ceil(d/128), 128]``, so that a row is contiguous in HBM."""

    w: jax.Array          # [d]
    f: jax.Array
    g: jax.Array          # [d]
    s_hist: jax.Array     # steps ring buffer (history_zeros)
    y_hist: jax.Array     # gradient-diff ring buffer (history_zeros)
    rho: jax.Array        # [m] 1/(s.y)
    count: jax.Array      # int32 number of valid history pairs
    it: jax.Array         # int32 outer iteration
    evals: jax.Array      # int32 objective.value_and_grad calls so far
    reason: jax.Array     # int32 ConvergenceReason
    history: jax.Array    # [max_iter+1] objective values
    w_hist: jax.Array     # [max_iter+1, d] coefficients (or [0] when off)
    abs_f_tol: jax.Array  # scalar, derived from f0 at init
    abs_g_tol: jax.Array  # scalar, derived from ||g0|| at init


def two_loop_direction(
    g: jax.Array, s_hist: jax.Array, y_hist: jax.Array, rho: jax.Array, count: jax.Array
) -> jax.Array:
    """Two-loop recursion over a masked ring buffer.

    History slots are ordered oldest→newest modulo m; slot i is valid iff
    i < count. Invalid slots have rho=0 so their updates are algebraic no-ops
    (alpha = rho*(s.q) = 0), which keeps the loop branch-free.
    """
    m = rho.shape[0]

    # History may be stored bf16 (config.history_dtype); rows are cast to
    # the working dtype on read so every dot/axpy accumulates full precision.
    wd = g.dtype
    # The recursion runs at the rows' padded width (g padded once, the
    # direction cut once): the padding is zero in every row, so it stays
    # zero in q and r and adds nothing to a dot product. No-ops where d is
    # a multiple of 128.
    dim = g.shape[-1]
    g = jnp.pad(g, (0, _row_width(s_hist) - dim))

    def s_row(idx):
        return _history_row(s_hist, idx, wd)

    def y_row(idx):
        return _history_row(y_hist, idx, wd)

    def bwd(i, carry):
        q, alphas = carry
        idx = jnp.mod(count - 1 - i, m)  # newest first
        valid = i < count
        r = jnp.where(valid, rho[idx], 0.0)
        a = r * jnp.dot(s_row(idx), q)
        q = q - a * y_row(idx)
        alphas = alphas.at[idx].set(a)
        return q, alphas

    q, alphas = jax.lax.fori_loop(0, m, bwd, (g, jnp.zeros_like(rho)))

    # initial Hessian scaling gamma = (s.y)/(y.y) of the newest valid pair
    newest = jnp.mod(count - 1, m)
    have = count > 0
    s_new = s_row(newest)
    y_new = y_row(newest)
    sy = jnp.dot(s_new, y_new)
    yy = jnp.dot(y_new, y_new)
    gamma = jnp.where(have & (yy > 0), sy / jnp.maximum(yy, 1e-30), 1.0)
    r_vec = gamma * q

    def fwd(i, r_vec):
        idx = jnp.mod(count - m + i, m)  # oldest first among the last m
        valid = i >= (m - jnp.minimum(count, m))
        r = jnp.where(valid, rho[idx], 0.0)
        beta = r * jnp.dot(y_row(idx), r_vec)
        return r_vec + jnp.where(valid, (alphas[idx] - beta), 0.0) * s_row(idx)

    r_vec = jax.lax.fori_loop(0, m, fwd, r_vec)
    return -r_vec[:dim]


def resolve_history_dtype(config: OptimizerConfig, working_dtype) -> jnp.dtype:
    """The storage dtype for s/y ring buffers (config.history_dtype or the
    working dtype) — shared by L-BFGS and OWL-QN."""
    return jnp.dtype(config.history_dtype) if config.history_dtype else working_dtype


def update_history(
    s_hist, y_hist, rho, count, s_vec, y_vec
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Curvature-guarded ring-buffer insert (skip when s.y too small),
    casting the pair to the buffers' storage dtype — shared by L-BFGS and
    OWL-QN so their history handling cannot diverge. The guard selects on
    the row, not on the buffer: a rejected pair rewrites one row with
    itself, an accepted one is an in-place dynamic-update-slice."""
    m = rho.shape[0]
    sy = jnp.dot(s_vec, y_vec)
    good_pair = sy > 1e-10 * jnp.maximum(jnp.dot(y_vec, y_vec), 1e-30)
    slot = jnp.mod(count, m)

    def put(hist, vec):
        row = jnp.where(good_pair, _as_history_row(vec, hist), hist[slot])
        return hist.at[slot].set(row)

    s_hist = put(s_hist, s_vec)
    y_hist = put(y_hist, y_vec)
    rho = jnp.where(
        good_pair, rho.at[slot].set(1.0 / jnp.maximum(sy, 1e-30)), rho
    )
    count = jnp.where(good_pair, count + 1, count)
    return s_hist, y_hist, rho, count


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
    return _LbfgsState(
        w=w0,
        f=f0,
        g=g0,
        s_hist=history_zeros(m, dim, hdtype),
        y_hist=history_zeros(m, dim, hdtype),
        rho=jnp.zeros((m,), dtype=dtype),
        count=jnp.int32(0),
        it=jnp.int32(0),
        evals=jnp.int32(1),
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
        d = two_loop_direction(s.g, s.s_hist, s.y_hist, s.rho, s.count)
        dphi0 = jnp.dot(d, s.g)
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
        s_hist, y_hist, rho, count = update_history(
            s.s_hist, s.y_hist, s.rho, s.count, s_vec, y_vec
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
            it=it,
            evals=evals,
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
