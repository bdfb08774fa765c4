"""Block-sharded GLM solving over streamed fixed-shape blocks.

Two modes, both built on the repo's existing optimizer primitives:

* ``solve_streaming`` — EXACT full-batch L-BFGS out of core. The GLM
  objective is a sum over rows plus an L2 term, and the normalization
  gradient map is linear, so accumulating per-block ``value_and_grad``
  (called with l2=0) across all blocks and adding ``0.5·λ·w·w / λ·w`` once
  reproduces the full-batch objective and gradient exactly (weight-0
  padding rows are algebraic no-ops). Directions and curvature updates
  reuse ``opt/lbfgs.py``'s ``two_loop_direction`` / ``update_history``;
  convergence uses ``opt/state.py``'s absolute-tolerance predicates. Each
  outer iteration costs one streamed accumulation pass per line-search
  trial.

* ``solve_streaming_stochastic`` — the resumable seam
  (``solve_init``/``solve_chunk``/``solve_finalize``, opt/solve.py) run as
  ONE jitted program per visited block group: shuffled block order per
  epoch, ``chunk_iters`` solver iterations per group, warm-started ``w``
  carried between groups, λ scaled by the group's weight fraction so the
  per-group optimum matches the full-batch regularization scale. Gated on
  held-out metric parity (tests/test_streaming.py), per the convergence
  guidance of arxiv 1702.07005 / 1811.01564.

Every jitted program calls ``_note_trace`` inside its traced body, so
``stream_trace_counts()`` counts actual (re)compiles — the CI parity gate
asserts the count does not grow with the number of blocks.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from functools import partial
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.losses.objective import GlmObjective
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, OptimizerType
from photon_ml_tpu.opt.lbfgs import (
    history_zeros,
    resolve_history_dtype,
    two_loop_direction,
    update_history,
)
from photon_ml_tpu.opt.solve import solve_chunk, solve_finalize, solve_init
from photon_ml_tpu.opt.state import (
    SolveResult,
    absolute_tolerances,
    function_values_converged,
    gradient_converged,
)
from photon_ml_tpu.telemetry import note_jit_trace
from photon_ml_tpu.types import ConvergenceReason

_TRACE_COUNTS: Counter = Counter()


def _note_trace(program: str, kind: str = "trace") -> None:
    """Python-side-effect compile counter: fires only on a jit cache miss
    (same pattern as estimators/random_effect.py)."""
    _TRACE_COUNTS[(program, kind)] += 1
    note_jit_trace(program, kind)


def stream_trace_counts() -> Dict[Tuple[str, str], int]:
    """(program, kind) -> number of actual jit traces in streaming solvers."""
    return dict(_TRACE_COUNTS)


def reset_stream_trace_counts() -> None:
    _TRACE_COUNTS.clear()


# BlockFn: fresh iterable of per-block LabeledData (offsets already fused
# with the CD residual). Each call streams one full pass from disk.
BlockFn = Callable[[], Iterable]


class BlockStatsProbe:
    """Per-block convergence-plane collector for one streamed solve.

    When a probe is passed to ``solve_streaming`` the accumulation pass runs
    ``acc_vg_probe`` instead of ``acc_vg``: same donated-accumulator math
    plus three extra scalar reductions per block — the block's partial loss,
    partial gradient norm, and a first-order Fenchel duality-gap surrogate
    ``f_k + <w, g_k>`` (the DuHL-style block importance score of
    arxiv 1702.07005, with dual variables implicitly refreshed at the
    current iterate). ``last_pass`` holds the scalars of the most recent
    completed pass — for a converged solve that is the final streamed
    epoch. With no probe the original programs run untouched, so the
    disabled path stays bitwise identical.
    """

    def __init__(self) -> None:
        self._pending: List[tuple] = []
        self._futures: List[tuple] = []
        self._visit_pending: List[int] = []
        self._visit: List[int] = []
        self._resolved: Optional[List[dict]] = None

    def begin_pass(self) -> None:
        self._pending = []
        self._visit_pending = []

    def on_block(self, partial_loss, partial_grad_norm, gap_estimate) -> None:
        self._pending.append((partial_loss, partial_grad_norm, gap_estimate))

    def note_visit(self, block: int) -> None:
        """Optional attribution hook: the block generator records each
        yielded block's TRUE index so ``last_pass`` labels stats by it
        instead of by enumerate position. Without it a degraded pass
        (on_block_error=skip) — or any non-natural visit order, like the
        residency plane's resident/streamed merge under skips — would
        silently misattribute every stat after the first gap."""
        self._visit_pending.append(int(block))

    def end_pass(self) -> None:
        # keep the futures; only the final completed pass is ever read, so
        # host resolution is deferred to the last_pass property — no D2H
        # sync on the intermediate line-search passes
        self._futures = self._pending
        self._visit = self._visit_pending
        self._pending = []
        self._visit_pending = []
        self._resolved = None

    @property
    def has_measurements(self) -> bool:
        """True once at least one streamed pass completed (the residency
        plane repins only on measured evidence)."""
        return bool(self._futures)

    @property
    def last_pass(self) -> List[dict]:
        if self._resolved is None:
            labels = (
                self._visit
                if len(self._visit) == len(self._futures)
                else list(range(len(self._futures)))
            )
            self._resolved = [
                {
                    "block": labels[i],
                    "partial_loss": float(f),
                    "partial_grad_norm": float(g),
                    "gap_estimate": float(gap),
                }
                for i, (f, g, gap) in enumerate(self._futures)
            ]
        return self._resolved


class StreamPrograms:
    """The jitted per-block programs of one streamed solve. Built once per
    objective (``for_objective`` memoizes) and reused across every block,
    every pass, and every CD outer iteration — so the trace count is
    independent of both block count and solve count."""

    _CACHE: Dict[GlmObjective, "StreamPrograms"] = {}

    @classmethod
    def for_objective(cls, objective: GlmObjective) -> "StreamPrograms":
        cached = cls._CACHE.get(objective)
        if cached is None:
            cached = cls._CACHE[objective] = cls(objective)
        return cached

    def __init__(self, objective: GlmObjective):
        # donated accumulators: f/g update in place, so a streamed pass
        # allocates no per-block device buffers — and because acc_vg
        # returns futures, the prefetcher's device_put of block k+1 is
        # dispatched while block k's value_and_grad is still executing
        # (the H2D/compute overlap measured as stream.upload_hidden_s)
        @partial(jax.jit, donate_argnums=(2, 3))
        def acc_vg(w, data, f_acc, g_acc):
            _note_trace("stream_vg")
            f, g = objective.value_and_grad(w, data, jnp.zeros((), w.dtype))
            return f_acc + f, g_acc + g

        @jax.jit
        def finalize(f, g, w, l2):
            _note_trace("stream_finalize")
            f_reg = f + 0.5 * l2 * jnp.dot(w, w)
            g_reg = g + l2 * w
            return f_reg, g_reg, jnp.linalg.norm(g_reg)

        @jax.jit
        def direction(g, s_hist, y_hist, rho, count):
            _note_trace("stream_direction")
            d = two_loop_direction(g, s_hist, y_hist, rho, count)
            dphi0 = jnp.dot(d, g)
            bad = dphi0 >= 0
            d = jnp.where(bad, -g, d)
            dphi0 = jnp.where(bad, -jnp.dot(g, g), dphi0)
            return d, dphi0, jnp.linalg.norm(d)

        @jax.jit
        def step(w, d, t):
            _note_trace("stream_step")
            return w + t * d

        # the buffers are donated so the new pair is written into its row in
        # place (the solver loop rebinds both at once)
        @partial(jax.jit, donate_argnums=(0, 1))
        def hist_update(s_hist, y_hist, rho, count, w_old, w_new, g_old, g_new):
            _note_trace("stream_history")
            s = (w_new - w_old).astype(s_hist.dtype)
            y = (g_new - g_old).astype(y_hist.dtype)
            return update_history(s_hist, y_hist, rho, count, s, y)

        @partial(jax.jit, donate_argnums=(2, 3))
        def acc_vg_probe(w, data, f_acc, g_acc):
            _note_trace("stream_vg_probe")
            f, g = objective.value_and_grad(w, data, jnp.zeros((), w.dtype))
            # convergence-plane extras: a few scalar reductions per block
            # (see BlockStatsProbe); compiled only when probing is on, so
            # the default path keeps the original acc_vg program
            gap = f + jnp.dot(w, g)
            return f_acc + f, g_acc + g, f, jnp.linalg.norm(g), gap

        @jax.jit
        def gap_probe(w, data):
            # the standalone gap scalar for the stochastic scheduler: same
            # first-order surrogate as acc_vg_probe but without the
            # accumulator plumbing (stochastic mode owns no f/g
            # accumulators). Returns a future; the epoch-end D2H resolve
            # is one host sync per epoch, not per block.
            _note_trace("stream_gap_probe")
            f, g = objective.value_and_grad(w, data, jnp.zeros((), w.dtype))
            return f + jnp.dot(w, g)

        self.acc_vg = acc_vg
        self.acc_vg_probe = acc_vg_probe
        self.gap_probe = gap_probe
        self.finalize = finalize
        self.direction = direction
        self.step = step
        self.hist_update = hist_update


@dataclasses.dataclass
class StreamSolveInfo:
    """Host-side accounting of one streamed solve."""

    passes: int = 0          # streamed accumulation passes over the dataset
    blocks: int = 0          # total blocks visited
    iterations: int = 0
    line_search_trials: int = 0


def _full_pass(
    programs: StreamPrograms, w, make_blocks: BlockFn, dim: int, l2, info,
    probe: Optional[BlockStatsProbe] = None,
):
    """One streamed accumulation of the EXACT full-batch (value, grad)."""
    f = jnp.zeros((), dtype=w.dtype)
    g = jnp.zeros((dim,), dtype=w.dtype)
    if probe is None:
        for data in make_blocks():
            f, g = programs.acc_vg(w, data, f, g)
            info.blocks += 1
    else:
        probe.begin_pass()
        for data in make_blocks():
            f, g, bf, bg, bgap = programs.acc_vg_probe(w, data, f, g)
            probe.on_block(bf, bg, bgap)
            info.blocks += 1
        probe.end_pass()
    info.passes += 1
    return programs.finalize(f, g, w, l2)


def solve_streaming(
    objective: GlmObjective,
    w0,
    make_blocks: Optional[BlockFn],
    configuration: GlmOptimizationConfiguration,
    l2_weight: Optional[float] = None,
    info: Optional[StreamSolveInfo] = None,
    probe: Optional[BlockStatsProbe] = None,
    pass_fn: Optional[Callable] = None,
) -> SolveResult:
    """Exact full-batch L-BFGS with the dataset streamed per pass.

    The line search is backtracking Armijo (each trial = one streamed
    value-and-grad pass, so the accepted point's gradient is free); with
    all blocks visited per pass the trajectory optimizes the identical
    full-batch objective as the in-memory solver and converges to the same
    optimum within solver tolerance.

    ``pass_fn`` replaces the local streamed accumulation with an external
    one — the cluster plane's distributed allreduce pass
    (``parallel/cluster``): called as ``pass_fn(w, l2)`` and expected to
    return the same ``(f_reg, g_reg, ||g_reg||)`` triple as
    ``StreamPrograms.finalize``, i.e. the EXACT full-batch regularized
    value and gradient at ``w``. The L-BFGS trajectory above the pass is
    then identical to single-host up to floating-point reassociation of
    the per-host partial sums.
    """
    if make_blocks is None and pass_fn is None:
        raise ValueError("solve_streaming needs make_blocks or pass_fn")
    cfg = configuration.optimizer_config
    if cfg.optimizer is OptimizerType.TRON:
        raise ValueError(
            "streaming full-batch mode supports first-order solvers (LBFGS);"
            " TRON needs Hessian-vector passes — use the in-memory trainer"
        )
    if configuration.l1_weight > 0:
        raise ValueError(
            "streaming full-batch mode does not support L1/OWL-QN yet; "
            "use stochastic mode or the in-memory trainer"
        )
    info = info if info is not None else StreamSolveInfo()
    w = jnp.asarray(w0, dtype=jnp.float32)
    dim = w.shape[-1]
    l2 = jnp.asarray(
        configuration.l2_weight if l2_weight is None else l2_weight,
        dtype=w.dtype,
    )
    programs = StreamPrograms.for_objective(objective)

    evaluations = 0

    def _pass(w_at):
        nonlocal evaluations
        evaluations += 1
        if pass_fn is not None:
            info.passes += 1
            return pass_fn(w_at, l2)
        return _full_pass(programs, w_at, make_blocks, dim, l2, info, probe)

    f, g, g_norm = _pass(w)
    abs_f_tol, abs_g_tol = absolute_tolerances(f, g_norm, cfg.tolerance)
    abs_f_tol = float(abs_f_tol)
    abs_g_tol = float(abs_g_tol)

    m = cfg.history_length
    hdtype = resolve_history_dtype(cfg, w.dtype)
    s_hist = history_zeros(m, dim, hdtype)
    y_hist = history_zeros(m, dim, hdtype)
    rho = jnp.zeros((m,), dtype=w.dtype)
    count = jnp.int32(0)

    history = [float(f)]
    reason = ConvergenceReason.MAX_ITERATIONS
    if float(g_norm) <= abs_g_tol:
        reason = ConvergenceReason.GRADIENT_CONVERGED

    it = 0
    while it < cfg.max_iterations and reason is ConvergenceReason.MAX_ITERATIONS:
        d, dphi0, d_norm = programs.direction(g, s_hist, y_hist, rho, count)
        dphi0_f = float(dphi0)
        # Breeze's firstStepSize heuristic, then the quasi-Newton step t=1
        t = 1.0 / max(float(d_norm), 1e-12) if int(count) == 0 else 1.0
        f_host = float(f)

        accepted = None
        for _ in range(max(1, cfg.max_line_search_iterations)):
            info.line_search_trials += 1
            w_try = programs.step(w, d, jnp.asarray(t, dtype=w.dtype))
            f_try, g_try, g_try_norm = _pass(w_try)
            if float(f_try) <= f_host + 1e-4 * t * dphi0_f:
                accepted = (w_try, f_try, g_try, g_try_norm)
                break
            t *= 0.5
        if accepted is None:
            reason = ConvergenceReason.OBJECTIVE_NOT_IMPROVING
            break

        w_new, f_new, g_new, g_new_norm = accepted
        s_hist, y_hist, rho, count = programs.hist_update(
            s_hist, y_hist, rho, count, w, w_new, g, g_new
        )
        it += 1
        info.iterations = it
        history.append(float(f_new))
        if float(g_new_norm) <= abs_g_tol:
            reason = ConvergenceReason.GRADIENT_CONVERGED
        elif abs(f_host - float(f_new)) <= abs_f_tol:
            reason = ConvergenceReason.FUNCTION_VALUES_CONVERGED
        w, f, g, g_norm = w_new, f_new, g_new, g_new_norm

    value_history = np.full((cfg.max_iterations + 1,), np.nan, dtype=np.float32)
    value_history[: len(history)] = history
    return SolveResult(
        w=w,
        value=f,
        grad_norm=g_norm,
        iterations=jnp.int32(it),
        evaluations=jnp.int32(evaluations),
        hessian_vecs=jnp.int32(0),
        rejected_steps=jnp.int32(0),
        reason=jnp.int32(reason.value),
        value_history=jnp.asarray(value_history),
    )


@jax.jit
def _concat_group(*ds):
    _note_trace("stream_group_concat")
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0), *ds)


def _group_data(datas: List):
    """Concatenate a fixed-size group of identically-shaped LabeledData
    along rows (leaf-wise). Group size is static per run, so the
    module-level jit traces once per size."""
    return datas[0] if len(datas) == 1 else _concat_group(*datas)


# (objective, configuration, chunk_iters) -> jitted init→chunk→finalize
_STOCHASTIC_CACHE: Dict[Tuple, Callable] = {}


def _stochastic_step(
    objective: GlmObjective,
    cfg: GlmOptimizationConfiguration,
    chunk_iters: int,
) -> Callable:
    key = (objective, cfg, int(chunk_iters))
    cached = _STOCHASTIC_CACHE.get(key)
    if cached is not None:
        return cached

    @jax.jit
    def group_step(w_in, data, l2_eff):
        _note_trace("stream_stochastic_chunk")
        state = solve_init(objective, w_in, data, cfg, l2_weight=l2_eff)
        state = solve_chunk(
            objective, state, data, cfg, l2_weight=l2_eff,
            num_iters=chunk_iters,
        )
        return solve_finalize(state, cfg)

    _STOCHASTIC_CACHE[key] = group_step
    return group_step


def _run_stochastic(
    objective: GlmObjective,
    w,
    make_blocks_ordered: Callable[[Optional[np.ndarray]], Iterable],
    cfg: GlmOptimizationConfiguration,
    num_blocks: int,
    total_weight: float,
    epochs: int,
    chunk_iters: int,
    blocks_per_update: int,
    seed: int,
    l2_full: float,
    info: StreamSolveInfo,
    scheduler=None,
) -> SolveResult:
    """The stochastic epoch loop.

    With no scheduler the visit order is the blind per-epoch
    ``rng.permutation`` — bitwise identical to the historical trajectory
    (the CI parity gate pins this). With a :class:`GapScheduler` the order
    comes from ``scheduler.epoch_order()`` and each visited block's
    first-order gap is probed (``stream_gap_probe``, one extra jitted
    scalar program) at the iterate it was visited with; the epoch-end
    resolve feeds the magnitudes back via ``scheduler.update`` — one D2H
    sync per epoch.
    """
    rng = np.random.default_rng(seed)
    group_step = _stochastic_step(objective, cfg, chunk_iters)
    gap_probe = (
        StreamPrograms.for_objective(objective).gap_probe
        if scheduler is not None
        else None
    )

    result = None
    for _ in range(max(1, epochs)):
        if scheduler is None:
            order = rng.permutation(num_blocks)
        else:
            order = scheduler.epoch_order()
        epoch_blocks = len(order)
        gap_futures: List = []
        visited: List[int] = []
        group: List = []
        group_weight = 0.0
        blocks_seen = 0
        for blk in make_blocks_ordered(order):
            # the stream may yield fewer blocks than ordered (degraded
            # on_block_error=skip); gap attribution must follow the
            # block's OWN index, falling back to order position for
            # callers whose block wrappers carry none
            idx = getattr(blk, "index", -1)
            visited.append(
                int(idx) if int(idx) >= 0 else int(order[blocks_seen])
            )
            if gap_probe is not None:
                gap_futures.append(gap_probe(w, blk.data))
            group.append(blk.data)
            group_weight += blk.weight_sum
            blocks_seen += 1
            info.blocks += 1
            boundary = (
                len(group) == blocks_per_update or blocks_seen == epoch_blocks
            )
            if not boundary:
                continue
            # ragged final group: pad with repeats of the last block so the
            # concat shape (and therefore the program) stays fixed
            while len(group) < blocks_per_update:
                group.append(group[-1])
            data = _group_data(group)
            frac = group_weight / max(total_weight, 1e-30)
            l2_eff = jnp.asarray(l2_full * frac, dtype=w.dtype)
            result = group_step(w, data, l2_eff)
            w = result.w
            info.iterations += int(result.iterations)
            group = []
            group_weight = 0.0
        if group:
            # a skipped block kept blocks_seen short of epoch_blocks, so
            # the in-loop boundary never flushed the tail — flush it here
            # (unreachable on a clean pass: the boundary clears the group)
            while len(group) < blocks_per_update:
                group.append(group[-1])
            data = _group_data(group)
            frac = group_weight / max(total_weight, 1e-30)
            l2_eff = jnp.asarray(l2_full * frac, dtype=w.dtype)
            result = group_step(w, data, l2_eff)
            w = result.w
            info.iterations += int(result.iterations)
        if scheduler is not None:
            missing = set(int(b) for b in order) - set(visited)
            if missing:
                # ordered but never yielded: permanently failed and
                # skipped — exclude from every later epoch's schedule
                scheduler.mark_failed(sorted(missing))
            scheduler.update(
                {
                    visited[pos]: float(v)
                    for pos, v in enumerate(gap_futures)
                }
            )
        info.passes += 1
    if result is None:
        raise RuntimeError(
            "no blocks streamed (every block failed or was skipped)"
        )
    return result


def solve_streaming_stochastic(
    objective: GlmObjective,
    w0,
    make_blocks_ordered: Callable[[Optional[np.ndarray]], Iterable],
    configuration: GlmOptimizationConfiguration,
    num_blocks: int,
    total_weight: float,
    epochs: int = 5,
    chunk_iters: int = 4,
    blocks_per_update: int = 1,
    seed: int = 0,
    l2_weight: Optional[float] = None,
    info: Optional[StreamSolveInfo] = None,
    scheduler=None,
) -> SolveResult:
    """Stochastic block-sharded solving on the resumable solver seam.

    Per epoch the block order is reshuffled — or, when a
    :class:`~photon_ml_tpu.streaming.gapsched.GapScheduler` is passed,
    chosen by staleness-decayed duality-gap importance (DuHL, arxiv
    1702.07005); every ``blocks_per_update`` consecutive blocks form one
    update group, solved with
    ``solve_init → solve_chunk(num_iters=chunk_iters) → solve_finalize``
    warm-started from the running ``w``. λ is scaled by the group's share
    of the total example weight so each group optimizes a consistently
    regularized subproblem. The whole init/chunk/finalize composition is
    one jitted program (traced once), so block count never retraces.
    """
    info = info if info is not None else StreamSolveInfo()
    return _run_stochastic(
        objective,
        jnp.asarray(w0, dtype=jnp.float32),
        make_blocks_ordered,
        configuration,
        num_blocks,
        total_weight,
        epochs,
        chunk_iters,
        blocks_per_update,
        seed,
        float(
            configuration.l2_weight if l2_weight is None else l2_weight
        ),
        info,
        scheduler=scheduler,
    )


def streamed_objective_value(
    objective: GlmObjective,
    w,
    make_blocks: BlockFn,
    dim: int,
    l2: float,
    info: Optional[StreamSolveInfo] = None,
) -> float:
    """Exact full-batch objective at ``w`` via one streamed pass (used to
    report the full-batch objective after a stochastic run)."""
    programs = StreamPrograms.for_objective(objective)
    info = info if info is not None else StreamSolveInfo()
    f, _, _ = _full_pass(
        programs, jnp.asarray(w, dtype=jnp.float32), make_blocks, dim,
        jnp.asarray(l2, dtype=jnp.float32), info,
    )
    return float(f)
