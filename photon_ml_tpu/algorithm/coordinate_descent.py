"""Block coordinate descent: the outer GAME training loop.

Reference parity: algorithm/CoordinateDescent.scala:40 (run :57, optimize
:97-321): per outer iteration, per coordinate — residual = total score minus
the coordinate's own score (:183), retrain the coordinate against the
residual, rescore, log the objective (:247-258), evaluate validation after
each coordinate update (:265-294), and keep the best full model seen by the
first evaluator (:299-307).

Score plane: the reference's aggressive RDD persist/unpersist choreography
becomes per-coordinate score arrays — but at production row counts those are
NOT small, so where they live matters. Two planes are supported:

- ``score_plane="device"`` (default): scores are device-resident
  ``jax.Array``s on the training mesh. The driver maintains a RUNNING total
  updated incrementally (``total += new_own - old_own``) and computes
  ``residual = total - own`` inside jitted programs with donated buffers —
  O(C·N) device work per outer iteration, ZERO row-length host transfers in
  the steady state, and the training objective re-uses the running total
  (one plane pass per update instead of two full C-way re-sums).
- ``score_plane="host"``: the numpy plane, kept for fallback and parity
  testing (and auto-selected under multi-controller runs, where the host
  path's ``fetch_global`` collectives are the proven ordering). It runs the
  SAME incremental algebra in numpy — bitwise-identical IEEE f32 ops, so
  the two planes train bitwise-equal models — but pays two row-length
  boundary crossings per update (score pull, residual push) plus the host
  memory traffic of the numpy adds.

``transfer_stats`` (opt.tracking.TransferStats) counts every row-length
array crossing the host/device boundary plus host plane re-sums; a
``TransferStatsEvent`` with per-iteration deltas is emitted after each outer
iteration.

Schedule: ``schedule="sync"`` (default) runs the strictly sequential loop
above. ``schedule="async"`` pipelines coordinate solves on the device
plane: each solve is dispatched onto a worker pool against the residual
computed from the *current* running total — which may still be missing up
to ``staleness`` in-flight updates — and completed solves are folded back
into the device total (``total += new - old``) in dispatch order. Residuals
are computed on the driver thread at dispatch time and reconciliation is
FIFO, so the trajectory is deterministic for a given ``staleness``;
``staleness=0`` reconciles everything before each dispatch and is
bitwise-identical to sync (the solve merely runs on a worker thread). A
full reconciliation barrier ends every outer iteration, so the plane never
lags across iterations.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinate import Coordinate
from photon_ml_tpu.algorithm.schedule import SCHEDULES, ScheduleExecutor
from photon_ml_tpu.evaluation.evaluators import nan_aware_better_than
from photon_ml_tpu.opt.tracking import TransferStats
from photon_ml_tpu.telemetry import get_registry, note_jit_trace, span
from photon_ml_tpu.telemetry.metrics import MESH_FETCH_BYTES

logger = logging.getLogger("photon_ml_tpu")

SCORE_PLANES = ("device", "host")


@functools.lru_cache(maxsize=None)
def _plane_programs():
    """Jitted score-plane algebra, cached per process. ``apply`` donates the
    running total so each incremental update writes in place instead of
    copying a row-length buffer."""

    def _apply(total, new_own, old_own):
        note_jit_trace("cd_plane", "apply")  # fires only on (re)trace
        return total + new_own - old_own

    def _residual(total, own):
        note_jit_trace("cd_plane", "residual")
        return total - own

    apply_ = jax.jit(_apply, donate_argnums=(0,))
    residual_ = jax.jit(_residual)
    return apply_, residual_


@dataclasses.dataclass
class CoordinateDescentResult:
    models: Dict[str, object]                 # final per-coordinate models
    best_models: Dict[str, object]            # best by validation (== models if no validation)
    best_metric: Optional[float]
    objective_history: List[Tuple[str, float]]  # (coordinate, training objective)
    validation_history: List[Tuple[str, float]]  # (coordinate, first-evaluator metric)


class CoordinateDescent:
    """Orchestrates sequential coordinate updates (host control flow; all
    heavy math happens inside the coordinates' jit programs)."""

    def __init__(
        self,
        coordinates: Dict[str, Coordinate],
        num_rows: int,
        update_order: Optional[Sequence[str]] = None,
        training_objective: Optional[Callable[[np.ndarray], float]] = None,
        regularization_term: Optional[
            Callable[[Dict[str, object]], float]
        ] = None,
        validate: Optional[Callable[[Dict[str, object]], float]] = None,
        validation_better_than: Optional[Callable[[float, float], bool]] = None,
        emitter: Optional[object] = None,
        score_plane: str = "device",
        schedule: str = "sync",
        staleness: int = 1,
        progress: Optional[object] = None,
    ) -> None:
        if not coordinates:
            raise ValueError("need at least one coordinate")
        if score_plane not in SCORE_PLANES:
            raise ValueError(
                f"score_plane must be one of {SCORE_PLANES}, got {score_plane!r}"
            )
        if schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {schedule!r}"
            )
        if int(staleness) < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        self.coordinates = coordinates
        self.num_rows = num_rows
        self.update_order = list(update_order) if update_order else list(coordinates)
        unknown = set(self.update_order) - set(coordinates)
        if unknown:
            raise ValueError(f"unknown coordinates in update order: {unknown}")
        self.training_objective = training_objective
        # optional Σ per-coordinate regularization over the current models:
        # the reference logs the objective decomposed into loss +
        # regularization per update (CoordinateDescent.scala:247-258)
        self.regularization_term = regularization_term
        self.validate = validate
        # Evaluator.better_than semantics (larger/smaller-is-better + NaN
        # policy) come from the evaluator itself; default: larger is better.
        self.validation_better_than = validation_better_than or nan_aware_better_than
        # optional event.EventEmitter: per-bucket SolverStatsEvent after each
        # random-effect coordinate update (adaptive-solve lane telemetry) and
        # a TransferStatsEvent per outer iteration
        self.emitter = emitter
        self.score_plane = score_plane
        # pipelined coordinate solves with bounded staleness; requires the
        # device plane (the host plane's numpy algebra is driver-owned), so
        # async over a host plane falls back to the sync loop at run time
        self.schedule = schedule
        self.staleness = int(staleness)
        # optional telemetry.progress.ConvergenceTracker: per-update
        # objective/grad/delta records plus the divergence watchdog (its
        # record_coordinate may raise DivergenceError, aborting the run).
        # None (the default) touches nothing — bitwise-identical training.
        self.progress = progress
        # transfer accounting of the most recent (or in-flight) run
        self.transfer_stats = TransferStats(
            score_plane=score_plane, num_rows=num_rows
        )

    def _effective_schedule(self) -> str:
        """Async needs device-resident score algebra; a host-plane run
        (chosen directly or forced by multi-controller) drops to sync."""
        if self.schedule == "async" and self.score_plane != "device":
            logger.warning(
                "schedule='async' requires the device score plane; "
                "falling back to the sync schedule on the %r plane",
                self.score_plane,
            )
            return "sync"
        return self.schedule

    def _emit_solver_stats(self, cid: str, coord: Coordinate) -> None:
        stats = getattr(coord, "last_solver_stats", None)
        if not stats:
            return
        for s in stats:
            logger.info("CD coordinate %s: %s", cid, s.to_summary_string())
        if self.emitter is None:
            return
        from photon_ml_tpu.event import SolverStatsEvent

        for s in stats:
            self.emitter.send_event(SolverStatsEvent.from_stats(cid, s))

    def _emit_transfer_stats(self, outer: int, prev: Dict[str, object]) -> None:
        """One TransferStatsEvent with THIS iteration's deltas."""
        t = self.transfer_stats
        t.outer_iterations += 1
        if self.emitter is None:
            return
        from photon_ml_tpu.event import TransferStatsEvent

        cur = t.snapshot()
        per_row = t.bytes_per_row_array
        d_h2d = int(cur["row_transfers_h2d"]) - int(prev["row_transfers_h2d"])
        d_d2h = int(cur["row_transfers_d2h"]) - int(prev["row_transfers_d2h"])
        self.emitter.send_event(
            TransferStatsEvent(
                score_plane=t.score_plane,
                outer_iteration=outer,
                num_rows=t.num_rows,
                row_transfers_h2d=d_h2d,
                row_transfers_d2h=d_d2h,
                row_bytes_h2d=d_h2d * per_row,
                row_bytes_d2h=d_d2h * per_row,
                host_score_sums=(
                    int(cur["host_score_sums"]) - int(prev["host_score_sums"])
                ),
                device_plane_updates=(
                    int(cur["device_plane_updates"])
                    - int(prev["device_plane_updates"])
                ),
            )
        )

    def _validate_counting_fetches(self, models, validating) -> float:
        """The held-out metric of ``models``; the ``cd/validate`` span is
        told the bytes its scoring gathered to the host (``fetch_bytes``:
        the counter ``mesh.fetch_bytes``, which counts while the tracer is
        on)."""
        registry = get_registry()
        before = registry.counter_value(MESH_FETCH_BYTES)
        metric = float(self.validate(models))
        validating.set_attrs(
            fetch_bytes=int(registry.counter_value(MESH_FETCH_BYTES) - before)
        )
        return metric

    def _record_progress(
        self,
        outer: int,
        cid: str,
        coord: Coordinate,
        prev_model,
        model,
        objective: float,
        loss: Optional[float],
        regularization: Optional[float],
    ) -> None:
        """Fold one coordinate update into the convergence tracker: the
        objective point, solver telemetry joined from the coordinate's
        last_tracker/last_solve_info, the coefficient-delta norm, and any
        streamed per-block stats. May raise DivergenceError (watchdog)."""
        tracker = self.progress
        if tracker is None:
            return
        solver_iterations = None
        convergence_reason = None
        grad_norm = None
        states = getattr(getattr(coord, "last_tracker", None), "states", None)
        if states is not None:
            solver_iterations = int(states.iterations)
            reason = states.convergence_reason
            convergence_reason = getattr(reason, "name", str(reason))
            grad_norm = getattr(states, "grad_norm", None)
        info = getattr(coord, "last_solve_info", None)
        line_search_trials = (
            int(info.line_search_trials) if info is not None else None
        )
        coef_delta_norm = None
        new_means = getattr(getattr(model, "coefficients", None), "means", None)
        if new_means is not None:
            old_means = getattr(
                getattr(prev_model, "coefficients", None), "means", None
            )
            delta = (
                new_means if old_means is None else new_means - old_means
            )
            coef_delta_norm = float(jnp.linalg.norm(delta))
        block_stats = getattr(coord, "last_block_stats", None)
        if block_stats:
            tracker.record_blocks(outer, cid, block_stats)
        schedule = getattr(coord, "last_schedule_decisions", None)
        if schedule:
            tracker.record_schedule(outer, cid, schedule)
            coord.last_schedule_decisions = None
        residency = getattr(coord, "last_residency_decisions", None)
        if residency:
            tracker.record_residency(outer, cid, residency)
            coord.last_residency_decisions = None
        cluster_events = getattr(coord, "last_cluster_events", None)
        if cluster_events:
            tracker.record_cluster(outer, cid, cluster_events)
            coord.last_cluster_events = None
        cluster_passes = getattr(coord, "last_cluster_passes", None)
        if cluster_passes:
            tracker.record_cluster_passes(outer, cid, cluster_passes)
            coord.last_cluster_passes = None
        skipped = getattr(coord, "last_skipped_blocks", None)
        if skipped:
            for s in skipped:
                tracker.record_resilience(
                    "block_skipped",
                    "stream.build_block",
                    s.get("error", ""),
                    outer=outer,
                    coordinate=cid,
                    block=s.get("block"),
                )
            coord.last_skipped_blocks = None
        tracker.record_coordinate(
            outer,
            cid,
            objective,
            loss=loss,
            regularization=regularization,
            grad_norm=grad_norm,
            coef_delta_norm=coef_delta_norm,
            solver_iterations=solver_iterations,
            line_search_trials=line_search_trials,
            convergence_reason=convergence_reason,
        )

    def run(
        self,
        num_iterations: int,
        initial_models: Optional[Dict[str, object]] = None,
        start_iteration: int = 0,
        initial_best: Optional[Tuple[Dict[str, object], float]] = None,
        on_iteration_end: Optional[Callable[[int, "CoordinateDescentResult"], None]] = None,
    ) -> CoordinateDescentResult:
        """``start_iteration``/``initial_best``/``on_iteration_end`` support
        checkpoint-resume: the callback fires after each outer iteration with
        the running result; resume passes the restored models and best-so-far
        back in and skips completed iterations."""
        schedule = self._effective_schedule()
        with span(
            "cd/run",
            score_plane=self.score_plane,
            num_rows=self.num_rows,
            iterations=num_iterations,
            schedule=schedule,
        ):
            run = self._run_async if schedule == "async" else self._run
            return run(
                num_iterations,
                initial_models,
                start_iteration,
                initial_best,
                on_iteration_end,
            )

    def _run(
        self,
        num_iterations: int,
        initial_models: Optional[Dict[str, object]],
        start_iteration: int,
        initial_best: Optional[Tuple[Dict[str, object], float]],
        on_iteration_end: Optional[Callable[[int, "CoordinateDescentResult"], None]],
    ) -> CoordinateDescentResult:
        device = self.score_plane == "device"
        stats = self.transfer_stats = TransferStats(
            score_plane=self.score_plane, num_rows=self.num_rows
        )
        models: Dict[str, object] = dict(initial_models or {})
        scores: Dict[str, object] = {}

        def _score(cid: str, model) -> object:
            """One coordinate's [num_rows] scores on the active plane."""
            coord = self.coordinates[cid]
            if not device:
                stats.record_d2h()  # host plane pulls every score to numpy
                return coord.score(model)
            if coord.supports_device_plane:
                return coord.score_device(model)
            # a coordinate without a device path (none of the built-in ones):
            # its host scores are pulled down then pushed back up onto the
            # device plane
            stats.record_d2h()
            stats.record_h2d()
            return coord.score_device(model)

        # initial scoring for warm-started models and the plane's running
        # total of it, waited for here (as ``cd/coordinate`` waits for its
        # update) so that no span's entry barrier holds their device time
        with span("cd/initial_scores", device_sync=True, coordinates=len(models)):
            for cid, model in models.items():
                scores[cid] = _score(cid, model)

            # Both planes maintain a RUNNING total (the legacy driver re-summed
            # all C coordinates TWICE per update — once for the residual, once
            # for the objective; host_score_sums stays 0 now and the regression
            # test pins that down). The two planes execute the same sequence of
            # IEEE f32 elementwise adds/subs — np on host, XLA on device — so
            # their residuals (and therefore the trained models) match bitwise.
            if device:
                apply_, residual_ = _plane_programs()
                zeros = jnp.zeros(self.num_rows, dtype=jnp.float32)
                # fresh buffer: ``apply_`` donates its first argument, and the
                # shared ``zeros`` must outlive every first-update residual
                total = jnp.zeros_like(zeros)
                for s in scores.values():
                    total = total + s
            else:
                total_np = np.zeros(self.num_rows, dtype=np.float32)
                for s in scores.values():
                    total_np = total_np + s

        objective_history: List[Tuple[str, float]] = []
        validation_history: List[Tuple[str, float]] = []
        best_metric: Optional[float] = None
        best_models: Dict[str, object] = {}
        if initial_best is not None:
            best_models, best_metric = dict(initial_best[0]), initial_best[1]

        for outer in range(start_iteration, num_iterations):
            with span("cd/outer_iter", outer=outer):
                prev_transfers = stats.snapshot()
                for cid in self.update_order:
                    coord = self.coordinates[cid]
                    stats.coordinate_updates += 1
                    prev_model = models.get(cid)
                    # partialScore = fullScore - ownScore (reference
                    # CoordinateDescent.scala:183)
                    with span(
                        "cd/coordinate",
                        device_sync=True,
                        coordinate=cid,
                        outer=outer,
                    ):
                        if device:
                            old_own = scores.get(cid)
                            residual = residual_(
                                total, old_own if old_own is not None else zeros
                            )
                            if coord.supports_device_plane:
                                model = coord.update_model_device(
                                    models.get(cid), residual
                                )
                            else:
                                stats.record_d2h()
                                model = coord.update_model(
                                    models.get(cid), np.asarray(residual)
                                )
                            models[cid] = model
                            with span("cd/score", coordinate=cid):
                                new_own = _score(cid, model)
                            # incremental running total: O(N) per update
                            # instead of a C-way re-sum; the old total's
                            # buffer is donated
                            total = apply_(
                                total,
                                new_own,
                                old_own if old_own is not None else zeros,
                            )
                            stats.device_plane_updates += 1
                            scores[cid] = new_own
                        else:
                            old_own = scores.get(cid)
                            residual = (
                                total_np - old_own
                                if old_own is not None
                                else total_np.copy()
                            )
                            # the coordinate pushes the residual
                            stats.record_h2d()
                            model = coord.update_model(models.get(cid), residual)
                            models[cid] = model
                            with span("cd/score", coordinate=cid):
                                new_own = _score(cid, model)
                            # same incremental algebra as the device plane,
                            # in numpy
                            total_np = (
                                total_np + new_own - old_own
                                if old_own is not None
                                else total_np + new_own
                            )
                            scores[cid] = new_own
                    self._emit_solver_stats(cid, coord)

                    if self.training_objective is not None:
                        with span("cd/objective", coordinate=cid, outer=outer):
                            # both planes re-use the running total — the
                            # legacy second full re-sum per update is gone
                            plane_total = total if device else total_np
                            loss_val = float(self.training_objective(plane_total))
                            if self.regularization_term is not None:
                                # objective = loss + regularization (reference
                                # CoordinateDescent.scala:247-258); the history
                                # and the log agree on what "objective" means
                                reg = float(self.regularization_term(models))
                                obj = loss_val + reg
                                objective_history.append((cid, obj))
                                logger.info(
                                    "CD iter %d coordinate %s: loss %.6f + "
                                    "regularization %.6f = objective %.6f",
                                    outer, cid, loss_val, reg, obj,
                                )
                            else:
                                reg, obj = None, loss_val
                                objective_history.append((cid, loss_val))
                                logger.info(
                                    "CD iter %d coordinate %s: training "
                                    "objective %.6f",
                                    outer, cid, loss_val,
                                )
                        self._record_progress(
                            outer, cid, coord, prev_model, models[cid],
                            obj, loss_val, reg,
                        )
                    if self.validate is not None:
                        with span(
                            "cd/validate", coordinate=cid, outer=outer
                        ) as validating:
                            metric = self._validate_counting_fetches(
                                models, validating
                            )
                            validation_history.append((cid, metric))
                            if self.progress is not None:
                                self.progress.record_validation(
                                    outer, cid, metric
                                )
                            logger.info(
                                "CD iter %d coordinate %s: validation %.6f",
                                outer, cid, metric,
                            )
                            # best-model tracking starts once EVERY coordinate
                            # has trained: a mid-first-iteration snapshot would
                            # be a partial model (missing whole coordinates on
                            # disk) — the reference's snapshots always carry
                            # all coordinates (CoordinateDescent.scala:265-294,
                            # its models hold initial coefficients from the
                            # start)
                            if all(c in models for c in self.update_order) and (
                                best_metric is None
                                or self.validation_better_than(metric, best_metric)
                            ):
                                best_metric = metric
                                best_models = dict(models)

                self._emit_transfer_stats(outer, prev_transfers)
                if on_iteration_end is not None:
                    on_iteration_end(
                        outer,
                        CoordinateDescentResult(
                            models=dict(models),
                            best_models=(
                                dict(best_models) if best_models else dict(models)
                            ),
                            best_metric=best_metric,
                            objective_history=list(objective_history),
                            validation_history=list(validation_history),
                        ),
                    )

        logger.info("CD %s", stats.to_summary_string())
        if self.validate is None or not best_models:
            best_models = dict(models)
        return CoordinateDescentResult(
            models=models,
            best_models=best_models,
            best_metric=best_metric,
            objective_history=objective_history,
            validation_history=validation_history,
        )

    # ------------------------------------------------------------- async
    def _solve_in_flight(self, cid, coord, model0, residual, stats, lock):
        """Worker-thread body of one dispatched coordinate solve: train
        against the (possibly stale) residual and rescore. Runs inside the
        executor's ``cd/overlap`` span; touches no driver-owned state —
        transfer counters are the only shared mutation, taken under the
        driver's lock with the same accounting as the sync device path."""
        if coord.supports_device_plane:
            model = coord.update_model_device(model0, residual)
        else:
            with lock:
                stats.record_d2h()
            model = coord.update_model(model0, np.asarray(residual))
            with lock:
                stats.record_d2h()
                stats.record_h2d()
        with span("cd/score", coordinate=cid):
            new_own = coord.score_device(model)
        return model, new_own

    def _run_async(
        self,
        num_iterations: int,
        initial_models: Optional[Dict[str, object]],
        start_iteration: int,
        initial_best: Optional[Tuple[Dict[str, object], float]],
        on_iteration_end: Optional[Callable[[int, "CoordinateDescentResult"], None]],
    ) -> CoordinateDescentResult:
        """Bounded-staleness pipelined schedule over the device plane.

        Per outer iteration, each coordinate's residual is computed on the
        driver from the CURRENT running total — which may still be missing
        the deltas of up to ``staleness`` unreconciled solves — and the
        solve is dispatched to the worker pool. Before every dispatch the
        driver reconciles down to the staleness bound (FIFO), folding each
        finished solve into the total (``total += new - old``) and
        recording its objective/validation entry at that point, so the
        histories keep the sync loop's one-entry-per-update structure. A
        full drain ends each iteration: the next iteration never sees a
        stale plane.
        """
        stats = self.transfer_stats = TransferStats(
            score_plane=self.score_plane, num_rows=self.num_rows
        )
        stats_lock = threading.Lock()
        models: Dict[str, object] = dict(initial_models or {})
        scores: Dict[str, object] = {}

        apply_, residual_ = _plane_programs()
        zeros = jnp.zeros(self.num_rows, dtype=jnp.float32)
        total = jnp.zeros_like(zeros)

        # initial scoring for warm-started models (same path as sync)
        with span("cd/initial_scores", device_sync=True, coordinates=len(models)):
            for cid, model in models.items():
                coord = self.coordinates[cid]
                if not coord.supports_device_plane:
                    stats.record_d2h()
                    stats.record_h2d()
                scores[cid] = coord.score_device(model)
                total = total + scores[cid]

        objective_history: List[Tuple[str, float]] = []
        validation_history: List[Tuple[str, float]] = []
        best_metric: Optional[float] = None
        best_models: Dict[str, object] = {}
        if initial_best is not None:
            best_models, best_metric = dict(initial_best[0]), initial_best[1]

        # pending: (cid, old_own, in-flight work) in dispatch order
        pending: List[Tuple[str, object, object]] = []
        executor = ScheduleExecutor(
            max_in_flight=min(len(self.update_order), self.staleness + 1),
            name="cd-async",
        )

        def _reconcile_one(outer: int) -> None:
            nonlocal total, best_metric, best_models
            cid, old_own, work = pending.pop(0)
            coord = self.coordinates[cid]
            prev_model = models.get(cid)
            with span(
                "cd/reconcile", device_sync=True, coordinate=cid, outer=outer
            ):
                model, new_own = work.result()
                models[cid] = model
                total = apply_(
                    total, new_own, old_own if old_own is not None else zeros
                )
                stats.device_plane_updates += 1
                scores[cid] = new_own
            self._emit_solver_stats(cid, coord)

            if self.training_objective is not None:
                with span("cd/objective", coordinate=cid, outer=outer):
                    loss_val = float(self.training_objective(total))
                    if self.regularization_term is not None:
                        reg = float(self.regularization_term(models))
                        obj = loss_val + reg
                        objective_history.append((cid, obj))
                        logger.info(
                            "CD iter %d coordinate %s: loss %.6f + "
                            "regularization %.6f = objective %.6f",
                            outer, cid, loss_val, reg, obj,
                        )
                    else:
                        reg, obj = None, loss_val
                        objective_history.append((cid, loss_val))
                        logger.info(
                            "CD iter %d coordinate %s: training "
                            "objective %.6f",
                            outer, cid, loss_val,
                        )
                self._record_progress(
                    outer, cid, coord, prev_model, models[cid],
                    obj, loss_val, reg,
                )
            if self.validate is not None:
                with span("cd/validate", coordinate=cid, outer=outer) as validating:
                    metric = self._validate_counting_fetches(models, validating)
                    validation_history.append((cid, metric))
                    if self.progress is not None:
                        self.progress.record_validation(outer, cid, metric)
                    logger.info(
                        "CD iter %d coordinate %s: validation %.6f",
                        outer, cid, metric,
                    )
                    if all(c in models for c in self.update_order) and (
                        best_metric is None
                        or self.validation_better_than(metric, best_metric)
                    ):
                        best_metric = metric
                        best_models = dict(models)

        try:
            for outer in range(start_iteration, num_iterations):
                with span("cd/outer_iter", outer=outer, schedule="async"):
                    prev_transfers = stats.snapshot()
                    for cid in self.update_order:
                        # bound the lag BEFORE dispatch: at most `staleness`
                        # unreconciled updates may be missing from the
                        # residual this coordinate trains against
                        while len(pending) > self.staleness:
                            _reconcile_one(outer)
                        coord = self.coordinates[cid]
                        stats.coordinate_updates += 1
                        old_own = scores.get(cid)
                        residual = residual_(
                            total, old_own if old_own is not None else zeros
                        )
                        work = executor.submit(
                            cid,
                            functools.partial(
                                self._solve_in_flight,
                                cid,
                                coord,
                                models.get(cid),
                                residual,
                                stats,
                                stats_lock,
                            ),
                            span_name="cd/overlap",
                            coordinate=cid,
                            outer=outer,
                        )
                        pending.append((cid, old_own, work))
                    # iteration barrier: fold everything before the next
                    # outer iteration (the plane lags within an iteration
                    # only)
                    while pending:
                        _reconcile_one(outer)

                    self._emit_transfer_stats(outer, prev_transfers)
                    if on_iteration_end is not None:
                        on_iteration_end(
                            outer,
                            CoordinateDescentResult(
                                models=dict(models),
                                best_models=(
                                    dict(best_models)
                                    if best_models
                                    else dict(models)
                                ),
                                best_metric=best_metric,
                                objective_history=list(objective_history),
                                validation_history=list(validation_history),
                            ),
                        )
        finally:
            executor.shutdown(wait=True)

        logger.info("CD %s", stats.to_summary_string())
        if self.validate is None or not best_models:
            best_models = dict(models)
        return CoordinateDescentResult(
            models=models,
            best_models=best_models,
            best_metric=best_metric,
            objective_history=objective_history,
            validation_history=validation_history,
        )
