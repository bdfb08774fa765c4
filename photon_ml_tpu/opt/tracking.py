"""Host-side optimization telemetry: per-solve and per-coordinate trackers.

Reference parity: OptimizationStatesTracker.scala:31 (per-iteration
(loss, time) ring buffer surfaced in logs/ModelTracker),
FixedEffectOptimizationTracker.scala and RandomEffectOptimizationTracker.scala
(statistics over millions of per-entity solves: convergence-reason counts and
iteration/loss distributions).

Device-side history already lives in opt.state.SolveResult (NaN-padded
``value_history``); these classes are the host-side view that turns one
SolveResult — or a vmap'd batch of them — into loggable summaries.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from photon_ml_tpu.opt.state import SolveResult
from photon_ml_tpu.types import ConvergenceReason


@dataclasses.dataclass(frozen=True)
class OptimizationStatesTracker:
    """History of one optimizer run (OptimizationStatesTracker.scala:31)."""

    values: np.ndarray  # [iterations+1] objective per iteration (trimmed)
    iterations: int
    convergence_reason: ConvergenceReason
    elapsed_seconds: Optional[float] = None
    # final-iterate gradient norm (the convergence plane's stationarity
    # signal; None for trackers built before the solve finished)
    grad_norm: Optional[float] = None

    @classmethod
    def from_result(
        cls, result: SolveResult, elapsed_seconds: Optional[float] = None
    ) -> "OptimizationStatesTracker":
        history = np.asarray(result.value_history)
        iters = int(result.iterations)
        return cls(
            values=history[: iters + 1],
            iterations=iters,
            convergence_reason=result.reason_enum(),
            elapsed_seconds=elapsed_seconds,
            grad_norm=float(result.grad_norm),
        )

    @property
    def converged(self) -> bool:
        return self.convergence_reason is not ConvergenceReason.NOT_CONVERGED

    def to_summary_string(self) -> str:
        head = (
            f"{self.iterations} iterations, reason={self.convergence_reason.name}"
        )
        if self.values.size:
            head += f", f0={self.values[0]:.6g}, f*={self.values[-1]:.6g}"
        if self.elapsed_seconds is not None:
            head += f", {self.elapsed_seconds:.3f}s"
        return head


@dataclasses.dataclass(frozen=True)
class FixedEffectOptimizationTracker:
    """One tracker per fixed-effect update (FixedEffectOptimizationTracker.scala)."""

    states: OptimizationStatesTracker

    def to_summary_string(self) -> str:
        return f"fixed-effect solve: {self.states.to_summary_string()}"


@dataclasses.dataclass(frozen=True)
class RandomEffectOptimizationTracker:
    """Aggregate convergence telemetry over per-entity solves
    (RandomEffectOptimizationTracker.scala): reason counts + iteration and
    final-loss distributions across all (unpadded) entities."""

    num_entities: int
    reason_counts: Dict[ConvergenceReason, int]
    iteration_stats: Dict[str, float]  # min/max/mean/p50/p90
    value_stats: Dict[str, float]

    @classmethod
    def from_results(
        cls,
        results: List[SolveResult],
        real_counts: "Optional[List[int]]" = None,
    ) -> "RandomEffectOptimizationTracker":
        """``results`` are vmap'd SolveResults (leading entity axis), one per
        bucket. ``real_counts`` (per bucket) excludes mesh-padding entity
        lanes from the telemetry; None means every lane is a real entity."""
        from photon_ml_tpu.parallel.mesh import fetch_global

        if real_counts is None:
            real_counts = [res.reason.shape[0] for res in results]
        reasons = [
            fetch_global(res.reason)[:k] for res, k in zip(results, real_counts)
        ]
        iters = [
            fetch_global(res.iterations)[:k] for res, k in zip(results, real_counts)
        ]
        finals = [
            fetch_global(res.value)[:k] for res, k in zip(results, real_counts)
        ]
        reason_all = np.concatenate(reasons) if reasons else np.zeros(0, np.int32)
        iter_all = np.concatenate(iters) if iters else np.zeros(0, np.int32)
        value_all = np.concatenate(finals) if finals else np.zeros(0, np.float32)

        counts = {
            r: int(np.sum(reason_all == r.value))
            for r in ConvergenceReason
            if np.any(reason_all == r.value)
        }
        return cls(
            num_entities=int(reason_all.size),
            reason_counts=counts,
            iteration_stats=_stats(iter_all.astype(np.float64)),
            value_stats=_stats(value_all.astype(np.float64)),
        )

    def to_summary_string(self) -> str:
        reason_part = ", ".join(
            f"{r.name}={c}" for r, c in sorted(self.reason_counts.items(), key=lambda kv: kv[0].value)
        )
        it = self.iteration_stats
        return (
            f"random-effect solves over {self.num_entities} entities: "
            f"[{reason_part}] iterations(mean={it.get('mean', 0):.1f}, "
            f"p50={it.get('p50', 0):.0f}, p90={it.get('p90', 0):.0f}, "
            f"max={it.get('max', 0):.0f})"
        )


@dataclasses.dataclass(frozen=True)
class SolverStats:
    """Per-bucket telemetry from the convergence-adaptive RE driver.

    ``executed_lane_iterations`` counts iterations actually dispatched
    (Σ over rounds and tiles of tile lanes × the tile's largest advance: a
    tile runs until its slowest lane stops); ``lockstep_lane_iterations``
    is what the one-shot vmap would have executed (num_entities × slowest
    entity's iteration count) — their ratio is the adaptive win.
    """

    bucket: int
    optimizer: str                 # 'lbfgs' | 'owlqn' | 'tron'
    num_entities: int
    rounds: int
    chunk_iters: int
    dispatch_widths: tuple         # lanes per round (tiles × tile lanes)
    iterations_p50: float
    iterations_p99: float
    iterations_max: int
    sum_entity_iterations: int     # Σ per-entity final iteration counts
    executed_lane_iterations: int
    lockstep_lane_iterations: int
    converged: int                 # entities with reason != NOT_CONVERGED
    chunk_retraces: int            # chunk traces this solve caused (1 for
                                   # a bucket shape's first, then 0)

    @property
    def wasted_lane_fraction(self) -> float:
        """Fraction of executed lane-iterations spent on already-converged
        or padding lanes (0 = perfect packing)."""
        if self.executed_lane_iterations == 0:
            return 0.0
        return 1.0 - self.sum_entity_iterations / self.executed_lane_iterations

    @property
    def lane_iteration_savings(self) -> float:
        """lockstep / executed — ≥1; ≥2 on skewed-convergence workloads."""
        if self.executed_lane_iterations == 0:
            return 1.0
        return self.lockstep_lane_iterations / self.executed_lane_iterations

    def to_summary_string(self) -> str:
        return (
            f"bucket {self.bucket} ({self.optimizer}, {self.num_entities} entities): "
            f"{self.rounds} rounds of K={self.chunk_iters} at widths "
            f"{list(self.dispatch_widths)}, iterations(p50={self.iterations_p50:.0f}, "
            f"p99={self.iterations_p99:.0f}, max={self.iterations_max}), "
            f"lane-iters executed={self.executed_lane_iterations} vs "
            f"lockstep={self.lockstep_lane_iterations} "
            f"({self.lane_iteration_savings:.2f}x saved, "
            f"wasted={self.wasted_lane_fraction:.1%}), "
            f"converged={self.converged}/{self.num_entities}"
        )


@dataclasses.dataclass
class TransferStats:
    """Score-plane transfer accounting for one coordinate-descent run.

    The CD driver owns one instance per ``run`` and counts every row-length
    (``num_rows``) score array that crosses the host/device boundary, plus
    the full host score-plane re-sums the legacy host plane performs. On the
    device plane the steady state is zero row transfers and zero host sums —
    ``tests/test_cd_device_scores.py`` gates on exactly that.
    """

    score_plane: str               # 'host' | 'device'
    num_rows: int
    bytes_per_row_array: int = 0   # num_rows * 4 (f32), set in __post_init__
    coordinate_updates: int = 0
    outer_iterations: int = 0
    host_score_sums: int = 0       # full C-way score-plane re-sums on host
    device_plane_updates: int = 0  # incremental total += new - old updates
    row_transfers_h2d: int = 0     # row-length arrays pushed host -> device
    row_transfers_d2h: int = 0     # row-length arrays pulled device -> host

    def __post_init__(self) -> None:
        self.bytes_per_row_array = int(self.num_rows) * 4

    def record_h2d(self, arrays: int = 1) -> None:
        self.row_transfers_h2d += int(arrays)

    def record_d2h(self, arrays: int = 1) -> None:
        self.row_transfers_d2h += int(arrays)

    @property
    def row_bytes_h2d(self) -> int:
        return self.row_transfers_h2d * self.bytes_per_row_array

    @property
    def row_bytes_d2h(self) -> int:
        return self.row_transfers_d2h * self.bytes_per_row_array

    @property
    def row_bytes_total(self) -> int:
        return self.row_bytes_h2d + self.row_bytes_d2h

    def per_outer_iteration(self) -> Dict[str, float]:
        """Steady-state rates: row arrays / bytes / sums per outer iteration."""
        it = max(self.outer_iterations, 1)
        return {
            "row_transfers_per_iter": (
                (self.row_transfers_h2d + self.row_transfers_d2h) / it
            ),
            "row_bytes_per_iter": self.row_bytes_total / it,
            "host_score_sums_per_iter": self.host_score_sums / it,
        }

    def snapshot(self) -> Dict[str, object]:
        out = {
            "score_plane": self.score_plane,
            "num_rows": self.num_rows,
            "coordinate_updates": self.coordinate_updates,
            "outer_iterations": self.outer_iterations,
            "host_score_sums": self.host_score_sums,
            "device_plane_updates": self.device_plane_updates,
            "row_transfers_h2d": self.row_transfers_h2d,
            "row_transfers_d2h": self.row_transfers_d2h,
            "row_bytes_h2d": self.row_bytes_h2d,
            "row_bytes_d2h": self.row_bytes_d2h,
        }
        out.update(self.per_outer_iteration())
        return out

    def to_summary_string(self) -> str:
        return (
            f"score plane '{self.score_plane}' over {self.num_rows} rows: "
            f"{self.coordinate_updates} updates in {self.outer_iterations} "
            f"outer iterations, {self.host_score_sums} host score sums, "
            f"{self.device_plane_updates} device plane updates, "
            f"row transfers h2d={self.row_transfers_h2d} "
            f"d2h={self.row_transfers_d2h} "
            f"({self.row_bytes_total / 1e6:.3f} MB)"
        )


def _stats(x: np.ndarray) -> Dict[str, float]:
    if x.size == 0:
        return {}
    return {
        "min": float(np.min(x)),
        "max": float(np.max(x)),
        "mean": float(np.mean(x)),
        "p50": float(np.percentile(x, 50)),
        "p90": float(np.percentile(x, 90)),
    }
