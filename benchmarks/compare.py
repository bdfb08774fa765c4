"""The arithmetic that decides ``correct``: gaps between what the program
produced and what the plain reference produced, each held to a limit of its
own (the limits live in the workload's traffic file, set from readings on
the chip; PERF.md gives the readings).

Leaves are compared by the gap between the norms, not the norm of the
difference, measured against the reference's norm of that leaf or of the
median leaf, whichever is larger.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def relative_gap(program: float, reference: float) -> float:
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_norm_gap(program: Dict[str, np.ndarray], reference: Dict[str, np.ndarray],
                        log: Optional[Callable[[str], None]] = None) -> float:
    """Largest over the leaves of | ||p|| - ||r|| | / max(||r||, median ||r||).
    ``log`` is told every leaf's norms and its gap against its own norm too,
    so that a run shows what the median does to the smallest leaf."""
    names = sorted(reference)
    ref_norms = {k: float(np.linalg.norm(np.asarray(reference[k], np.float64))) for k in names}
    median = float(np.median(list(ref_norms.values())))
    worst = 0.0
    for k in names:
        p = float(np.linalg.norm(np.asarray(program[k], np.float64)))
        gap = abs(p - ref_norms[k])
        worst = max(worst, gap / max(ref_norms[k], median, 1e-30))
        if log is not None:
            log(f"leaf {k}: reference norm {ref_norms[k]:.6g}, program norm {p:.6g}, gap over its "
                f"own norm {gap / max(ref_norms[k], 1e-30):.3g}, over max(own, median "
                f"{median:.6g}) {gap / max(ref_norms[k], median, 1e-30):.3g}")
    return worst


def picked_update(validation: Sequence[float], first_fit: bool) -> int:
    """Which update's model a fit returns, by the rule the configuration
    states: the held-out metric's first maximum over the updates at which
    every coordinate has a model (in a fit from nothing, only the last)."""
    if first_fit or not validation:
        return len(validation) - 1
    best = 0
    for i, v in enumerate(validation):
        if v > validation[best]:
            best = i
    return best


def training_numbers(program_histories: List[dict], program_models: Dict[int, dict],
                     reference_steps: List[list],
                     log: Optional[Callable[[str], None]] = None) -> Dict[str, float]:
    """loss_gap: worst relative gap of the objective after every block
    update of the compared steps. auc_gap: worst absolute gap of the
    held-out AUC there. change_gap: worst leaf's gap of norms of the model
    each compared step returned (the start is the zero model, so a model's
    norm is the norm of its change)."""
    loss_gap = auc_gap = change_gap = 0.0
    for step, (hist, snaps) in enumerate(zip(program_histories, reference_steps)):
        if len(hist["objective"]) != len(snaps):
            return {"loss_gap": math.inf, "auc_gap": math.inf, "change_gap": math.inf}
        for p, snap in zip(hist["objective"], snaps):
            loss_gap = max(loss_gap, relative_gap(p, snap.objective))
        for p, snap in zip(hist["validation"], snaps):
            auc_gap = max(auc_gap, abs(p - snap.auc))
        snap = snaps[picked_update(hist["validation"], first_fit=step == 0)]
        reference = {"fixed": np.asarray(snap.fixed)}
        reference.update({k: np.asarray(v) for k, v in snap.random.items()})
        change_gap = max(change_gap, worst_leaf_norm_gap(
            program_models[step], reference,
            log and (lambda line, step=step: log(f"step {step} {line}"))))
    return {"loss_gap": _finite(loss_gap), "auc_gap": _finite(auc_gap),
            "change_gap": _finite(change_gap)}


def scored_objective_gap(histories: List[dict], models: Dict[int, dict],
                         evaluate: Callable) -> Dict[str, float]:
    """Every kept model that a step returned, scored by the reference: the
    relative gap between the objective the program reported for the update
    it returned and the reference scorer's objective of that same model.
    It reads the arithmetic of the timed path's maps, score plane and loss,
    free of where the solvers stopped; the worst over the kept steps."""
    worst = 0.0
    for step, model in models.items():
        hist = histories[step]
        i = picked_update(hist["validation"], first_fit=step == 0)
        worst = max(worst, relative_gap(hist["objective"][i], evaluate(model).objective))
    return {"scored_objective_gap": _finite(worst)}


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else math.inf


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, number, limit)]) — every limit must be met by a
    number that was read; a limit with no number, or a NaN, is a failure."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name, math.nan)
        rows.append((name, value, limit))
        if not (isinstance(value, float) and value <= limit):
            ok = False
    return ok, rows
