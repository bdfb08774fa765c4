#!/usr/bin/env python
"""Convergence-regression sentinel: fresh progress ledger vs golden history.

CI trains the tiny golden ratings fixture with ``--progress-out`` and hands
the resulting ``progress.jsonl`` to this script, which compares the run's
convergence TRAJECTORY against the golden records committed in
``tests/fixtures/ratings/convergence_golden.jsonl``:

* ``golden_fixture_final_objective`` — the final training objective; the
  gate fires when the fresh value sits above the reference by more than
  ``--objective-tolerance`` (relative, default 1%);
* ``golden_fixture_iterations_to_tol`` — coordinate updates until the
  objective stays within tolerance of its final value; fires when the
  fresh run needs more than reference + ``--iteration-slack`` updates;
* optionally, with ``--target-metric``, iterations until the held-out
  metric reaches the target (``golden_fixture_iterations_to_target``).

These are OPTIMIZATION quantities — deterministic on the fixed-seed CPU
fixture and independent of wall-clock noise: a slower machine converges in
exactly the same number of updates to exactly the same objective. Infrastructure
problems (missing ledger, no progress records, no golden baseline) report
and pass; only a measured degradation fails.

Usage:
    python -m photon_ml_tpu.cli.train_game ... --progress-out /tmp/p.jsonl
    python dev-scripts/check_convergence_trajectory.py /tmp/p.jsonl \
        [--history tests/fixtures/ratings/convergence_golden.jsonl] \
        [--objective-tolerance 0.01] \
        [--iteration-slack 1] [--target-metric 0.9 [--lower-is-better]]
"""
import argparse
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_jsonl(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # crash-truncated tail is fine; analyze the prefix
    return out


def _iters_to_tolerance(objectives, tolerance):
    """1-based count of coordinate updates until the objective stays within
    ``tolerance`` (relative) of its final value. Mirrors
    photon_ml_tpu.telemetry.progress._iters_to_tolerance — keep in sync."""
    if not objectives:
        return None
    final = objectives[-1]
    scale = max(1.0, abs(final))
    for i in range(len(objectives)):
        if all(abs(o - final) <= tolerance * scale for o in objectives[i:]):
            return i + 1
    return None


def _iters_to_target(progress, target, higher_is_better):
    for rec in progress:
        if rec.get("kind") != "validation":
            continue
        m = float(rec["metric"])
        if (m >= target) if higher_is_better else (m <= target):
            return int(rec["outer"]) + 1
    return None


def _golden(history_path, metric):
    """Latest mode=convergence history record for ``metric`` (None if the
    baseline was never recorded)."""
    if not os.path.exists(history_path):
        return None
    value = None
    for rec in _read_jsonl(history_path):
        if rec.get("mode") == "convergence" and rec.get("metric") == metric:
            value = rec.get("value")
    return value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("ledger", help="progress.jsonl from a --progress-out run")
    ap.add_argument(
        "--history",
        default=os.path.join(
            REPO, "tests", "fixtures", "ratings", "convergence_golden.jsonl"
        ),
        help="file holding the golden mode=convergence records",
    )
    ap.add_argument(
        "--objective-tolerance", type=float, default=0.01,
        help="fail when the fresh final objective exceeds the golden one by "
             "more than this relative margin (default 0.01)",
    )
    ap.add_argument(
        "--tolerance", type=float, default=1e-3,
        help="relative tolerance defining 'converged' for the "
             "iterations-to-tolerance count (default 1e-3; must match the "
             "value used when the golden record was taken)",
    )
    ap.add_argument(
        "--iteration-slack", type=int, default=1,
        help="fail when the fresh run needs more than golden + slack "
             "updates to reach tolerance (default 1)",
    )
    ap.add_argument(
        "--target-metric", type=float, default=None,
        help="also gate iterations-to-target on the held-out metric trace",
    )
    ap.add_argument(
        "--lower-is-better", action="store_true",
        help="the held-out metric improves downward (RMSE-style)",
    )
    args = ap.parse_args(argv)

    try:
        records = _read_jsonl(args.ledger)
    except OSError as e:
        print(f"convergence-trajectory: cannot read ledger ({e}); skipping")
        return 0
    progress = [r for r in records if r.get("type") == "progress"]
    coord = [r for r in progress if r.get("kind") == "coordinate"]
    if not coord:
        print(
            "convergence-trajectory: ledger carries no coordinate progress "
            "records; nothing to gate — skipping"
        )
        return 0
    anomalies = [r for r in progress if r.get("kind") == "anomaly"]
    if anomalies:
        a = anomalies[0]
        print(
            "convergence-trajectory: FAIL — run recorded a divergence "
            f"anomaly ({a.get('anomaly_kind')} at outer {a.get('outer')}, "
            f"coordinate {a.get('coordinate')!r})"
        )
        return 1

    objectives = [float(r["objective"]) for r in coord]
    final_obj = objectives[-1]
    iters = _iters_to_tolerance(objectives, args.tolerance)
    print(
        f"convergence-trajectory: {len(objectives)} update(s), final "
        f"objective {final_obj:.6g}, iterations-to-tolerance "
        f"{iters if iters is not None else 'not reached'}"
    )
    if not math.isfinite(final_obj):
        print("convergence-trajectory: FAIL — non-finite final objective")
        return 1

    failures = []
    ref_obj = _golden(args.history, "golden_fixture_final_objective")
    if ref_obj is None:
        print(
            "convergence-trajectory: no golden_fixture_final_objective in "
            f"{args.history}; objective gate skipped"
        )
    else:
        allowed = float(ref_obj) + args.objective_tolerance * max(
            1.0, abs(float(ref_obj))
        )
        print(
            f"convergence-trajectory: final objective {final_obj:.6g} vs "
            f"golden {float(ref_obj):.6g} (allowed <= {allowed:.6g})"
        )
        if final_obj > allowed:
            failures.append(
                f"final objective {final_obj:.6g} exceeds golden "
                f"{float(ref_obj):.6g} by more than "
                f"{args.objective_tolerance:.2%}"
            )

    ref_iters = _golden(args.history, "golden_fixture_iterations_to_tol")
    if ref_iters is None:
        print(
            "convergence-trajectory: no golden_fixture_iterations_to_tol in "
            f"{args.history}; iteration gate skipped"
        )
    else:
        allowed_iters = int(ref_iters) + args.iteration_slack
        shown = iters if iters is not None else "not reached"
        print(
            f"convergence-trajectory: iterations-to-tolerance {shown} vs "
            f"golden {int(ref_iters)} (allowed <= {allowed_iters})"
        )
        if iters is None or iters > allowed_iters:
            failures.append(
                f"iterations-to-tolerance {shown} exceeds golden "
                f"{int(ref_iters)} + slack {args.iteration_slack}"
            )

    if args.target_metric is not None:
        t_iters = _iters_to_target(
            progress, args.target_metric, not args.lower_is_better
        )
        ref_t = _golden(args.history, "golden_fixture_iterations_to_target")
        shown = t_iters if t_iters is not None else "not reached"
        if ref_t is None:
            print(
                f"convergence-trajectory: iterations-to-target {shown} "
                "(no golden record; gate skipped)"
            )
        else:
            allowed_t = int(ref_t) + args.iteration_slack
            print(
                f"convergence-trajectory: iterations-to-target {shown} vs "
                f"golden {int(ref_t)} (allowed <= {allowed_t})"
            )
            if t_iters is None or t_iters > allowed_t:
                failures.append(
                    f"iterations-to-target-metric {shown} exceeds golden "
                    f"{int(ref_t)} + slack {args.iteration_slack}"
                )

    if failures:
        for f in failures:
            print(f"convergence-trajectory: FAIL — {f}")
        return 1
    print("convergence-trajectory: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
