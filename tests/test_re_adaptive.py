"""Convergence-adaptive random-effect solving (tier-1 parity gate).

The adaptive driver (estimators/random_effect.py) replaces the one-shot
lockstep ``vmap(solve)`` per bucket with chunked solver rounds + lane
compaction + pow2 re-dispatch. These tests pin down the contract:

- coefficients match the one-shot path to <=1e-5 for LBFGS / OWL-QN / TRON,
  including warm starts and proj_valid padding (the chunked while_loop
  follows the exact same per-lane trajectory as the uninterrupted loop);
- on a skewed-convergence warm-started workload the driver cuts executed
  lane-iterations >=2x vs lockstep (asserted from SolverStats);
- compiled-program count is bounded by the pow2 ladder (asserted via the
  module's jit-trace counter) and same-shape re-runs add zero retraces;
- SolverStats flows out through coordinate descent as SolverStatsEvent.

Deliberately NOT marked slow: this is the regression gate for the adaptive
path, so it runs in the fast lane.
"""

import numpy as np
import pytest

from photon_ml_tpu.algorithm.coordinate import RandomEffectCoordinate
from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_ml_tpu.data import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.estimators.random_effect import (
    solver_trace_counts,
    train_random_effects,
)
from photon_ml_tpu.event import EventEmitter, EventListener, SolverStatsEvent
from photon_ml_tpu.opt import (
    AdaptiveSolveConfig,
    GlmOptimizationConfiguration,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.types import RegularizationType, TaskType

ADAPTIVE = AdaptiveSolveConfig(enabled=True, chunk_iters=8, min_lanes=8)
ONESHOT = AdaptiveSolveConfig(enabled=False)


def _cfg(optimizer="lbfgs", reg=RegularizationType.L2, weight=0.1,
         adaptive=ADAPTIVE):
    opt = (OptimizerConfig.tron() if optimizer == "tron"
           else OptimizerConfig.lbfgs())
    return GlmOptimizationConfiguration(
        optimizer_config=opt,
        regularization=RegularizationContext(reg),
        regularization_weight=weight,
        adaptive=adaptive,
    )


def _sparse_problem(rng, n_entities=20, samples=(5, 40), global_dim=30,
                    logistic=False):
    """Entities observe different slices of the global space, so the bucket
    carries proj_valid padding; sample counts are ragged, so cost-sorted
    packing and lane compaction both engage."""
    rows, cols, vals, ids, labels = [], [], [], [], []
    r = 0
    for e in range(n_entities):
        eid = f"ent{e:03d}"
        n_e = int(rng.integers(*samples))
        feats = np.sort(
            rng.choice(global_dim, size=int(rng.integers(3, 8)), replace=False)
        )
        w_e = rng.normal(size=len(feats)).astype(np.float32)
        for _ in range(n_e):
            x = rng.normal(size=len(feats)).astype(np.float32)
            z = float(x @ w_e)
            y = (1.0 if rng.random() < 1.0 / (1.0 + np.exp(-z)) else 0.0) \
                if logistic else z
            for c, v in zip(feats, x):
                rows.append(r)
                cols.append(c)
                vals.append(float(v))
            ids.append(eid)
            labels.append(y)
            r += 1
    return ids, np.array(rows), np.array(cols), np.array(vals, np.float32), \
        np.array(labels, np.float32), global_dim


def _build(ids, rows, cols, vals, gdim, labels, num_buckets=1):
    cfg = RandomEffectDataConfiguration(
        random_effect_type="ent", num_buckets=num_buckets
    )
    return build_random_effect_dataset(ids, rows, cols, vals, gdim, labels, cfg)


def _skewed_warm_pair(rng, n_entities=64, n_hard=6, d=6):
    """The nearline re-solve profile: warm model from batch A; batch B keeps
    the easy entities' labels (lanes converge in a couple of iterations) but
    gives the hard tail fresh near-separable labels (lanes run long)."""
    rows, cols, vals, ids = [], [], [], []
    labels_a, labels_b = [], []
    r = 0
    for e in range(n_entities):
        eid = f"m{e:05d}"
        hard = e < n_hard
        n_e = 500 if hard else int(rng.integers(5, 30))
        w_e = rng.normal(size=d).astype(np.float32) * 0.5
        w_fresh = rng.normal(size=d).astype(np.float32) * 10.0
        for _ in range(n_e):
            x = rng.normal(size=d).astype(np.float32)
            z = float(x @ w_e)
            ya = 1.0 if rng.random() < 1.0 / (1.0 + np.exp(-z)) else 0.0
            yb = ya if not hard else (1.0 if float(x @ w_fresh) > 0 else 0.0)
            for c in range(d):
                rows.append(r)
                cols.append(c)
                vals.append(float(x[c]))
            ids.append(eid)
            labels_a.append(ya)
            labels_b.append(yb)
            r += 1
    rows, cols = np.array(rows), np.array(cols)
    vals = np.array(vals, np.float32)
    ds_a = _build(ids, rows, cols, vals, d, np.array(labels_a, np.float32))
    ds_b = _build(ids, rows, cols, vals, d, np.array(labels_b, np.float32))
    return ds_a, ds_b


def _rows(model):
    return {str(eid): coefs for eid, coefs in model.items()}


def _assert_models_close(m_a, m_b, tol=1e-5):
    ra, rb = _rows(m_a), _rows(m_b)
    assert set(ra) == set(rb)
    for eid in ra:
        keys = set(ra[eid]) | set(rb[eid])
        for k in keys:
            assert abs(ra[eid].get(k, 0.0) - rb[eid].get(k, 0.0)) <= tol, (
                f"entity {eid} coef {k}: {ra[eid].get(k)} vs {rb[eid].get(k)}"
            )


@pytest.mark.parametrize(
    "optimizer,reg,task,logistic",
    [
        ("lbfgs", RegularizationType.L2, TaskType.LOGISTIC_REGRESSION, True),
        ("lbfgs", RegularizationType.L1, TaskType.LOGISTIC_REGRESSION, True),
        ("tron", RegularizationType.L2, TaskType.LINEAR_REGRESSION, False),
    ],
    ids=["lbfgs", "owlqn", "tron"],
)
def test_adaptive_matches_oneshot(rng, optimizer, reg, task, logistic):
    ids, rows, cols, vals, labels, gdim = _sparse_problem(rng, logistic=logistic)
    ds = _build(ids, rows, cols, vals, gdim, labels)
    weight = 0.01 if reg is RegularizationType.L1 else 0.1
    stats = []
    m_ad, res_ad = train_random_effects(
        ds, task, _cfg(optimizer, reg, weight, ADAPTIVE), stats_out=stats
    )
    m_os, res_os = train_random_effects(
        ds, task, _cfg(optimizer, reg, weight, ONESHOT)
    )
    _assert_models_close(m_ad, m_os)
    # the chunked loop follows the identical per-lane trajectory, so even
    # the iteration counts agree
    for a, b in zip(res_ad, res_os):
        np.testing.assert_array_equal(
            np.asarray(a.iterations), np.asarray(b.iterations)
        )
    assert stats and stats[0].rounds >= 1
    assert stats[0].converged == stats[0].num_entities


def test_adaptive_matches_oneshot_warm_start_and_variances(rng):
    ds_a, ds_b = _skewed_warm_pair(rng, n_entities=24, n_hard=3)
    cfg_os = _cfg("lbfgs", weight=1e-6, adaptive=ONESHOT)
    warm, _ = train_random_effects(
        ds_a, TaskType.LOGISTIC_REGRESSION, cfg_os
    )
    kw = dict(initial_model=warm, compute_variances=True)
    m_ad, _ = train_random_effects(
        ds_b, TaskType.LOGISTIC_REGRESSION,
        _cfg("lbfgs", weight=1e-6, adaptive=ADAPTIVE), **kw
    )
    m_os, _ = train_random_effects(
        ds_b, TaskType.LOGISTIC_REGRESSION, cfg_os, **kw
    )
    _assert_models_close(m_ad, m_os)


def test_lane_iteration_savings_at_least_2x(rng):
    """ISSUE acceptance: on the skewed-convergence warm-started workload the
    adaptive driver must cut executed lane-iterations >=2x vs lockstep."""
    ds_a, ds_b = _skewed_warm_pair(rng)
    cfg_os = _cfg("lbfgs", weight=1e-6, adaptive=ONESHOT)
    warm, _ = train_random_effects(ds_a, TaskType.LOGISTIC_REGRESSION, cfg_os)
    stats = []
    train_random_effects(
        ds_b, TaskType.LOGISTIC_REGRESSION,
        _cfg("lbfgs", weight=1e-6, adaptive=ADAPTIVE),
        initial_model=warm, stats_out=stats,
    )
    assert len(stats) == 1
    s = stats[0]
    assert s.converged == s.num_entities
    assert s.executed_lane_iterations > 0
    assert s.lane_iteration_savings >= 2.0, s.to_summary_string()
    assert s.rounds >= 2  # savings must come from compaction, not luck


def test_pow2_ladder_bounds_recompiles(rng):
    ids, rows, cols, vals, labels, gdim = _sparse_problem(
        rng, n_entities=24, logistic=True
    )
    ds1 = _build(ids, rows, cols, vals, gdim, labels)
    cfg = _cfg("lbfgs", weight=0.1, adaptive=ADAPTIVE)
    before = dict(solver_trace_counts())
    stats1 = []
    train_random_effects(
        ds1, TaskType.LOGISTIC_REGRESSION, cfg, stats_out=stats1
    )
    after = dict(solver_trace_counts())
    key = ("re_chunk", "lbfgs")
    delta1 = after.get(key, 0) - before.get(key, 0)

    s = stats1[0]
    widths = list(s.dispatch_widths)
    assert widths[0] == s.num_entities
    # a round that compacts nothing dispatches its width again; only a new
    # width has to sit on the ladder
    for prev, w in zip(widths, widths[1:]):
        if w != prev:
            assert w & (w - 1) == 0, f"non-pow2 re-dispatch width {w}"
        assert w >= ADAPTIVE.min_lanes
    assert widths == sorted(widths, reverse=True)
    # ladder bound: the initial width plus at most one program per pow2
    # step between next_pow2(E) and min_lanes
    e_pow2 = 1 << (s.num_entities - 1).bit_length()
    ladder = 1 + max(0, e_pow2.bit_length() - ADAPTIVE.min_lanes.bit_length())
    assert delta1 <= ladder, (delta1, ladder, widths)
    assert s.chunk_retraces == delta1

    # same bucket shapes, different labels: every program is cache-hit
    labels2 = labels[::-1].copy()
    ds2 = _build(ids, rows, cols, vals, gdim, labels2)
    mid = dict(solver_trace_counts())
    stats2 = []
    train_random_effects(
        ds2, TaskType.LOGISTIC_REGRESSION, cfg, stats_out=stats2
    )
    end = dict(solver_trace_counts())
    assert end.get(key, 0) == mid.get(key, 0), "same-shape re-run retraced"
    assert stats2[0].chunk_retraces == 0


def test_small_buckets_fall_back_to_oneshot(rng):
    """Savings come only from compaction; at E <= min_lanes there is nothing
    to compact, so the driver must use the fused one-shot program."""
    ids, rows, cols, vals, labels, gdim = _sparse_problem(
        rng, n_entities=6, logistic=True
    )
    ds = _build(ids, rows, cols, vals, gdim, labels)
    stats = []
    train_random_effects(
        ds, TaskType.LOGISTIC_REGRESSION,
        _cfg("lbfgs", weight=0.1, adaptive=ADAPTIVE), stats_out=stats
    )
    assert stats[0].rounds == 1
    assert stats[0].dispatch_widths == (stats[0].num_entities,)
    assert stats[0].chunk_retraces == 0


class _Capture(EventListener):
    def __init__(self):
        self.events = []

    def on_event(self, event):
        self.events.append(event)


def test_solver_stats_event_emitted_from_cd(rng):
    ids, rows, cols, vals, labels, gdim = _sparse_problem(
        rng, n_entities=16, logistic=True
    )
    ds = _build(ids, rows, cols, vals, gdim, labels)
    n_rows = len(ids)
    coord = RandomEffectCoordinate(
        dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION,
        configuration=_cfg("lbfgs", weight=0.1, adaptive=ADAPTIVE),
        base_offsets=np.zeros(n_rows, dtype=np.float32),
    )
    emitter = EventEmitter()
    cap = _Capture()
    emitter.register_listener(cap)
    cd = CoordinateDescent({"per-ent": coord}, num_rows=n_rows, emitter=emitter)
    cd.run(1)
    ev = [e for e in cap.events if isinstance(e, SolverStatsEvent)]
    assert ev, "no SolverStatsEvent reached the listener"
    e = ev[0]
    assert e.coordinate_id == "per-ent"
    assert e.num_entities == 16
    assert e.executed_lane_iterations > 0
    assert e.lockstep_lane_iterations >= e.executed_lane_iterations
    assert 0.0 <= e.wasted_lane_fraction < 1.0
    assert len(e.dispatch_widths) == e.rounds
