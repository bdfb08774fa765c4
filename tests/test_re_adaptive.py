"""Convergence-adaptive random-effect solving (tier-1 parity gate).

The adaptive driver (estimators/random_effect.py) replaces the one-shot
lockstep ``vmap(solve)`` per bucket with chunked solver rounds over the tiles
that hold a live lane: the state stays at the bucket's width, and a round's
live tiles are an operand of the one chunk program. These tests pin down the
contract:

- coefficients match the one-shot path to <=1e-5 for LBFGS / OWL-QN / TRON,
  including warm starts and proj_valid padding (the chunked while_loop
  follows the exact same per-lane trajectory as the uninterrupted loop);
- on a skewed-convergence warm-started workload the driver cuts executed
  lane-iterations >=2x vs lockstep (asserted from SolverStats);
- a bucket shape compiles one init, one chunk and one extract program
  (asserted via the module's jit-trace counter and the compile spans), and
  same-shape re-runs trace nothing, whatever their live counts;
- the tile loop's edges: a last tile past the bucket's end, every lane done
  in round 0, one live lane left in the last tile, variances;
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
    _tile_lanes,
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
from photon_ml_tpu.telemetry.span import disable_tracing, enable_tracing, span
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
    packing and the live-lane tiles both engage."""
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


def _skewed_warm_pair(rng, n_entities=64, n_hard=6, d=6, hard_samples=500):
    """The nearline re-solve profile: warm model from batch A; batch B keeps
    the easy entities' labels (lanes converge in a couple of iterations) but
    gives the hard tail fresh near-separable labels (lanes run long). A
    bucket orders its entities by falling sample count: ``hard_samples``
    under 5 puts the hard lanes last."""
    rows, cols, vals, ids = [], [], [], []
    labels_a, labels_b = [], []
    r = 0
    for e in range(n_entities):
        eid = f"m{e:05d}"
        hard = e < n_hard
        n_e = hard_samples if hard else int(rng.integers(5, 30))
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
    assert s.rounds >= 2  # savings must come from dropped tiles, not luck


_RE_PROGRAMS = [(p, "lbfgs") for p in ("re_init", "re_chunk", "re_extract")]


def _program_traces():
    counts = solver_trace_counts()
    return {key: counts.get(key, 0) for key in _RE_PROGRAMS}


def test_one_chunk_program_per_bucket_shape(rng):
    ids, rows, cols, vals, labels, gdim = _sparse_problem(
        rng, n_entities=43, logistic=True
    )
    cfg = _cfg("lbfgs", weight=0.1, adaptive=ADAPTIVE)
    task = TaskType.LOGISTIC_REGRESSION
    T = _tile_lanes(43, ADAPTIVE.min_lanes)
    assert T == ADAPTIVE.min_lanes

    before = _program_traces()
    stats = []
    model, _ = train_random_effects(
        _build(ids, rows, cols, vals, gdim, labels), task, cfg, stats_out=stats
    )
    first = _program_traces()
    # a bucket shape's first solve traces each of its programs once,
    # however many rounds and widths it went through
    assert {k: first[k] - before[k] for k in _RE_PROGRAMS} == dict.fromkeys(
        _RE_PROGRAMS, 1
    )
    assert stats[0].chunk_retraces == 1
    assert stats[0].rounds >= 2

    # same bucket shapes, other labels and warm starts, so other live counts
    # every round (the first re-run is the first solve's labels from its own
    # model: every lane stops in round 0): nothing is traced, lowered or
    # compiled again
    tracer = enable_tracing(device_sync=False, clear=True)
    try:
        for flip in (labels.copy(), 1.0 - labels, np.roll(labels, 7)):
            with span("re/train"):
                model, _ = train_random_effects(
                    _build(ids, rows, cols, vals, gdim, flip), task, cfg,
                    initial_model=model, stats_out=stats,
                )
        compiles = [
            (r.name, r.attrs["fun_name"]) for r in tracer.spans()
            if r.name.startswith("jit/") and r.name != "jit/cache"
            and r.attrs["under"].startswith("re/train")
        ]
    finally:
        disable_tracing()
    assert compiles == []
    assert _program_traces() == first, "same-shape re-run retraced"
    assert [s.chunk_retraces for s in stats[1:]] == [0, 0, 0]

    for s in stats:
        widths = list(s.dispatch_widths)
        # round 0 takes every tile; later rounds only the tiles that hold a
        # live lane
        assert widths[0] == -(-s.num_entities // T) * T
        assert all(w % T == 0 and w >= T for w in widths)
        assert widths == sorted(widths, reverse=True)
        assert s.converged == s.num_entities
    # the live counts did differ from call to call
    assert len({s.dispatch_widths for s in stats}) >= 2


def _edge_ragged_last_tile(rng):
    """E = 21 with tiles of 8: the last tile holds 5 lanes and 3 copies."""
    ids, rows, cols, vals, labels, gdim = _sparse_problem(
        rng, n_entities=21, logistic=True
    )
    ds = _build(ids, rows, cols, vals, gdim, labels)
    return ds, TaskType.LOGISTIC_REGRESSION, 0.1, {}


def _edge_all_done_in_round_0(rng):
    """Zero labels from a zero start: every gradient is exactly zero, each
    lane stops at its first test, and no second round is dispatched."""
    ids, rows, cols, vals, labels, gdim = _sparse_problem(rng, n_entities=21)
    ds = _build(ids, rows, cols, vals, gdim, np.zeros_like(labels))
    return ds, TaskType.LINEAR_REGRESSION, 0.1, {}


def _edge_single_live_lane_in_last_tile(rng):
    """One hard entity with the fewest samples: it is the bucket's last
    lane, in the half-empty last tile, and outlives every other lane."""
    ds_a, ds_b = _skewed_warm_pair(
        rng, n_entities=21, n_hard=1, hard_samples=3
    )
    warm, _ = train_random_effects(
        ds_a, TaskType.LOGISTIC_REGRESSION,
        _cfg("lbfgs", weight=1e-6, adaptive=ONESHOT),
    )
    return ds_b, TaskType.LOGISTIC_REGRESSION, 1e-6, {"initial_model": warm}


def _edge_variances(rng):
    ds, task, weight, kw = _edge_ragged_last_tile(rng)
    return ds, task, weight, {**kw, "compute_variances": True}


@pytest.mark.parametrize(
    "case",
    [
        _edge_ragged_last_tile,
        _edge_all_done_in_round_0,
        _edge_single_live_lane_in_last_tile,
        _edge_variances,
    ],
    ids=lambda f: f.__name__[len("_edge_"):],
)
def test_tile_loop_edges_match_oneshot(rng, case):
    ds, task, weight, kw = case(rng)
    stats = []
    m_ad, res_ad = train_random_effects(
        ds, task, _cfg("lbfgs", weight=weight, adaptive=ADAPTIVE),
        stats_out=stats, **kw
    )
    m_os, res_os = train_random_effects(
        ds, task, _cfg("lbfgs", weight=weight, adaptive=ONESHOT), **kw
    )
    _assert_models_close(m_ad, m_os)
    (a,), (b,) = res_ad, res_os
    its = np.asarray(a.iterations)
    np.testing.assert_array_equal(its, np.asarray(b.iterations))
    np.testing.assert_array_equal(np.asarray(a.reason), np.asarray(b.reason))

    (s,) = stats
    E, T = s.num_entities, ADAPTIVE.min_lanes
    assert E == 21 and s.dispatch_widths[0] == 24
    assert s.sum_entity_iterations == int(its.sum())
    # per tile: T lanes times the tile's slowest lane, so never under the
    # iterations the entities made and never over lockstep on whole tiles
    assert s.sum_entity_iterations <= s.executed_lane_iterations
    assert s.executed_lane_iterations <= 24 * int(its.max())
    if case is _edge_all_done_in_round_0:
        assert s.rounds == 1 and its.max() <= 1
    if case is _edge_single_live_lane_in_last_tile:
        # the last round's one tile: the bucket's last lane and 7 done ones
        assert int(np.argmax(its)) == E - 1
        assert s.rounds >= 2 and s.dispatch_widths[-1] == T
        assert np.sum(its > (s.rounds - 1) * ADAPTIVE.chunk_iters) == 1
    if case is _edge_variances:
        for va, vb in zip(m_ad.variances, m_os.variances):
            np.testing.assert_allclose(
                np.asarray(va), np.asarray(vb), rtol=1e-4, atol=1e-7
            )


def test_small_buckets_fall_back_to_oneshot(rng):
    """Savings come only from dropped tiles; at E <= min_lanes there is one
    tile at most, so the driver must use the fused one-shot program."""
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
