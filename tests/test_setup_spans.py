"""Set-up seen from inside the program: the spans of the feature build
(``route/layout``, ``route/slot_perm``, ``route/plan``, ``route/place``),
of the uploads (``data/upload``) and of the random-effect builds
(``re/build_dataset``), what they carry, that they cost nothing while the
tracer is off, and the benchmark's readers of them
(``benchmarks/layer_metrics/setup_*.py``) on a hand-made span list."""

import os
import sys

import jax
import numpy as np
import pytest

from photon_ml_tpu.data.game_data import FeatureShard
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops import fused_perm, sparse_perm
from photon_ml_tpu.telemetry.span import (
    disable_tracing,
    enable_tracing,
    get_tracer,
    span,
    union_seconds,
)
from tests._tiny_glmix import _tiny_glmix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTE = ("route/layout", "route/slot_perm", "route/plan", "route/place")
BUILDERS = {"benes": sparse_perm.from_coo, "fused": fused_perm.from_coo}
LAYOUTS = {"flat": 1, "split": 2}


def _coo(n=64, d=2048, k=6, seed=0):
    rng = np.random.default_rng(seed)
    return (np.repeat(np.arange(n), k), rng.integers(0, d, n * k),
            rng.standard_normal(n * k).astype(np.float32), (n, d))


def _build(engine, layout, plan_cache):
    rows, cols, vals, shape = _coo()
    return BUILDERS[engine](rows, cols, vals, shape, plan_cache=str(plan_cache),
                            kp_cap=None, col_split=LAYOUTS[layout])


def _plans(features):
    return [b.plan for b in getattr(features, "blocks", (features,))]


def _device_bytes(tree):
    return sum(a.nbytes for a in jax.tree.leaves(tree) if isinstance(a, jax.Array))


def _traced(build):
    """(what ``build()`` returns, the spans it made, the span it ran in)."""
    tracer = enable_tracing(device_sync=False)
    try:
        with span("build") as outer:
            out = build()
        return out, tracer.spans(), outer
    finally:
        disable_tracing()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("engine", sorted(BUILDERS))
def test_a_feature_build_spans_its_layout_plan_and_upload(engine, layout, tmp_path):
    builds = [_traced(lambda: _build(engine, layout, tmp_path)) for _ in range(2)]
    for build, cached in zip(builds, (False, True)):
        features, spans, outer = build
        mine = [s for s in spans if s.name != "build" and not s.name.startswith("jit/")]
        assert {s.name for s in mine} == set(ROUTE) | {"data/upload"}
        # siblings under the build, none nested in another
        assert all(s.parent_id == outer.span_id for s in mine)
        plans = _plans(features)
        assert len(plans) == LAYOUTS[layout]
        layouts = [s.attrs for s in mine if s.name == "route/layout"]
        assert layouts[0] == {"nnz": 64 * 6, "blocks": LAYOUTS[layout]}
        assert sorted(s.attrs["slots"] for s in mine if s.name == "route/slot_perm") == sorted(
            p.size for p in plans)
        routed = [s.attrs for s in mine if s.name == "route/plan"]
        assert sorted(a["slots"] for a in routed) == sorted(p.size for p in plans)
        assert all(a["cached"] is cached for a in routed)
        assert sum(a["bytes"] for a in routed) == sum(
            f.stat().st_size for f in tmp_path.iterdir())
        uploads = [s.attrs for s in mine if s.name == "data/upload"]
        assert {a["what"] for a in uploads} == {"features", "plan"}
        assert sum(a["bytes"] for a in uploads) == _device_bytes(features)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("engine", sorted(BUILDERS))
def test_with_the_tracer_off_a_build_records_and_waits_for_nothing(
        engine, layout, tmp_path, monkeypatch):
    traced, _, _ = _traced(lambda: _build(engine, layout, tmp_path))
    waits = []
    real_wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waits.append(x) or real_wait(x))
    recorded = len(get_tracer())
    plain = _build(engine, layout, tmp_path)
    assert waits == [] and len(get_tracer()) == recorded
    assert jax.tree.structure(plain) == jax.tree.structure(traced)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(traced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_random_effect_build_is_host_work_then_one_upload():
    data = _tiny_glmix(0)
    shard = data.feature_shards["per_user"]

    def build():
        return build_random_effect_dataset(
            data.id_tags["userId"], shard.rows, shard.cols, shard.vals, shard.dim,
            data.labels, RandomEffectDataConfiguration("userId", num_buckets=2),
        )

    ds, spans, outer = _traced(build)
    mine = [s for s in spans if s.parent_id == outer.span_id and not s.name.startswith("jit/")]
    assert [s.name for s in mine] == ["re/build_dataset", "data/upload"]
    host, uploaded = mine
    blocks = [(np.asarray(b.weights) > 0, np.asarray(b.proj_valid), b.X.size) for b in ds.buckets]
    assert host.attrs == {
        "entities": ds.num_entities, "buckets": len(ds.buckets),
        # an active row's real local columns, against every cell of the blocks
        "real_cells": sum(int((w.sum(1) * v.sum(1)).sum()) for w, v, _ in blocks),
        "padded_cells": sum(int(size) for _, _, size in blocks),
    }
    assert 0 < host.attrs["real_cells"] <= host.attrs["padded_cells"]
    assert host.start_s + host.duration_s <= uploaded.start_s
    assert uploaded.attrs["what"] == "re_bucket"
    assert uploaded.attrs["bytes"] == _device_bytes((ds.buckets, ds.passive, ds.row_gather))
    recorded = len(get_tracer())
    plain = build()
    assert len(get_tracer()) == recorded
    for a, b in zip(jax.tree.leaves((plain.buckets, plain.passive, plain.row_gather)),
                    jax.tree.leaves((ds.buckets, ds.passive, ds.row_gather))):
        assert isinstance(a, jax.Array)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_route_and_upload_spans_cover_the_fixed_effect_build(tmp_path, monkeypatch):
    """Through ``GameEstimator.fit_multiple`` with a held-out set: what is
    left of the fixed effect's ``game/build_coordinate`` outside the route
    and upload spans is Python between them. At this size it weighs far
    more than on the chip, where the spans hold seconds each; on the CPU,
    one test process alone, the spans held 0.986 - 0.987 of the build in
    five runs."""
    from photon_ml_tpu.estimators.game import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_ml_tpu.types import TaskType

    monkeypatch.setenv("PHOTON_ML_TPU_PLAN_CACHE", str(tmp_path))
    data, held_out = _tiny_glmix(0), _tiny_glmix(1, rows_per_user=4)
    for part, seed in ((data, 0), (held_out, 1)):
        rows, cols, vals, (n, d) = _coo(part.num_rows, seed=seed)
        part.feature_shards["global"] = FeatureShard(rows, cols, vals, d)
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={
            "fixed": FixedEffectCoordinateConfiguration("global", sparse_engine="benes"),
            "per_user": RandomEffectCoordinateConfiguration(
                "per_user", RandomEffectDataConfiguration("userId")),
        },
    )
    tracer = enable_tracing(device_sync=True)
    try:
        estimator.fit_multiple(data, validation_data=held_out, configs=[{}])
        spans = tracer.spans()
    finally:
        disable_tracing()
    built = [s for s in spans if s.name == "game/build_coordinate"
             and s.attrs["kind"] == "FixedEffectCoordinateConfiguration"]
    assert len(built) == 1
    lo, hi = built[0].start_s, built[0].start_s + built[0].duration_s
    inside = [s for s in spans if s.name in ROUTE + ("data/upload",)
              and lo <= s.start_s <= hi]
    assert {s.name for s in inside} == set(ROUTE) | {"data/upload"}
    covered = union_seconds((s.start_s, s.start_s + s.duration_s) for s in inside)
    assert covered >= 0.8 * (hi - lo), (covered, hi - lo)


# -- the benchmark's readers -----------------------------------------------


@pytest.fixture(scope="module")
def readers():
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import run as harness

        yield {m.NAME: m for m in harness.list_layer_metrics() if m.NAME.startswith("setup_")}
    finally:
        sys.path.remove(ROOT)


def _span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs, "depth": 1}


# window [100, 120]; two threads' spans overlap; what starts inside the
# window is no set-up; a span that straddles the window's start is cut there
HAND_MADE = [
    _span("route/layout", 10.0, 20.0, nnz=8, blocks=2),
    _span("route/layout", 15.0, 25.0, nnz=8, blocks=2),      # another tile's thread
    _span("route/slot_perm", 25.0, 27.0, slots=16384),
    _span("route/place", 26.0, 28.0),
    _span("route/place", 105.0, 106.0),                       # inside the window
    _span("route/plan", 30.0, 34.0, cached=False, slots=16384, bytes=10),
    _span("route/plan", 32.0, 36.0, cached=True, slots=16384, bytes=10),
    _span("route/plan", 110.0, 111.0, cached=False, slots=16384, bytes=10),
    _span("data/upload", 40.0, 42.0, what="features", bytes=100),
    _span("data/upload", 41.0, 44.0, what="plan", bytes=50),
    _span("data/upload", 99.0, 101.0, what="rows", bytes=7),  # cut at 100
    _span("data/upload", 112.0, 113.0, what="rows", bytes=1000),
    _span("re/build_dataset", 50.0, 53.0, entities=4, buckets=1),
    _span("re/build_dataset", 52.0, 54.0, entities=4, buckets=1),
    _span("jit/trace", 60.0, 62.0, fun_name="f", phase="trace", under=""),
    _span("jit/lower", 61.0, 63.0, fun_name="f", phase="lower", under=""),
    _span("jit/backend", 98.0, 102.0, fun_name="g", phase="backend", under=""),  # cut at 100
    _span("jit/trace", 105.0, 106.0, fun_name="h", phase="trace", under=""),
    _span("jit/cache", 62.0, 62.0, hit=False, under=""),
    _span("jit/cache", 63.0, 63.0, hit=True, under=""),
    _span("jit/cache", 98.0, 98.0, hit=False, under=""),
    _span("jit/cache", 110.0, 110.0, hit=False, under=""),
    _span("glm/solve", 101.0, 119.0, iterations=3, evaluations=4),
]
READ = {
    "setup_layout_s": 18.0, "setup_plan_s": 6.0, "setup_plans_routed": 1,
    "setup_upload_s": 5.0, "setup_upload_bytes": 157, "setup_re_build_s": 4.0,
    "setup_compile_s": 5.0, "setup_cache_misses": 2,
}


def _context(spans):
    return {"window": (100.0, 120.0), "steps": 2, "window_s": 20.0, "spans": spans,
            "counters": [{}] * 3}


@pytest.mark.parametrize("name", sorted(READ))
def test_a_setup_reader_takes_the_union_before_the_window(readers, name):
    assert readers[name].read(_context(HAND_MADE)) == pytest.approx(READ[name])


@pytest.mark.parametrize("name", sorted(READ))
def test_a_setup_reader_is_silent_without_its_spans(readers, name):
    """A program before these spans (only the window's own): ``None``."""
    assert readers[name].read(_context([_span("glm/solve", 101.0, 119.0)])) is None
