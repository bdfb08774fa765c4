"""Request-plane tests: lifecycle sampling, tail attribution, SLO budget,
scenario harness.

The load-bearing guarantees, per ISSUE acceptance criteria:

- **Disabled-path parity**: replaying the same stream with no plane, with a
  plane at ``sample_rate=0``, and with a fully-sampling plane produces
  BITWISE-identical scores — observation may never perturb the data path.
- **Attribution completeness**: stage boundaries telescope, so each sampled
  record's per-stage durations sum to its end-to-end latency and the tail
  breakdown's attribution coverage is ~1.0 (>= the 0.95 acceptance floor).
- **Sampler determinism**: the seeded hash tags the same request ids
  regardless of submission order, batch boundaries, or thread.
- **Ledger round trip**: sampled records written through RunLedger pass
  ``validate_ledger``'s ``request`` schema and reconstruct the same report
  through ``analyze_run --requests``'s ``request_report``.
- **SLO math**: burn rate = bad_fraction / (1 - objective); the budget
  exhausts at burn >= 1, degrades /healthz, and recovers as the rolling
  window ages violations out.
- **Scenario harness**: each named scenario deterministically reshapes the
  stream (preserving it), and ``run_scenario`` emits per-stage p50/p99,
  residency and an SLO verdict.
"""

import time

import numpy as np
import pytest

from photon_ml_tpu import testing
from photon_ml_tpu.serving import (
    GameScorer,
    MicroBatcher,
    RequestPlane,
    SLOTracker,
    ServingMetrics,
    build_scenario,
    pack_game_model,
    replay_requests,
    requests_from_game_data,
    run_scenario,
)
from photon_ml_tpu.serving.requestplane import (
    INTERFERENCE_KINDS,
    REQUEST_STAGES,
    sample_hash,
)
from photon_ml_tpu.serving.scenarios import SCENARIO_NAMES, make_row_swap_fn
from photon_ml_tpu.telemetry.analyze import (
    format_request_report,
    request_report,
)
from photon_ml_tpu.telemetry.sinks import RunLedger
from photon_ml_tpu.telemetry.validate import validate_ledger
from photon_ml_tpu.types import TaskType

TASK = TaskType.LOGISTIC_REGRESSION
COORDS = {
    "fixed": {"feature_shard": "global"},
    "per_user": {"feature_shard": "per_entity", "random_effect_type": "userId"},
}
BUCKETS = (1, 2, 4, 8, 16)


@pytest.fixture(scope="module")
def glmix():
    data, _ = testing.generate_glmix_data(
        task=TASK, n_entities=8, rows_per_entity=10, d_global=8, d_entity=4,
        seed=11,
    )
    model = testing.generate_game_model(data, TASK, COORDS, seed=3)
    return data, pack_game_model(model)


def _requests(glmix):
    data, artifact = glmix
    return artifact, requests_from_game_data(data, artifact)


class TestSampler:
    def test_deterministic_and_order_independent(self):
        ids = [f"req-{i}" for i in range(512)]
        plane = RequestPlane(sample_rate=8, seed=42)
        tagged = {rid for rid in ids if plane.sampled(rid)}
        # same ids, reversed submission order, different batch boundaries:
        # identical tag set
        rev = list(reversed(ids))
        via_batches = set()
        for lo in range(0, len(rev), 7):
            chunk = rev[lo:lo + 7]
            via_batches.update(
                chunk[i] for i in plane.sample_indices(chunk)
            )
        assert via_batches == tagged
        assert tagged  # rate 8 over 512 ids can't tag nothing

    def test_rate_semantics(self):
        ids = [f"r{i}" for i in range(1000)]
        assert RequestPlane(sample_rate=0).sample_indices(ids) == []
        assert RequestPlane(sample_rate=1).sample_indices(ids) == list(
            range(1000)
        )
        n = len(RequestPlane(sample_rate=16, seed=0).sample_indices(ids))
        # ~1/16 of 1000 = 62.5; the hash is uniform enough for loose bounds
        assert 20 <= n <= 130
        with pytest.raises(ValueError):
            RequestPlane(sample_rate=-1)

    def test_seed_changes_the_sample(self):
        ids = [f"r{i}" for i in range(1000)]
        a = set(RequestPlane(sample_rate=8, seed=1).sample_indices(ids))
        b = set(RequestPlane(sample_rate=8, seed=2).sample_indices(ids))
        assert a != b

    def test_hash_is_stable(self):
        # pinned: a changed hash would silently re-tag every deployment
        assert sample_hash("request-0", 0) == sample_hash("request-0", 0)
        assert sample_hash("request-0", 0) != sample_hash("request-1", 0)
        assert sample_hash("request-0", 0) != sample_hash("request-0", 7)


class TestRecordBatch:
    def test_stages_telescope_to_total(self):
        plane = RequestPlane(sample_rate=1)
        t0 = 100.0
        stages = {
            "featurize_done": t0 + 0.003,
            "route_done": t0 + 0.004,
            "dispatch_done": t0 + 0.006,
            "device_done": t0 + 0.009,
        }
        plane.record_batch(
            "sealed", 8, 5, [("a", t0 - 0.002), ("b", t0 - 0.001)],
            t0, stages, t0 + 0.010,
        )
        for rec in plane.records():
            assert set(rec["stages"]) == set(REQUEST_STAGES)
            assert all(v >= 0 for v in rec["stages"].values())
            assert sum(rec["stages"].values()) == pytest.approx(
                rec["total_s"], rel=1e-9
            )

    def test_out_of_order_boundaries_clamp_monotonic(self):
        plane = RequestPlane(sample_rate=1)
        t0 = 50.0
        # device_done BEFORE route_done (async clock skew): clamped, never
        # negative
        stages = {
            "featurize_done": t0 + 0.004,
            "route_done": t0 + 0.003,
            "dispatch_done": t0 + 0.002,
            "device_done": t0 + 0.001,
        }
        plane.record_batch("sealed", 4, 4, [("x", t0)], t0, stages, t0 + 0.005)
        (rec,) = plane.records()
        assert all(v >= 0 for v in rec["stages"].values())
        assert rec["total_s"] == pytest.approx(0.005, rel=1e-9)

    def test_missing_stage_clock_degrades_to_queue_reply(self):
        plane = RequestPlane(sample_rate=1)
        plane.record_batch("sealed", 4, 1, [("x", 10.0)], 10.002, None, 10.01)
        (rec,) = plane.records()
        assert rec["stages"]["queue"] == pytest.approx(0.002, rel=1e-9)
        assert rec["stages"]["reply"] == pytest.approx(0.008, rel=1e-9)
        for stage in ("featurize", "route", "dispatch", "device"):
            assert rec["stages"][stage] == 0.0

    def test_interference_overlap_is_windowed(self):
        plane = RequestPlane(sample_rate=1)
        plane.note_interference("swap_pause", 10.004, 10.006)
        plane.note_interference("admission", 20.0, 20.1)  # outside window
        plane.note_interference("swap_pause", 10.0, 10.0)  # empty: dropped
        plane.record_batch("sealed", 4, 1, [("x", 10.0)], 10.005, None, 10.01)
        (rec,) = plane.records()
        inter = rec["interference"]
        assert inter["swap_pause_s"] == pytest.approx(0.002, rel=1e-6)
        assert "admission_s" not in inter
        assert set(k[:-2] for k in inter) <= set(INTERFERENCE_KINDS)

    def test_ring_is_bounded(self):
        plane = RequestPlane(sample_rate=1, capacity=4)
        for i in range(10):
            plane.record_batch(
                "sealed", 1, 1, [(f"r{i}", 1.0)], 1.001, None, 1.002
            )
        assert len(plane.records()) == 4
        assert plane.sampled_total == 10
        plane.reset_records()
        assert plane.records() == []
        assert plane.sampled_total == 10


class TestSLOTracker:
    def test_healthy_budget(self):
        slo = SLOTracker(latency_threshold_s=0.05)
        slo.observe_many(np.full(1000, 0.001))
        st = slo.status()
        assert st["verdict"] == "ok"
        assert st["healthy"] is True
        assert st["availability"] == 1.0
        assert st["error_budget_remaining"] == 1.0

    def test_availability_burn_exhausts(self):
        slo = SLOTracker(availability_objective=0.999)
        slo.observe_many(np.full(99, 0.001), errors=1)
        st = slo.status()
        # 1/100 errors against a 0.1% budget: burn 10x
        assert st["availability_burn_rate"] == pytest.approx(10.0, rel=1e-6)
        assert st["error_budget_remaining"] == 0.0
        assert "availability" in st["verdict"]
        assert slo.health()["healthy"] is False
        assert "degraded" in slo.health()

    def test_latency_burn(self):
        slo = SLOTracker(latency_threshold_s=0.01, latency_objective=0.99)
        lat = np.full(100, 0.001)
        lat[:5] = 0.5  # 5% slow against a 1% allowance: burn 5x
        slo.observe_many(lat)
        st = slo.status()
        assert st["latency_burn_rate"] == pytest.approx(5.0, rel=1e-6)
        assert "latency" in st["verdict"]

    def test_window_ages_out_violations(self):
        now = [1000.0]
        slo = SLOTracker(
            availability_objective=0.9, window_s=30.0, num_buckets=3,
            clock=lambda: now[0],
        )
        slo.observe_many(np.full(2, 0.001), errors=2)
        assert slo.status()["healthy"] is False
        # advance past the whole window: the violation falls out, fresh
        # healthy traffic restores the budget
        now[0] += 40.0
        slo.observe_many(np.full(10, 0.001))
        st = slo.status()
        assert st["window_errors"] == 0
        assert st["healthy"] is True

    def test_gauges_exported(self):
        from photon_ml_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()
        slo = SLOTracker(registry=reg)
        slo.observe_many(np.full(10, 0.001))
        slo.status()
        gauges = reg.snapshot()["gauges"]
        for name in (
            "serving.slo.availability",
            "serving.slo.latency_ok_rate",
            "serving.slo.burn_rate",
            "serving.slo.error_budget_remaining",
            "serving.slo.budget_exhausted",
        ):
            assert name in gauges


class TestDisabledPathParity:
    """The request plane may observe, never perturb."""

    def test_scores_bitwise_identical_across_plane_modes(self, glmix):
        artifact, requests = _requests(glmix)

        def _scores(plane):
            scorer = GameScorer(artifact)
            results, _ = replay_requests(
                scorer, requests, bucket_sizes=BUCKETS, plane=plane
            )
            return np.array([r.score for r in results], dtype=np.float32)

        base = _scores(None)
        off = _scores(RequestPlane(sample_rate=0))
        sampled = _scores(RequestPlane(sample_rate=1))
        assert np.array_equal(base, off)
        assert np.array_equal(base, sampled)

    def test_continuous_scores_bitwise_identical(self, glmix):
        artifact, requests = _requests(glmix)

        def _scores(plane):
            scorer = GameScorer(artifact)
            results, _ = replay_requests(
                scorer, requests, bucket_sizes=BUCKETS, plane=plane,
                continuous=True, max_wait_s=0.001,
            )
            return np.array([r.score for r in results], dtype=np.float32)

        assert np.array_equal(
            _scores(None), _scores(RequestPlane(sample_rate=1))
        )


class TestPlaneIntegration:
    def test_sealed_replay_records_and_ledger_round_trip(
        self, glmix, tmp_path
    ):
        artifact, requests = _requests(glmix)
        ledger_path = str(tmp_path / "ledger.jsonl")
        ledger = RunLedger(ledger_path)
        ledger.write("meta", phase="start", label="plane-test")
        plane = RequestPlane(sample_rate=1, ledger=ledger)
        scorer = GameScorer(artifact)
        results, snapshot = replay_requests(
            scorer, requests, bucket_sizes=BUCKETS, plane=plane
        )
        ledger.write("meta", phase="finish")
        ledger.close()
        assert len(results) == len(requests)
        assert plane.sampled_total == len(requests)

        # schema round trip: every sampled record validates as a ledger
        # "request" kind and reconstructs the analyzer report
        records = validate_ledger(ledger_path)
        reqs = [r for r in records if r["type"] == "request"]
        assert len(reqs) == len(requests)
        report = request_report(records)
        assert report["num_records"] == len(requests)
        # acceptance: the per-stage tail breakdown explains >= 95% of the
        # end-to-end tail latency (telescoping makes it ~100%)
        assert report["tail"]["attribution_coverage"] >= 0.95
        assert report["tail"]["exemplars"]
        assert set(report["stages"]) == set(REQUEST_STAGES)
        text = format_request_report(report)
        for stage in REQUEST_STAGES:
            assert stage in text
        # the replay snapshot carries the live view of the same plane
        assert snapshot["request_plane"]["sampled_total"] == len(requests)

    def test_continuous_replay_records_stages(self, glmix):
        artifact, requests = _requests(glmix)
        # a generous latency budget: CPU smoke latencies must not flip the
        # verdict, this test is about stage attribution, not SLO tuning
        plane = RequestPlane(sample_rate=1, slo=SLOTracker(
            latency_threshold_s=60.0
        ))
        scorer = GameScorer(artifact)
        results, snapshot = replay_requests(
            scorer, requests, bucket_sizes=BUCKETS, plane=plane,
            continuous=True, max_wait_s=0.001,
        )
        assert len(results) == len(requests)
        recs = plane.records()
        assert len(recs) == len(requests)
        assert {r["batcher"] for r in recs} == {"continuous"}
        # device work happened, so sampled batches must attribute nonzero
        # scoring-side time (featurize..device), not lump it all in queue
        scoring = sum(
            r["stages"]["featurize"] + r["stages"]["route"]
            + r["stages"]["dispatch"] + r["stages"]["device"]
            for r in recs
        )
        assert scoring > 0
        assert snapshot["slo"]["verdict"] == "ok"

    def test_stage_less_scorer_still_records(self, glmix):
        artifact, requests = _requests(glmix)

        class NoStageScorer:
            """A scorer whose score_batch predates the stage clock."""

            def __init__(self, inner):
                self._inner = inner

            def score_batch(self, requests, bucket_size=None):
                return self._inner.score_batch(requests, bucket_size)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        plane = RequestPlane(sample_rate=1)
        batcher = MicroBatcher(
            NoStageScorer(GameScorer(artifact)), bucket_sizes=BUCKETS,
            plane=plane,
        )
        out = []
        for req in requests:
            out.extend(batcher.submit(req))
        out.extend(batcher.flush())
        assert len(out) == len(requests)
        recs = plane.records()
        assert len(recs) == len(requests)
        # no stage clock: scoring time lands in the terminal reply stage,
        # totals still telescope
        for rec in recs:
            assert sum(rec["stages"].values()) == pytest.approx(
                rec["total_s"], rel=1e-9
            )

    def test_swap_pause_interference_via_metrics(self, glmix):
        artifact, requests = _requests(glmix)
        plane = RequestPlane(sample_rate=1)
        metrics = ServingMetrics(request_plane=plane)
        scorer = GameScorer(artifact)
        batcher = MicroBatcher(
            scorer, bucket_sizes=BUCKETS, metrics=metrics, plane=plane
        )
        for req in requests[:4]:
            batcher.submit(req)
        # a hot-swap pause reported mid-flight must overlap the pending
        # requests' windows
        metrics.observe_swap(generation=1, rows_updated=8, blackout_s=0.01)
        batcher.flush()
        kinds = set()
        for rec in plane.records():
            kinds.update(k[:-2] for k in (rec.get("interference") or {}))
        assert "swap_pause" in kinds


class TestRequestReport:
    def test_empty_is_none(self):
        assert request_report([]) is None
        assert request_report([{"type": "span", "name": "x"}]) is None

    def test_worst_bucket_and_exemplars(self):
        recs = []
        for i in range(20):
            bucket = 16 if i < 18 else 64
            total = 0.001 if i < 18 else 0.5
            recs.append({
                "type": "request",
                "request_id": f"r{i}",
                "bucket": bucket,
                "stages": {
                    "queue": total, "featurize": 0.0, "route": 0.0,
                    "dispatch": 0.0, "device": 0.0, "reply": 0.0,
                },
                "total_s": total,
            })
        report = request_report(recs)
        assert report["tail"]["worst_bucket"] == 64
        assert report["tail"]["worst_stage"] == "queue"
        assert len(report["tail"]["exemplars"]) <= 3
        assert all(x.startswith("r") for x in report["tail"]["exemplars"])


class TestScenarios:
    def test_catalog_and_determinism(self, glmix):
        _, requests = _requests(glmix)
        for name in SCENARIO_NAMES:
            a = build_scenario(name, requests, seed=7, num_phases=6)
            b = build_scenario(name, requests, seed=7, num_phases=6)
            if name == "tenant_isolation":
                # the flooding tenant replays its mid-run share on top
                # of the full stream, so this scenario carries MORE
                # requests than the input; every other shape preserves
                # the stream exactly
                assert a.num_requests > len(requests), name
            else:
                assert a.num_requests == len(requests), name
            assert [len(p.requests) for p in a.phases] == [
                len(p.requests) for p in b.phases
            ], name
            assert [
                [r.request_id for r in p.requests] for p in a.phases
            ] == [
                [r.request_id for r in p.requests] for p in b.phases
            ], name

    def test_unknown_scenario_rejected(self, glmix):
        _, requests = _requests(glmix)
        with pytest.raises(ValueError):
            build_scenario("lunar_eclipse", requests)
        with pytest.raises(ValueError):
            build_scenario("steady", [])

    def test_cold_flood_remaps_to_cold_ids(self, glmix):
        _, requests = _requests(glmix)
        scn = build_scenario("cold_entity_flood", requests, num_phases=4)
        flood = scn.phases[-1].requests
        assert all(r.request_id.endswith("-cold") for r in flood)
        # remapped ids stay within the observed population (known to the
        # model, unlikely to be resident)
        observed = {
            eid for r in requests for eid in r.entity_ids.values()
        }
        for r in flood:
            assert set(r.entity_ids.values()) <= observed

    def test_hot_swap_phases_are_interior(self, glmix):
        _, requests = _requests(glmix)
        scn = build_scenario("hot_swap_under_load", requests, num_phases=6)
        flags = [p.swap for p in scn.phases]
        assert flags[0] is False and flags[-1] is False
        assert any(flags[1:-1])

    def test_run_scenario_emits_contract_fields(self, glmix):
        artifact, requests = _requests(glmix)
        scorer = GameScorer(artifact)
        metrics = ServingMetrics()
        slo = SLOTracker()
        plane = RequestPlane(sample_rate=1, slo=slo)
        scn = build_scenario(
            "steady", requests, num_phases=3, pause_s=0.0
        )
        doc = run_scenario(
            scn, scorer, bucket_sizes=BUCKETS, metrics=metrics,
            plane=plane, slo=slo, continuous=False,
        )
        assert doc["name"] == "steady"
        assert doc["num_requests"] == len(requests)
        assert doc["requests_per_s"] > 0
        stages = doc["request_plane"]["stages"]
        for stage in REQUEST_STAGES:
            assert "p50_s" in stages[stage] and "p99_s" in stages[stage]
        assert doc["request_plane"]["tail"]["attribution_coverage"] >= 0.95
        assert doc["slo_verdict"] in (doc["slo"]["verdict"],)

    def test_swap_fn_drives_generations(self, glmix):
        artifact, requests = _requests(glmix)
        scorer = GameScorer(artifact)
        metrics = ServingMetrics()
        swap_fn = make_row_swap_fn(scorer, metrics, rows_per_swap=2, seed=1)
        assert swap_fn is not None
        swap_fn()
        swap_fn()
        snap = metrics.snapshot()
        assert snap["swaps"]["num_swaps"] == 2
        assert snap["swaps"]["rows_updated_total"] == 4
