"""Tenancy plane tests: multi-model variant serving over ONE shared scorer.

The load-bearing guarantees, per ISSUE acceptance criteria:

- a single tenant on the base variant scores BITWISE identically through
  the tenancy plane and through the plain sharded path;
- per-variant delta overlays diverge ONLY the delta-touched entities of
  the variant they are applied to — the base variant and every other
  variant stay bitwise unchanged — and a rollback restores bitwise state;
- variant chains are fingerprint-checked: a delta built against the
  wrong chain head is refused, per variant;
- the router is deterministic and seeded, ramps are monotone (raising a
  ramp keeps every request the variant already served), pins override;
- ``route_many`` and ``route`` make identical decisions (the bulk replay
  path cannot drift from the per-request path);
- per-tenant quotas shed ONLY the flooding tenant, priority reserves the
  global pool for high-priority tenants, and sheds are charged to the
  shedding tenant's own SLO error budget — never another tenant's;
- per-tenant SLO trackers expose independent error budgets, rendered as
  tenant-labeled Prometheus series;
- the tenancy scenarios (tenant_isolation / ramped_rollout /
  nearline_loop) build and run end to end, producing the per-tenant SLO
  verdicts.
"""

import dataclasses
import tempfile

import numpy as np
import pytest

from photon_ml_tpu.incremental import build_delta
from photon_ml_tpu.serving import (
    DEFAULT_TENANTS,
    RequestPlane,
    ServingMetrics,
    ShardedGameScorer,
    TenancyPlane,
    TenantBudget,
    TenantQuota,
    ValidationGate,
    VariantRegistry,
    VariantRouter,
    build_scenario,
    build_tenant_slos,
    make_nearline_fn,
    run_scenario,
    tag_requests,
)
from photon_ml_tpu.serving.tenancy import BASE_VARIANT, tag_request
from photon_ml_tpu.telemetry.metrics import MetricsRegistry

from test_serving_sharded import MAX_NNZ, _artifact, _requests

BUCKETS = (1, 2, 4, 8, 16, 32)
N_ENT = 64


def _scorer(art=None, **kw):
    return ShardedGameScorer(
        art if art is not None else _artifact(),
        max_nnz=MAX_NNZ,
        num_shards=2,
        **kw,
    )


def _scores(scorer, requests, view=None):
    out = scorer.score_batch(
        requests, bucket_size=len(requests), view=view
    )
    return {r.request_id: r.score for r in out}


def _delta_for(art, entities, seed=0, scale=0.5):
    rng = np.random.default_rng(seed)
    dim = art.tables["per_user"].dim
    re_updates = {
        "per_user": {
            e: {
                int(j): float(x)
                for j, x in zip(
                    rng.integers(0, dim, 2), rng.normal(0.0, scale, 2)
                )
            }
            for e in entities
        }
    }
    return re_updates


class TestVariantRegistry:
    def test_base_parity_through_plane(self):
        """One tenant, base variant only — scores
        through the tenancy plane are bitwise identical to the plain
        sharded path."""
        art = _artifact()
        reqs = _requests(64, ghost_every=11)
        plain = _scores(_scorer(art), reqs)
        tenancy = TenancyPlane(
            VariantRegistry(_scorer(art)),
            metrics=ServingMetrics(),
            bucket_sizes=(1, 2, 4, 8, 16, 32, 64),
        )
        out = tenancy.replay(tag_requests(reqs, "solo"), poll_every=0)
        assert len(out) == len(reqs)
        for r in out:
            rid = r.request_id.split("!", 1)[1]
            assert r.score == plain[rid], rid

    def test_variant_divergence_is_isolated(self):
        art = _artifact()
        reqs = _requests(64)
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        reg.add_variant("v1")
        reg.add_variant("v2")
        before = _scores(scorer, reqs)
        touched = ["u3", "u5"]
        report = reg.apply_delta(
            "v1", build_delta(_delta_for(art, touched), art, generation=1)
        )
        assert report.rows_updated == len(touched)
        assert report.new_overlay_rows == len(touched)
        assert not report.rolled_back
        # v1 differs exactly on requests hitting touched entities
        v1 = _scores(scorer, reqs, view=reg.view("v1"))
        for r in reqs:
            hit = r.entity_ids.get("userId") in touched
            assert (v1[r.request_id] != before[r.request_id]) == hit, (
                r.request_id
            )
        # base and v2 are bitwise untouched
        assert _scores(scorer, reqs) == before
        assert reg.view("v2") is None  # undiverged -> plain path
        assert reg.state(BASE_VARIANT).overlay_row_count == 0

    def test_rollback_restores_bitwise(self):
        art = _artifact()
        reqs = _requests(48)
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        reg.add_variant("v1")
        before = _scores(scorer, reqs)
        reg.apply_delta(
            "v1", build_delta(_delta_for(art, ["u1"]), art, generation=1)
        )
        # second generation rewrites the SAME overlay row in place
        d2 = build_delta(
            _delta_for(art, ["u1"], seed=9),
            art,
            base_fingerprint=reg.state("v1").fingerprint,
            generation=2,
        )
        reg.apply_delta("v1", d2)
        assert reg.state("v1").generation == 2
        assert reg.rollback("v1")
        st = reg.state("v1")
        assert st.generation == 1 and st.rollbacks == 1
        assert _scores(scorer, reqs) == before  # base never moved

    def test_gated_bad_delta_rejected_and_rolled_back(self):
        """A registry built with a per-variant ValidationGate refuses a
        delta that wrecks ranking: the swap report says rolled_back, the
        variant's generation never advances, the base stays bitwise
        untouched — and a benign delta still applies afterwards."""
        art = _artifact()
        reqs = _requests(64)
        scorer = _scorer(art)
        # labels = the base scorer's own top-half ranking, so baseline
        # AUC is 1.0 by construction and the gate measures pure drift
        base = scorer.score_batch(reqs, bucket_size=len(reqs))
        scores = np.asarray([r.score for r in base], dtype=np.float32)
        labels = (scores > np.median(scores)).astype(np.float32)
        reg = VariantRegistry(
            scorer,
            gate=ValidationGate(
                reqs, labels,
                max_auc_regression=0.02,
                bucket_size=len(reqs),
            ),
        )
        reg.add_variant("candidate")
        before = _scores(scorer, reqs)
        # 12 entities is well inside the overlay-slot headroom (the
        # shards hold 2x40 slots, 64 of them the resident base) yet a
        # scale-50 perturbation on them wrecks ranking far past the gate
        bad = build_delta(
            _delta_for(
                art, [f"u{i}" for i in range(12)], seed=5, scale=50.0
            ),
            art,
            generation=1,
        )
        report = reg.apply_delta("candidate", bad)
        assert report.rolled_back is True
        assert report.baseline_metric == pytest.approx(1.0)
        assert (
            report.validation_metric
            < report.baseline_metric - 0.02
        )
        st = reg.state("candidate")
        assert st.generation == 0 and st.rollbacks == 1
        assert _scores(scorer, reqs) == before  # base never moved
        # a benign delta on the same variant still clears the gate
        good = build_delta(
            _delta_for(art, ["u1"], seed=2, scale=0.01),
            art,
            generation=1,
        )
        report2 = reg.apply_delta("candidate", good)
        assert not report2.rolled_back
        assert reg.state("candidate").generation == 1
        assert report2.validation_metric >= 1.0 - 0.02

    def test_chain_check_refuses_wrong_head(self):
        art = _artifact()
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        reg.add_variant("v1")
        # in-memory deltas carry fingerprint=None (save_delta fills it);
        # stamp one so the variant's chain head is real and checkable
        d1 = dataclasses.replace(
            build_delta(_delta_for(art, ["u2"]), art, generation=1),
            fingerprint="f" * 16,
        )
        reg.apply_delta("v1", d1)
        stale = build_delta(
            _delta_for(art, ["u4"], seed=3),
            art,
            base_fingerprint="0" * 16,
            generation=2,
        )
        with pytest.raises(ValueError, match="chains to base"):
            reg.apply_delta("v1", stale)
        assert reg.state("v1").generation == 1

    def test_unknown_variant_raises(self):
        reg = VariantRegistry(_scorer())
        with pytest.raises(KeyError):
            reg.state("nope")


class TestVariantRouter:
    def test_deterministic_and_seeded(self):
        r1 = VariantRouter(seed=5)
        r1.set_ramp("cand", 30.0)
        r2 = VariantRouter(seed=5)
        r2.set_ramp("cand", 30.0)
        ids = [f"r{i}" for i in range(400)]
        a = [r1.route("t", i) for i in ids]
        assert a == [r2.route("t", i) for i in ids]
        r3 = VariantRouter(seed=6)
        r3.set_ramp("cand", 30.0)
        assert a != [r3.route("t", i) for i in ids]

    def test_ramp_is_monotone(self):
        """Raising a ramp keeps every request the variant already
        served — the property a rollout needs."""
        router = VariantRouter(seed=1)
        ids = [f"req-{i}" for i in range(500)]
        router.set_ramp("cand", 10.0)
        at10 = {i for i in ids if router.route("t", i) == "cand"}
        router.set_ramp("cand", 55.0)
        at55 = {i for i in ids if router.route("t", i) == "cand"}
        assert at10 <= at55
        assert len(at55) > len(at10)

    def test_route_many_matches_route(self):
        router = VariantRouter(seed=3)
        router.set_ramp("a", 15.0)
        router.set_ramp("b", 40.0)
        router.pin("pinned", "a")
        ids = [f"x{i}" for i in range(300)]
        bulk = VariantRouter(seed=3)
        bulk.set_ramp("a", 15.0)
        bulk.set_ramp("b", 40.0)
        bulk.pin("pinned", "a")
        for tenant in ("alpha", "pinned", None):
            assert bulk.route_many(tenant, ids) == [
                router.route(tenant, i) for i in ids
            ]
        assert router.decisions == bulk.decisions

    def test_ramp_validation(self):
        router = VariantRouter()
        with pytest.raises(ValueError, match="sum to"):
            router.set_ramp("a", 60.0)
            router.set_ramp("b", 60.0)
        with pytest.raises(ValueError, match="in \\[0, 100\\]"):
            router.set_ramp("a", 120.0)

    def test_pin_overrides_ramp(self):
        router = VariantRouter(seed=0)
        router.set_ramp("cand", 100.0)
        router.pin("vip", BASE_VARIANT)
        assert router.route("vip", "r1") == BASE_VARIANT
        assert router.route("other", "r1") == "cand"
        router.pin("vip", None)
        assert router.route("vip", "r1") == "cand"


class TestTenantQuota:
    def test_flooder_sheds_alone(self):
        quota = TenantQuota({
            "a": TenantBudget(rate=1.0, burst=10),
            "b": TenantBudget(rate=1.0, burst=10),
        })
        for _ in range(25):
            quota.try_admit("a")
        for _ in range(8):
            assert quota.try_admit("b")
        stats = quota.stats()["tenants"]
        assert stats["a"]["shed"] == 15
        assert stats["b"]["shed"] == 0

    def test_priority_reserve(self):
        """The reserve fraction of the global pool is spendable only by
        top-priority tenants once the pool drains low."""
        quota = TenantQuota(
            {
                "gold": TenantBudget(rate=1.0, burst=100, priority=1),
                "bronze": TenantBudget(rate=1.0, burst=100, priority=0),
            },
            global_rate=1.0,
            global_burst=10,
            reserve_fraction=0.5,
        )
        admitted_bronze = sum(
            1 for _ in range(10) if quota.try_admit("bronze")
        )
        admitted_gold = sum(1 for _ in range(5) if quota.try_admit("gold"))
        assert admitted_bronze == 5  # stops at the reserve floor
        assert admitted_gold == 5    # reserve is theirs

    def test_unbudgeted_tenant_draws_global_pool(self):
        quota = TenantQuota({}, global_rate=1.0, global_burst=3)
        got = sum(1 for _ in range(5) if quota.try_admit("stranger"))
        assert got == 3


class TestTenancyPlane:
    def _stack(self, quota=None, registry_metrics=None):
        art = _artifact()
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        mreg = (
            registry_metrics
            if registry_metrics is not None
            else MetricsRegistry()
        )
        slos = build_tenant_slos(
            ("alpha", "beta"), registry=mreg, latency_threshold_s=5.0
        )
        plane = RequestPlane(sample_rate=4, tenant_slos=slos)
        tenancy = TenancyPlane(
            reg,
            plane=plane,
            quota=quota,
            metrics=ServingMetrics(),
            metrics_registry=mreg,
            bucket_sizes=BUCKETS,
        )
        return art, tenancy, plane, mreg

    def test_shed_charges_only_the_flooder(self):
        quota = TenantQuota({
            "alpha": TenantBudget(rate=1.0, burst=5),
            "beta": TenantBudget(rate=1.0, burst=100),
        })
        _, tenancy, plane, _ = self._stack(quota=quota)
        reqs = _requests(40)
        stream = tag_requests(reqs[:20], "alpha") + tag_requests(
            reqs[20:], "beta"
        )
        out = tenancy.replay(stream, poll_every=0)
        assert len(out) == 25  # 5 alpha + 20 beta
        assert plane.tenant_errors.get("alpha", 0) == 15
        assert plane.tenant_errors.get("beta", 0) == 0
        alpha = plane.tenant_slos["alpha"].status()
        beta = plane.tenant_slos["beta"].status()
        assert alpha["verdict"].startswith("budget_exhausted")
        assert beta["verdict"] == "ok"

    def test_tenant_metrics_are_label_scoped(self):
        from photon_ml_tpu.serving import prometheus_text

        quota = TenantQuota({
            "alpha": TenantBudget(rate=1.0, burst=2),
        })
        _, tenancy, _, mreg = self._stack(quota=quota)
        tenancy.replay(
            tag_requests(_requests(8), "alpha"), poll_every=0
        )
        text = prometheus_text(mreg.snapshot())
        assert 'photon_serving_tenant_requests{tenant="alpha"} 8' in text
        assert 'photon_serving_tenant_shed{tenant="alpha"} 6' in text

    def test_tenant_separator_rejected_in_name(self):
        with pytest.raises(ValueError, match="must not contain"):
            tag_request(_requests(1)[0], "bad!tenant")

    def test_status_reports_all_layers(self):
        quota = TenantQuota({"alpha": TenantBudget(rate=1.0, burst=50)})
        _, tenancy, _, _ = self._stack(quota=quota)
        tenancy.replay(tag_requests(_requests(8), "alpha"), poll_every=0)
        doc = tenancy.status()
        assert BASE_VARIANT in doc["variants"]
        assert "alpha" in doc["quota"]["tenants"]
        assert doc["tenants"]["alpha"]["requests"] == 8
        assert doc["tenants"]["alpha"]["slo"]["verdict"] == "ok"


class TestTenancyScenarios:
    def _scenario_stack(self, registry):
        mreg = MetricsRegistry()
        slos = build_tenant_slos(
            DEFAULT_TENANTS, registry=mreg, latency_threshold_s=5.0
        )
        plane = RequestPlane(sample_rate=4, tenant_slos=slos)
        return TenancyPlane(
            registry,
            router=VariantRouter(seed=1),
            plane=plane,
            metrics=ServingMetrics(),
            metrics_registry=mreg,
            bucket_sizes=BUCKETS,
        ), plane

    def test_tenant_isolation_scenario(self):
        art = _artifact()
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        reg.add_variant("candidate")
        tenancy, plane = self._scenario_stack(reg)
        reqs = _requests(120)
        scenario = build_scenario(
            "tenant_isolation", reqs, seed=0, num_phases=6, pause_s=0.0
        )
        assert scenario.tenants == DEFAULT_TENANTS
        # fair total with headroom: flooder (alpha) must shed, others not
        quota = TenantQuota({
            t: TenantBudget(rate=1.0, burst=55) for t in DEFAULT_TENANTS
        })
        tenancy.quota = quota
        doc = run_scenario(
            scenario, [scorer], BUCKETS, ServingMetrics(),
            plane=plane, tenancy=tenancy,
        )
        assert doc["isolation_ok"] is True
        assert doc["flooding_tenant"] == "alpha"
        assert doc["tenant_shed"]["alpha"] > 0
        assert doc["tenants"]["beta"]["slo_verdict"] == "ok"
        assert doc["tenants"]["gamma"]["slo_verdict"] == "ok"

    def test_ramped_rollout_scenario(self):
        art = _artifact()
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        reg.add_variant("candidate")
        reg.apply_delta(
            "candidate",
            build_delta(_delta_for(art, ["u1", "u7"]), art, generation=1),
        )
        tenancy, plane = self._scenario_stack(reg)
        reqs = _requests(120)
        scenario = build_scenario(
            "ramped_rollout", reqs, seed=0, num_phases=6, pause_s=0.0
        )
        ramps = [p.ramp_percent for p in scenario.phases]
        assert ramps[0] == 0.0 and ramps[-1] == 100.0
        assert ramps == sorted(ramps)
        doc = run_scenario(
            scenario, [scorer], BUCKETS, ServingMetrics(),
            plane=plane, tenancy=tenancy,
        )
        assert doc["num_requests"] == len(reqs)
        assert doc["variant_shares"].get("candidate", 0.0) > 0.1
        assert set(doc["tenants"]) == set(DEFAULT_TENANTS)

    def test_nearline_loop_scenario(self):
        art = _artifact()
        scorer = _scorer(art)
        reg = VariantRegistry(scorer)
        reg.add_variant("candidate")
        tenancy, plane = self._scenario_stack(reg)
        tenancy.router.set_ramp("candidate", 50.0)
        reqs = _requests(120)
        scenario = build_scenario(
            "nearline_loop", reqs, seed=0, num_phases=6, pause_s=0.0
        )
        with tempfile.TemporaryDirectory() as watch:
            nearline_fn = make_nearline_fn(
                reg,
                ["candidate"],
                {"per_user": [f"u{i}" for i in range(32)]},
                rows_per_delta=4,
                seed=3,
                watch_dir=watch,
            )
            doc = run_scenario(
                scenario, [scorer], BUCKETS, ServingMetrics(),
                plane=plane, tenancy=tenancy, nearline_fn=nearline_fn,
            )
        assert doc["num_requests"] == len(reqs)
        assert doc["nearline"]["deltas_applied"] > 0
        assert doc["nearline"]["rollbacks"] == 0
        assert doc["nearline"]["generations"]["candidate"] > 0
        # fingerprint chain advanced to the last applied generation
        st = reg.state("candidate")
        assert st.generation == doc["nearline"]["generations"]["candidate"]
        assert st.fingerprint is not None

    def test_nearline_bad_delta_rolls_back_in_scenario(self):
        """The delta-apply path of the nearline_loop scenario runs
        through the gate: a nearline trainer emitting deliberately-bad
        generations (huge-scale row updates) gets every swap rolled
        back, the scenario doc counts the rollbacks, and the variant's
        chain head never advances."""
        art = _artifact()
        scorer = _scorer(art)
        reqs = _requests(120)
        gate_reqs = reqs[:48]
        base = scorer.score_batch(gate_reqs, bucket_size=len(gate_reqs))
        scores = np.asarray([r.score for r in base], dtype=np.float32)
        labels = (scores > np.median(scores)).astype(np.float32)
        reg = VariantRegistry(
            scorer,
            gate=ValidationGate(
                gate_reqs, labels,
                max_auc_regression=0.02,
                bucket_size=len(gate_reqs),
            ),
        )
        reg.add_variant("candidate")
        tenancy, plane = self._scenario_stack(reg)
        tenancy.router.set_ramp("candidate", 50.0)
        scenario = build_scenario(
            "nearline_loop", reqs, seed=0, num_phases=6, pause_s=0.0
        )
        nearline_fn = make_nearline_fn(
            reg,
            ["candidate"],
            {"per_user": [f"u{i}" for i in range(12)]},
            rows_per_delta=12,
            scale=50.0,  # deliberately ranking-wrecking generations
            seed=3,
        )
        doc = run_scenario(
            scenario, [scorer], BUCKETS, ServingMetrics(),
            plane=plane, tenancy=tenancy, nearline_fn=nearline_fn,
        )
        assert doc["num_requests"] == len(reqs)
        assert doc["nearline"]["rollbacks"] > 0
        assert doc["nearline"]["deltas_applied"] == 0
        assert doc["nearline"]["generations"]["candidate"] == 0
        st = reg.state("candidate")
        assert st.generation == 0
        assert st.rollbacks == doc["nearline"]["rollbacks"]

    def test_tenancy_scenario_requires_plane(self):
        scenario = build_scenario("tenant_isolation", _requests(24))
        with pytest.raises(ValueError, match="tenancy"):
            run_scenario(
                scenario, [_scorer()], BUCKETS, ServingMetrics()
            )


class TestOverlayAdmissionSeed:
    def test_overlay_rows_seed_request_frequency(self):
        """A freshly claimed overlay row must not be the importance
        plane's first eviction victim: the claim seeds one request of
        frequency so ``freq x norm`` ranks it like a just-requested
        row."""
        art = _artifact()
        scorer = _scorer(art, eviction_policy="importance")
        reg = VariantRegistry(scorer)
        reg.add_variant("v1")
        touched = ["u3", "u5"]
        reg.apply_delta(
            "v1", build_delta(_delta_for(art, touched), art, generation=1)
        )
        coord = scorer.routing["per_user"]
        for eid in touched:
            row = reg.state("v1").overlay_rows["per_user"][eid]
            assert coord._freq[row] > 0.0, eid
            assert coord.importance_of(np.array([row]))[0] > 0.0

    def test_default_policy_overlay_seed_is_noop(self):
        art = _artifact()
        scorer = _scorer(art)  # "oldest": no frequency plane at all
        reg = VariantRegistry(scorer)
        reg.add_variant("v1")
        reg.apply_delta(
            "v1", build_delta(_delta_for(art, ["u2"]), art, generation=1)
        )
        assert scorer.routing["per_user"]._freq is None
