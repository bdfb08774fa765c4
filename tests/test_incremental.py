"""Nearline incremental training + zero-downtime hot-swap tests.

The load-bearing guarantees, per ISSUE acceptance criteria:

- an incremental update over the full event set with one fixed-effect
  refresh reproduces one full warm-started CD outer pass (the warm-start
  path is the SAME solve, just restricted to touched entities);
- delta artifacts round-trip (atomic dir write, content fingerprint),
  chain by base fingerprint, and ``compact`` folds a chain into a full
  artifact identical to applying the deltas in memory;
- a hot swap mutates the live scorer's tables with ZERO additional XLA
  compilations (params are jit arguments), updates scores for touched
  entities only, invalidates exactly the touched hot-cache rows, and a
  failed validation gate rolls back to the previous generation;
- ``save_artifact`` is atomic under crash injection (the old artifact
  survives; no tmp litter);
- end-to-end nearline loop: train -> serve -> new events -> update ->
  publish -> watch -> swap, through the same ``replay_requests`` plumbing
  the ``serve_game --watch-deltas`` CLI uses.
"""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu.data import RandomEffectDataConfiguration
from photon_ml_tpu.data.game_data import FeatureShard, GameData
from photon_ml_tpu.estimators.game import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_ml_tpu.incremental import (
    DeltaArtifact,
    OverlayIndexMap,
    apply_delta,
    build_delta,
    compact,
    delta_dir_name,
    discover_deltas,
    fingerprint_dir,
    incremental_update,
    load_delta,
    rebase_delta,
    save_delta,
    verify_chain,
)
from photon_ml_tpu.opt import GlmOptimizationConfiguration, RegularizationContext
from photon_ml_tpu.serving import (
    GameScorer,
    HotSwapManager,
    ValidationGate,
    load_artifact,
    pack_game_model,
    replay_requests,
    save_artifact,
)
from photon_ml_tpu.serving.replay import max_nnz_of, requests_from_game_data
from photon_ml_tpu.types import RegularizationType, TaskType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_USERS, ROWS, DG, DU = 8, 20, 6, 3
TOUCHED = [f"u{i}" for i in range(4)]          # re-solved by the update
UNTOUCHED = [f"u{i}" for i in range(4, N_USERS)]
NEW = ["v0", "v1"]                             # first seen in the events

L2 = lambda lam: GlmOptimizationConfiguration(  # noqa: E731
    regularization=RegularizationContext(RegularizationType.L2),
    regularization_weight=lam,
)


def _estimator(num_outer=1):
    return GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinates={
            "fixed": FixedEffectCoordinateConfiguration("g", L2(0.1)),
            "per_user": RandomEffectCoordinateConfiguration(
                "u", RandomEffectDataConfiguration(random_effect_type="userId"),
                L2(1.0),
            ),
        },
        num_outer_iterations=num_outer,
    )


def _coo(X):
    r, c = np.nonzero(X)
    return FeatureShard(rows=r, cols=c, vals=X[r, c], dim=X.shape[1])


def _dataset(rng, users, rows, wg, wu):
    n = len(users) * rows
    Xg = rng.normal(size=(n, DG)).astype(np.float32)
    Xu = rng.normal(size=(n, DU)).astype(np.float32)
    ids = np.repeat(users, rows)
    y = Xg @ wg + np.array([Xu[i] @ wu[ids[i]] for i in range(n)], np.float32)
    y += 0.05 * rng.normal(size=n).astype(np.float32)
    return GameData(
        labels=y,
        feature_shards={"g": _coo(Xg), "u": _coo(Xu)},
        id_tags={"userId": ids},
    )


@pytest.fixture(scope="module")
def nearline(tmp_path_factory):
    """One trained base model + one events batch + one published delta,
    shared read-only by the module (fit once, not per test)."""
    rng = np.random.default_rng(7)
    wg = rng.normal(size=DG).astype(np.float32)
    all_users = [f"u{i}" for i in range(N_USERS)] + NEW
    wu = {u: rng.normal(size=DU).astype(np.float32) for u in all_users}

    base_data = _dataset(rng, [f"u{i}" for i in range(N_USERS)], ROWS, wg, wu)
    events = _dataset(rng, TOUCHED + NEW, ROWS // 2, wg, wu)

    fit = _estimator(num_outer=2).fit(base_data)
    artifact = pack_game_model(fit.model, model_name="nearline-test")

    root = tmp_path_factory.mktemp("nearline")
    artifact_dir = str(root / "artifact")
    save_artifact(artifact, artifact_dir)

    update = incremental_update(
        _estimator(), fit.model, events, refresh_fixed_iterations=0,
        merge=False,
    )
    deltas_dir = str(root / "deltas")
    delta = build_delta(
        update.re_updates, artifact,
        base_fingerprint=fingerprint_dir(artifact_dir),
        generation=1, created_at_unix=100.0,
    )
    delta = save_delta(delta, os.path.join(deltas_dir, delta_dir_name(1)))
    return {
        "base_data": base_data,
        "events": events,
        "fit": fit,
        "artifact": artifact,
        "artifact_dir": artifact_dir,
        "update": update,
        "delta": delta,
        "deltas_dir": deltas_dir,
        "delta_dir": os.path.join(deltas_dir, delta_dir_name(1)),
    }


class TestIncrementalTrainer:
    def test_incremental_equals_full_pass(self, nearline):
        """Acceptance: an update whose events are the FULL dataset, with
        one FE refresh, reproduces one full warm-started CD outer pass."""
        base, data = nearline["fit"], nearline["base_data"]
        full = _estimator(num_outer=1).fit(
            data, initial_models=dict(base.model.models)
        )
        inc = incremental_update(
            _estimator(), base.model, data, refresh_fixed_iterations=1,
        )
        np.testing.assert_allclose(
            np.asarray(inc.fe_updates["fixed"]),
            np.asarray(full.model.models["fixed"].coefficients.means),
            atol=2e-4,
        )
        got_re = inc.models["per_user"]
        want_re = full.model.models["per_user"]
        assert set(got_re.entity_to_loc) == set(want_re.entity_to_loc)
        for eid in want_re.entity_to_loc:
            got = dict(got_re.coefficients_for(eid))
            want = dict(want_re.coefficients_for(eid))
            for k in set(got) | set(want):
                assert got.get(k, 0.0) == pytest.approx(
                    want.get(k, 0.0), abs=2e-4
                ), (eid, k)

    def test_touched_and_new_entities(self, nearline):
        upd = nearline["update"]
        assert set(upd.touched_entities["per_user"]) == set(TOUCHED + NEW)
        assert set(upd.new_entities["per_user"]) == set(NEW)
        assert upd.num_events == nearline["events"].num_rows
        # merge=False keeps ONLY the touched entities in the RE sub-model
        assert set(upd.models["per_user"].entity_to_loc) == set(TOUCHED + NEW)

    def test_merge_folds_old_rows(self, nearline):
        upd = incremental_update(
            _estimator(), nearline["fit"].model, nearline["events"],
        )
        merged = upd.models["per_user"]
        assert set(merged.entity_to_loc) == {
            f"u{i}" for i in range(N_USERS)
        } | set(NEW)
        # untouched entities keep their exact old coefficients
        old = nearline["fit"].model.models["per_user"]
        for eid in UNTOUCHED:
            assert dict(merged.coefficients_for(eid)) == pytest.approx(
                dict(old.coefficients_for(eid))
            )


class TestDeltaArtifact:
    def test_round_trip_and_fingerprint(self, nearline):
        delta, ddir = nearline["delta"], nearline["delta_dir"]
        loaded = load_delta(ddir)
        assert loaded.fingerprint == delta.fingerprint
        assert loaded.base_fingerprint == delta.base_fingerprint
        assert loaded.generation == 1
        assert loaded.num_rows_updated == delta.num_rows_updated > 0
        ids0, rows0 = delta.re_rows["per_user"]
        ids1, rows1 = loaded.re_rows["per_user"]
        assert ids1 == list(ids0)
        np.testing.assert_allclose(rows1, rows0, atol=0)
        # the fingerprint is the dir content hash — stable across loads
        assert fingerprint_dir(ddir) == delta.fingerprint

    def test_apply_matches_compact(self, nearline, tmp_path):
        folded = apply_delta(nearline["artifact"], nearline["delta"])
        out = str(tmp_path / "compacted")
        fp = compact(nearline["artifact_dir"], [nearline["delta_dir"]], out)
        reloaded = load_artifact(out)
        assert fp == fingerprint_dir(out)
        for cid, table in folded.tables.items():
            np.testing.assert_allclose(
                np.asarray(reloaded.tables[cid].weights),
                np.asarray(table.weights), atol=1e-7,
            )
            if table.entity_index is not None:
                for eid in TOUCHED + NEW:
                    assert reloaded.tables[cid].entity_index.get_index(
                        eid
                    ) == table.entity_index.get_index(eid)

    def test_broken_chain_raises(self, nearline):
        bogus = DeltaArtifact(
            base_fingerprint="0" * 16, generation=2,
            re_rows=dict(nearline["delta"].re_rows), fe_updates={},
            created_at_unix=0.0, fingerprint="f" * 16,
        )
        with pytest.raises(ValueError, match="chain broken"):
            verify_chain(
                fingerprint_dir(nearline["artifact_dir"]),
                [nearline["delta"], bogus],
            )

    def test_overlay_index_map(self, nearline):
        base = nearline["artifact"].tables["per_user"].entity_index
        n = len(base)
        overlay = OverlayIndexMap(base, {"v0": n, "v1": n + 1})
        assert len(overlay) == n + 2
        assert overlay.get_index("v0") == n
        assert overlay.get_feature_name(n + 1) == "v1"
        assert overlay.get_index("u0") == base.get_index("u0")

    def test_independent_chains_share_one_base(self, nearline, tmp_path):
        """The multi-variant shape: TWO independent delta chains rooted at
        the SAME base fingerprint (one per served variant). Each chain
        verifies and compacts on its own; splicing a link from one chain
        into the other is refused."""
        base_fp = fingerprint_dir(nearline["artifact_dir"])
        art = nearline["artifact"]
        upd = nearline["update"].re_updates

        def _scaled(s):
            return {
                cid: {
                    eid: {k: v * s for k, v in m.items()}
                    for eid, m in ents.items()
                }
                for cid, ents in upd.items()
            }

        def _chain(scale, root):
            d1 = build_delta(
                _scaled(scale), art, base_fingerprint=base_fp, generation=1
            )
            d1 = save_delta(d1, os.path.join(root, delta_dir_name(1)))
            d2 = build_delta(
                _scaled(scale * 3), art,
                base_fingerprint=d1.fingerprint, generation=2,
            )
            d2 = save_delta(d2, os.path.join(root, delta_dir_name(2)))
            return root, [d1, d2]

        dir_a, chain_a = _chain(0.5, str(tmp_path / "variant-a"))
        dir_b, chain_b = _chain(-1.0, str(tmp_path / "variant-b"))
        assert chain_a[0].fingerprint != chain_b[0].fingerprint
        verify_chain(base_fp, chain_a)
        verify_chain(base_fp, chain_b)
        with pytest.raises(ValueError, match="chain broken"):
            verify_chain(base_fp, [chain_a[0], chain_b[1]])
        # each chain compacts to its OWN artifact == its in-memory fold
        for chain, root in ((chain_a, dir_a), (chain_b, dir_b)):
            folded = apply_delta(apply_delta(art, chain[0]), chain[1])
            out = os.path.join(root, "compacted")
            compact(
                nearline["artifact_dir"],
                [os.path.join(root, delta_dir_name(g)) for g in (1, 2)],
                out,
            )
            reloaded = load_artifact(out)
            for cid, table in folded.tables.items():
                np.testing.assert_allclose(
                    np.asarray(reloaded.tables[cid].weights),
                    np.asarray(table.weights), atol=1e-7,
                )

    def test_rebase_retargets_chain_head(self, nearline):
        """``rebase_delta`` moves a base-rooted delta onto a variant's own
        chain head: the copy verifies there, the input is untouched, and
        the content fingerprint is cleared (new content, unsaved)."""
        delta = nearline["delta"]
        moved = rebase_delta(delta, "a" * 16)
        assert moved.base_fingerprint == "a" * 16
        assert moved.fingerprint is None
        assert delta.base_fingerprint != "a" * 16  # input untouched
        verify_chain("a" * 16, [moved])
        with pytest.raises(ValueError, match="chain broken"):
            verify_chain("a" * 16, [delta])

    def test_discover_deltas_sorted(self, nearline, tmp_path):
        d = str(tmp_path / "watch")
        os.makedirs(os.path.join(d, "delta-000002"))
        assert discover_deltas(d) == []  # no manifest yet
        for g in (2, 1):
            save_delta(nearline["delta"], os.path.join(d, delta_dir_name(g)))
        assert [os.path.basename(p) for p in discover_deltas(d)] == [
            "delta-000001", "delta-000002",
        ]


def _serving_stack(nearline, **scorer_kw):
    requests = requests_from_game_data(
        nearline["events"], nearline["artifact"]
    )
    scorer = GameScorer(
        nearline["artifact"], max_nnz=max_nnz_of(requests),
        growth_headroom=True, **scorer_kw,
    )
    return scorer, requests


def _scores(scorer, requests, bucket=16):
    out = {}
    for i in range(0, len(requests), bucket):
        for r in scorer.score_batch(requests[i:i + bucket], bucket_size=bucket):
            out[r.request_id] = r.score
    return out


class TestHotSwap:
    def test_swap_updates_touched_scores_without_rejit(self, nearline):
        """Acceptance: in-place swap adds ZERO XLA compilations; touched
        entities' scores move, untouched entities' scores are bit-equal."""
        scorer, requests = _serving_stack(nearline)
        before = _scores(scorer, requests)
        compiles = scorer.compile_count

        manager = HotSwapManager(
            scorer, fingerprint=fingerprint_dir(nearline["artifact_dir"])
        )
        report = manager.apply_delta(nearline["delta_dir"])
        assert not report.rolled_back
        assert report.generation == manager.generation == 1
        assert report.compiles_added == 0
        assert report.regrew == ()  # NEW ids fit the power-of-two headroom
        assert report.rows_updated == nearline["delta"].num_rows_updated
        assert manager.fingerprint == nearline["delta"].fingerprint

        after = _scores(scorer, requests)
        assert scorer.compile_count == compiles  # same bucket, no retrace
        by_user = {
            req.request_id: req.entity_ids["userId"] for req in requests
        }
        moved = {rid for rid in before if before[rid] != after[rid]}
        assert {by_user[rid] for rid in moved} <= set(TOUCHED + NEW)
        assert any(by_user[rid] in TOUCHED for rid in moved)
        # new entities scored cold (FE-only) before, personalized after
        assert any(by_user[rid] in NEW for rid in moved)

    def test_swap_invalidates_touched_cache_rows_only(self, nearline):
        scorer, requests = _serving_stack(nearline, cache_capacity=16)
        _scores(scorer, requests)  # populate the hot cache
        cache = scorer.caches["per_user"]
        index = nearline["artifact"].tables["per_user"].entity_index
        touched_rows = {index.get_index(e) for e in TOUCHED}
        resident_before = set(cache.cached_entities())
        assert resident_before & touched_rows

        manager = HotSwapManager(scorer)
        manager.apply_delta(nearline["delta_dir"])
        resident_after = set(cache.cached_entities())
        assert not resident_after & touched_rows  # stale rows evicted
        # untouched residents survive the swap untouched
        assert resident_before - touched_rows <= resident_after

    def test_validation_gate_rollback(self, nearline):
        """Acceptance: a delta that tanks held-out AUC is rolled back —
        scores, generation and fingerprint all restore."""
        scorer, requests = _serving_stack(nearline)
        labels = np.asarray(
            nearline["events"].labels
            > np.median(nearline["events"].labels),
            dtype=np.float32,
        )
        gate = ValidationGate(requests, labels, max_auc_regression=0.05, bucket_size=16)
        base_fp = fingerprint_dir(nearline["artifact_dir"])
        manager = HotSwapManager(scorer, fingerprint=base_fp, gate=gate)
        before = _scores(scorer, requests)
        compiles = scorer.compile_count

        garbage = DeltaArtifact(
            base_fingerprint=base_fp, generation=1,
            re_rows={
                "per_user": (
                    list(TOUCHED),
                    np.full((len(TOUCHED), DU), -50.0, np.float32),
                )
            },
            fe_updates={}, created_at_unix=0.0, fingerprint="bad0" * 4,
        )
        report = manager.apply_delta(garbage)
        assert report.rolled_back
        assert report.validation_metric < report.baseline_metric - 0.05
        assert manager.generation == 0
        assert manager.fingerprint == base_fp
        after = _scores(scorer, requests)
        assert before == after  # bit-identical restore
        # gate evaluation reuses a warmed bucket: still no extra compiles
        assert scorer.compile_count == compiles

    def test_good_delta_passes_gate(self, nearline):
        scorer, requests = _serving_stack(nearline)
        labels = np.asarray(
            nearline["events"].labels
            > np.median(nearline["events"].labels),
            dtype=np.float32,
        )
        gate = ValidationGate(requests, labels, max_auc_regression=0.05, bucket_size=16)
        manager = HotSwapManager(
            scorer, fingerprint=fingerprint_dir(nearline["artifact_dir"]),
            gate=gate,
        )
        report = manager.apply_delta(nearline["delta_dir"])
        assert not report.rolled_back
        assert report.validation_metric is not None
        assert manager.generation == 1

    def test_poll_directory_applies_once(self, nearline):
        scorer, _ = _serving_stack(nearline)
        manager = HotSwapManager(
            scorer, fingerprint=fingerprint_dir(nearline["artifact_dir"])
        )
        reports = manager.poll_directory(nearline["deltas_dir"])
        assert [r.generation for r in reports] == [1]
        assert manager.poll_directory(nearline["deltas_dir"]) == []

    def test_chain_mismatch_rejected(self, nearline):
        scorer, _ = _serving_stack(nearline)
        manager = HotSwapManager(scorer, fingerprint="0" * 16)
        with pytest.raises(ValueError, match="chain"):
            manager.apply_delta(nearline["delta_dir"])


class TestEndToEndNearline:
    def test_train_serve_update_publish_swap(self, nearline, tmp_path):
        """The full nearline loop through the serve_game --watch-deltas
        plumbing: replay sees the pre-swap scores, a delta lands in the
        watch dir, the next poll swaps it in between batches."""
        watch = str(tmp_path / "watch")
        os.makedirs(watch)
        scorer, requests = _serving_stack(nearline)
        manager = HotSwapManager(
            scorer, fingerprint=fingerprint_dir(nearline["artifact_dir"])
        )
        before = _scores(scorer, requests)
        compiles = scorer.compile_count

        # replay with nothing to watch: no swap
        _, snap0 = replay_requests(
            scorer, requests, bucket_sizes=(16,),
            swap_manager=manager, watch_dir=watch, poll_every=8,
        )
        assert snap0["swap_reports"] == []

        # the nearline trainer publishes a delta mid-stream
        save_delta(nearline["delta"], os.path.join(watch, delta_dir_name(1)))
        results, snap1 = replay_requests(
            scorer, requests, bucket_sizes=(16,),
            swap_manager=manager, watch_dir=watch, poll_every=8,
        )
        assert len(snap1["swap_reports"]) == 1
        assert snap1["swap_reports"][0]["generation"] == 1
        assert not snap1["swap_reports"][0]["rolled_back"]
        assert manager.generation == 1

        after = {r.request_id: r.score for r in results}
        by_user = {
            req.request_id: req.entity_ids["userId"] for req in requests
        }
        changed = {
            by_user[rid] for rid in before if before[rid] != after[rid]
        }
        assert changed <= set(TOUCHED + NEW) and changed
        for rid in before:
            if by_user[rid] in UNTOUCHED:
                assert before[rid] == after[rid]
        # zero additional compilations across the whole swap + replay
        assert scorer.compile_count == compiles


class TestAtomicArtifactSave:
    def test_crash_mid_write_preserves_old_artifact(
        self, nearline, tmp_path, monkeypatch
    ):
        """Crash injection: dying mid-write must leave the previous
        artifact loadable and no tmp litter behind."""
        from photon_ml_tpu.serving import artifact as artifact_mod

        target = str(tmp_path / "artifact")
        save_artifact(nearline["artifact"], target)
        fp = fingerprint_dir(target)

        real = artifact_mod._write_artifact_contents

        def _boom(artifact, out_dir):
            real(artifact, out_dir)  # full payload written, then we die
            raise RuntimeError("injected crash before publish")

        monkeypatch.setattr(artifact_mod, "_write_artifact_contents", _boom)
        with pytest.raises(RuntimeError, match="injected crash"):
            save_artifact(nearline["artifact"], target)
        monkeypatch.undo()

        assert fingerprint_dir(target) == fp  # old artifact intact
        load_artifact(target)
        litter = [
            n for n in os.listdir(tmp_path)
            if n.startswith((".artifact-tmp-", ".artifact-old-"))
        ]
        assert litter == []

    def test_first_write_crash_leaves_nothing(
        self, nearline, tmp_path, monkeypatch
    ):
        from photon_ml_tpu.serving import artifact as artifact_mod

        target = str(tmp_path / "fresh")
        monkeypatch.setattr(
            artifact_mod, "_write_artifact_contents",
            lambda *a: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        with pytest.raises(RuntimeError):
            save_artifact(nearline["artifact"], target)
        assert not os.path.exists(target)
        assert [n for n in os.listdir(tmp_path) if n.startswith(".")] == []


RATINGS = os.path.join(REPO, "tests", "fixtures", "ratings")


@pytest.fixture(scope="module")
def ratings_artifact(tmp_path_factory):
    """Golden-fixture CLI plumbing: a saved model dir, its exported serving
    artifact, and the coordinate-config file that trained it."""
    from photon_ml_tpu import testing
    from photon_ml_tpu.cli.serve_game import main as serve_main
    from photon_ml_tpu.io.data_reader import (
        FeatureShardConfiguration,
        read_game_data,
    )
    from photon_ml_tpu.io.model_io import save_game_model

    shards_raw = {
        "global": {"feature_bags": ["features"], "add_intercept": True},
        "per_user": {"feature_bags": ["userFeatures"], "add_intercept": False},
    }
    shard_cfg = {
        sid: FeatureShardConfiguration(
            feature_bags=s["feature_bags"],
            add_intercept=s["add_intercept"],
        )
        for sid, s in shards_raw.items()
    }
    data, index_maps, _ = read_game_data(
        [os.path.join(RATINGS, "train")], shard_cfg, id_tags=["userId"],
    )
    model = testing.generate_game_model(
        data, TaskType.LINEAR_REGRESSION,
        {
            "fixed": {"feature_shard": "global"},
            "per_user": {
                "feature_shard": "per_user", "random_effect_type": "userId",
            },
        },
        seed=5,
    )
    root = tmp_path_factory.mktemp("ratings-nearline")
    model_dir = str(root / "model")
    save_game_model(
        model, model_dir, index_maps=index_maps,
        configurations={"feature_shards": shards_raw},
    )
    artifact_dir = str(root / "artifact")
    assert serve_main([
        "--model-dir", model_dir, "--export-artifact-dir", artifact_dir,
    ]) == 0
    cfg = {
        "feature_shards": shards_raw,
        "coordinates": {
            "fixed": {
                "type": "fixed", "feature_shard": "global",
                "optimizer": {"regularization": "L2",
                              "regularization_weight": 0.1},
            },
            "per_user": {
                "type": "random", "feature_shard": "per_user",
                "random_effect_type": "userId",
                "optimizer": {"regularization": "L2",
                              "regularization_weight": 1.0},
            },
        },
    }
    cfg_path = str(root / "game.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    return {
        "model_dir": model_dir,
        "artifact_dir": artifact_dir,
        "config": cfg_path,
    }


class TestNearlineCli:
    def test_update_game_publishes_chained_deltas(
        self, ratings_artifact, tmp_path, capsys
    ):
        """update_game publishes delta-000001, a second run auto-chains
        delta-000002 to it, and serve_game --watch-deltas swaps both into
        the live scorer mid-replay."""
        from photon_ml_tpu.cli.serve_game import main as serve_main
        from photon_ml_tpu.cli.update_game import main as update_main

        deltas = str(tmp_path / "deltas")
        argv = [
            "--base-artifact-dir", ratings_artifact["artifact_dir"],
            "--model-dir", ratings_artifact["model_dir"],
            "--coordinate-config", ratings_artifact["config"],
            "--events-data-dirs", os.path.join(RATINGS, "train"),
            "--output-dir", deltas,
        ]
        assert update_main(argv) == 0
        first = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert first["generation"] == 1
        assert first["rows_updated"] > 0
        assert first["base_fingerprint"] == fingerprint_dir(
            ratings_artifact["artifact_dir"]
        )
        assert os.path.isdir(os.path.join(deltas, "delta-000001"))

        assert update_main(argv + ["--refresh-fixed-iterations", "1"]) == 0
        second = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert second["generation"] == 2
        assert second["base_fingerprint"] == first["fingerprint"]
        assert second["fixed_effects_refreshed"] == ["fixed"]
        chain = [
            load_delta(d) for d in discover_deltas(deltas)
        ]
        verify_chain(
            fingerprint_dir(ratings_artifact["artifact_dir"]), chain
        )

        metrics_file = str(tmp_path / "metrics.json")
        assert serve_main([
            "--artifact-dir", ratings_artifact["artifact_dir"],
            "--data-dirs", os.path.join(RATINGS, "test"),
            "--max-requests", "100",
            "--bucket-sizes", "4,16",
            "--watch-deltas", deltas,
            "--watch-chunk", "64",
            "--metrics-output", metrics_file,
        ]) == 0
        capsys.readouterr()
        with open(metrics_file) as f:
            snap = json.load(f)
        assert [r["generation"] for r in snap["swap_reports"]] == [1, 2]
        assert not any(r["rolled_back"] for r in snap["swap_reports"])
        assert snap["swaps"]["current_generation"] == 2
        assert snap["swaps"]["num_rollbacks"] == 0

    def test_update_game_compacts_chain(
        self, ratings_artifact, tmp_path, capsys
    ):
        from photon_ml_tpu.cli.update_game import main as update_main

        deltas = str(tmp_path / "deltas")
        compacted = str(tmp_path / "compacted")
        assert update_main([
            "--base-artifact-dir", ratings_artifact["artifact_dir"],
            "--model-dir", ratings_artifact["model_dir"],
            "--coordinate-config", ratings_artifact["config"],
            "--events-data-dirs", os.path.join(RATINGS, "train"),
            "--output-dir", deltas,
            "--compact-into", compacted,
        ]) == 0
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1]
        )
        assert summary["compacted_fingerprint"] == fingerprint_dir(compacted)
        load_artifact(compacted)  # the folded chain is a full artifact
