"""Online serving subsystem tests.

The load-bearing guarantees, per ISSUE acceptance criteria:

- the serving path reproduces the offline ``GameModel.score`` to 1e-6 on a
  GLMix fixture, including rows whose entities are absent from the model
  (FE-only fallback, the reference left-join semantics);
- the microbatcher compiles at most one XLA program per bucket size, even
  across differently-shaped request streams;
- LRU cache eviction order, hit accounting and batch pinning;
- artifact export/load round trip (npy tables + PHIX off-heap entity maps);
- the ``serve_game`` CLI never silently rots (fast smoke over the golden
  ratings fixture).
"""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu import testing
from photon_ml_tpu.serving import (
    GameScorer,
    HotEntityCache,
    MicroBatcher,
    ScoreRequest,
    ServingMetrics,
    load_artifact,
    pack_game_model,
    replay_requests,
    requests_from_game_data,
    save_artifact,
)
from photon_ml_tpu.types import TaskType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RATINGS = os.path.join(REPO, "tests", "fixtures", "ratings")

TASK = TaskType.LOGISTIC_REGRESSION
COORDS = {
    "fixed": {"feature_shard": "global"},
    "per_user": {"feature_shard": "per_entity", "random_effect_type": "userId"},
}


@pytest.fixture(scope="module")
def glmix():
    data, _ = testing.generate_glmix_data(
        task=TASK, n_entities=8, rows_per_entity=10, d_global=8, d_entity=4,
        seed=11,
    )
    model = testing.generate_game_model(data, TASK, COORDS, seed=3)
    return data, model, pack_game_model(model)


class TestScoringParity:
    def test_serving_matches_game_model(self, glmix):
        """Acceptance: replayed serving margins == offline GameModel.score
        to 1e-6 on the fixture."""
        data, model, artifact = glmix
        scorer = GameScorer(artifact)
        requests = requests_from_game_data(data, artifact)
        results, snapshot = replay_requests(
            scorer, requests, bucket_sizes=(1, 2, 4, 8, 16)
        )
        assert [r.request_id for r in results] == [
            req.request_id for req in requests
        ]
        expected = model.score(data) + data.offsets
        got = np.array([r.score for r in results], dtype=np.float32)
        np.testing.assert_allclose(got, expected, atol=1e-6)
        # the mean goes through the task link-inverse (sigmoid here)
        means = np.array([r.mean for r in results], dtype=np.float32)
        np.testing.assert_allclose(
            means, 1.0 / (1.0 + np.exp(-expected.astype(np.float64))),
            atol=1e-6,
        )
        assert snapshot["num_requests"] == len(requests)

    def test_unseen_entities_fall_back_to_fe_only(self, glmix):
        """Acceptance: rows naming entities the model never saw score
        FE-only — identical to GameModel.score's left-join zero — not NaN."""
        data, model, artifact = glmix
        cold_data = data.slice_rows(np.arange(data.num_rows) < 16)
        ids = np.array(cold_data.id_tags["userId"], dtype=object).copy()
        ids[::2] = [f"ghost-{i}" for i in range(len(ids[::2]))]
        cold_data.id_tags["userId"] = ids

        scorer = GameScorer(artifact)
        results = scorer.score_batch(
            requests_from_game_data(cold_data, artifact), bucket_size=16
        )
        got = np.array([r.score for r in results], dtype=np.float32)
        assert np.isfinite(got).all()
        expected = model.score(cold_data) + cold_data.offsets
        np.testing.assert_allclose(got, expected, atol=1e-6)
        # and the ghost rows really are the fixed effect alone
        fe_only = model.score_coordinate("fixed", cold_data)
        np.testing.assert_allclose(got[::2], fe_only[::2], atol=1e-6)
        for r in results[::2]:
            assert r.cold_coordinates == ("per_user",)
        for r in results[1::2]:
            assert r.cold_coordinates == ()

    def test_request_without_entity_id_is_fe_only(self, glmix):
        _, _, artifact = glmix
        scorer = GameScorer(artifact)
        req = ScoreRequest(
            "no-entity", {"global": {1: 2.0}, "per_entity": {0: 1.0}}
        )
        (res,) = scorer.score_batch([req])
        fe_w = np.asarray(artifact.tables["fixed"].weights)
        assert res.score == pytest.approx(2.0 * fe_w[1], abs=1e-6)
        assert res.cold_coordinates == ("per_user",)

    def test_padding_does_not_change_scores(self, glmix):
        """Bucket-padding correctness: a request's score is independent of
        the batch composition around it."""
        data, _, artifact = glmix
        requests = requests_from_game_data(data, artifact)[:7]
        scorer = GameScorer(artifact)
        solo = [scorer.score_batch([r], bucket_size=8)[0] for r in requests]
        together = scorer.score_batch(requests, bucket_size=8)
        for a, b in zip(solo, together):
            assert a.score == b.score  # bitwise: same reduction order
            assert a.mean == b.mean

    def test_offsets_are_applied(self, glmix):
        _, _, artifact = glmix
        scorer = GameScorer(artifact)
        base = ScoreRequest("a", {"global": {0: 1.0}})
        shifted = ScoreRequest("b", {"global": {0: 1.0}}, offset=0.5)
        ra, rb = scorer.score_batch([base, shifted])
        assert rb.score == pytest.approx(ra.score + 0.5, abs=1e-6)


class TestCompileDiscipline:
    def test_one_xla_program_per_bucket(self, glmix):
        """Acceptance: across two differently-shaped request streams the
        scorer traces exactly one program per bucket size used."""
        data, _, artifact = glmix
        scorer = GameScorer(artifact)
        requests = requests_from_game_data(data, artifact)
        assert scorer.compile_count == 0

        # stream 1: 19 requests through buckets (4, 8) -> drains two 8s
        # (full) and the 3-leftover through the 4 bucket
        replay_requests(scorer, requests[:19], bucket_sizes=(4, 8))
        assert scorer.compile_count == 2

        # stream 2, differently shaped: 5 requests, same buckets -> the
        # 8-drain and the 4-drain signatures are already compiled
        replay_requests(scorer, requests[19:24], bucket_sizes=(4, 8))
        assert scorer.compile_count == 2

        # a genuinely new bucket size is one more program, exactly
        scorer.score_batch(requests[:2], bucket_size=2)
        assert scorer.compile_count == 3
        scorer.score_batch(requests[5:7], bucket_size=2)
        assert scorer.compile_count == 3

    def test_batcher_pads_to_buckets(self, glmix):
        data, _, artifact = glmix
        scorer = GameScorer(artifact)
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            scorer, bucket_sizes=(2, 4), metrics=metrics
        )
        requests = requests_from_game_data(data, artifact)[:7]
        out = []
        for r in requests[:3]:
            out.extend(batcher.submit(r))
        assert batcher.queue_depth == 3  # below max bucket: still queued
        assert out == []
        out.extend(batcher.flush())
        assert len(out) == 3 and batcher.queue_depth == 0
        snap = metrics.snapshot()
        # 3 pending flush through one 4-bucket (fill 3/4)
        assert snap["num_batches"] == 1
        assert snap["batch_fill_ratio"] == pytest.approx(0.75)


class TestHotEntityCache:
    def test_lru_eviction_order_and_accounting(self):
        backing = np.arange(18, dtype=np.float32).reshape(6, 3)
        cache = HotEntityCache(backing, capacity=2)

        cache.lookup(np.array([0]))          # miss, fill slot
        cache.lookup(np.array([1]))          # miss, cache now full
        assert (cache.hits, cache.misses, cache.evictions) == (0, 2, 0)
        cache.lookup(np.array([0]))          # hit: 0 becomes MRU
        assert cache.hits == 1
        cache.lookup(np.array([2]))          # evicts 1 (LRU), not 0
        assert cache.evictions == 1
        assert cache.cached_entities() == [0, 2]

        # resident rows hold the backing data; the cold slot stays zero
        slots = cache.lookup(np.array([0, 2, -1]))
        table = np.asarray(cache.table)
        np.testing.assert_array_equal(table[slots[0]], backing[0])
        np.testing.assert_array_equal(table[slots[1]], backing[2])
        assert slots[2] == cache.cold_slot
        np.testing.assert_array_equal(table[slots[2]], 0.0)
        assert cache.cold == 1

        stats = cache.stats()
        assert stats["capacity"] == 2 and stats["resident"] == 2
        assert stats["hits"] == cache.hits and stats["misses"] == cache.misses
        assert stats["hit_rate"] == pytest.approx(
            cache.hits / (cache.hits + cache.misses)
        )

    def test_hit_rate_and_stats_before_any_lookup(self):
        """Regression: ``hit_rate()``/``stats()`` on a fresh cache (zero
        lookups) must return 0.0, not raise ZeroDivisionError — the
        introspection endpoint scrapes caches that may never have served."""
        backing = np.ones((4, 2), dtype=np.float32)
        cache = HotEntityCache(backing, capacity=2)
        assert cache.hit_rate() == 0.0
        stats = cache.stats()
        assert stats["hit_rate"] == 0.0
        assert stats["hits"] == 0 and stats["misses"] == 0

    def test_duplicate_entities_in_one_batch_hit(self):
        backing = np.ones((4, 2), dtype=np.float32)
        cache = HotEntityCache(backing, capacity=2)
        slots = cache.lookup(np.array([3, 3, 3]))
        assert len(set(slots.tolist())) == 1
        assert (cache.hits, cache.misses) == (2, 1)

    def test_batch_pinning_guards_capacity(self):
        backing = np.ones((8, 2), dtype=np.float32)
        cache = HotEntityCache(backing, capacity=2)
        with pytest.raises(RuntimeError, match="capacity"):
            cache.lookup(np.array([0, 1, 2]))  # 3 distinct > 2 slots

    def test_batcher_rejects_undersized_cache(self, glmix):
        _, _, artifact = glmix
        scorer = GameScorer(artifact, cache_capacity=4)
        with pytest.raises(ValueError, match="max bucket"):
            MicroBatcher(scorer, bucket_sizes=(8,))

    def test_cached_scoring_equals_uncached(self, glmix):
        """The cache is a pure locality optimization: scores through a
        small LRU must equal full-table gathers, and the accounting must
        line up with the replayed stream."""
        data, _, artifact = glmix
        requests = requests_from_game_data(data, artifact)
        full = GameScorer(artifact)
        cached = GameScorer(artifact, cache_capacity=4)
        r_full, _ = replay_requests(full, requests, bucket_sizes=(4,))
        r_cached, snap = replay_requests(cached, requests, bucket_sizes=(4,))
        np.testing.assert_allclose(
            [r.score for r in r_full], [r.score for r in r_cached], atol=0
        )
        stats = snap["caches"]["per_user"]
        assert stats["hits"] + stats["misses"] == len(requests)
        assert snap["cache_hit_rate"] == pytest.approx(stats["hit_rate"])


class TestFullTableHeadroom:
    def test_pad_rows_reserve_zero_headroom(self):
        """``pad_rows`` puts zero rows between the live rows and the cold
        slot; appends land in them in place (no shape change)."""
        from photon_ml_tpu.serving.scorer import _FullTable

        backing = np.arange(12, dtype=np.float32).reshape(6, 2)
        table = _FullTable(backing, pad_rows=8)
        assert table.capacity == 8 and table.cold_slot == 8
        dev = np.asarray(table.table)
        assert dev.shape == (9, 2)
        np.testing.assert_array_equal(dev[6:], 0.0)  # headroom + cold
        table.update_rows(np.array([6]), np.array([[5.0, 7.0]]))
        dev = np.asarray(table.table)
        np.testing.assert_array_equal(dev[6], [5.0, 7.0])
        assert table.num_rows == 7
        with pytest.raises(ValueError, match="capacity"):
            table.update_rows(np.array([8]), np.array([[1.0, 1.0]]))

    def test_hot_swap_append_into_headroom_zero_retrace(self, glmix):
        """Acceptance: with ``growth_headroom`` a swap can append a brand
        new entity into a zero headroom row — content becomes servable
        with ZERO added compiles (shape unchanged, params are jit args)."""
        from photon_ml_tpu.indexmap import DefaultIndexMap
        from photon_ml_tpu.serving import ServingArtifact, ServingTable

        _, _, artifact = glmix
        scorer = GameScorer(artifact, growth_headroom=True)
        per = artifact.tables["per_user"]
        n = per.weights.shape[0]
        provider = scorer._providers["per_user"]
        assert provider.capacity > n  # headroom actually reserved

        req = ScoreRequest(
            request_id="new-entity",
            features={"global": {0: 1.0}, "per_entity": {0: 1.0}},
            entity_ids={"userId": "brand-new"},
        )
        scorer.score_batch([req], bucket_size=4)
        warm = scorer.compile_count

        new_row = np.full((1, per.dim), 0.25, dtype=np.float32)
        ids = {
            per.entity_index.get_feature_name(i): i for i in range(n)
        }
        ids["brand-new"] = n
        candidate = ServingArtifact(
            task=artifact.task,
            tables={
                **{
                    cid: t
                    for cid, t in artifact.tables.items()
                    if cid != "per_user"
                },
                "per_user": ServingTable(
                    feature_shard=per.feature_shard,
                    random_effect_type=per.random_effect_type,
                    weights=np.vstack([np.asarray(per.weights), new_row]),
                    entity_index=DefaultIndexMap(ids),
                ),
            },
            model_name=artifact.model_name,
        )
        before = scorer.score_batch([req], bucket_size=4)[0]
        assert before.cold_coordinates == ("per_user",)
        # the swap: append bytes into the headroom row, then flip the
        # artifact (entity index) so routing can see the new entity
        scorer.update_random_effect_rows(
            "per_user", np.array([n]), new_row
        )
        scorer.set_artifact(candidate)
        after = scorer.score_batch([req], bucket_size=4)[0]
        assert after.cold_coordinates == ()
        assert after.score == pytest.approx(before.score + 0.25)
        assert scorer.compile_count == warm  # zero retraces


class TestMetrics:
    def test_snapshot_shape(self):
        metrics = ServingMetrics()
        for i in range(10):
            metrics.observe_batch(n_real=3, bucket_size=4, queue_depth=i % 3)
            for _ in range(3):
                metrics.observe_latency(0.001 * (i + 1))
        snap = metrics.snapshot(
            cache_stats={"re": {"hits": 9, "misses": 1, "hit_rate": 0.9}},
            compile_count=2,
        )
        assert snap["num_requests"] == 30 and snap["num_batches"] == 10
        assert snap["batch_fill_ratio"] == pytest.approx(0.75)
        assert (
            snap["latency_p50_s"]
            <= snap["latency_p95_s"]
            <= snap["latency_p99_s"]
            <= snap["latency_max_s"]
        )
        assert sum(snap["latency_histogram"].values()) == 30
        assert snap["queue_depth_max"] == 2
        assert snap["xla_compiles"] == 2
        assert snap["cache_hit_rate"] == pytest.approx(0.9)

    def test_empty_snapshot(self):
        snap = ServingMetrics().snapshot()
        assert snap["num_requests"] == 0
        assert "latency_p99_s" not in snap
        assert "queue_wait_p99_s" not in snap
        assert "swaps" not in snap

    def test_queue_wait_separate_from_latency(self):
        metrics = ServingMetrics()
        for _ in range(4):
            metrics.observe_queue_wait(0.002)
            metrics.observe_latency(0.010)
        snap = metrics.snapshot()
        assert snap["queue_wait_p50_s"] == pytest.approx(0.002)
        assert (
            snap["queue_wait_p50_s"]
            <= snap["queue_wait_p99_s"]
            <= snap["queue_wait_max_s"]
        )
        assert snap["latency_p50_s"] == pytest.approx(0.010)

    def test_bounded_memory_under_sustained_load(self):
        """A long-lived scorer must not grow per-observation state without
        limit: after 100k observations the reservoirs stay at their fixed
        capacity while counts/means/maxima stay exact and the percentile
        estimates stay stable."""
        from photon_ml_tpu.serving.metrics import RESERVOIR_SIZE

        metrics = ServingMetrics()
        rng = np.random.default_rng(42)
        n = 100_000
        lats = rng.lognormal(mean=-6.0, sigma=0.5, size=n)
        for i, lat in enumerate(lats):
            metrics.observe_latency(float(lat))
            metrics.observe_queue_wait(float(lat) * 0.25)
            if i % 8 == 0:
                metrics.observe_batch(n_real=7, bucket_size=8, queue_depth=i % 5)
        # bounded: the retained sample arrays never exceed capacity
        assert len(metrics._latencies) == RESERVOIR_SIZE
        assert len(metrics._queue_waits) == RESERVOIR_SIZE
        assert metrics._latencies.samples().size == RESERVOIR_SIZE

        snap = metrics.snapshot()
        # exact aggregates survive the sampling
        assert metrics._latencies.count == n
        assert sum(snap["latency_histogram"].values()) == n
        # snapshot rounds to 6 decimals
        assert snap["latency_mean_s"] == pytest.approx(lats.mean(), abs=1e-6)
        assert snap["latency_max_s"] == pytest.approx(lats.max(), abs=1e-6)
        assert snap["queue_depth_mean"] == pytest.approx(2.0, abs=0.01)
        assert snap["queue_depth_max"] == 4
        # percentile ESTIMATES stay close to the exact stream percentiles
        p50, p99 = np.percentile(lats, [50, 99])
        assert snap["latency_p50_s"] == pytest.approx(p50, rel=0.05)
        assert snap["latency_p99_s"] == pytest.approx(p99, rel=0.10)
        assert snap["queue_wait_p50_s"] == pytest.approx(p50 * 0.25, rel=0.05)

    def test_small_counts_stay_exact(self):
        """Below reservoir capacity nothing is sampled: percentiles are
        computed from every observation, as before the bound."""
        metrics = ServingMetrics()
        vals = [0.001 * (i + 1) for i in range(30)]
        for v in vals:
            metrics.observe_latency(v)
        snap = metrics.snapshot()
        assert snap["latency_p50_s"] == pytest.approx(
            float(np.percentile(vals, 50))
        )
        assert snap["latency_max_s"] == pytest.approx(0.030)

    def test_swap_counters(self):
        metrics = ServingMetrics()
        metrics.observe_swap(
            generation=1, rows_updated=12, blackout_s=0.01, staleness_s=2.5
        )
        metrics.observe_swap(
            generation=1, rows_updated=0, blackout_s=0.02, rolled_back=True
        )
        swaps = metrics.snapshot()["swaps"]
        assert swaps["num_swaps"] == 2 and swaps["num_rollbacks"] == 1
        # a rollback never advances the generation or the row counters
        assert swaps["current_generation"] == 1
        assert swaps["rows_updated_total"] == 12
        assert swaps["max_blackout_s"] == pytest.approx(0.02)
        assert swaps["last_staleness_s"] == pytest.approx(2.5)

    def test_reservoir_percentile_empty_is_nan(self):
        """An empty reservoir answers NaN shaped like q — scalar q gives a
        scalar NaN, array q gives an all-NaN array — never an IndexError."""
        from photon_ml_tpu.serving.metrics import _Reservoir

        res = _Reservoir(capacity=8)
        scalar = res.percentile(50.0)
        assert np.isscalar(scalar) or np.ndim(scalar) == 0
        assert np.isnan(scalar)
        arr = res.percentile(np.array([50.0, 99.0]))
        assert arr.shape == (2,)
        assert np.isnan(arr).all()

    def test_reservoir_percentile_single_sample(self):
        """One observation: every quantile is that observation."""
        from photon_ml_tpu.serving.metrics import _Reservoir

        res = _Reservoir(capacity=8)
        res.add(0.042)
        assert res.percentile(0.0) == pytest.approx(0.042)
        assert res.percentile(50.0) == pytest.approx(0.042)
        assert res.percentile(99.0) == pytest.approx(0.042)

    def test_reservoir_percentile_array_matches_scalar(self):
        """Vector q answers elementwise-equal to the scalar calls."""
        from photon_ml_tpu.serving.metrics import _Reservoir

        res = _Reservoir(capacity=64)
        res.add_many([0.001 * (i + 1) for i in range(30)])
        qs = np.array([10.0, 50.0, 90.0, 99.0])
        vec = res.percentile(qs)
        assert vec.shape == qs.shape
        for q, v in zip(qs, vec):
            assert v == pytest.approx(res.percentile(float(q)))


class TestBatcherDeadline:
    def test_poll_drains_on_deadline(self, glmix):
        """Deadline policy: nothing drains before max_wait_s; once the
        OLDEST pending request times out, everything pending rides along."""
        data, _, artifact = glmix
        scorer = GameScorer(artifact)
        now = [0.0]
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            scorer, bucket_sizes=(4, 16), metrics=metrics,
            clock=lambda: now[0], max_wait_s=0.005,
        )
        requests = requests_from_game_data(data, artifact)[:3]
        for r in requests:
            batcher.submit(r)
            now[0] += 0.001
        assert batcher.poll() == []  # oldest has waited 2ms < 5ms
        assert batcher.queue_depth == 3
        now[0] = 0.006
        out = batcher.poll()
        assert len(out) == 3 and batcher.queue_depth == 0
        snap = metrics.snapshot()
        # queue wait is measured enqueue->dequeue, separate from latency
        assert snap["queue_wait_max_s"] == pytest.approx(0.006)
        assert snap["queue_wait_p50_s"] <= snap["queue_wait_max_s"]
        assert snap["num_batches"] == 1

    def test_poll_accepts_external_now(self, glmix):
        data, _, artifact = glmix
        scorer = GameScorer(artifact)
        batcher = MicroBatcher(
            scorer, bucket_sizes=(4,), clock=lambda: 0.0, max_wait_s=1.0,
        )
        batcher.submit(requests_from_game_data(data, artifact)[0])
        assert batcher.poll(now=0.5) == []
        assert len(batcher.poll(now=1.5)) == 1

    def test_poll_without_deadline_raises(self, glmix):
        _, _, artifact = glmix
        batcher = MicroBatcher(GameScorer(artifact), bucket_sizes=(4,))
        with pytest.raises(ValueError, match="max_wait_s"):
            batcher.poll()

    def test_negative_deadline_rejected(self, glmix):
        _, _, artifact = glmix
        with pytest.raises(ValueError, match="max_wait_s"):
            MicroBatcher(
                GameScorer(artifact), bucket_sizes=(4,), max_wait_s=-0.1
            )


class TestArtifact:
    def test_export_load_round_trip(self, glmix, tmp_path):
        data, model, artifact = glmix
        out = str(tmp_path / "artifact")
        save_artifact(artifact, out)

        # layout: metadata + npy tables + PHIX off-heap entity store
        assert os.path.exists(os.path.join(out, "model-metadata.json"))
        assert os.path.exists(os.path.join(out, "fixed-effect", "fixed.npy"))
        re_dir = os.path.join(out, "random-effect", "per_user")
        assert os.path.exists(os.path.join(re_dir, "table.npy"))
        assert os.path.exists(
            os.path.join(re_dir, "entity-index", "partition-0.bin")
        )

        loaded = load_artifact(out)
        assert loaded.task is TASK
        np.testing.assert_array_equal(
            np.asarray(loaded.tables["fixed"].weights),
            np.asarray(artifact.tables["fixed"].weights),
        )
        np.testing.assert_array_equal(
            np.asarray(loaded.tables["per_user"].weights),
            np.asarray(artifact.tables["per_user"].weights),
        )
        # entity rows resolve identically through the off-heap store
        for eid in sorted(set(map(str, data.id_tags["userId"]))):
            assert loaded.entity_row("per_user", eid) == artifact.entity_row(
                "per_user", eid
            )
        assert loaded.entity_row("per_user", "ghost") == -1

        requests = requests_from_game_data(data, loaded)
        results = GameScorer(loaded).score_batch(requests, len(requests))
        expected = model.score(data) + data.offsets
        np.testing.assert_allclose(
            np.array([r.score for r in results]), expected, atol=1e-6
        )

    def test_feature_index_round_trip(self, glmix, tmp_path):
        from photon_ml_tpu.indexmap import DefaultIndexMap

        data, model, _ = glmix
        imap = DefaultIndexMap({f"f{i}": i for i in range(8)})
        artifact = pack_game_model(model, index_maps={"global": imap})
        out = str(tmp_path / "artifact")
        save_artifact(artifact, out)
        loaded = load_artifact(out)
        assert set(loaded.feature_index) == {"global"}
        for name in ("f0", "f3", "f7"):
            assert loaded.feature_index["global"].get_index(name) == (
                imap.get_index(name)
            )
        assert loaded.feature_index["global"].get_index("missing") == -1

    def test_load_rejects_non_artifact_dir(self, glmix, tmp_path):
        from photon_ml_tpu.io.model_io import save_game_model_metadata

        save_game_model_metadata(str(tmp_path), TASK)
        with pytest.raises(ValueError, match="serving"):
            load_artifact(str(tmp_path))


class TestEvents:
    def test_scoring_events_emitted(self, glmix):
        from photon_ml_tpu.event import (
            EventEmitter,
            EventListener,
            ScoringFinishEvent,
            ScoringStartEvent,
        )

        data, _, artifact = glmix
        seen = []

        class Recorder(EventListener):
            def on_event(self, event):
                seen.append(event)

        emitter = EventEmitter()
        emitter.register_listener(Recorder())
        requests = requests_from_game_data(data, artifact)[:6]
        replay_requests(
            GameScorer(artifact), requests, bucket_sizes=(2, 4),
            emitter=emitter, model_id="m1",
        )
        assert [type(e) for e in seen] == [ScoringStartEvent, ScoringFinishEvent]
        start, finish = seen
        assert start.model_id == "m1" and start.num_requests == 6
        assert finish.num_requests == 6
        assert finish.metrics["num_requests"] == 6
        assert finish.wall_seconds >= 0

    def test_register_listener_class_bad_module(self):
        from photon_ml_tpu.event import EventEmitter

        emitter = EventEmitter()
        with pytest.raises(ValueError, match="no_such_module.Listener"):
            emitter.register_listener_class("no_such_module.Listener")

    def test_register_listener_class_bad_attribute(self):
        from photon_ml_tpu.event import EventEmitter

        emitter = EventEmitter()
        with pytest.raises(
            ValueError, match="photon_ml_tpu.event.*NoSuchListener"
        ):
            emitter.register_listener_class("photon_ml_tpu.event.NoSuchListener")

    def test_register_listener_class_not_dotted(self):
        from photon_ml_tpu.event import EventEmitter

        with pytest.raises(ValueError, match="dotted"):
            EventEmitter().register_listener_class("JustAName")


def _ratings_model_dir(tmp_path_factory):
    """A GAME model over the committed golden ratings fixture (random
    coefficients — CLI plumbing under test, not model quality)."""
    from photon_ml_tpu.io.data_reader import (
        FeatureShardConfiguration,
        read_game_data,
    )
    from photon_ml_tpu.io.model_io import save_game_model

    shard_cfg = {
        "global": FeatureShardConfiguration(
            feature_bags=["features"], add_intercept=True
        ),
        "per_user": FeatureShardConfiguration(
            feature_bags=["userFeatures"], add_intercept=False
        ),
    }
    data, index_maps, _ = read_game_data(
        [os.path.join(RATINGS, "train")], shard_cfg, id_tags=["userId"],
    )
    model = testing.generate_game_model(
        data, TaskType.LINEAR_REGRESSION,
        {
            "fixed": {"feature_shard": "global"},
            "per_user": {
                "feature_shard": "per_user",
                "random_effect_type": "userId",
            },
        },
        seed=5,
    )
    out = str(tmp_path_factory.mktemp("ratings-model"))
    save_game_model(
        model, out, index_maps=index_maps,
        configurations={
            "feature_shards": {
                "global": {"feature_bags": ["features"], "add_intercept": True},
                "per_user": {
                    "feature_bags": ["userFeatures"], "add_intercept": False,
                },
            }
        },
    )
    return out


@pytest.fixture(scope="module")
def ratings_model_dir(tmp_path_factory):
    return _ratings_model_dir(tmp_path_factory)


class TestServeGameCli:
    def test_smoke_over_golden_fixture(self, ratings_model_dir, tmp_path):
        """Tier-1 smoke: pack + export + replay a few hundred requests from
        the committed ratings fixture through the real CLI entrypoint."""
        from photon_ml_tpu.cli.serve_game import main as serve_main

        artifact_dir = str(tmp_path / "artifact")
        metrics_file = str(tmp_path / "metrics.json")
        rc = serve_main([
            "--model-dir", ratings_model_dir,
            "--data-dirs", os.path.join(RATINGS, "test"),
            "--export-artifact-dir", artifact_dir,
            "--metrics-output", metrics_file,
            "--max-requests", "200",
            "--bucket-sizes", "4,16",
            "--cache-capacity", "64",
        ])
        assert rc == 0
        with open(metrics_file) as f:
            snap = json.load(f)
        assert snap["num_requests"] == 200
        assert snap["latency_p99_s"] > 0
        assert snap["requests_per_s"] > 0
        assert snap["xla_compiles"] <= 2  # one program per bucket, at most
        assert 0.0 <= snap["cache_hit_rate"] <= 1.0
        assert snap["batch_fill_ratio"] > 0

        # second leg of train -> export -> serve: serve from the artifact
        metrics2 = str(tmp_path / "metrics2.json")
        rc = serve_main([
            "--artifact-dir", artifact_dir,
            "--data-dirs", os.path.join(RATINGS, "test"),
            "--metrics-output", metrics2,
            "--max-requests", "50",
        ])
        assert rc == 0
        with open(metrics2) as f:
            assert json.load(f)["num_requests"] == 50

    def test_tenants_flag_tracks_per_tenant_slo(
        self, ratings_model_dir, tmp_path
    ):
        """--tenants + --slo-latency-ms: the replayed stream is tagged
        round-robin and each tenant's SLO tracker writes its own
        tenant-labeled serving.slo.* series into the process registry."""
        from photon_ml_tpu.cli.serve_game import main as serve_main
        from photon_ml_tpu.serving import prometheus_text
        from photon_ml_tpu.telemetry.metrics import get_registry

        metrics_file = str(tmp_path / "metrics.json")
        rc = serve_main([
            "--model-dir", ratings_model_dir,
            "--data-dirs", os.path.join(RATINGS, "test"),
            "--metrics-output", metrics_file,
            "--max-requests", "64",
            "--bucket-sizes", "4,16",
            "--cache-capacity", "64",
            "--tenants", "alpha,beta",
            "--slo-latency-ms", "1000",
        ])
        assert rc == 0
        with open(metrics_file) as f:
            assert json.load(f)["num_requests"] == 64
        text = prometheus_text(get_registry().snapshot())
        assert 'tenant="alpha"' in text
        assert 'tenant="beta"' in text

    def test_variants_flag_serves_through_tenancy_plane(
        self, ratings_model_dir, tmp_path
    ):
        """--variants: the replay runs through the full tenancy plane —
        per-tenant quota admission, the seeded variant router, and one
        batcher per variant over the shared sharded scorer — and the
        snapshot carries the tenancy status block."""
        from photon_ml_tpu.cli.serve_game import main as serve_main

        metrics_file = str(tmp_path / "metrics.json")
        rc = serve_main([
            "--model-dir", ratings_model_dir,
            "--data-dirs", os.path.join(RATINGS, "test"),
            "--metrics-output", metrics_file,
            "--max-requests", "128",
            "--bucket-sizes", "4,16",
            "--tenants", "alpha,beta",
            "--slo-latency-ms", "1000",
            "--variants", "candidate",
            "--variant-ramp", "50",
            "--tenant-rate", "1",
            "--tenant-burst", "40",
        ])
        assert rc == 0
        with open(metrics_file) as f:
            snap = json.load(f)
        assert snap["serving_mode"] == "sharded-tenancy"
        ten = snap["tenancy"]
        # both variants exist and both actually served traffic
        assert set(ten["variants"]) == {"base", "candidate"}
        assert ten["router"]["ramps"]["*"]["candidate"] == 50.0
        assert ten["router"]["decisions"].get("candidate", 0) > 0
        assert ten["router"]["decisions"].get("base", 0) > 0
        # the candidate is undiverged: scores stay bitwise the base's
        assert ten["variants"]["candidate"]["diverged"] is False
        # quota: each tenant gets 64 of the 128; burst 40 sheds the rest,
        # charged per tenant
        quota = ten["quota"]["tenants"]
        for tenant in ("alpha", "beta"):
            assert quota[tenant]["admitted"] >= 40
            assert quota[tenant]["shed"] > 0
        # sheds never reach the scorer
        assert snap["num_requests"] == sum(
            quota[t]["admitted"] for t in ("alpha", "beta")
        )
        assert snap["num_results"] == snap["num_requests"]
        # per-tenant SLO budgets rode along on the shared request plane
        assert set(ten["tenants"]) == {"alpha", "beta"}

    def test_variants_rejects_cached_mode(self, ratings_model_dir):
        from photon_ml_tpu.cli.serve_game import main as serve_main

        with pytest.raises(SystemExit, match="cache-capacity"):
            serve_main([
                "--model-dir", ratings_model_dir,
                "--data-dirs", os.path.join(RATINGS, "test"),
                "--max-requests", "8",
                "--cache-capacity", "64",
                "--variants", "candidate",
            ])

    def test_export_only_invocation(self, ratings_model_dir, tmp_path):
        from photon_ml_tpu.cli.serve_game import main as serve_main

        artifact_dir = str(tmp_path / "artifact")
        rc = serve_main([
            "--model-dir", ratings_model_dir,
            "--export-artifact-dir", artifact_dir,
        ])
        assert rc == 0
        assert load_artifact(artifact_dir).tables["per_user"].n_entities > 0

    def test_nothing_to_do_exits_nonzero(self, ratings_model_dir):
        from photon_ml_tpu.cli.serve_game import main as serve_main

        assert serve_main(["--model-dir", ratings_model_dir]) == 2


class TestScoreGameMissingEntityPolicy:
    @pytest.fixture(scope="class")
    def scored_setup(self, tmp_path_factory):
        """Model over the ratings train split, scored against the test
        split PLUS rows naming users the model never saw."""
        import shutil

        from photon_ml_tpu.io.avro import read_avro_dir
        from photon_ml_tpu.io.data_reader import write_training_examples

        model_dir = _ratings_model_dir(tmp_path_factory)
        data_dir = tmp_path_factory.mktemp("score-data")
        recs = list(
            read_avro_dir(os.path.join(RATINGS, "test"))
        )[:30]
        ghosts = 0
        for i, rec in enumerate(recs):
            rec.setdefault("metadataMap", {})
            if i % 3 == 0:
                rec["metadataMap"]["userId"] = f"ghost-{i}"
                ghosts += 1
            rec["uid"] = f"row-{i:04d}"
        assert ghosts > 0

        def to_writer(rec):
            out = {
                "uid": rec["uid"],
                "label": rec.get("label"),
                "metadataMap": rec.get("metadataMap"),
            }
            for bag in ("features", "userFeatures", "movieFeatures"):
                if rec.get(bag):
                    out[bag] = [
                        (f["name"], f["term"], f["value"]) for f in rec[bag]
                    ]
            return out

        write_training_examples(
            str(data_dir / "part-00000.avro"), [to_writer(r) for r in recs]
        )
        return model_dir, str(data_dir), ghosts

    def test_fe_only_policy_scores_unknown_entities(
        self, scored_setup, tmp_path
    ):
        """Satellite regression: unknown entities score FE-only — never
        NaN, never a crash — matching the serving path's fallback."""
        from photon_ml_tpu.cli.score_game import parse_args, run
        from photon_ml_tpu.io.scores_io import load_scores

        model_dir, data_dir, _ = scored_setup
        out = str(tmp_path / "scores")
        run(parse_args([
            "--data-dirs", data_dir,
            "--model-dir", model_dir,
            "--output-dir", out,
            "--missing-entity-policy", "fe-only",
        ]))
        scored = {s.uid: s for s in load_scores(out)}
        assert len(scored) == 30
        scores = np.array(
            [scored[f"row-{i:04d}"].prediction_score for i in range(30)]
        )
        assert np.isfinite(scores).all()

        # ghost rows = fixed-effect-only scores, computed independently
        from photon_ml_tpu.io.data_reader import (
            FeatureShardConfiguration,
            read_game_data,
        )
        from photon_ml_tpu.io.model_io import load_game_model

        model, index_maps = load_game_model(model_dir)
        data, _, uids = read_game_data(
            [data_dir],
            {
                "global": FeatureShardConfiguration(
                    feature_bags=["features"], add_intercept=True
                ),
                "per_user": FeatureShardConfiguration(
                    feature_bags=["userFeatures"], add_intercept=False
                ),
            },
            index_maps, id_tags=["userId"], is_response_required=False,
        )
        fe_only = model.score_coordinate("fixed", data) + data.offsets
        by_uid = dict(zip(uids, fe_only))
        for i in range(0, 30, 3):
            uid = f"row-{i:04d}"
            assert scored[uid].prediction_score == pytest.approx(
                float(by_uid[uid]), abs=1e-5
            )

    def test_error_policy_raises(self, scored_setup, tmp_path):
        from photon_ml_tpu.cli.score_game import parse_args, run

        model_dir, data_dir, _ = scored_setup
        with pytest.raises(ValueError, match="ghost-0"):
            run(parse_args([
                "--data-dirs", data_dir,
                "--model-dir", model_dir,
                "--output-dir", str(tmp_path / "scores"),
                "--missing-entity-policy", "error",
            ]))
