"""The benchmark's machine-read contract, in smoke mode on CPU.

The driver runs ``python bench.py`` at the end of every round and parses
exactly one JSON line; this gate keeps that contract honest (keys, types,
the north-star grid tile as the headline, pinned-vs-fresh baseline
reporting, engine A/B recording incl. the quality-gated bf16 entry) without
TPU hardware, and that a non-smoke run without a TPU prints no number.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow  # each case re-runs bench.py as a child


def _smoke_env(**extra):
    env = dict(
        os.environ,
        BENCH_SMOKE="1",
        JAX_PLATFORMS="cpu",
        BENCH_PLAN_CACHE="",
    )
    env.update(extra)
    return env


def test_bench_smoke_contract():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=900, env=_smoke_env(),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    payload = json.loads(line)

    assert payload["metric"] == "glmix_logistic_train_throughput"
    assert payload["unit"] == "example_passes/sec/chip"
    assert payload["value"] > 0
    assert payload["vs_baseline"] > 0
    assert "error" not in payload
    assert "stale" not in payload

    # the HEADLINE is the north-star workload: the single-chip tile of the
    # 1B-coefficient grid layout (VERDICT r4 #4)
    assert payload["headline_workload"] == (
        "grid_2^24_coef_chip_tile_of_1B_layout"
    )
    assert payload["value"] == payload["grid16m_passes_per_s"]
    assert payload["grid16m_engine"] in ("ell", "benes", "fused")
    assert payload["grid16m_iterations"] >= 1

    # the convergence clock runs on the headline workload
    assert payload["wallclock_to_auc_s"] >= 0
    assert payload["auc_final"] >= payload["auc_target"]

    # both baseline ratios are reported; vs_baseline is one of them
    assert payload["vs_baseline_fresh"] > 0
    assert payload["vs_baseline"] in (
        payload["vs_baseline_fresh"], payload.get("vs_baseline_pinned")
    )

    # every engine of the small-dim A/B is recorded, including the
    # reduced-precision candidate; the small-dim best is at least the best
    # EXACT engine (fused_bf16 only takes it when its quality gate passes)
    engines = payload["engines"]
    for key in ("ell", "benes", "fused", "fused_bf16"):
        assert key in engines and engines[key] > 0, engines
    exact_best = max(v for k, v in engines.items() if k != "fused_bf16")
    assert payload["smalldim_passes_per_s"] >= exact_best
    assert payload["smalldim_vs_baseline"] > 0


def test_bench_without_tpu_prints_no_number():
    """Off smoke mode the training bench measures the chip or nothing: on a
    host where JAX finds only the CPU it exits non-zero with empty stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env=_smoke_env(BENCH_SMOKE="0"), cwd=REPO,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def _artifact_fingerprint(path):
    """(exists, content) of a bench artifact — smoke runs must leave the
    committed full-scale record untouched."""
    if not os.path.exists(path):
        return (False, None)
    with open(path) as f:
        return (True, f.read())


def test_bench_re_adaptive_contract():
    """``--re-adaptive`` emits one JSON line with the lane-efficiency and
    speedup fields the driver parses, and the adaptive path must beat
    lockstep on executed lane-iterations even at smoke scale."""
    artifact = os.path.join(REPO, "BENCH_RE_ADAPTIVE.json")
    before = _artifact_fingerprint(artifact)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--re-adaptive"],
        capture_output=True, text=True, timeout=900, env=_smoke_env(),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    payload = json.loads(line)

    assert payload["metric"] == "re_adaptive_speedup"
    assert "error" not in payload
    assert payload["unit"] == "x_vs_oneshot"
    assert payload["value"] > 0
    assert payload["adaptive_wall_s"] > 0
    assert payload["oneshot_wall_s"] > 0
    assert payload["executed_lane_iterations"] > 0
    # lane compaction must shed work relative to the lockstep equivalent
    assert payload["lane_iteration_savings"] is not None
    assert payload["lane_iteration_savings"] > 1.0
    assert 0.0 <= payload["wasted_lane_fraction"] < 1.0
    # one entry per bucket; widths start at the bucket size and descend
    # through powers of two
    for widths, rounds in zip(payload["dispatch_widths"], payload["rounds"]):
        assert len(widths) == rounds
        assert widths == sorted(widths, reverse=True)
        for w in widths[1:]:
            assert w & (w - 1) == 0
    assert payload["chunk_iters"] >= 1
    # smoke mode must not touch the committed full-scale artifact
    # (BENCH_RE_ADAPTIVE_WRITE gates the file write, mirroring the other
    # sub-benches)
    assert _artifact_fingerprint(artifact) == before


def test_bench_cd_scores_contract():
    """``--cd-scores`` emits one JSON line with the score-plane fields the
    driver parses. The overhead-reduction ratio is noisy at smoke scale, so
    the gate pins the DETERMINISTIC claims: zero row transfers per steady
    iteration on the device plane, exact parity, and no host re-sums."""
    artifact = os.path.join(REPO, "BENCH_CD_SCORES.json")
    before = _artifact_fingerprint(artifact)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--cd-scores"],
        capture_output=True, text=True, timeout=900, env=_smoke_env(),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    payload = json.loads(line)

    assert payload["metric"] == "cd_score_plane_overhead_reduction"
    assert "error" not in payload
    assert payload["unit"] == "fraction_vs_host_plane"
    assert payload["value"] is not None
    assert payload["host_wall_s"] > 0
    assert payload["device_wall_s"] > 0
    assert payload["host_overhead_s"] > 0
    assert payload["device_overhead_s"] > 0
    # host and device planes must train the same model
    assert payload["parity_max_abs_diff"] <= 1e-6
    dev = payload["device_transfers"]
    host = payload["host_transfers"]
    # device plane: zero row-length transfers in the steady state
    assert dev["score_plane"] == "device"
    assert dev["row_transfers_h2d"] == 0
    assert dev["row_transfers_d2h"] == 0
    assert dev["row_transfers_per_iter"] == 0.0
    assert dev["device_plane_updates"] == dev["coordinate_updates"]
    # host plane: 2 row arrays per update (score pull + residual push)
    assert host["score_plane"] == "host"
    assert host["row_transfers_h2d"] == host["coordinate_updates"]
    assert host["row_transfers_d2h"] == host["coordinate_updates"]
    # the double-total_score() fix: no full C-way re-sums on either plane
    assert host["host_score_sums"] == 0
    assert dev["host_score_sums"] == 0
    # smoke mode must not touch the committed full-scale artifact
    assert _artifact_fingerprint(artifact) == before


def test_bench_streaming_contract(tmp_path):
    """``--streaming`` emits one JSON line A/B-ing the out-of-core streamed
    fit against the in-memory fit on the same on-disk Avro dataset. Wall
    clocks are noisy at smoke scale, so the gate pins the DETERMINISTIC
    claims: >=4 fixed-shape blocks, held-out AUC parity within 1e-3, zero
    post-warmup retraces, and honest decode/stall accounting behind the
    hide ratio."""
    artifact = os.path.join(REPO, "BENCH_STREAMING.json")
    history = os.path.join(REPO, "BENCH_HISTORY.jsonl")
    before = _artifact_fingerprint(artifact)
    history_before = _artifact_fingerprint(history)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--streaming"],
        capture_output=True, text=True, timeout=900,
        env=_smoke_env(BENCH_TELEMETRY_DIR=str(tmp_path)),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    payload = json.loads(line)

    assert payload["metric"] == "streaming_fit_wall_s"
    assert "error" not in payload
    assert payload["unit"] == "seconds"
    assert payload["value"] > 0
    assert payload["inmemory_fit_s"] > 0
    assert payload["cold_epoch_s"] > 0
    assert payload["warm_epoch_s"] > 0
    # the acceptance shape: at least 4 fixed-size blocks over several files
    assert payload["num_blocks"] >= 4
    assert payload["num_files"] >= 2
    assert payload["blocks_streamed"] >= payload["num_blocks"]
    # streamed full-batch trains the same model (held-out AUC parity)
    assert payload["auc_delta"] <= 1e-3
    # fixed shapes: nothing compiles after the first streamed fit
    assert payload["retraces_after_warmup"] == 0
    # prefetch accounting is internally consistent
    assert payload["decode_s"] > 0
    assert payload["decode_work_s"] > 0
    assert payload["stall_s"] >= 0
    assert payload["upload_hidden_s"] >= 0
    assert 0.0 <= payload["prefetch_hide_ratio"] <= 1.0
    assert payload["staging_bound_mb"] >= 0
    # the decoded block cache: the cold fit re-visits blocks from the cache
    # after its first data pass, and the warm fit does ZERO Avro work —
    # every warm block is a cache hit
    assert payload["cache_hit_blocks"] >= 0
    assert payload["warm_decode_work_s"] == 0.0
    assert payload["warm_cache_hit_blocks"] == payload["warm_blocks_streamed"]
    assert payload["warm_blocks_streamed"] >= payload["num_blocks"]
    assert payload["warm_prefetch_hide_ratio"] == 1.0
    # H2D byte accounting is live on both epochs
    assert payload["cold_h2d_bytes"] > 0
    assert payload["warm_h2d_bytes"] > 0
    # hierarchical residency arm: the gap-pinned resident set halves (at
    # least) the warm-epoch upload bytes on the same trajectory, adds no
    # programs, and the byte ledger telescopes exactly
    res = payload["residency"]
    assert 1 <= res["resident_blocks"] < payload["num_blocks"]
    assert res["h2d_ratio"] <= 0.5
    assert res["h2d_bytes"] + res["h2d_saved_bytes"] == (
        payload["warm_h2d_bytes"]
    )
    assert res["auc_delta"] <= 1e-3
    assert res["retraces"] == 0
    assert res["resident_matches_gap_topk"] is True
    assert len(res["resident_set"]) == res["resident_blocks"]
    assert res["pins"] >= res["resident_blocks"]
    # gap-guided scheduling A/B (DuHL): the fields the driver parses, with
    # sane visit accounting and both arms' trajectories recorded; the
    # shuffle arm visits every block every epoch so it always streams more
    assert payload["gap_visits_to_target"] >= 1
    assert payload["shuffle_visits_to_target"] >= 1
    assert payload["gap_vs_shuffle_visits"] > 0
    gap_ab = payload["gap_schedule_ab"]
    assert gap_ab["num_blocks"] > len(gap_ab["hard_blocks"]) >= 1
    assert 0.5 <= gap_ab["target_auc"] <= 1.0
    assert gap_ab["shuffle_trajectory"] and gap_ab["gap_trajectory"]
    assert (
        gap_ab["shuffle_trajectory"][-1][0] > gap_ab["gap_trajectory"][-1][0]
    )
    telemetry = payload["telemetry"]
    assert telemetry["validated"] is True
    assert telemetry["ledger"].startswith(str(tmp_path))
    # every stream_* program traced exactly once across both fits AND the
    # gap-scheduling A/B (which reuses the per-block program shapes and
    # drives the solver seam directly, below the row-plane programs)
    stream_traces = {
        k: v for k, v in telemetry["jit_traces"].items()
        if k.startswith("stream_")
    }
    assert stream_traces and all(v == 1 for v in stream_traces.values()), (
        stream_traces
    )
    assert "stream_gap_probe/trace" in stream_traces
    # smoke mode leaves committed records untouched
    assert _artifact_fingerprint(artifact) == before
    assert _artifact_fingerprint(history) == history_before


def test_bench_streaming_committed_artifact():
    """The committed full-scale record must back the PR's headline claims:
    the WARM epoch (every block reloaded from the decoded block cache) does
    zero Avro work, hides everything by the wall-based hide ratio, and
    lands within 1.2x of the in-memory fit; the prefetcher hides >=50% of
    cold decode wall clock when the host has a core to decode on (overlap
    is physically impossible on one CPU, where the decode thread and the
    solver timeshare; the record then must show the honest degraded
    accounting); AUC parity holds on >=4 blocks; nothing retraces after
    warmup; and the streamed fit's peak host RSS stays bounded (it must
    not grow past the in-memory fit's)."""
    artifact = os.path.join(REPO, "BENCH_STREAMING.json")
    assert os.path.exists(artifact), "full-scale --streaming record missing"
    with open(artifact) as f:
        payload = json.load(f)
    assert payload["metric"] == "streaming_fit_wall_s"
    assert payload["num_blocks"] >= 4
    if payload["cpus"] >= 2:
        assert payload["prefetch_hide_ratio"] >= 0.5
        assert payload["decode_workers"] >= 1
    else:
        # single-CPU record: decode work must be fully accounted and the
        # stall side must show it was exposed, not silently dropped
        assert payload["decode_workers"] == 0
        assert payload["decode_s"] > 0
        assert 0.0 <= payload["prefetch_hide_ratio"] <= 1.0
    # warm-epoch contract: zero decode work, every block a cache hit, the
    # wall-based hide ratio >= 0.8, and wall clock within 1.2x in-memory
    assert payload["warm_decode_work_s"] == 0.0
    assert payload["warm_cache_hit_blocks"] == payload["warm_blocks_streamed"]
    assert payload["warm_prefetch_hide_ratio"] >= 0.8
    assert payload["warm_epoch_s"] <= 1.2 * payload["inmemory_fit_s"]
    assert payload["upload_hidden_s"] >= 0
    assert payload["auc_delta"] <= 1e-3
    assert payload["retraces_after_warmup"] == 0
    assert payload["peak_rss_stream_delta_mb"] <= (
        payload["peak_rss_inmemory_delta_mb"]
        + payload["staging_bound_mb"] * 4 + 256
    )
    # hierarchical residency: the committed record must back the headline
    # claim — the gap-pinned resident set cuts warm-epoch H2D bytes >=2x
    # at bitwise AUC parity, the set was CHOSEN by the gap probe (equals
    # the top-k of the final measured gaps, not a static prefix), and the
    # residency fit is no slower than the plain warm epoch
    res = payload["residency"]
    assert payload["warm_h2d_bytes"] >= 2 * res["h2d_bytes"]
    assert res["auc_delta"] <= 1e-6
    assert res["retraces"] == 0
    assert res["resident_matches_gap_topk"] is True
    assert res["warm_epoch_s"] <= 1.2 * payload["warm_epoch_s"]
    # DuHL gap scheduling: the committed record must back the headline
    # claim — the gap-scheduled arm sustains the held-out AUC target in
    # >=2x fewer block visits than the blind per-epoch shuffle
    assert payload["gap_vs_shuffle_visits"] >= 2.0
    assert payload["gap_schedule_ab"]["target_reached"] == {
        "gap": True, "shuffle": True
    }


def test_bench_cd_async_contract(tmp_path):
    """``--cd-async`` emits one JSON line comparing the sync and async CD
    schedules. The speedup ratio is noisy at smoke scale, so the gate pins
    the DETERMINISTIC claims: AUC parity between the arms, retrace parity
    (the async schedule compiles nothing new), nonzero per-phase overlap
    attribution with near-full ledger coverage, and a bounded overlap
    fraction."""
    artifact = os.path.join(REPO, "BENCH_CD_ASYNC.json")
    history = os.path.join(REPO, "BENCH_HISTORY.jsonl")
    before = _artifact_fingerprint(artifact)
    history_before = _artifact_fingerprint(history)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--cd-async"],
        capture_output=True, text=True, timeout=900,
        env=_smoke_env(BENCH_TELEMETRY_DIR=str(tmp_path)),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    payload = json.loads(line)

    assert payload["metric"] == "cd_async_outer_iter_speedup"
    assert "error" not in payload
    assert payload["unit"] == "x_vs_sync"
    assert payload["value"] > 0
    assert payload["sync_wall_s"] > 0
    assert payload["async_wall_s"] > 0
    assert payload["staleness"] >= 1
    # both arms train to the same quality — the async gate
    assert abs(payload["auc_delta"]) <= 0.05
    # the async schedule reuses the sync pow2 program registry: no new
    # solver traces after the sync warmup
    assert payload["trace_parity"] is True
    # the analyzer attributed concurrency: every pipelined phase shows
    # nonzero overlap, and the busy-time-relative fraction is bounded
    for phase in ("fe_solve", "re_solve", "cd_driver"):
        assert payload["overlap_s"][phase] > 0, payload["overlap_s"]
    assert 0.0 < payload["overlap_fraction"] < 1.0
    assert payload["ledger_coverage"] >= 0.95
    # both arms stay on the device plane with zero steady-state row moves
    for arm in ("sync_transfers", "async_transfers"):
        t = payload[arm]
        assert t["score_plane"] == "device"
        assert t["row_transfers_h2d"] == 0
        assert t["row_transfers_d2h"] == 0
        assert t["device_plane_updates"] == t["coordinate_updates"]
    # CPU smoke runs under emulated device latency, and says so
    assert payload["device_latency_emulated"] is True
    assert payload["emulated_latency_s"] > 0
    telemetry = payload["telemetry"]
    assert telemetry["validated"] is True
    assert telemetry["ledger"].startswith(str(tmp_path))
    # smoke mode leaves committed records untouched
    assert _artifact_fingerprint(artifact) == before
    assert _artifact_fingerprint(history) == history_before


def test_bench_cd_async_committed_artifact():
    """The committed full-scale record must back the PR's headline claim:
    >=1.3x outer-iteration speedup at AUC parity with honest labeling of
    the latency-emulation methodology."""
    artifact = os.path.join(REPO, "BENCH_CD_ASYNC.json")
    assert os.path.exists(artifact), "full-scale --cd-async record missing"
    with open(artifact) as f:
        payload = json.load(f)
    assert payload["metric"] == "cd_async_outer_iter_speedup"
    assert payload["value"] >= 1.3
    assert abs(payload["auc_delta"]) <= 0.02
    assert payload["trace_parity"] is True
    assert payload["ledger_coverage"] >= 0.95
    assert "device_latency_emulated" in payload
    if payload["device_latency_emulated"]:
        assert payload["emulated_latency_s"] > 0


def test_bench_tuning_contract(tmp_path):
    """``--tuning`` closes the telemetry loop: default replay under a run
    ledger -> analyzer replay -> tuner proposal -> tuned replay, with the
    default-vs-tuned deltas in the payload. Smoke must leave both the
    committed artifact AND the perf-trajectory history untouched."""
    artifact = os.path.join(REPO, "BENCH_TUNING.json")
    history = os.path.join(REPO, "BENCH_HISTORY.jsonl")
    before = _artifact_fingerprint(artifact)
    history_before = _artifact_fingerprint(history)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--tuning"],
        capture_output=True, text=True, timeout=900,
        env=_smoke_env(BENCH_TELEMETRY_DIR=str(tmp_path)),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    payload = json.loads(line)

    assert payload["metric"] == "tuning_p99_delta_s"
    assert "error" not in payload
    assert payload["unit"] == "seconds_default_minus_tuned"
    # both arms fully recorded, with the connecting proposal
    for arm in ("default", "tuned"):
        assert payload[arm]["latency_p99_s"] > 0
        assert payload[arm]["bucket_sizes"]
        assert payload[arm]["cache_capacity"] > 0
    assert payload["value"] == pytest.approx(
        payload["default"]["latency_p99_s"]
        - payload["tuned"]["latency_p99_s"],
        abs=1e-6,
    )
    assert set(payload["deltas"]) == {
        "latency_p99_s", "requests_per_s", "xla_compiles"
    }
    # the proposal audited the full knob space and the A/B always has a
    # control + at least one trial arm
    assert payload["proposal"]["knobs_considered"] >= 4
    assert len(payload["proposal"]["candidates"]) >= 2
    # the analyzer replay attributed the ledger's wall-clock
    assert payload["report_coverage"] >= 0.95
    telemetry = payload["telemetry"]
    assert telemetry["validated"] is True
    assert telemetry["ledger_records"] > 0
    # telemetry files land in BENCH_TELEMETRY_DIR, not the repo
    assert telemetry["ledger"].startswith(str(tmp_path))
    # smoke mode leaves committed records untouched
    assert _artifact_fingerprint(artifact) == before
    assert _artifact_fingerprint(history) == history_before


def test_bench_serving_validates_own_telemetry(tmp_path):
    """Every telemetry-mode sub-bench validates its own ledger + Chrome
    trace before writing the BENCH artifact; the files are real and land
    outside the repo."""
    from photon_ml_tpu.telemetry import validate_chrome_trace, validate_ledger

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serving"],
        capture_output=True, text=True, timeout=900,
        env=_smoke_env(BENCH_TELEMETRY_DIR=str(tmp_path)),
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    telemetry = payload["telemetry"]
    assert telemetry["validated"] is True
    # the paths the bench reported really validate from the outside too
    records = validate_ledger(telemetry["ledger"])
    assert len(records) == telemetry["ledger_records"]
    validate_chrome_trace(telemetry["trace"])
    span_names = {r["name"] for r in records if r["type"] == "span"}
    assert any(n.startswith("serve/") for n in span_names)


def test_bench_history_append_when_opted_in(tmp_path):
    """BENCH_HISTORY_WRITE opts a smoke run into the perf-trajectory
    append."""
    import shutil

    shutil.copy(os.path.join(REPO, "bench.py"), tmp_path / "bench.py")
    env = _smoke_env(
        BENCH_HISTORY_WRITE="1",
        BENCH_TELEMETRY_DIR=str(tmp_path / "telemetry"),
        PYTHONPATH=REPO,
    )
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench.py"), "--tuning"],
        capture_output=True, text=True, timeout=900, env=env, cwd=tmp_path,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    history = tmp_path / "BENCH_HISTORY.jsonl"
    assert history.exists()
    (rec,) = [json.loads(l) for l in history.read_text().splitlines()]
    assert rec["mode"] == "tuning"
    assert rec["metric"] == "tuning_p99_delta_s"
    assert isinstance(rec["value"], (int, float))
    assert rec["ts"] > 0 and rec["host"]


def test_bench_multihost_committed_artifact():
    """The committed full-scale --multihost record must back the PR's
    observability claims alongside the scaling headline: every cluster arm
    carries the coordinator's skew attribution (busy / allreduce-wait /
    bubble decomposition covering ~100% of pass wall), and the headline
    2-host skew/comm-wait fields are present with sane values — at
    unchanged scaling (the data-parallel speedup must not regress to pay
    for the telemetry, which piggybacks on existing messages)."""
    artifact = os.path.join(REPO, "BENCH_MULTIHOST.json")
    assert os.path.exists(artifact), "full-scale --multihost record missing"
    with open(artifact) as f:
        payload = json.load(f)
    assert payload["metric"] == "multihost_speedup_2hosts"
    # scaling headline unchanged by the observability plane
    assert payload["value"] >= 1.8
    assert payload["speedup_4hosts"] is None or payload["speedup_4hosts"] >= 3.0
    assert payload["auc_parity_delta"] <= 1e-3
    # headline skew/comm-wait attribution for the 2-host arm
    assert 0.0 <= payload["allreduce_wait_frac_2hosts"] < 1.0
    assert payload["straggler_index_2hosts"] >= 1.0
    assert payload["skew_attribution_coverage_2hosts"] >= 0.95
    # per-arm skew: exact decomposition, per-host busy attribution
    for hosts, arm in payload["hosts"].items():
        skew = arm["skew"]
        assert skew is not None, f"arm {hosts} missing skew profile"
        assert skew["passes"] >= 1
        assert skew["attribution_coverage"] >= 0.95
        assert (
            skew["busy_frac"]
            + skew["allreduce_wait_frac"]
            + skew["coordinator_bubble_frac"]
        ) == pytest.approx(skew["attribution_coverage"], abs=0.01)
        assert len(skew["hosts_busy_s"]) == int(hosts)
        assert all(v > 0 for v in skew["hosts_busy_s"].values())
    # the chaos arm profiles too (the surviving host absorbs the blocks)
    chaos_skew = payload["chaos"]["skew"]
    assert chaos_skew is not None
    assert chaos_skew["attribution_coverage"] >= 0.95


def test_bench_history_residency_mode(tmp_path, monkeypatch):
    """The streaming bench appends a 'residency' perf-trajectory record —
    the warm-epoch H2D byte ratio — alongside the streaming headline."""
    import bench

    history = tmp_path / "BENCH_HISTORY.jsonl"
    monkeypatch.setattr(bench, "_HISTORY_PATH", str(history))
    monkeypatch.setattr(bench, "_SMOKE", False)
    bench._append_history(
        {
            "metric": "residency_warm_h2d_ratio",
            "value": 0.35,
            "unit": "x_of_warm_h2d_bytes",
        },
        "residency",
    )
    (rec,) = [json.loads(l) for l in history.read_text().splitlines()]
    assert rec["mode"] == "residency"
    assert rec["metric"] == "residency_warm_h2d_ratio"
    assert 0 < rec["value"] < 1
    assert rec["ts"] > 0 and rec["host"]
