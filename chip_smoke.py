#!/usr/bin/env python3
"""Does photon-ml-tpu start, train and serve on the chip?

    python chip_smoke.py

drives the system's main path once on one TPU chip, through the entry points
a user calls, and exits 0 only if every phase ran there and every check held:

  native     the four C++ components, built from the committed source on
             this machine.
  engine     the fixed-effect tile of the 1B-coefficient GLMix deployment
             (2^20 rows x 16 nnz/row over 2^24 columns) routed by
             ``GameData.sparse_features("auto")``: it must come out as the
             fused Pallas engine, and its matvec / rmatvec / rmatvec_sq must
             agree with the ELL engine on the same device.
  train      ``GameEstimator.fit`` (the call ``train_game`` makes) on that
             tile plus per-user and per-item random effects: L-BFGS + L2, two
             outer iterations, held-out AUC, device score plane, adaptive
             random-effect solver — the defaults.
  cli        ``train_game`` -> ``score_game`` -> ``serve_game`` on the tiny
             ratings fixture: argument parsing, start-up, the Avro decoder,
             the model writer, the sharded scorer and its admission step.
  multichip  the same fit on a 2 x 2 device grid, where there are four chips.

Nothing is caught: the first failed check or exception ends the run with a
traceback and no result line. Everything runs in this one process (a chip
belongs to one process); the CLIs are called through their ``main(argv)``.

Without a TPU the script exits non-zero before printing anything else. A
rehearsal of the same code at a tiny size runs on the CPU, and only when the
command line says so:

    python chip_smoke.py --platform cpu --size tiny

The last line of stdout is one JSON object, ``{"ok": true, "device": ...}``.
Timings printed here are a smoke's (one cold run, few calls): they say where
a cold start spends its time, and are not benchmark results.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# ``full`` is a 2^20-row x 16-nonzero tile over 2^24 columns with the chip's
# share of two random effects beside it; ``tiny`` shrinks every shape.
SIZES = {
    "full": dict(
        n_rows=1 << 20, nnz_per_row=16, n_cols=1 << 24,
        n_users=1 << 14, n_items=1 << 12, item_cap=2048,
    ),
    "tiny": dict(
        n_rows=1 << 12, nnz_per_row=16, n_cols=1 << 13,
        n_users=64, n_items=48, item_cap=256,
    ),
}
RE_DIM = 16          # projected dim of both random effects
ZIPF_EXPONENT = 1.1  # item popularity
HELD_OUT_FRACTION = 32
FE_ITERATIONS = 5    # a few solver iterations: convergence is not the point
RE_ITERATIONS = 20   # > the adaptive driver's chunk of 8, so it runs rounds
AGREEMENT_ATOL = 2e-3

EXIT_NO_TPU = 4
EXIT_PARTIAL = 5


class SmokeFailure(AssertionError):
    pass


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# --------------------------------------------------------------------------
# Measurement: wall clocks, and the tracer's spans of JAX's compile phases.
# --------------------------------------------------------------------------


def compile_window(start: float, end: float):
    """(compile seconds, programs compiled) inside [start, end], from the
    compile spans of the program's own tracer (telemetry/compile_spans.py):
    the union of the trace, lowering and backend-compile intervals cut to
    the window, and the backend-compile spans that ended inside it."""
    from photon_ml_tpu.telemetry import get_tracer, union_seconds

    tracer = get_tracer()
    intervals, programs = [], 0
    for s in tracer.spans():
        if s.name not in ("jit/trace", "jit/lower", "jit/backend"):
            continue
        a = tracer.origin_perf + s.start_s
        b = a + s.duration_s
        if s.name == "jit/backend" and start <= b <= end:
            programs += 1
        if min(b, end) > max(a, start):
            intervals.append((max(a, start), min(b, end)))
    return union_seconds(intervals), programs


def timed_calls(fn, *args, steady: int = 3):
    """(result, first-call seconds, median steady-call seconds); every call
    ends in block_until_ready."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    rest = []
    for _ in range(steady):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        rest.append(time.perf_counter() - t0)
    return out, first, float(np.median(rest))


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return f"not reported by {jax.devices()[0].platform}"
    return f"{stats['peak_bytes_in_use']:,}"


# --------------------------------------------------------------------------
# The seeded GLMix problem.
# --------------------------------------------------------------------------


def make_glmix(size: dict, seed: int):
    """(train, held-out) GameData drawn from one true model: a sparse fixed
    effect over ``n_cols`` columns plus per-user and per-item random effects
    of dim RE_DIM; users uniform, items Zipf-popular."""
    import numpy as np

    from photon_ml_tpu.data.game_data import FeatureShard, GameData

    rng = np.random.default_rng(seed)
    n_cols, k = size["n_cols"], size["nnz_per_row"]
    w_true = (rng.standard_normal(n_cols) * 0.1).astype(np.float32)
    u_true = (rng.standard_normal((size["n_users"], RE_DIM)) * 0.3).astype(np.float32)
    v_true = (rng.standard_normal((size["n_items"], RE_DIM)) * 0.3).astype(np.float32)
    popularity = 1.0 / np.arange(1, size["n_items"] + 1) ** ZIPF_EXPONENT
    popularity /= popularity.sum()
    dense_cols = np.arange(RE_DIM, dtype=np.int64)

    def draw(n):
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        cols = rng.integers(0, n_cols, n * k).astype(np.int64)
        vals = rng.standard_normal(n * k).astype(np.float32)
        users = rng.integers(0, size["n_users"], n)
        items = rng.choice(size["n_items"], n, p=popularity)
        x_user = rng.standard_normal((n, RE_DIM)).astype(np.float32)
        x_item = rng.standard_normal((n, RE_DIM)).astype(np.float32)
        z = (
            (vals * w_true[cols]).reshape(n, k).sum(-1)
            + (x_user * u_true[users]).sum(-1)
            + (x_item * v_true[items]).sum(-1)
        )
        labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
        re_rows = np.repeat(np.arange(n, dtype=np.int64), RE_DIM)
        re_cols = np.tile(dense_cols, n)
        return GameData(
            labels=labels,
            feature_shards={
                "global": FeatureShard(rows, cols, vals, n_cols),
                "per_user": FeatureShard(re_rows, re_cols, x_user.ravel(), RE_DIM),
                "per_item": FeatureShard(re_rows, re_cols, x_item.ravel(), RE_DIM),
            },
            id_tags={"userId": users, "itemId": items},
        )

    return draw(size["n_rows"]), draw(size["n_rows"] // HELD_OUT_FRACTION)


def glmix_estimator(size: dict, sparse_engine: str, parallel=None, emitter=None):
    from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_ml_tpu.estimators.game import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_ml_tpu.opt.config import (
        GlmOptimizationConfiguration,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.types import RegularizationType, TaskType

    def lbfgs_l2(iterations):
        return GlmOptimizationConfiguration(
            optimizer_config=OptimizerConfig.lbfgs(max_iterations=iterations),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={
            "fixed": FixedEffectCoordinateConfiguration(
                "global", lbfgs_l2(FE_ITERATIONS), sparse_engine=sparse_engine
            ),
            "per_user": RandomEffectCoordinateConfiguration(
                "per_user",
                RandomEffectDataConfiguration("userId"),
                lbfgs_l2(RE_ITERATIONS),
            ),
            # a Zipf head needs what a user gives skewed entities: an active
            # cap, and buckets so the tail is not padded to the head
            "per_item": RandomEffectCoordinateConfiguration(
                "per_item",
                RandomEffectDataConfiguration(
                    "itemId",
                    active_data_upper_bound=size["item_cap"],
                    num_buckets=4,
                ),
                lbfgs_l2(RE_ITERATIONS),
            ),
        },
        num_outer_iterations=2,
        parallel=parallel,
        emitter=emitter,
    )


class Run:
    """What the phases share: sizes, the rehearsal switch, lazily built data."""

    def __init__(self, size_name: str, rehearsal: bool, seed: int):
        self.size = SIZES[size_name]
        self.rehearsal = rehearsal
        self.seed = seed
        # off the TPU "auto" is ELL by design, so a rehearsal asks for the
        # fused engine by name (and runs its kernels in the interpreter)
        self.sparse_engine = "fused" if rehearsal else "auto"
        self._data = None
        self.data_build_s = 0.0

    def data(self):
        if self._data is None:
            t0 = time.perf_counter()
            self._data = make_glmix(self.size, self.seed)
            self.data_build_s = time.perf_counter() - t0
        return self._data


# --------------------------------------------------------------------------
# Phases.
# --------------------------------------------------------------------------


def phase_native(run: Run) -> None:
    """Each native component loads from a library built here, now."""
    import numpy as np

    from photon_ml_tpu.indexmap import offheap
    from photon_ml_tpu.io import native_reader
    from photon_ml_tpu.ops import routing
    from photon_ml_tpu.utils import nativesort

    native_dir = os.path.join(REPO, "photon_ml_tpu", "native")
    # git ignores the built libraries, but a copy of a working tree can carry
    # ones built elsewhere
    for stale in glob.glob(os.path.join(native_dir, "_*.so")):
        os.unlink(stale)
    loaders = {
        "eulercolor": routing._load_native,
        "sortperm": nativesort._load_native,
        "avrodecode": native_reader._load_native,
        "indexstore": offheap._load_native,
    }
    for name, load in loaders.items():
        t0 = time.perf_counter()
        check(load() is not None, f"native component {name} did not load")
        built = glob.glob(os.path.join(native_dir, f"_{name}.*.so"))
        check(len(built) == 1, f"{name}: expected one library built here, found {built}")
        say(
            f"  {name}: built {os.path.basename(built[0])} "
            f"in {time.perf_counter() - t0:.1f}s"
        )
    # and they answer
    color = routing.euler_color(
        np.repeat(np.arange(4), 128), np.tile(np.arange(4), 128), 128, 4, 4
    )
    check(color.min() == 0 and color.max() == 127, "euler_color misbehaves")


def barrier_waits_for_compute_stream(run: Run, device=None) -> None:
    """The tracer's device barrier (telemetry/span.py), behind every
    ``device_sync`` span: dispatched after a device program of about half a
    second (on ``device``; the default one if None), it must not return
    before that program has retired."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.telemetry.span import _device_barrier

    side = 256 if run.rehearsal else 4096

    @jax.jit
    def long_program(x, n):
        return jax.lax.fori_loop(
            0, n, lambda i, a: (a @ a) * jnp.bfloat16(1.0 / side), x
        )

    x = jax.device_put(jnp.ones((side, side), jnp.bfloat16), device)
    jax.block_until_ready(long_program(x, 8))
    t0 = time.perf_counter()
    jax.block_until_ready(long_program(x, 64))
    per_matmul = (time.perf_counter() - t0) / 64
    n = max(8, int(0.5 / per_matmul))
    _device_barrier()
    t0 = time.perf_counter()
    busy = long_program(x, n)
    _device_barrier()
    t1 = time.perf_counter()
    retired = busy.is_ready()
    jax.block_until_ready(busy)
    t2 = time.perf_counter()
    say(
        f"  span barrier: returned {(t1 - t0) * 1e3:.1f}ms after a program on "
        f"{'the default device' if device is None else device} that retired after "
        f"{(t2 - t0) * 1e3:.1f}ms was dispatched"
    )
    check(retired, "the span barrier returned before the program ahead of it retired")


def phase_engine(run: Run) -> None:
    """Route the fixed-effect tile; fused engine vs ELL on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.ops import permute_net
    from photon_ml_tpu.ops.fused_perm import FusedBenesFeatures, parse_plan
    from photon_ml_tpu.ops.sparse_perm import ColumnSplitFeatures

    train, _ = run.data()
    say(f"  data build {run.data_build_s:.1f}s (host, seeded)")

    t0 = time.perf_counter()
    feats = train.sparse_features("global", engine=run.sparse_engine)
    jax.block_until_ready(feats)
    say(
        f"  routing prep {time.perf_counter() - t0:.1f}s "
        "(host Euler coloring + plan upload; not compile time)"
    )

    blocks = feats.blocks if isinstance(feats, ColumnSplitFeatures) else (feats,)
    routed = [b for b in blocks if isinstance(b, FusedBenesFeatures)]
    check(
        routed and len(routed) == len(blocks),
        f"expected the fused engine, got {type(feats).__name__} of "
        f"{sorted({type(b).__name__ for b in blocks})}",
    )
    check(
        all(b._fused_ok() for b in routed),
        "a fused block would run unfused_execute",
    )
    first = routed[0]
    say(
        f"  engine: {type(feats).__name__}, {len(routed)} fused block(s) of "
        f"{first.size:,} slots, K={first.ell_k} KP={first.csc_k}, "
        f"{len(parse_plan(first.plan).descents)} recursion level(s), spill "
        f"side {'yes' if first.spill_rows is not None else 'no'}"
    )
    # stage-by-stage executor (the benes engine): any plan here
    # short enough to drop to the XLA gather?
    xla_plans = sum(
        1 for b in routed if not permute_net._use_pallas(b.plan.size // 128)
    )
    if xla_plans and not run.rehearsal:
        say(f"  NOTE: {xla_plans} plan(s) would run permute_net's XLA gather")

    barrier_waits_for_compute_stream(run)

    ell = train.ell_features("global")
    rng = np.random.default_rng(run.seed + 1)
    w = jnp.asarray(rng.standard_normal(feats.dim).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(feats.num_rows).astype(np.float32))
    say("  map          fused first / steady     ell first / steady     max|diff|")
    for op, vec in (("matvec", w), ("rmatvec", c), ("rmatvec_sq", c)):
        # the features go in as an argument: closed over, their gigabyte of
        # plan and values would be baked into the program as constants
        apply = jax.jit(lambda f, v, op=op: getattr(f, op)(v))
        got, f_first, f_steady = timed_calls(apply, feats, vec)
        with jax.default_matmul_precision("highest"):
            want, e_first, e_steady = timed_calls(apply, ell, vec)
        check(got.shape == want.shape, f"{op}: shape {got.shape} vs {want.shape}")
        diff = float(jnp.max(jnp.abs(got - want)))
        say(
            f"  {op:11s} {f_first:7.2f}s / {f_steady * 1e3:8.1f}ms   "
            f"{e_first:7.2f}s / {e_steady * 1e3:8.1f}ms   {diff:.2e}"
        )
        check(
            np.isfinite(diff) and diff <= AGREEMENT_ATOL,
            f"{op}: fused and ELL engines differ by {diff:.3e} > {AGREEMENT_ATOL}",
        )


def _span_windows(name: str, since: float, **attrs):
    """(start, end) in perf_counter time of every finished span ``name``
    that began at or after ``since`` and whose attributes include ``attrs``."""
    from photon_ml_tpu.telemetry import get_tracer

    tracer = get_tracer()
    out = []
    for s in tracer.spans():
        start = tracer.origin_perf + s.start_s
        if (s.name == name and start >= since
                and all(s.attrs.get(k) == v for k, v in attrs.items())):
            out.append((start, start + s.duration_s))
    return out


class _SolverStatsListener:
    def __init__(self):
        self.events = []

    def on_event(self, event) -> None:
        from photon_ml_tpu.event import SolverStatsEvent

        if isinstance(event, SolverStatsEvent):
            self.events.append(event)

    def close(self) -> None:
        pass


def _fit_and_report(run: Run, estimator, fit_fn):
    """Run ``fit_fn`` (the tracer is on for the whole smoke); print where its
    time went; check the loss fell and the held-out AUC means something."""
    import numpy as np

    train, _ = run.data()
    t0 = time.perf_counter()
    fit = fit_fn()
    t1 = time.perf_counter()

    for cid in estimator.coordinate_configs:
        for a, b in _span_windows("game/build_coordinate", t0, coordinate=cid):
            say(f"  build coordinate {cid}: {b - a:.1f}s (host grouping/routing + upload)")
    for outer in range(estimator.num_outer_iterations):
        for a, b in _span_windows("cd/outer_iter", t0, outer=outer):
            compile_s, programs = compile_window(a, b)
            label = "first call" if outer == 0 else "steady"
            say(
                f"  outer iteration {outer} ({label}): {b - a:.1f}s, of which "
                f"compile {compile_s:.1f}s in {programs} program(s)"
            )
            for cid in estimator.update_order:
                for ca, cb in _span_windows("cd/coordinate", t0, coordinate=cid, outer=outer):
                    say(f"    {cid}: {cb - ca:.2f}s")
    say(f"  fit wall {t1 - t0:.1f}s")

    history = [v for _, v in fit.objective_history]
    at_zero = float(train.num_rows * np.log(2.0))
    say(
        f"  training objective: {at_zero:.1f} at w=0 -> {history[0]:.1f} -> "
        f"{history[-1]:.1f} over {len(history)} coordinate updates"
    )
    check(all(np.isfinite(history)), f"non-finite objective in {history}")
    check(
        history[0] < at_zero and history[-1] < history[0],
        f"training loss did not fall: {at_zero} -> {history}",
    )
    auc = fit.validation_metric
    say(f"  held-out AUC {auc:.4f} ({[round(v, 4) for _, v in fit.validation_history]})")
    check(auc is not None and np.isfinite(auc) and auc > 0.5, f"held-out AUC {auc}")
    return fit


def phase_train(run: Run) -> None:
    """GameEstimator.fit at full width, one device, all defaults."""
    from photon_ml_tpu.estimators.random_effect import solver_trace_counts
    from photon_ml_tpu.event import EventEmitter
    from photon_ml_tpu.telemetry import jit_trace_counts

    train, held_out = run.data()
    listener = _SolverStatsListener()
    emitter = EventEmitter()
    emitter.register_listener(listener)
    estimator = glmix_estimator(run.size, run.sparse_engine, emitter=emitter)
    _fit_and_report(
        run, estimator, lambda: estimator.fit(train, validation_data=held_out)
    )
    check(emitter.listener_errors == 0, "an event listener raised")

    # the programs that donate their carry ran, and nothing read a donated
    # buffer (that raises): the score plane's in-place update ...
    transfers = estimator.last_transfer_stats
    check(
        transfers.score_plane == "device"
        and transfers.device_plane_updates == transfers.coordinate_updates == 6
        and transfers.row_transfers_d2h == transfers.row_transfers_h2d == 0,
        f"score plane did not stay on the device: {transfers}",
    )
    check(
        jit_trace_counts().get("cd_plane/apply", 0) >= 1,
        f"cd_plane apply never traced: {jit_trace_counts()}",
    )
    # ... and the adaptive random-effect solver's chunk step
    rounds = [e.rounds for e in listener.events]
    chunk_traces = sum(
        n for (prog, _), n in solver_trace_counts().items() if prog == "re_chunk"
    )
    say(
        f"  adaptive RE solver: {len(rounds)} bucket solves, rounds {sorted(set(rounds))}, "
        f"chunk program traced {chunk_traces}x; score plane: "
        f"{transfers.device_plane_updates} in-place updates, 0 row transfers"
    )
    check(
        chunk_traces >= 1 and max(rounds, default=0) >= 2,
        f"adaptive chunk step did not run in rounds: {rounds}",
    )


def phase_cli(run: Run) -> None:
    """train_game -> score_game -> serve_game on the ratings fixture."""
    import numpy as np

    from photon_ml_tpu.cli import score_game, serve_game, train_game
    from photon_ml_tpu.io.avro import read_avro_file
    from photon_ml_tpu.io.data_reader import write_training_examples
    from photon_ml_tpu.io.scores_io import load_scores
    from photon_ml_tpu.serving import (
        AdmissionController,
        ShardedGameScorer,
        load_artifact,
        replay_requests,
        requests_from_game_data,
    )
    from photon_ml_tpu.serving.replay import max_nnz_of, read_request_data

    ratings = os.path.join(REPO, "tests", "fixtures", "ratings")
    work = tempfile.mkdtemp(prefix="chip_smoke_cli_")
    try:
        re_opt = {"regularization": "L2", "regularization_weight": 1.0}
        config = {
            "feature_shards": {
                "global": {"feature_bags": ["features"], "add_intercept": True},
                "per_user": {"feature_bags": ["userFeatures"], "add_intercept": False},
                "per_movie": {"feature_bags": ["movieFeatures"], "add_intercept": False},
            },
            "coordinates": {
                "fixed": {
                    "type": "fixed", "feature_shard": "global",
                    "optimizer": {"optimizer": "TRON", **re_opt,
                                  "regularization_weight": 10.0},
                },
                "per_user": {
                    "type": "random", "feature_shard": "per_user",
                    "random_effect_type": "userId", "optimizer": re_opt,
                },
                "per_movie": {
                    "type": "random", "feature_shard": "per_movie",
                    "random_effect_type": "movieId", "optimizer": re_opt,
                },
            },
            "update_order": ["fixed", "per_user", "per_movie"],
        }
        config_path = os.path.join(work, "game.json")
        with open(config_path, "w") as f:
            json.dump(config, f)

        t0 = time.perf_counter()
        rc = train_game.main([
            "--train-data-dirs", os.path.join(ratings, "train"),
            "--validation-data-dirs", os.path.join(ratings, "test"),
            "--coordinate-config", config_path,
            "--task", "LINEAR_REGRESSION",
            "--output-dir", os.path.join(work, "model"),
            "--evaluator", "RMSE",
            "--num-outer-iterations", "2",
        ])
        check(rc == 0, f"train_game exited {rc}")
        say(f"  train_game {time.perf_counter() - t0:.1f}s")
        model_dir = os.path.join(work, "model", "best")

        # requests: held-out rows, a few of them for entities never trained on
        records = []
        test_file = os.path.join(ratings, "test", "part-00000.avro")
        for i, rec in enumerate(read_avro_file(test_file)):
            if i >= 48:
                break
            for bag in ("features", "userFeatures", "movieFeatures"):
                rec[bag] = [(x["name"], x["term"], x["value"]) for x in rec[bag]]
            tags = dict(rec["metadataMap"])
            if i < 6:
                tags["userId"] = f"unseen-user-{i}"
            if 3 <= i < 9:
                tags["movieId"] = f"unseen-movie-{i}"
            rec["metadataMap"] = tags
            records.append(rec)
        request_dir = os.path.join(work, "requests")
        os.makedirs(request_dir)
        write_training_examples(os.path.join(request_dir, "part-00000.avro"), records)

        t0 = time.perf_counter()
        rc = score_game.main([
            "--data-dirs", request_dir, "--model-dir", model_dir,
            "--output-dir", os.path.join(work, "scores"), "--evaluator", "RMSE",
        ])
        check(rc == 0, f"score_game exited {rc}")
        say(f"  score_game {time.perf_counter() - t0:.1f}s")
        offline = {s.uid: s for s in load_scores(os.path.join(work, "scores"))}
        check(len(offline) == len(records), f"{len(offline)} scores for {len(records)} rows")
        seen = [
            s for s in offline.values()
            if not any(str(v).startswith("unseen") for v in s.id_tags.values())
        ]
        rmse = float(np.sqrt(np.mean(
            [(s.prediction_score - s.label) ** 2 for s in seen]
        )))
        say(f"  held-out RMSE on trained entities {rmse:.4f} (CPU golden gate: < 0.45)")
        check(np.isfinite(rmse) and rmse < 0.45, f"ratings RMSE {rmse}")

        # the serving CLI: export, continuous batching, background admission
        artifact_dir = os.path.join(work, "artifact")
        metrics_path = os.path.join(work, "serve.json")
        t0 = time.perf_counter()
        rc = serve_game.main([
            "--model-dir", model_dir, "--data-dirs", request_dir,
            "--export-artifact-dir", artifact_dir,
            "--device-budget-rows", "48", "--admit-batch", "8",
            "--bucket-sizes", "1,4,16", "--metrics-output", metrics_path,
        ])
        check(rc == 0, f"serve_game exited {rc}")
        with open(metrics_path) as f:
            snapshot = json.load(f)
        say(
            f"  serve_game {time.perf_counter() - t0:.1f}s: "
            f"{snapshot['num_requests']} requests, mode {snapshot['serving_mode']}, "
            f"admission {snapshot.get('admission')}"
        )
        check(snapshot["num_requests"] == len(records), "serve_game dropped requests")
        admission_stats = snapshot["admission"]
        check(
            admission_stats["admit_failures"] == 0
            and not admission_stats["thread_dead"]
            and admission_stats["thread_crashes"] == 0,
            f"admission failed inside serve_game: {admission_stats}",
        )

        # the scorer itself, from the artifact the CLI exported: its answers
        # against score_game's for the same rows
        artifact = load_artifact(artifact_dir)
        data, uids = read_request_data(artifact, [request_dir])
        requests = requests_from_game_data(data, artifact, uids=uids)
        nnz = max_nnz_of(requests)

        def agree(results, what):
            worst = max(
                abs(r.score - offline[r.request_id].prediction_score) for r in results
            )
            say(f"  {what}: {len(results)} answers, max|serve - score_game| {worst:.2e}")
            check(worst <= 1e-4, f"{what}: serving differs from score_game by {worst}")

        full = ShardedGameScorer(artifact, max_nnz=nnz)
        results, _ = replay_requests(full, requests, bucket_sizes=(1, 4, 16))
        check(
            any(r.cold_coordinates for r in results[:9]),
            "requests for unseen entities were not served fixed-effect-only",
        )
        agree(results, "full residency")

        # admission: a 48-row budget keeps 36 of the 60 movies resident and
        # leaves 12 slots of headroom; requests for up to 8 movies outside
        # the 36 start cold, are admitted by the donated scatter, and then
        # answer in full
        tight = ShardedGameScorer(artifact, max_nnz=nnz, device_budget_rows=48)
        admission = AdmissionController([tight], admit_batch=8)
        tight.attach_admission(admission)
        admission.warmup()
        movie_routing = tight.routing["per_movie"]
        cold_movies = sorted({
            r.entity_ids["movieId"] for r in requests
            if (row := artifact.entity_row("per_movie", r.entity_ids["movieId"])) >= 0
            and not movie_routing.is_resident(row)
        })[:8]
        cold_requests = [
            r for r in requests if r.entity_ids["movieId"] in cold_movies
        ]
        before, _ = replay_requests(tight, cold_requests, bucket_sizes=(1, 4, 16))
        admitted = admission.drain()
        after, _ = replay_requests(tight, cold_requests, bucket_sizes=(1, 4, 16))
        stats = admission.stats()
        cold_before = sum(1 for r in before if "per_movie" in r.cold_coordinates)
        cold_after = sum(1 for r in after if "per_movie" in r.cold_coordinates)
        say(
            f"  admission: {cold_before} of {len(before)} answers without their "
            f"movie row before, {admitted} rows admitted in {stats['steps']} "
            f"step(s), {cold_after} without it after"
        )
        check(cold_before > 0, "no request started cold: admission had nothing to do")
        check(
            admitted > 0 and stats["admit_failures"] == 0
            and stats["dropped_total"] == 0,
            f"admission step failed: {stats}",
        )
        check(cold_after == 0, "admitted rows are still served cold")
        agree(after, "after admission")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def phase_multichip(run: Run) -> None:
    """The same fit over a 2 x 2 (data x feat) grid of chips."""
    import jax

    from photon_ml_tpu.estimators.game import ParallelConfiguration

    if jax.device_count() < 4:
        say(f"  multichip: not run ({jax.device_count()} device)")
        return
    train, held_out = run.data()
    # the tiles take their engine from the grid; the coordinate's own engine
    # is left to the held-out scoring: "auto", which is ELL for rows this few
    # (a routed engine there is dispatched eagerly and compiles at every call)
    estimator = glmix_estimator(
        run.size, "auto",
        parallel=ParallelConfiguration(n_data=2, n_feat=2, engine="fused"),
    )
    # under the scope a fit opens, a device_sync span waits for the grid's
    # last device as for the first
    with estimator._grid_barrier():
        barrier_waits_for_compute_stream(run, estimator._mesh.devices[-1, -1])
    # GameEstimator.fit is these two calls; made by hand to keep hold of the
    # coordinates, whose placement is what this phase is about
    built = {}

    def fit():
        for cid, cfg in estimator.coordinate_configs.items():
            built[cid] = estimator._build_coordinate(cid, cfg, train)
        return estimator._run_fit(built, train, held_out, None, None)

    first = _fit_and_report(run, estimator, fit)

    # a second fit on the same coordinates traces, lowers and compiles
    # nothing: what PR 21's 115 programs an iteration would have failed
    t0 = time.perf_counter()
    again = estimator._run_fit(built, train, held_out, None, None)
    t1 = time.perf_counter()
    compile_s, programs = compile_window(t0, t1)
    say(f"  second fit {t1 - t0:.1f}s, compile {compile_s:.2f}s in {programs} program(s)")
    check(
        compile_s == 0.0 and programs == 0,
        f"the second grid fit compiled: {compile_s:.2f}s in {programs} program(s)",
    )
    check(
        [v for _, v in again.objective_history] == [v for _, v in first.objective_history],
        "the second grid fit read other objectives than the first",
    )

    grid = set(estimator._mesh.devices.ravel().tolist())
    check(len(grid) == 4, f"mesh has {len(grid)} devices")
    per_device = {d.id: 0 for d in grid}

    def on_grid(what, tree):
        leaves = [a for a in jax.tree.leaves(tree) if isinstance(a, jax.Array)]
        check(leaves, f"{what}: no device arrays")
        for a in leaves:
            devices = {s.device for s in a.addressable_shards}
            check(
                devices == grid,
                f"{what}: a {a.shape} array sits on {sorted(d.id for d in devices)}, "
                "not on all four devices",
            )
            for s in a.addressable_shards:
                per_device[s.device.id] += s.data.nbytes
        say(f"  {what}: {len(leaves)} arrays, each on devices {sorted(d.id for d in grid)}")

    fixed = built["fixed"].data
    on_grid("routed tiles", fixed.features.shards)
    on_grid("batch arrays", (fixed.labels, fixed.offsets, fixed.weights))
    for cid in ("per_user", "per_item"):
        on_grid(f"{cid} buckets", built[cid].dataset.buckets)
    say(f"  bytes placed per device: {per_device}")
    check(
        max(per_device.values()) < 2 * min(per_device.values()),
        f"placement is lopsided: {per_device}",
    )


PHASE_FUNCTIONS = {
    "native": phase_native,
    "engine": phase_engine,
    "train": phase_train,
    "cli": phase_cli,
    "multichip": phase_multichip,
}
PHASES = tuple(PHASE_FUNCTIONS)


# --------------------------------------------------------------------------
# Entry.
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--platform", choices=("tpu", "cpu"), default="tpu",
        help="cpu = a rehearsal of the code path, said so in the output; "
             "the only way this script accepts a CPU",
    )
    ap.add_argument(
        "--size", choices=sorted(SIZES), default="full",
        help="tiny shrinks every shape (for the rehearsal)",
    )
    ap.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset; a subset never exits 0",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; choose from {list(PHASES)}")
    phases = [p for p in PHASES if p in phases]

    rehearsal = args.platform == "cpu"
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # four or more virtual devices, so the multichip phase rehearses too
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    device = jax.devices()[0]
    if device.platform != args.platform:
        sys.stderr.write(
            f"chip_smoke: JAX found platform {device.platform!r} "
            f"({device.device_kind}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}), not a TPU. This script "
            "checks the program on the chip and has no other mode; a CPU "
            "rehearsal must be asked for: --platform cpu --size tiny\n"
        )
        return EXIT_NO_TPU

    import jaxlib

    import photon_ml_tpu  # noqa: F401 - fail here, before any output, if absent
    from photon_ml_tpu.utils.cachedir import enable_compilation_cache

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu_version = version("libtpu")
    except PackageNotFoundError:
        libtpu_version = "not installed"
    device_doc = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    say(
        f"platform={device.platform} device_kind={device.device_kind!r} "
        f"devices={len(jax.devices())} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}"
    )
    if rehearsal:
        from photon_ml_tpu.ops import fused_perm

        fused_perm._INTERPRET = True
        say(
            "REHEARSAL on the CPU: same code path, nothing skipped but the "
            f"device check; size {args.size!r}; the fused engine is asked for "
            "by name and its kernels run in the Pallas interpreter. No number "
            "below is a device number."
        )

    cache_dir = enable_compilation_cache()
    if cache_dir is None:
        say("compile cache: off on the CPU backend")
    else:
        entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        placed = "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "default, in the checkout"
        say(f"compile cache: {cache_dir} ({placed}); {entries} entries at start")

    # the program's own tracer, on for the whole smoke: its compile spans
    # are the compile seconds and program counts printed below
    from photon_ml_tpu.telemetry import enable_tracing, get_registry

    enable_tracing(device_sync=True)
    run = Run(args.size, rehearsal, args.seed)
    walls = {}
    t_start = time.perf_counter()
    for name in phases:
        say(f"phase {name}:")
        t0 = time.perf_counter()
        PHASE_FUNCTIONS[name](run)
        t1 = time.perf_counter()
        compile_s, programs = compile_window(t0, t1)
        walls[name] = round(t1 - t0, 1)
        say(
            f"phase {name}: ok in {t1 - t0:.1f}s (compile {compile_s:.1f}s in "
            f"{programs} program(s)); peak_bytes_in_use {peak_bytes()}"
        )
    # only programs that take over 0.5 s to compile go through the cache
    hits = int(get_registry().counter_value("jit.cache.hits"))
    misses = int(get_registry().counter_value("jit.cache.misses"))
    cache_line = "off" if cache_dir is None else (
        f"was {'warm' if hits > misses else 'cold'}: "
        f"{hits} hit(s) / {misses} miss(es)"
    )
    say(
        f"total {time.perf_counter() - t_start:.1f}s; phase walls {walls}; "
        f"compile cache {cache_line}"
    )

    if len(phases) < len(PHASES):
        say(
            f"PARTIAL RUN: phases {phases} passed, "
            f"{[p for p in PHASES if p not in phases]} not run; exit {EXIT_PARTIAL}"
        )
        print(json.dumps({"ok": False, "partial": True, "device": device_doc}))
        return EXIT_PARTIAL
    result = {"ok": True, "device": device_doc}
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
