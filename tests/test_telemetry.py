"""Telemetry subsystem tests: span tracer, metrics registry, sinks and
validators (unit, fast lane), plus the driver-level smoke gate — tiny CPU
train/score/serve/update runs with --telemetry-out/--trace-out whose ledger
and Chrome trace are schema-validated (slow lane; CI runs this file whole
as the telemetry smoke gate)."""

import json
import threading
import time

import numpy as np
import pytest

from photon_ml_tpu.telemetry import (
    MetricsRegistry,
    RunLedger,
    TelemetryEventListener,
    chrome_trace_events,
    format_summary_table,
    get_registry,
    get_tracer,
    jit_trace_counts,
    span_tree_summary,
    validate_chrome_trace,
    validate_ledger,
    write_chrome_trace,
)
from photon_ml_tpu.telemetry.span import (
    NOOP_SPAN,
    disable_tracing,
    enable_tracing,
    span,
    timed_span,
    union_seconds,
)
from tests._tiny_glmix import _tiny_glmix, _tiny_glmix_estimator


@pytest.fixture()
def tracer():
    """Enabled global tracer, wall-clock only; always disabled afterwards."""
    t = enable_tracing(device_sync=False, clear=True)
    get_registry().reset()
    yield t
    disable_tracing()


class TestSpans:
    def test_disabled_returns_noop_singleton(self):
        disable_tracing()
        s = span("anything", key=1)
        assert s is NOOP_SPAN
        with s:
            pass  # no-op context manager works and records nothing
        assert s.set_attrs(more=2) is s

    def test_nesting_parent_path_depth(self, tracer):
        with span("outer", a=1):
            with span("inner"):
                pass
        recs = {r.name: r for r in tracer.spans()}
        assert recs["inner"].parent_id == recs["outer"].span_id
        assert recs["inner"].path == "outer/inner"
        assert recs["inner"].depth == 2
        assert recs["outer"].parent_id is None
        assert recs["outer"].depth == 1
        assert recs["outer"].attrs == {"a": 1}
        assert recs["outer"].duration_s >= recs["inner"].duration_s >= 0

    def test_exception_tagged_not_swallowed(self, tracer):
        with pytest.raises(KeyError):
            with span("boom"):
                raise KeyError("x")
        (rec,) = tracer.spans()
        assert rec.failed and rec.error == "KeyError"

    def test_threads_nest_independently(self, tracer):
        def worker(i):
            with span(f"w{i}"):
                with span("child"):
                    pass

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        with span("main_parent"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        children = [r for r in tracer.spans() if r.name == "child"]
        assert len(children) == 4
        # thread spans chain to their own thread's root, never to the main
        # thread's open span (contextvars do not leak across threads)
        by_id = {r.span_id: r for r in tracer.spans()}
        for c in children:
            assert by_id[c.parent_id].name.startswith("w")

    def test_set_attrs_during_block(self, tracer):
        with span("s") as s:
            s.set_attrs(rows=10)
        (rec,) = tracer.spans()
        assert rec.attrs == {"rows": 10}

    def test_timed_span_measures_when_disabled(self):
        disable_tracing()
        sp = timed_span("phase")
        with sp:
            pass
        assert sp.duration_s >= 0.0 and not sp.failed
        assert len(get_tracer().spans()) == 0 or all(
            r.name != "phase" for r in get_tracer().spans()
        )


class TestTimerShims:
    def test_timer_accumulates_and_counts_failures(self):
        from photon_ml_tpu.utils.timer import Timer

        disable_tracing()
        timer = Timer()
        with timer.time("ok"):
            pass
        with timer.time("ok"):
            pass
        with pytest.raises(ValueError):
            with timer.time("bad"):
                raise ValueError("x")
        assert timer.durations["ok"] >= 0.0
        assert "bad" in timer.durations  # failed phases still accumulate
        assert timer.failures == {"bad": 1}
        assert timer.failed("bad") and not timer.failed("ok")

    def test_timer_thread_safe(self):
        from photon_ml_tpu.utils.timer import Timer

        timer = Timer()

        def work():
            for _ in range(50):
                with timer.time("p"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert timer.durations["p"] >= 0.0 and not timer.failures

    def test_timed_lands_as_span_when_tracing(self, tracer):
        from photon_ml_tpu.utils.timer import Timed

        with Timed("load model"):
            pass
        assert [r.name for r in tracer.spans()] == ["load model"]


class TestMetricsRegistry:
    def test_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        reg.count("c")
        reg.count("c", 4)
        reg.gauge("g", 2.0)
        reg.gauge("g", 1.0)  # peak stays at 2
        for v in range(100):
            reg.observe("h", float(v))
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 5
        assert snap["gauges"]["g"] == {"last": 1.0, "peak": 2.0}
        h = snap["histograms"]["h"]
        assert h["count"] == 100 and h["max"] == 99.0
        assert 40 <= h["p50"] <= 60
        json.dumps(snap)  # snapshot must be plain JSON
        reg.reset()
        assert reg.snapshot() == {
            "counters": {}, "gauges": {}, "histograms": {}
        }

    def test_absorbers_duck_typed(self):
        class Stats:
            num_entities = 7
            rounds = 2
            executed_lane_iterations = 30
            lockstep_lane_iterations = 90
            chunk_retraces = 1
            iterations_p99 = 12.0
            converged = False

        class Transfers:
            row_transfers_h2d = 3
            row_transfers_d2h = 1
            row_bytes_h2d = 300
            row_bytes_d2h = 100
            host_score_sums = 0
            device_plane_updates = 6
            coordinate_updates = 6
            outer_iterations = 2

        reg = MetricsRegistry()
        reg.record_solver_stats(Stats(), coordinate="per_user")
        reg.record_transfer_stats(Transfers())
        reg.record_serving_snapshot({"latency_p99_ms": 4.5, "caches": {}})
        snap = reg.snapshot()
        assert snap["counters"]["solver.per_user.entities"] == 7
        assert snap["counters"]["solver.per_user.unconverged_buckets"] == 1
        assert snap["counters"]["transfer.row_bytes_h2d"] == 300
        assert snap["gauges"]["serving.latency_p99_ms"]["last"] == 4.5
        assert "serving.caches" not in snap["gauges"]  # non-numeric skipped

    def test_note_jit_trace_counts_retraces_only(self):
        import jax

        reg = get_registry()
        reg.reset()
        from photon_ml_tpu.telemetry import note_jit_trace

        @jax.jit
        def f(x):
            note_jit_trace("test_prog", "unit")
            return x + 1

        f(np.float32(1.0))
        f(np.float32(2.0))  # cache hit: no retrace, no count
        assert jit_trace_counts()["test_prog/unit"] == 1
        f(np.ones((2,), np.float32))  # new shape → retrace
        assert jit_trace_counts()["test_prog/unit"] == 2
        assert reg.counter_value("jit.traces") == 2


class TestSinksAndValidators:
    def test_ledger_round_trip(self, tmp_path, tracer):
        with span("a"):
            with span("b"):
                pass
        path = tmp_path / "sub" / "ledger.jsonl"  # parent dir auto-created
        ledger = RunLedger(str(path))
        ledger.write("meta", phase="start", label="t")
        for rec in tracer.spans():
            ledger.write_span(rec, tracer.origin_unix)
        ledger.write("metrics", snapshot=get_registry().snapshot())
        ledger.write("meta", phase="finish", label="t")
        ledger.close()
        records = validate_ledger(str(path))
        assert [r["type"] for r in records] == [
            "meta", "span", "span", "metrics", "meta"
        ]
        spans = [r for r in records if r["type"] == "span"]
        assert {s["path"] for s in spans} == {"a", "a/b"}
        assert all(not s["failed"] for s in spans)

    def test_ledger_validator_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"type": "span", "ts": 1.0}\n')  # missing span fields
        with pytest.raises(ValueError, match="span"):
            validate_ledger(str(p))
        p.write_text("not json\n")
        with pytest.raises(ValueError, match="invalid JSON"):
            validate_ledger(str(p))
        p.write_text("")
        with pytest.raises(ValueError, match="empty"):
            validate_ledger(str(p))

    def test_chrome_trace_round_trip(self, tmp_path, tracer):
        with span("cd/run", plane="device"):
            with pytest.raises(RuntimeError):
                with span("cd/outer_iter"):
                    raise RuntimeError("x")
        out = tmp_path / "trace.json"
        n = write_chrome_trace(str(out), tracer.spans(), metadata={"k": 1})
        assert n == 2
        doc = validate_chrome_trace(str(out))
        events = {e["name"]: e for e in doc["traceEvents"]}
        assert events["cd/run"]["cat"] == "cd"
        assert events["cd/outer_iter"]["args"]["error"] == "RuntimeError"
        assert all(e["ph"] == "X" for e in doc["traceEvents"])

    def test_chrome_trace_validator_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"traceEvents": [{"name": "x"}]}))
        with pytest.raises(ValueError):
            validate_chrome_trace(str(p))
        p.write_text(json.dumps({"nope": []}))
        with pytest.raises(ValueError):
            validate_chrome_trace(str(p))

    def test_span_tree_summary_depth_filter(self, tracer):
        with span("cd/run"):  # slash in the NAME is not extra depth
            with span("cd/outer_iter"):
                with span("cd/coordinate"):
                    pass
        full = span_tree_summary(tracer.spans())
        assert set(full) == {
            "cd/run", "cd/run/cd/outer_iter",
            "cd/run/cd/outer_iter/cd/coordinate",
        }
        top2 = span_tree_summary(tracer.spans(), max_depth=2)
        assert set(top2) == {"cd/run", "cd/run/cd/outer_iter"}
        assert top2["cd/run"]["count"] == 1

    def test_format_summary_table(self, tracer):
        with span("fit"):
            pass
        get_registry().count("jit.traces.prog", 3)
        table = format_summary_table(
            tracer.spans(), get_registry().snapshot(), "unit"
        )
        assert "fit" in table and "prog" in table


class TestEventBridge:
    def test_events_land_in_ledger_and_registry(self, tmp_path):
        from photon_ml_tpu.event import (
            EventEmitter,
            ModelSwapEvent,
            ScoringFinishEvent,
            SolverStatsEvent,
            TrainingStartEvent,
        )

        reg = MetricsRegistry()
        ledger = RunLedger(str(tmp_path / "ledger.jsonl"))
        emitter = EventEmitter()
        emitter.register_listener(
            TelemetryEventListener(ledger=ledger, registry=reg)
        )
        emitter.send_event(TrainingStartEvent(task="LOGISTIC_REGRESSION"))
        emitter.send_event(SolverStatsEvent(
            coordinate_id="per_user", bucket=0, optimizer="lbfgs",
            num_entities=4, rounds=1, dispatch_widths=(4,),
            iterations_p50=3.0, iterations_p99=5.0,
            executed_lane_iterations=12, lockstep_lane_iterations=20,
            wasted_lane_fraction=0.4,
        ))
        emitter.send_event(ScoringFinishEvent(
            model_id="m", num_requests=10, wall_seconds=0.5,
            metrics={"latency_p99_ms": 3.0},
        ))
        emitter.send_event(ModelSwapEvent(
            model_id="m", generation=1, fingerprint=None,
            coordinates=("per_user",), rows_updated=5, blackout_s=0.01,
        ))
        emitter.clear_listeners()
        assert emitter.listener_errors == 0
        records = validate_ledger(str(ledger.path))
        events = [r["event"] for r in records if r["type"] == "event"]
        assert events == [
            "TrainingStartEvent", "SolverStatsEvent",
            "ScoringFinishEvent", "ModelSwapEvent",
        ]
        snap = reg.snapshot()
        assert snap["counters"]["events.TrainingStartEvent"] == 1
        assert snap["counters"]["solver.per_user.entities"] == 4
        assert snap["gauges"]["serving.latency_p99_ms"]["last"] == 3.0
        assert snap["counters"]["serving.swaps"] == 1

    def test_failing_listener_isolated_from_bridge(self, tmp_path):
        from photon_ml_tpu.event import EventEmitter, TrainingStartEvent
        from tests._listeners import CollectingListener, FailingListener

        CollectingListener.received = []
        FailingListener.raised = 0
        ledger = RunLedger(str(tmp_path / "ledger.jsonl"))
        emitter = EventEmitter()
        emitter.register_listener_class("tests._listeners.FailingListener")
        emitter.register_listener(
            TelemetryEventListener(ledger=ledger, registry=MetricsRegistry())
        )
        emitter.register_listener_class("tests._listeners.CollectingListener")
        emitter.send_event(TrainingStartEvent(task="T"))
        emitter.clear_listeners()
        # the failing listener raised on the event AND on close, yet both
        # other listeners saw everything
        assert FailingListener.raised == 1
        assert emitter.listener_errors == 2
        assert len(CollectingListener.received) == 1
        events = [
            r for r in validate_ledger(str(ledger.path))
            if r["type"] == "event"
        ]
        assert len(events) == 1

    def test_register_listener_class_error_paths(self):
        from photon_ml_tpu.event import EventEmitter

        emitter = EventEmitter()
        with pytest.raises(ValueError, match="dotted"):
            emitter.register_listener_class("NoDots")
        with pytest.raises(ValueError, match="failed to import"):
            emitter.register_listener_class("no.such.module.Listener")
        with pytest.raises(ValueError, match="no attribute"):
            emitter.register_listener_class("tests._listeners.Missing")
        with pytest.raises(ValueError, match="not an instantiable"):
            emitter.register_listener_class("tests._listeners.NOT_A_LISTENER")
        assert emitter._listeners == []


# ---------------------------------------------------------------------------
# Driver smoke gate: tiny CPU end-to-end runs through the real CLIs with
# telemetry on. CI runs this whole file as the telemetry gate.
# ---------------------------------------------------------------------------


class TestRegistryLifecycle:
    """Two telemetry sessions in one process must not bleed into each
    other, and --auto-tune's fresh-registry trials must not pollute the
    process-global registry (the isolation contract autotune.py documents)."""

    def test_two_start_run_sessions_isolated(self, tmp_path):
        from photon_ml_tpu.telemetry import note_jit_trace, start_run
        from photon_ml_tpu.telemetry.span import disable_tracing, span

        get_registry().reset()
        first = tmp_path / "first.jsonl"
        run1 = start_run("one", ledger_path=str(first), device_sync=False)
        try:
            with span("cd/first"):
                note_jit_trace("prog_a")
            run1.finish()
        finally:
            disable_tracing()
        assert jit_trace_counts() == {"prog_a": 1}

        # session 2 starts from a reset registry; start_run(clear=True)
        # already drops session 1's spans
        get_registry().reset()
        assert jit_trace_counts() == {}
        second = tmp_path / "second.jsonl"
        run2 = start_run("two", ledger_path=str(second), device_sync=False)
        try:
            with span("re/second"):
                note_jit_trace("prog_b", kind="fwd")
            run2.finish()
        finally:
            disable_tracing()
        assert jit_trace_counts() == {"prog_b/fwd": 1}

        records2 = validate_ledger(str(second))
        names2 = {r["name"] for r in records2 if r["type"] == "span"}
        assert names2 == {"re/second"}  # session 1's span did not carry over
        (metrics2,) = [r for r in records2 if r["type"] == "metrics"]
        counters2 = metrics2["snapshot"]["counters"]
        assert "jit.traces.prog_b/fwd" in counters2
        assert "jit.traces.prog_a" not in counters2  # no cross-session leak
        # session 1's ledger is intact and still its own
        records1 = validate_ledger(str(first))
        assert {r["name"] for r in records1 if r["type"] == "span"} == {
            "cd/first"
        }

    def test_fresh_trial_registry_cannot_leak(self):
        get_registry().reset()
        trial_a = MetricsRegistry()
        trial_a.count("serving.compile_count", 5)
        trial_b = MetricsRegistry()
        # trial A's counters are invisible to trial B AND to the global
        assert trial_b.counter_value("serving.compile_count") == 0.0
        assert get_registry().counter_value("serving.compile_count") == 0.0
        trial_b.gauge("judge", 1.0)
        assert "judge" not in trial_a.snapshot()["gauges"]

    def test_checkpoint_leaves_analyzable_prefix(self, tmp_path):
        """RunLedger.flush() via TelemetryRun.checkpoint(): the ledger is a
        valid prefix BEFORE finish, and finish does not re-write the
        checkpointed spans."""
        from photon_ml_tpu.telemetry import start_run
        from photon_ml_tpu.telemetry.span import disable_tracing, span

        get_registry().reset()
        path = tmp_path / "ledger.jsonl"
        run = start_run("ckpt", ledger_path=str(path), device_sync=False)
        try:
            with span("cd/outer_iter"):
                pass
            run.checkpoint("iter-0")
            mid = validate_ledger(str(path))  # readable pre-finish
            assert [r["name"] for r in mid if r["type"] == "span"] == [
                "cd/outer_iter"
            ]
            assert any(
                r["type"] == "meta" and r.get("phase") == "checkpoint"
                for r in mid
            )
            with span("cd/coordinate"):
                pass
            run.finish()
        finally:
            disable_tracing()
        final = validate_ledger(str(path))
        spans = [r["name"] for r in final if r["type"] == "span"]
        assert spans == ["cd/outer_iter", "cd/coordinate"]  # no double write

    def test_truncated_tail_tolerated_with_warning(self, tmp_path):
        from photon_ml_tpu.telemetry import TruncatedLedgerWarning, start_run
        from photon_ml_tpu.telemetry.span import disable_tracing, span

        get_registry().reset()
        path = tmp_path / "crash.jsonl"
        run = start_run("crash", ledger_path=str(path), device_sync=False)
        try:
            with span("cd/run"):
                pass
            run.finish()
        finally:
            disable_tracing()
        with open(path, "a") as f:
            f.write('{"type": "span", "name": "killed mid-wr')  # no newline
        with pytest.warns(TruncatedLedgerWarning, match="partial record"):
            records = validate_ledger(str(path))
        assert [r["name"] for r in records if r["type"] == "span"] == [
            "cd/run"
        ]
        # strict mode still treats the same tail as corruption
        with pytest.raises(ValueError, match="invalid JSON"):
            validate_ledger(str(path), allow_truncated_tail=False)

    def test_mid_file_garbage_still_hard_error(self, tmp_path):
        path = tmp_path / "corrupt.jsonl"
        path.write_text(
            '{"type": "meta", "ts": 1.0, "phase": "start"}\n'
            "not json at all\n"
            '{"type": "meta", "ts": 2.0, "phase": "finish"}\n'
        )
        with pytest.raises(ValueError, match="invalid JSON"):
            validate_ledger(str(path))


class TestCompileSpans:
    """JAX's compile phases as spans under the work that caused them
    (telemetry/compile_spans.py), and Tracer.add_interval behind them."""

    @staticmethod
    def _compile_records(tracer, fun="<lambda>"):
        return [
            r for r in tracer.spans()
            if r.name.startswith("jit/") and fun in r.attrs.get("fun_name", "")
        ]

    @pytest.mark.parametrize("phase", ["trace", "lower", "backend"])
    def test_fresh_jit_leaves_phase_span_under_open_span(self, tracer, phase):
        import jax
        import jax.numpy as jnp

        x = jnp.ones(3)
        with span("outer") as outer:
            jax.jit(lambda v: jnp.sin(v) * 2 + jnp.cos(v))(x).block_until_ready()
        recs = [r for r in self._compile_records(tracer) if r.name == f"jit/{phase}"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec.attrs["under"] == "outer"
        assert rec.attrs["phase"] == phase
        assert rec.attrs["fun_name"]
        assert rec.parent_id == outer.span_id
        assert rec.path == f"outer/jit/{phase}"
        assert rec.depth == 2
        assert rec.duration_s > 0
        # on the tracer's clock, inside the span that was open
        out = [r for r in tracer.spans() if r.name == "outer"][0]
        assert out.start_s <= rec.start_s + 1e-3
        assert rec.start_s + rec.duration_s <= out.start_s + out.duration_s + 1e-3

    def test_nested_trace_events_leave_one_span(self, tracer):
        """A jit traced inside another, and every jnp function called during
        a trace, reports its own trace event; only the outermost is kept."""
        import jax
        import jax.numpy as jnp

        inner = jax.jit(lambda v: jnp.sin(v) * 3)
        x = jnp.ones(4)
        with span("outer"):
            jax.jit(lambda v: inner(v) + inner(2 * v))(x).block_until_ready()
        traces = [r for r in tracer.spans()
                  if r.name == "jit/trace" and r.attrs["under"] == "outer"]
        assert [r.attrs["fun_name"] for r in traces] == ["<lambda>"]
        # of one phase no two spans of the thread overlap (flood control);
        # phases may nest in each other, so a time is a union
        for phase in ("jit/trace", "jit/lower", "jit/backend"):
            recs = sorted(
                (r for r in tracer.spans() if r.name == phase),
                key=lambda r: r.start_s,
            )
            for a, b in zip(recs, recs[1:]):
                assert a.start_s + a.duration_s <= b.start_s + 1e-6
        out = [r for r in tracer.spans() if r.name == "outer"][0]
        compiles = [
            (r.start_s, r.start_s + r.duration_s)
            for r in tracer.spans()
            if r.name.startswith("jit/") and r.attrs["under"] == "outer"
        ]
        assert 0 < union_seconds(compiles) <= out.duration_s + 1e-3

    def test_trace_inside_a_lowering_is_kept(self, tracer):
        """What JAX traces while it lowers (a kernel body, a custom rule)
        reports after the lowering began and before it ends: the lowering
        must not swallow it, or nobody can tell Python tracing from the
        lowering proper."""
        import jax.monitoring

        trace = "/jax/core/compile/jaxpr_trace_duration"
        lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
        with span("outer"):
            time.sleep(0.02)
            jax.monitoring.record_event_duration_secs(trace, 0.004, fun_name="body")
            jax.monitoring.record_event_duration_secs(trace, 0.012, fun_name="kernel")
            jax.monitoring.record_event_duration_secs(lower, 0.018, fun_name="solve")
        recs = [(r.name, r.attrs["fun_name"]) for r in tracer.spans() if r.name != "outer"]
        assert recs == [("jit/trace", "kernel"), ("jit/lower", "solve")]
        spans = {r.attrs["fun_name"]: r for r in tracer.spans() if r.name != "outer"}
        assert spans["solve"].start_s < spans["kernel"].start_s
        both = [(r.start_s, r.start_s + r.duration_s) for r in spans.values()]
        assert union_seconds(both) == pytest.approx(spans["solve"].duration_s, abs=2e-3)

    @pytest.mark.parametrize(
        "intervals, seconds",
        [
            ([], 0.0),
            ([(1.0, 2.0)], 1.0),
            ([(1.0, 2.0), (3.0, 3.5)], 1.5),                 # disjoint
            ([(3.0, 3.5), (1.0, 4.0), (1.5, 2.0)], 3.0),     # nested, unsorted
            ([(1.0, 2.0), (1.5, 3.0), (3.0, 4.0)], 3.0),     # overlapping, touching
        ],
    )
    def test_union_seconds(self, intervals, seconds):
        assert union_seconds(intervals) == pytest.approx(seconds)

    @pytest.mark.parametrize("hit", [True, False])
    def test_cache_event_becomes_span_and_counter(self, tracer, hit):
        import jax.monitoring

        kind = "hits" if hit else "misses"
        with span("outer"):
            jax.monitoring.record_event(f"/jax/compilation_cache/cache_{kind}")
        recs = [r for r in tracer.spans() if r.name == "jit/cache"]
        assert len(recs) == 1
        assert recs[0].attrs == {"hit": hit, "under": "outer"}
        assert recs[0].duration_s == 0.0
        assert get_registry().counter_value(f"jit.cache.{kind}") == 1

    def test_listeners_record_nothing_when_off(self, tracer):
        import jax
        import jax.monitoring
        import jax.numpy as jnp

        disable_tracing()
        tracer.clear()
        jax.jit(lambda v: v * 5 - 1)(jnp.ones(2)).block_until_ready()
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        assert len(tracer) == 0
        assert get_registry().counter_value("jit.cache.misses") == 0

    def test_add_interval_absorbs_nested_of_its_name_and_thread_only(self):
        from photon_ml_tpu.telemetry.span import Tracer

        t = Tracer(enabled=True)
        base = t.origin_perf
        t.add_interval("x", base + 1.0, base + 2.0, absorb_nested=True, tag="first")
        t.add_interval("x", base + 3.0, base + 3.2, absorb_nested=True, tag="a")
        t.add_interval("mark", base + 3.1, base + 3.1, tag="kept")  # not absorbing
        t.add_interval("y", base + 3.25, base + 3.3, absorb_nested=True, tag="other name")
        t.add_interval("x", base + 3.3, base + 3.9, absorb_nested=True, tag="b")
        t.add_interval("x", base + 2.5, base + 4.0, absorb_nested=True, tag="outer")
        assert [(r.name, r.attrs["tag"]) for r in t.spans()] == [
            ("x", "first"), ("mark", "kept"), ("y", "other name"), ("x", "outer"),
        ]
        got = t.spans()[-1]
        assert got.start_s == pytest.approx(2.5)
        assert got.duration_s == pytest.approx(1.5)
        assert got.parent_id is None and got.path == "x"

        other = threading.Thread(
            target=lambda: t.add_interval("x", base + 0.0, base + 9.0, absorb_nested=True, tag="t2")
        )
        other.start()
        other.join(timeout=10)
        assert not other.is_alive()
        assert len(t) == 5  # another thread's enclosing interval absorbs nothing here
        t.enabled = False
        t.add_interval("x", base + 5.0, base + 6.0)
        assert len(t) == 5

    def test_spans_written_to_the_ledger_are_never_dropped(self, tmp_path):
        """A checkpoint seals what it wrote: an enclosing compile event that
        ends later leaves the written span in the tracer too, so ledger and
        tracer hold the same spans."""
        import jax.monitoring

        from photon_ml_tpu.telemetry import start_run

        trace = "/jax/core/compile/jaxpr_trace_duration"
        get_registry().reset()
        path = tmp_path / "ledger.jsonl"
        run = start_run("sealed", ledger_path=str(path), device_sync=False)
        try:
            time.sleep(0.03)
            jax.monitoring.record_event_duration_secs(trace, 0.002, fun_name="dropped")
            jax.monitoring.record_event_duration_secs(trace, 0.004, fun_name="inner")
            run.checkpoint("mid-trace")
            jax.monitoring.record_event_duration_secs(trace, 0.02, fun_name="outer")
            in_tracer = sorted(r.attrs["fun_name"] for r in run.tracer.spans())
            run.finish()
        finally:
            disable_tracing()
        in_ledger = sorted(
            r["attrs"]["fun_name"] for r in validate_ledger(str(path)) if r["type"] == "span"
        )
        assert in_tracer == in_ledger == ["inner", "outer"]


class TestTrainingPathSpans:
    """The step is covered by named spans end to end (ISSUE 26)."""

    COVERING = ("game/prepare_fit", "cd/initial_scores", "cd/coordinate",
                "cd/objective", "cd/validate", "glm/train")

    @pytest.fixture(scope="class")
    def traced_fits(self):
        data, held_out = _tiny_glmix(0), _tiny_glmix(1, rows_per_user=4)
        t = enable_tracing(device_sync=True, clear=True)
        try:
            fits = _tiny_glmix_estimator().fit_multiple(
                data, validation_data=held_out, configs=[{}, {}], warm_start=True
            )
        finally:
            disable_tracing()
        return fits, t.spans()

    @pytest.mark.parametrize("name", [
        "game/prepare_fit", "cd/initial_scores", "cd/score", "glm/train",
        "glm/solve", "re/round_wait",
    ])
    def test_span_is_recorded(self, traced_fits, name):
        _, spans = traced_fits
        assert any(s.name == name for s in spans)

    def test_span_nesting_and_attrs(self, traced_fits):
        _, spans = traced_fits
        solves = [s for s in spans if s.name == "glm/solve"]
        assert len(solves) == 2  # one fixed-effect solve a fit
        for s in solves:
            assert s.path.endswith("fe/solve/glm/train/glm/solve")
            assert s.attrs["evaluations"] >= s.attrs["iterations"] + 1
        trains = [s for s in spans if s.name == "glm/train"]
        assert all(s.attrs == {"optimizer": "LBFGS", "weights": 1} for s in trains)
        assert {s.attrs["coordinate"] for s in spans if s.name == "cd/score"} == {
            "fixed", "per_user"}
        assert all("re/adaptive_round" in s.path for s in spans if s.name == "re/round_wait")
        # the second fit is warm-started: two models scored before its first update
        assert sorted(s.attrs["coordinates"] for s in spans if s.name == "cd/initial_scores") == [0, 2]
        # compile spans say which work caused them
        under = {s.attrs["under"] for s in spans if s.name.startswith("jit/")}
        assert any("glm/train" in u for u in under)
        assert any("re/train" in u for u in under)

    @pytest.mark.parametrize("fit_index", [0, 1])
    def test_named_spans_cover_the_fit(self, traced_fits, fit_index):
        _, spans = traced_fits
        starts = sorted(s.start_s for s in spans if s.name == "game/prepare_fit")
        ends = sorted(s.start_s + s.duration_s for s in spans if s.name == "game/fit")
        lo, hi = starts[fit_index], ends[fit_index]
        cut = [
            (max(s.start_s, lo), min(s.start_s + s.duration_s, hi))
            for s in spans if s.name in self.COVERING
        ]
        covered = union_seconds((a, b) for a, b in cut if b > a)
        assert covered >= 0.95 * (hi - lo), (covered, hi - lo)


@pytest.fixture(scope="module")
def tiny_avro(tmp_path_factory):
    """Tiny GLMix logistic fixture (8 users) + a config whose RE coordinate
    opts into the adaptive driver with min_lanes small enough to engage on
    8 entities, so re/adaptive_round spans appear in the gate."""
    from photon_ml_tpu.io.data_reader import write_training_examples

    root = tmp_path_factory.mktemp("telemetry_glmix")
    rng = np.random.default_rng(3)
    n_users, rows, dg, du = 8, 12, 5, 3
    wg = rng.normal(size=dg)
    wu = {f"user{i}": rng.normal(size=du) for i in range(n_users)}

    def make(n_rows, seed):
        r = np.random.default_rng(seed)
        records = []
        for i in range(n_rows):
            user = f"user{i % n_users}"
            xg = r.normal(size=dg)
            xu = r.normal(size=du)
            z = xg @ wg + xu @ wu[user]
            y = 1.0 if 1 / (1 + np.exp(-z)) > r.random() else 0.0
            records.append({
                "uid": f"r{i}",
                "label": y,
                "features": [("g", str(j), xg[j]) for j in range(dg)],
                "userFeatures": [("u", str(j), xu[j]) for j in range(du)],
                "metadataMap": {"userId": user},
            })
        return records

    train_dir = root / "train"
    test_dir = root / "test"
    train_dir.mkdir()
    test_dir.mkdir()
    write_training_examples(
        str(train_dir / "part-00000.avro"), make(n_users * rows, 1)
    )
    write_training_examples(
        str(test_dir / "part-00000.avro"), make(n_users * 4, 2)
    )
    config = {
        "feature_shards": {
            "global": {"feature_bags": ["features"], "add_intercept": True},
            "per_user": {
                "feature_bags": ["userFeatures"], "add_intercept": False,
            },
        },
        "coordinates": {
            "fixed": {
                "type": "fixed",
                "feature_shard": "global",
                "optimizer": {
                    "optimizer": "LBFGS",
                    "regularization": "L2",
                    "regularization_weight": 0.1,
                },
            },
            "per_user": {
                "type": "random",
                "feature_shard": "per_user",
                "random_effect_type": "userId",
                "optimizer": {
                    "optimizer": "LBFGS",
                    "regularization": "L2",
                    "regularization_weight": 1.0,
                    "adaptive": {
                        "enabled": True, "chunk_iters": 4, "min_lanes": 2,
                    },
                },
            },
        },
        "update_order": ["fixed", "per_user"],
    }
    cfg_path = root / "game.json"
    cfg_path.write_text(json.dumps(config))
    return {"root": root, "train": train_dir, "test": test_dir,
            "config": cfg_path}


@pytest.mark.slow
class TestDriverTelemetrySmoke:
    @pytest.fixture(scope="class")
    def trained(self, tiny_avro, tmp_path_factory):
        """One traced train_game run shared by the downstream driver tests:
        model dir + validated ledger/trace paths."""
        from tests._listeners import CollectingListener

        from photon_ml_tpu.cli.train_game import parse_args, run

        CollectingListener.received = []
        out = tmp_path_factory.mktemp("telemetry_out")
        ledger_path = out / "train.jsonl"
        trace_path = out / "train-trace.json"
        run(parse_args([
            "--train-data-dirs", str(tiny_avro["train"]),
            "--validation-data-dirs", str(tiny_avro["test"]),
            "--coordinate-config", str(tiny_avro["config"]),
            "--task", "LOGISTIC_REGRESSION",
            "--output-dir", str(out / "model"),
            "--evaluator", "AUC",
            "--event-listeners", "tests._listeners.CollectingListener",
            "--telemetry-out", str(ledger_path),
            "--trace-out", str(trace_path),
        ]))
        return {
            "out": out,
            "model": out / "model" / "best",
            "ledger": ledger_path,
            "trace": trace_path,
            "events": list(CollectingListener.received),
        }

    def test_train_ledger_and_trace_schemas(self, trained):
        records = validate_ledger(str(trained["ledger"]))
        doc = validate_chrome_trace(str(trained["trace"]))
        span_paths = {r["path"] for r in records if r["type"] == "span"}
        # spans from coordinate descent AND the adaptive RE driver
        assert any("cd/outer_iter" in p for p in span_paths)
        assert any("cd/coordinate" in p for p in span_paths)
        assert any("re/adaptive_round" in p for p in span_paths)
        assert any("re/solve_bucket" in p for p in span_paths)
        assert len(doc["traceEvents"]) > 0
        # every existing Event was bridged into the ledger
        event_names = [r["event"] for r in records if r["type"] == "event"]
        assert "PhotonSetupEvent" in event_names
        assert "TrainingStartEvent" in event_names
        assert "TrainingFinishEvent" in event_names
        assert "SolverStatsEvent" in event_names
        # zero listener errors, recorded in the finish meta record
        finish = [
            r for r in records
            if r["type"] == "meta" and r.get("phase") == "finish"
        ]
        assert len(finish) == 1 and finish[0]["listener_errors"] == 0
        assert finish[0]["num_spans"] == len(
            [r for r in records if r["type"] == "span"]
        )
        # the user listener rode along untouched
        assert len(trained["events"]) > 0

    def test_train_failing_listener_isolated(self, tiny_avro, tmp_path):
        """A listener that raises on every event must not fail the driver;
        the swallowed count lands in the ledger's finish record."""
        from tests._listeners import FailingListener

        from photon_ml_tpu.cli.train_game import parse_args, run

        FailingListener.raised = 0
        ledger_path = tmp_path / "ledger.jsonl"
        run(parse_args([
            "--train-data-dirs", str(tiny_avro["train"]),
            "--coordinate-config", str(tiny_avro["config"]),
            "--task", "LOGISTIC_REGRESSION",
            "--output-dir", str(tmp_path / "model"),
            "--event-listeners", "tests._listeners.FailingListener",
            "--telemetry-out", str(ledger_path),
        ]))
        assert FailingListener.raised > 0
        records = validate_ledger(str(ledger_path))
        finish = [
            r for r in records
            if r["type"] == "meta" and r.get("phase") == "finish"
        ][0]
        assert finish["listener_errors"] > 0

    def test_train_bad_listener_fails_fast(self, tiny_avro, tmp_path):
        from photon_ml_tpu.cli.train_game import parse_args, run

        with pytest.raises(ValueError, match="no attribute"):
            run(parse_args([
                "--train-data-dirs", str(tiny_avro["train"]),
                "--coordinate-config", str(tiny_avro["config"]),
                "--task", "LOGISTIC_REGRESSION",
                "--output-dir", str(tmp_path / "model"),
                "--event-listeners", "tests._listeners.Missing",
            ]))

    def test_score_game_telemetry_and_listeners(self, trained, tiny_avro,
                                                tmp_path):
        from tests._listeners import CollectingListener

        from photon_ml_tpu.cli.score_game import parse_args, run

        CollectingListener.received = []
        ledger_path = tmp_path / "score.jsonl"
        trace_path = tmp_path / "score-trace.json"
        run(parse_args([
            "--data-dirs", str(tiny_avro["test"]),
            "--model-dir", str(trained["model"]),
            "--output-dir", str(tmp_path / "scores"),
            "--evaluator", "AUC",
            "--event-listeners", "tests._listeners.CollectingListener",
            "--telemetry-out", str(ledger_path),
            "--trace-out", str(trace_path),
        ]))
        records = validate_ledger(str(ledger_path))
        validate_chrome_trace(str(trace_path))
        event_names = [r["event"] for r in records if r["type"] == "event"]
        assert "ScoringStartEvent" in event_names
        assert "ScoringFinishEvent" in event_names
        names = {n for n in (type(e).__name__
                             for e in CollectingListener.received)}
        assert "ScoringFinishEvent" in names
        # Timer phases land as spans (score, save scores, ...)
        span_names = {r["name"] for r in records if r["type"] == "span"}
        assert "score" in span_names

    def test_serve_game_telemetry(self, trained, tiny_avro, tmp_path):
        from photon_ml_tpu.cli.serve_game import parse_args, run

        ledger_path = tmp_path / "serve.jsonl"
        trace_path = tmp_path / "serve-trace.json"
        run(parse_args([
            "--model-dir", str(trained["model"]),
            "--data-dirs", str(tiny_avro["test"]),
            "--max-requests", "16",
            "--bucket-sizes", "1,2,4",
            "--metrics-output", str(tmp_path / "metrics.json"),
            "--telemetry-out", str(ledger_path),
            "--trace-out", str(trace_path),
        ]))
        records = validate_ledger(str(ledger_path))
        validate_chrome_trace(str(trace_path))
        span_paths = {r["path"] for r in records if r["type"] == "span"}
        assert any("serve/replay" in p for p in span_paths)
        assert any("serve/score_batch" in p for p in span_paths)
        event_names = [r["event"] for r in records if r["type"] == "event"]
        assert "ScoringFinishEvent" in event_names
        # the bridged snapshot landed as serving.* gauges in the metrics
        # record
        (metrics,) = [r for r in records if r["type"] == "metrics"]
        assert "serving.num_requests" in metrics["snapshot"]["gauges"]

    def test_update_game_telemetry(self, trained, tiny_avro, tmp_path):
        from photon_ml_tpu.cli.serve_game import (
            parse_args as serve_args,
            run as serve_run,
        )
        from photon_ml_tpu.cli.update_game import parse_args, run

        artifact_dir = tmp_path / "artifact"
        serve_run(serve_args([
            "--model-dir", str(trained["model"]),
            "--export-artifact-dir", str(artifact_dir),
        ]))
        ledger_path = tmp_path / "update.jsonl"
        run(parse_args([
            "--base-artifact-dir", str(artifact_dir),
            "--model-dir", str(trained["model"]),
            "--coordinate-config", str(tiny_avro["config"]),
            "--events-data-dirs", str(tiny_avro["test"]),
            "--output-dir", str(tmp_path / "deltas"),
            "--telemetry-out", str(ledger_path),
        ]))
        records = validate_ledger(str(ledger_path))
        span_paths = {r["path"] for r in records if r["type"] == "span"}
        assert any("incremental/update" in p for p in span_paths)
        assert any("incremental/resolve" in p for p in span_paths)
        finish = [
            r for r in records
            if r["type"] == "meta" and r.get("phase") == "finish"
        ][0]
        assert finish["listener_errors"] == 0

    def test_disabled_default_bitwise_identical(self, tiny_avro, tmp_path):
        """Telemetry must not perturb training: the same tiny fit with and
        without tracing produces bitwise-identical coefficients."""
        from photon_ml_tpu.cli.train_game import parse_args, run
        from photon_ml_tpu.io.model_io import load_game_model

        def train(tag, telemetry):
            out = tmp_path / tag
            argv = [
                "--train-data-dirs", str(tiny_avro["train"]),
                "--coordinate-config", str(tiny_avro["config"]),
                "--task", "LOGISTIC_REGRESSION",
                "--output-dir", str(out),
            ]
            if telemetry:
                argv += ["--telemetry-out", str(out / "ledger.jsonl")]
            run(parse_args(argv))
            model, _ = load_game_model(str(out / "best"))
            return model

        # with tracing off nothing of the tracer runs: the compile listeners
        # (registered by earlier traced runs in this process) record nothing
        tracer = get_tracer()
        tracer.clear()
        cache_events = (get_registry().counter_value("jit.cache.hits"),
                        get_registry().counter_value("jit.cache.misses"))
        plain = train("plain", telemetry=False)
        assert len(tracer) == 0
        assert cache_events == (get_registry().counter_value("jit.cache.hits"),
                                get_registry().counter_value("jit.cache.misses"))
        traced = train("traced", telemetry=True)
        assert any(s.name == "jit/trace" for s in tracer.spans())
        fixed_p = np.asarray(plain.models["fixed"].coefficients.means)
        fixed_t = np.asarray(traced.models["fixed"].coefficients.means)
        np.testing.assert_array_equal(fixed_p, fixed_t)
        re_p = dict(plain.models["per_user"].items())
        re_t = dict(traced.models["per_user"].items())
        assert re_p == re_t  # exact per-entity sparse coefficient equality


class TestCrossThreadSpanPropagation:
    """The async CD schedule's telemetry contract: spans opened inside a
    ScheduleExecutor worker parent under the span that was live at the
    DISPATCH site (contextvars are copied at submit), not under the
    worker thread's own (empty) context — and the resulting cross-thread
    span tree survives ledger validation."""

    def test_worker_span_parents_under_dispatch_site(self, tracer):
        from photon_ml_tpu.algorithm.schedule import ScheduleExecutor

        def work():
            with span("fe/solve"):
                return 7

        with ScheduleExecutor(max_in_flight=2, name="t-sched") as ex:
            with span("cd/outer_iter", outer=0):
                w = ex.submit("fe", work, coordinate="fe", outer=0)
                assert w.result() == 7
        by_name = {r.name: r for r in tracer.spans()}
        overlap = by_name["cd/overlap"]
        assert overlap.parent_id == by_name["cd/outer_iter"].span_id
        assert overlap.attrs == {"coordinate": "fe", "outer": 0}
        assert by_name["fe/solve"].parent_id == overlap.span_id
        # the overlap span really ran on the pool thread, not the driver
        assert overlap.thread_id != by_name["cd/outer_iter"].thread_id
        assert overlap.thread_name.startswith("t-sched")

    def test_plain_thread_still_isolated(self, tracer):
        """Bare threads (no executor) keep today's behavior: their spans
        root independently — propagation is an explicit submit-time copy,
        not a global change to span parenting."""
        def worker():
            with span("w/root"):
                pass

        with span("driver"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        by_name = {r.name: r for r in tracer.spans()}
        assert by_name["w/root"].parent_id is None

    def test_concurrent_worker_spans_survive_ledger_validation(
        self, tmp_path, tracer
    ):
        """Two workers dispatched from one iteration write interleaved,
        genuinely concurrent spans; the ledger schema and the analyzer both
        accept the result (validate, then analyze_records must attribute
        nonzero overlap)."""
        import time as _time

        from photon_ml_tpu.algorithm.schedule import ScheduleExecutor
        from photon_ml_tpu.telemetry.analyze import analyze_records

        def work(tag):
            def _run():
                with span(f"fe/solve_{tag}" if tag == "a" else f"re/train_{tag}"):
                    _time.sleep(0.05)
                return tag
            return _run

        with ScheduleExecutor(max_in_flight=2) as ex:
            with span("cd/outer_iter", outer=0):
                wa = ex.submit("a", work("a"), coordinate="a", outer=0)
                wb = ex.submit("b", work("b"), coordinate="b", outer=0)
                assert wa.result() == "a"
                assert wb.result() == "b"

        path = tmp_path / "ledger.jsonl"
        ledger = RunLedger(str(path))
        # the run window must bracket the spans (spans are flushed at run
        # finish in production; here they are replayed after the fact, so
        # pin the start record to the tracer origin)
        ledger.write("meta", phase="start", label="xthread",
                     ts=tracer.origin_unix)
        for rec in tracer.spans():
            ledger.write_span(rec, tracer.origin_unix)
        ledger.write("meta", phase="finish", label="xthread")
        ledger.close()
        records = validate_ledger(str(path))
        spans = [r for r in records if r["type"] == "span"]
        assert len(spans) == 5  # outer_iter + 2 overlap + 2 solves
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            if s["name"] != "cd/outer_iter":
                assert s["parent_id"] in by_id
        report = analyze_records(records)
        # the two 50ms worker spans ran concurrently: the analyzer shares
        # the segment instead of double-counting it, and reports overlap
        assert report.coverage <= 1.05
        assert report.overlap_s > 0
