#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

finds ``workloads/<name>.json``, which names a configuration file
(``configs/``) and a traffic file (``traffic/``); the traffic file names its
step driver (a module under ``traffic/``) and gives its parameters and the
limits of the comparison that decides ``correct``. Per-layer metrics are the
modules under ``layer_metrics/``, found by listing the directory. A new cell,
configuration, traffic mix or layer metric is a new file (README.md).

A workload finds a TPU or exits non-zero before printing a result. Only a
workload file that says ``"rehearsal": true`` runs on the CPU; its output is
named a rehearsal and carries no device metric.

Last line of stdout: the result object. Last lines of stderr: each number
compared, beside its limit.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
EXIT_NO_DEVICE = 4
EXIT_BAD_WORKLOAD = 2


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    with open(path) as f:
        return json.load(f)


def load_workload(name: str) -> dict:
    if not name or any(c in name for c in "/\\") or name.startswith("."):
        raise SystemExit(f"bad workload name {name!r}")
    if not os.path.exists(os.path.join(HERE, "workloads", f"{name}.json")):
        sys.stderr.write(f"run.py: no workload file workloads/{name}.json\n")
        raise SystemExit(EXIT_BAD_WORKLOAD)
    w = load_json("workloads", f"{name}.json")
    w["name"] = name
    w["config_doc"] = load_json(w["config_file"].replace("benchmarks/", "", 1))
    w["traffic_doc"] = load_json("traffic", f"{w['traffic']}.json")
    return w


def place_caches() -> dict:
    """Fixed directories inside the checkout, so that only a cell's first
    run there routes and compiles. ``JAX_COMPILATION_CACHE_DIR`` is kept
    where the caller set it; the program then sets no directory in code."""
    plans = os.path.join(CACHE, "plans")
    os.makedirs(plans, exist_ok=True)
    os.environ["PHOTON_ML_TPU_PLAN_CACHE"] = plans
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    return {"plans": plans, "jax": os.environ["JAX_COMPILATION_CACHE_DIR"]}


def list_layer_metrics():
    """One module per metric, named after it (a metric's name may hold
    dots, so the modules are loaded by path)."""
    import importlib.util

    modules = []
    folder = os.path.join(HERE, "layer_metrics")
    for f in sorted(os.listdir(folder)):
        if not f.endswith(".py") or f.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + f[:-3].replace(".", "_"), os.path.join(folder, f)
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if module.NAME != f[:-3]:
            raise RuntimeError(f"layer_metrics/{f} names its metric {module.NAME!r}")
        modules.append(module)
    return modules


def jsonable(x):
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


class Start:
    """What every entry point of the benchmark does before it touches the
    program: the workload's files, the caches, the look for a chip, the
    rehearsal's interpreter switch, the compile cache. ``run.py`` and
    ``calibrate.py`` both begin here."""

    def __init__(self, workload_name: str, program: str = "run.py"):
        if CHECKOUT not in sys.path:
            sys.path.insert(0, CHECKOUT)
        self.workload = load_workload(workload_name)
        self.config, self.traffic = self.workload["config_doc"], self.workload["traffic_doc"]
        self.rehearsal = bool(self.workload.get("rehearsal", False))
        self.caches = place_caches()

        import jax

        self.devices = jax.devices()
        self.device = device = self.devices[0]
        self.chips = int(self.workload["chips"])
        if not self.rehearsal and (device.platform != "tpu" or len(self.devices) < self.chips):
            sys.stderr.write(
                f"{program}: workload {workload_name!r} needs {self.chips} TPU chip(s); JAX found "
                f"{len(self.devices)} x {device.platform} ({device.device_kind}). A cell has no "
                "other mode; only a *.tiny.json rehearsal file runs on the CPU.\n"
            )
            raise SystemExit(EXIT_NO_DEVICE)
        if self.rehearsal and device.platform == "tpu":
            sys.stderr.write(f"{program}: a rehearsal workload is for the CPU\n")
            raise SystemExit(EXIT_BAD_WORKLOAD)
        self.tag = f"[{device.platform}:{device.device_kind} x{len(self.devices)}]"
        if self.rehearsal:
            self.tag += " REHEARSAL"

        import photon_ml_tpu  # noqa: F401 - the system under test; absent -> fail here
        from photon_ml_tpu.utils.cachedir import enable_compilation_cache

        if self.rehearsal:
            from photon_ml_tpu.ops import fused_perm

            fused_perm._INTERPRET = True
        self.compile_cache = enable_compilation_cache()

    def driver(self, seed: int, say):
        """(the traffic's driver module, a driver of it for ``seed``)."""
        module = importlib.import_module(f"benchmarks.traffic.{self.traffic['driver']}")
        return module, module.Driver(self.config, self.traffic, seed, self.rehearsal, say)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    start = Start(args.workload)
    traffic, rehearsal = start.traffic, start.rehearsal
    device, devices, chips, tag = start.device, start.devices, start.chips, start.tag

    def say(message: str) -> None:
        print(f"{tag} {message}", flush=True)

    import jax

    if rehearsal:
        say("rehearsal on the CPU: the fused kernels run in the Pallas interpreter; "
            "nothing printed here is a device number")
    say(f"compile cache {start.compile_cache} ; plan cache {start.caches['plans']}")

    from benchmarks import compare, meter as meters, tracing, work
    from benchmarks.layer_metrics._compile import PHASES as COMPILE_PHASES
    from benchmarks.traffic.steps import Window

    memory_meter = meters.MemoryMeter().start()
    tracer = None
    if args.trace:
        from photon_ml_tpu.telemetry import enable_tracing

        tracer = enable_tracing(device_sync=True)
    trace_dir = os.path.join(CACHE, "trace", args.workload)
    clock = {}

    def start_profile() -> None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        clock["mark_perf_ns"] = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(tracing.MARK):
            pass

    def stop_profile() -> None:
        jax.profiler.stop_trace()

    _, driver = start.driver(args.seed, say)
    driver.prepare()
    say(f"prepared at {time.perf_counter() - PROCESS_START:.1f}s")
    def window_starts() -> None:
        memory_meter.end_of_setup()
        if args.trace:
            start_profile()

    window = Window(
        seconds=args.seconds,
        on_start=window_starts,
        on_end=stop_profile if args.trace else None,
        on_step=memory_meter.read,
    )
    driver.run(window)
    setup_s = window.start - PROCESS_START
    memory = memory_meter.result()
    peak = memory["peak_bytes"]
    if tracer is not None:
        from photon_ml_tpu.telemetry import disable_tracing

        disable_tracing()
    say("steps " + " ".join(f"{s:.2f}s" for s in window.step_seconds))
    say(f"window {window.length:.3f}s, {window.steps} step(s), set-up {setup_s:.1f}s, "
        f"peak {peak:,} bytes held at one instant ({memory})")

    device_doc = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
        "memory_peak_in_use_bytes": memory["peak_bytes_in_use"],
        "memory_peak_reserved_bytes": memory["peak_bytes_reserved"],
        "memory_readings": memory["readings"],
    }
    metrics, breakdown = {}, None
    if not args.trace:
        metrics.update(driver.end_to_end(window))
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        reduced, host_spans = None, []
        spans = [
            {"name": s.name, "start": tracer.origin_perf + s.start_s,
             "end": tracer.origin_perf + s.start_s + s.duration_s,
             "attrs": dict(s.attrs), "depth": s.depth}
            for s in tracer.spans()
        ]
        compiles = [s for s in spans if s["name"] in COMPILE_PHASES
                    and s["end"] > window.start and s["start"] < window.end]
        say(f"compile spans of any phase inside the window: {len(compiles)}"
            + "".join(f" {s['name']}:{s['attrs'].get('fun_name')}" for s in compiles[:8]))
        if not rehearsal:
            path = tracing.find_xplane(trace_dir)
            events, mark = tracing.load_events(path)
            if mark is None:
                raise RuntimeError("the trace holds no clock mark; cannot place the window")
            to_trace = lambda t: mark + (t * 1e9 - clock["mark_perf_ns"])  # noqa: E731
            window_ns = (to_trace(window.start), to_trace(window.end))
            reduced = tracing.reduce_trace(events, window_ns, chips)
            if reduced["busy_s"] <= 0:
                raise RuntimeError("no operation ran on the device inside the traced window")
            for s in sorted(spans, key=lambda s: s["depth"]):
                if s["end"] >= window.start and s["start"] <= window.end:
                    if s["name"] in COMPILE_PHASES:
                        label = "compile (trace, lower, backend)"
                    else:
                        label = s["name"] + (
                            f"[{s['attrs']['coordinate']}]" if "coordinate" in s["attrs"] else ""
                        )
                    host_spans.append((to_trace(s["start"]), to_trace(s["end"]), label))
            # compiles are the innermost host activity: let them win ties
            host_spans.sort(key=lambda h: h[2].startswith("compile"))
            gaps = tracing.idle_gaps(reduced["busy_intervals"], window_ns, host_spans)
            top = sorted(reduced["self_times"].items(), key=lambda kv: -kv[1])[:10]
            breakdown = {
                "device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps[:10]],
            }
            device_doc["busy_s"] = reduced["busy_s"]
            device_doc["window_s"] = reduced["window_s"]
            shutil.rmtree(trace_dir, ignore_errors=True)
        context = {
            "window": (window.start, window.end), "steps": window.steps,
            "window_s": window.length, "spans": spans,
            "counters": driver.step_counters, "shapes": driver.work_shapes(),
            "times": getattr(driver, "times", {}), "trace": reduced,
            "peaks": None if rehearsal else work.load_peaks(device.device_kind),
        }
        for module in list_layer_metrics():
            if rehearsal and module.SOURCE == "device_trace":
                continue
            value = module.read(context)
            if value is not None:
                metrics[module.NAME] = {"value": value, "unit": module.UNIT}

    # the comparison that decides `correct`: after the window has closed, the
    # peak has been read and the program's state is freed
    driver.collect()
    driver.release()
    t0 = time.perf_counter()
    numbers = driver.check()
    correct, rows = compare.verdict(numbers, traffic["limits"])
    say(f"reference and comparison {time.perf_counter() - t0:.1f}s")

    result = {
        "correct": bool(correct),
        "attempted": window.steps,
        "failed": 0,
        "metrics": metrics,
        "device": device_doc,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearsal:
        result["rehearsal"] = True
    result["compared"] = {
        name: {"value": jsonable(value), "limit": limit} for name, value, limit in rows
    }
    sys.stdout.flush()
    for name, value, limit in rows:
        sys.stderr.write(f"{tag} compared {name} = {value!r} limit {limit!r} "
                         f"{'ok' if value <= limit else 'EXCEEDED'}\n")
    sys.stderr.write(f"{tag} correct = {correct}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
