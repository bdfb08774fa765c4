"""Shared by the readers of the spans the program's tracer makes of JAX's
compile phases (photon_ml_tpu/telemetry/compile_spans.py): ``jit/trace``,
``jit/lower``, ``jit/backend`` with attrs ``fun_name``, ``phase`` and
``under`` (the path of the span that was open when JAX compiled), and the
zero-length ``jit/cache`` with ``hit``.

Every time here is a *union* of intervals cut to the measured window, never a
sum of durations: the events nest and overlap (a trace inside a lowering,
two threads compiling at once), and a sum of them is not a time
(JAX's own event durations summed to 40.5 s in a 39.4 s step; ledger, PR 25). Host
seconds. A program without these spans (any commit before PR 26) gives every
reader here ``None``."""

PHASES = ("jit/trace", "jit/lower", "jit/backend")


def clipped(context, spans):
    """[(start, end)] of ``spans`` cut to the window; what lies outside it
    is left out."""
    lo, hi = context["window"]
    cut = ((max(s["start"], lo), min(s["end"], hi)) for s in spans)
    return [(a, b) for a, b in cut if b > a]


def union_seconds(intervals):
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def has_compile_spans(context):
    return any(s["name"].startswith("jit/") for s in context["spans"])


def compile_spans(context, names=PHASES, under=None, not_under=()):
    """The compile spans named ``names`` whose ``under`` path holds the
    span name ``under`` (any, if None) and none of ``not_under``."""
    return [
        s for s in context["spans"]
        if s["name"] in names
        and (under is None or under in s["attrs"].get("under", ""))
        and not any(n in s["attrs"].get("under", "") for n in not_under)
    ]


def union_per_step(context, spans):
    """Union of ``spans`` inside the window over the steps; None where the
    program makes no compile spans at all."""
    if not has_compile_spans(context):
        return None
    return union_seconds(clipped(context, spans)) / context["steps"]
