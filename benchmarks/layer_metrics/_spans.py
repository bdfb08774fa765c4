"""Shared by the readers: spans of the program's host tracer inside the
measured window. Since PR 26 a ``device_sync`` span waits for the device work
dispatched inside it (the tracer's barrier blocks on a trivial jitted
program; PERF.md section 6) and ``glm/solve`` blocks on its result while the
tracer is on, so such a span's seconds are host time plus device wait."""


def in_window(context, name, **attrs):
    lo, hi = context["window"]
    return [
        s for s in context["spans"]
        if s["name"] == name and lo <= s["start"] <= hi
        and all(s["attrs"].get(k) == v for k, v in attrs.items())
    ]


def seconds(spans):
    return sum(s["end"] - s["start"] for s in spans)


def window_counters(context):
    """Counters of the window's steps: the last ones (warm-up's come first)."""
    return context["counters"][-context["steps"]:]


def window_solves(context):
    """Attrs of the window's ``glm/solve`` spans that say what the solver
    counted (``iterations``, ``evaluations``, ``hessian_vecs``)."""
    return [s["attrs"] for s in in_window(context, "glm/solve")
            if s["attrs"].get("evaluations") is not None]


def window_lanes(context):
    """The random-effect lane counters of the window's steps."""
    return [lane for c in window_counters(context) for lane in c.get("re_lanes", [])]
