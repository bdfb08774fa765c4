"""Shared by the readers: spans of the program's host tracer inside the
measured window. Host seconds: the tracer's device barrier is unverified on
a TPU stream (PERF.md section 7)."""


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
