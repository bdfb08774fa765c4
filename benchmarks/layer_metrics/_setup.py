"""Shared by the readers of set-up: the program's spans that start before
the measured window opens. The tracer is on from before the driver's
``prepare``, so the feature build (``route/layout``, ``route/slot_perm``,
``route/plan``, ``route/place``), the uploads (``data/upload``), the
random-effect builds (``re/build_dataset``) and the warm-up step's compiles
all lie there. Times are *unions* of the spans cut at the window's start,
never sums: a grid's tiles are built four at once on threads of their own,
so their spans overlap, and compile phases nest. A program that makes none
of a reader's spans (one older than these spans) gives ``None``."""
from benchmarks.layer_metrics._compile import union_seconds

LAYOUT = ("route/layout", "route/slot_perm", "route/place")


def before_window(context, names):
    lo = context["window"][0]
    return [s for s in context["spans"] if s["name"] in names and s["start"] < lo]


def union_before(context, names):
    spans = before_window(context, names)
    if not spans:
        return None
    lo = context["window"][0]
    return union_seconds((s["start"], min(s["end"], lo)) for s in spans)
