"""Host seconds in the factored coordinate's updates: the ``cd/coordinate``
spans of the window that hold an ``mf/update`` span (the alternations'
projections, latent solves and matrix solves, the regroup of the offsets and
the score; device wait included), over the steps."""
from benchmarks.layer_metrics import _mf, _spans

NAME, UNIT, SOURCE = "mf_solve_s.step", "s/step", "program_span"


def read(context):
    spans = _mf.coordinate_spans(context)
    return _spans.seconds(spans) / context["steps"] if spans else None
