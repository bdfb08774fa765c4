"""Compile seconds (trace, lowering, backend) caused by the factored
coordinate: the union of the program's ``jit/*`` spans inside the window
that were recorded under an ``mf/update`` span (the projection program, the
latent solves' programs, the matrix solve), over the steps. None where no
factored coordinate trains."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "mf_compile_s.step", "s/step", "program_span"


def read(context):
    if not any(s["name"] == "mf/update" for s in context["spans"]):
        return None
    return _compile.union_per_step(context, _compile.compile_spans(context, under="mf/update"))
