"""Compile seconds (trace, lowering, backend) caused by the fixed-effect
solve: the union of the program's ``jit/*`` spans inside the window that
were recorded under a ``glm/train`` span, over the steps."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "fe_compile_s.step", "s/step", "program_span"


def read(context):
    return _compile.union_per_step(context, _compile.compile_spans(context, under="glm/train"))
