"""Host seconds a step spends lowering jaxprs to MLIR modules: the union of
the program's ``jit/lower`` spans inside the window, over the steps."""
from benchmarks.layer_metrics import _compile

NAME, UNIT, SOURCE = "lower_s.step", "s/step", "program_span"


def read(context):
    return _compile.union_per_step(context, _compile.compile_spans(context, ("jit/lower",)))
