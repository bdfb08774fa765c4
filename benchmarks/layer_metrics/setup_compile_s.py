"""Host seconds of set-up in JAX's compile phases: the union of the
``jit/trace``, ``jit/lower`` and ``jit/backend`` spans that start before
the window (the feature build's and the warm-up step's traces, lowerings,
compiles and loads from the persistent cache)."""
from benchmarks.layer_metrics import _compile, _setup

NAME, UNIT, SOURCE = "setup_compile_s", "s", "program_span"


def read(context):
    return _setup.union_before(context, _compile.PHASES)
