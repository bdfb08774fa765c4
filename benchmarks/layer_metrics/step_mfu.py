"""The whole step's share of the chip's peak: the least time the chip needs
for the window's algorithmic work (work.step_work: from shapes, the
``glm/solve`` spans' ``evaluations`` and ``hessian_vecs`` and the
random-effect lane counters; bytes bound it) over the window. Needs no
kernel name, so it still bounds a step whose kernels a later PR renames or
removes."""
from benchmarks import work
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "step_mfu", "%", "program_counter"


def read(context):
    if context["peaks"] is None:
        return None
    flops, nbytes = work.step_work(
        context["shapes"], _spans.window_solves(context), _spans.window_lanes(context))
    if not nbytes:
        return None
    least, _ = work.least_seconds(flops, nbytes, context["peaks"])
    return 100.0 * least / context["window_s"]
