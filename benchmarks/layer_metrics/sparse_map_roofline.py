"""The routed sparse-map kernels' share of their roofline: the least time
the chip needs for the window's fixed-effect maps (work.fe_map: the matrix
once at 8 bytes a nonzero, the input vector read, the output written; bytes
bound it) over the summed device time of the routed-map Pallas kernels in
the trace (tracing.group_name: custom calls to ``tpu_custom_call`` whose
operands are plan indices).

The count is the algorithm's minimum (work.fe_maps): two maps a
value-and-gradient and two a Hessian-vector product, from the ``evaluations``
and ``hessian_vecs`` of the window's ``glm/solve`` spans. The time is every
call's: a map the program makes beyond those (``hessian_vec`` computes the
margins again at every product, the score-plane matvecs after a solve) is in
the time and not in the count, and the spill side's scatter-add and the maps'
XLA prologue are in neither. So the share reads low, never high. None where
the trace names no such kernel (a later PR that renames or removes them
leaves this silent; ``step_mfu`` still bounds the step) or the spans carry
no counts."""
from benchmarks import work
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "sparse_map_roofline", "%", "device_trace"
KERNELS = "pallas:routed_map_kernel"


def read(context):
    trace, peaks = context["trace"], context["peaks"]
    if trace is None or peaks is None:
        return None
    seconds = trace["self_times"].get(KERNELS)
    maps = work.fe_maps(_spans.window_solves(context))
    if not seconds or not maps:
        return None
    shapes = context["shapes"]
    flops, nbytes = work.fe_map(shapes["nnz"], shapes["n_rows"], shapes["n_cols"])
    least, _ = work.least_seconds(maps * flops, maps * nbytes, peaks)
    return 100.0 * least / seconds
