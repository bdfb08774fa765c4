"""The routed sparse-map kernels' share of their roofline: the least time
the chip needs for the window's fixed-effect maps (work.fe_map: the matrix
once at 8 bytes a nonzero, the input vector read, the output written; bytes
bound it) over the summed device time of the routed-map Pallas kernels in
the trace (tracing.group_name: custom calls to ``tpu_custom_call`` whose
operands are plan indices).

The count of maps is a lower bound: two an iteration of every fixed-effect
solve plus two for its first evaluation; line-search retries and the
score-plane matvecs run the same kernels and are in the time but not in the
count, and the spill side's scatter-add and the maps' XLA prologue are in
neither. So the share reads low, never high. None where the trace names no
such kernel (a later PR that renames or removes them leaves this silent;
``step_mfu`` still bounds the step)."""
from benchmarks import work
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "sparse_map_roofline", "%", "device_trace"
KERNELS = "pallas:routed_map_kernel"


def read(context):
    trace, peaks = context["trace"], context["peaks"]
    if trace is None or peaks is None:
        return None
    seconds = trace["self_times"].get(KERNELS)
    iterations = [c["fe_iterations"] for c in _spans.window_counters(context)
                  if c.get("fe_iterations") is not None]
    if not seconds or not iterations:
        return None
    maps = sum(2 * (i + 1) for i in iterations)
    shapes = context["shapes"]
    flops, nbytes = work.fe_map(shapes["nnz"], shapes["n_rows"], shapes["n_cols"])
    least, _ = work.least_seconds(maps * flops, maps * nbytes, peaks)
    return 100.0 * least / seconds
