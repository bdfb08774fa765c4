"""Host seconds of set-up handing host arrays to the devices: the union of
the ``data/upload`` spans before the window (the routed features and
plans, the random-effect buckets, the labels, weights and offsets, a grid
tile's own pieces). Each waits for the arrays it uploaded."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "setup_upload_s", "s", "program_span"


def read(context):
    return _setup.union_before(context, ("data/upload",))
