"""Host seconds of set-up building the random effects' datasets: the union
of the ``re/build_dataset`` spans before the window (grouping by entity,
projection, bucket planning and packing; the upload after it is a
``data/upload``). None in a cell with no random effect."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "setup_re_build_s", "s", "program_span"


def read(context):
    return _setup.union_before(context, ("re/build_dataset",))
