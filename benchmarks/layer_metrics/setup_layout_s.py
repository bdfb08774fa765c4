"""Host seconds of set-up that decide the routed layout and place the
values in it: the union of ``route/layout`` (coalescing, the hot side, the
layout planner, the cut into column blocks or grid tiles, the spill),
``route/slot_perm`` (slot positions and the permutation) and ``route/place``
(the inverse plan, the values laid out on the host) before the window."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "setup_layout_s", "s", "program_span"


def read(context):
    return _setup.union_before(context, _setup.LAYOUT)
