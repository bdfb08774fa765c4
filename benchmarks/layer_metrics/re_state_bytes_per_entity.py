"""Bytes of random-effect solver state an entity, as the device lays it out:
the ``state_bytes`` of the window's ``re/solve_bucket`` spans (the bucket's
init program, compiled: its output's size) over their ``entities``. Only an
adaptive bucket's span carries it. None where no span does (a program older
than the attr, or a cell with no random effect)."""
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "re_state_bytes_per_entity", "B/entity", "program_counter"


def read(context):
    spans = [s["attrs"] for s in _spans.in_window(context, "re/solve_bucket")
             if s["attrs"].get("state_bytes") is not None]
    entities = sum(int(a["entities"]) for a in spans)
    if not entities:
        return None
    return sum(int(a["state_bytes"]) for a in spans) / entities
