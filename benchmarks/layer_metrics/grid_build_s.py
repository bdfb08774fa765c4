"""Host seconds in which a tile of a device grid was being built: the union
of the ``grid/build_tile`` spans (a tile's routing or plan-cache read, its
layout and its upload to its own device; tiles are built a few at a time, so
the spans overlap). Part of ``routing_prep_s``. None where the program makes
no such span."""
from photon_ml_tpu.telemetry import union_seconds

NAME, UNIT, SOURCE = "grid_build_s", "s", "program_span"


def read(context):
    tiles = [(s["start"], s["end"]) for s in context["spans"] if s["name"] == "grid/build_tile"]
    return union_seconds(tiles) if tiles else None
