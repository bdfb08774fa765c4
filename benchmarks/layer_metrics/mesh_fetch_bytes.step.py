"""Bytes of device arrays the held-out scoring brought to the host, a step:
the ``fetch_bytes`` of the window's ``cd/validate`` spans (the program's
counter ``mesh.fetch_bytes``, fed by ``parallel.mesh.fetch_global``, read
before and after each validation), summed over the steps: on a device grid
the sharded models gathered whole for the host's scoring. None where the
spans carry no such count or name no mesh of more than one device (one
chip's models are fetched too, but no gather across devices stands behind
the bytes).""" 
from benchmarks.layer_metrics import _spans

NAME, UNIT, SOURCE = "mesh_fetch_bytes.step", "bytes/step", "program_counter"


def read(context):
    on_mesh = [s for s in context["spans"] if s["name"] == "game/build_coordinate"
               and int(s["attrs"].get("devices") or 1) > 1]
    if not on_mesh:
        return None
    counted = [s["attrs"]["fetch_bytes"] for s in _spans.in_window(context, "cd/validate")
               if s["attrs"].get("fetch_bytes") is not None]
    if not counted:
        return None
    return float(sum(counted)) / context["steps"]
