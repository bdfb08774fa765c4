"""Bytes set-up handed to the devices: the sum of the ``bytes`` of the
``data/upload`` spans before the window, every replica counted."""
from benchmarks.layer_metrics import _setup

NAME, UNIT, SOURCE = "setup_upload_bytes", "bytes", "program_counter"


def read(context):
    uploads = _setup.before_window(context, ("data/upload",))
    if not uploads:
        return None
    return sum(s["attrs"].get("bytes", 0) for s in uploads)
