"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, the idle share, per-kernel sums, the
operations that took most time, and the idle gaps by what the host was doing.

``load_events`` reads the profiler's ``.xplane.pb`` with nothing but JAX and
turns it into plain dicts; everything after that is arithmetic on those, so
``selfcheck`` can run it on the small recorded trace under fixtures/.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
MARK = "bench/clock_mark"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_events(path: str) -> Tuple[List[dict], Optional[int]]:
    """(device-op events, trace time in ns of the host's clock mark).

    An event is {"device": int, "name": str, "start_ns": float, "dur_ns":
    float} for every event on an operations line of a device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[dict] = []
    mark = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in OP_LINES:
                dev = int(m.group(1))
                for e in line.events:
                    events.append({
                        "device": dev, "name": e.name,
                        "start_ns": float(e.start_ns), "dur_ns": float(e.duration_ns),
                    })
            elif not m and mark is None:
                for e in line.events:
                    if e.name == MARK:
                        mark = int(e.start_ns)
                        break
    return events, mark


_SUFFIX = re.compile(r"[.\d]+$")
_OP_KIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def group_name(name: str) -> str:
    """A short, stable name for a device operation. The trace names an
    operation by its whole HLO line (``%fusion.12 = f32[...] fusion(...)``);
    operations that differ only in their number are one group. A Pallas
    kernel is a ``custom-call`` to ``tpu_custom_call``, named after whatever
    jaxpr it sits in (``%_lambda_.7``, ``%body.3``, ``%tpu_custom_call.1``);
    the ones with an int8 operand (``s8[...]``: routing-plan stage indices)
    are the routed sparse-map kernels (``_descend_call``, ``_base_call``,
    ``_ascend_call`` of ops/fused_perm.py, which the trace does not tell
    apart)."""
    head, _, rest = name.partition(" = ")
    stem = _SUFFIX.sub("", head.lstrip("%")) or head
    if 'custom_call_target="tpu_custom_call"' in name:
        return "pallas:routed_map_kernel" if " s8[" in rest else f"pallas:{stem}"
    kind = _OP_KIND.search(" " + rest) if rest else None
    return f"{stem} ({kind.group(1)})" if kind and kind.group(1) not in stem else stem


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """(total length, merged intervals) of a set of [start, end) in ns."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged) / 1e9, [(a, b) for a, b in merged]


def self_times(events: Sequence[dict]) -> Dict[str, float]:
    """Seconds by operation name, each event's time less its children's (a
    ``while`` spans its body's operations on the same line)."""
    total: Dict[str, float] = defaultdict(float)
    by_device: Dict[int, List[dict]] = defaultdict(list)
    for e in events:
        by_device[e["device"]].append(e)
    for evs in by_device.values():
        evs = sorted(evs, key=lambda e: (e["start_ns"], -e["dur_ns"]))
        stack: List[List] = []  # [end_ns, name, self_ns]
        for e in evs:
            start, end = e["start_ns"], e["start_ns"] + e["dur_ns"]
            while stack and stack[-1][0] <= start:
                done = stack.pop()
                total[done[1]] += done[2] / 1e9
            if stack:
                stack[-1][2] -= min(e["dur_ns"], stack[-1][0] - start)
            stack.append([end, group_name(e["name"]), e["dur_ns"]])
        while stack:
            done = stack.pop()
            total[done[1]] += done[2] / 1e9
    return dict(total)


def reduce_trace(events: Sequence[dict], window_ns: Tuple[float, float], devices: int) -> dict:
    """busy_s (averaged over the devices used), window_s, idle share, the
    merged busy intervals of device 0 and self time by operation."""
    lo, hi = window_ns
    clipped = []
    for e in events:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a:
            clipped.append({**e, "start_ns": a, "dur_ns": b - a})
    busy_total, merged0 = 0.0, []
    for dev in range(devices):
        s, merged = union_seconds(
            (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in clipped if e["device"] == dev
        )
        busy_total += s
        if dev == 0:
            merged0 = merged
    window_s = (hi - lo) / 1e9
    busy_s = busy_total / max(devices, 1)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "busy_intervals": merged0,
        "self_times": self_times(clipped),
        "events": len(clipped),
    }


def idle_gaps(busy_intervals: Sequence[Tuple[float, float]], window_ns: Tuple[float, float],
              host_spans: Sequence[Tuple[float, float, str]], longest: int = 400) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing. Every moment of a gap
    between busy intervals goes to the last host span in ``host_spans`` that
    covers it (so order them outermost first, innermost last), or to
    "host:unattributed". Only the ``longest`` gaps are walked; the rest is
    one sum. ``host_spans`` are (start_ns, end_ns, label) on the trace's
    clock."""
    lo, hi = window_ns
    gaps, cursor = [], lo
    for a, b in busy_intervals:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    by_label: Dict[str, float] = defaultdict(float)
    gaps.sort(key=lambda g: g[0] - g[1])
    for a, b in gaps[:longest]:
        inside = [(max(sa, a), min(sb, b), label) for sa, sb, label in host_spans
                  if sb > a and sa < b]
        cuts = sorted({a, b} | {p for sa, sb, _ in inside for p in (sa, sb)})
        for ca, cb in zip(cuts, cuts[1:]):
            mid, label = (ca + cb) / 2, "host:unattributed"
            for sa, sb, name in inside:
                if sa <= mid < sb:
                    label = name
            by_label[label] += (cb - ca) / 1e9
    rest = sum(b - a for a, b in gaps[longest:]) / 1e9
    if rest:
        by_label["short gaps, not attributed"] += rest
    return sorted(by_label.items(), key=lambda kv: -kv[1])
