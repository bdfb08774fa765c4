"""Static-permutation routing through a radix-128 Clos/Benes network.

Why this exists: the GLM hot loop is a sparse matvec/rmatvec pair
(reference: ValueAndGradientAggregator.scala:132-153 runs sparse axpy per
Spark partition). A TPU has no vectorized arbitrary gather/scatter — XLA
lowers both to a ~10ns/element scalar loop — but it *does* have a fast
within-row 128-lane shuffle (`tpu.dynamic_gather`), fast transposes, and
fast dense reductions. Any static permutation of an ``[R, 128]`` array
factors (Slepian–Duguid / Clos routing) into

    (within-row lane shuffle) o (per-lane row movement) o (within-row shuffle)

where the middle stage recurses with R -> R/128 until R <= 8, at which point
it is a sublane shuffle inside one hardware tile. Routing = proper
128-edge-coloring of the (source row, destination row) incidence multigraph,
computed once at data-prep time by Euler-split halving
(native/eulercolor.cpp). At run time a permutation of N elements costs
~2*log_128(N)-1 lane-shuffle passes — all dense vector work, no scalar core.

This module is host-side (numpy): it builds the stage plan and provides a
reference ``host_apply`` used by tests. Device execution lives in
``ops/permute_net.py``; the sparse-feature engine built on top lives in
``ops/sparse_perm.py``.
"""

from __future__ import annotations

import ctypes
import os
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Sequence, Union

import numpy as np

LANES = 128
MAX_SUBLANES = 8  # hardware sublane-gather window (tpu.dynamic_gather dim 0)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_SRC = _NATIVE_DIR / "eulercolor.cpp"

# The numpy colorer walks every pairing cycle in Python: fine for the plans
# tests build on a host without g++, out of the question for a production
# plan (16.7M slots per block of the 2^24-column tile).
_NUMPY_COLOR_MAX_EDGES = 1 << 18

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False

_LAYOUT_SRC = _NATIVE_DIR / "routedlayout.cpp"
_layout_lib: Optional[ctypes.CDLL] = None
_layout_tried = False
# index dtypes the native inverse takes: a routed plan holds int32, one read
# back from the plan cache int8
_INVERT_DTYPES = {np.dtype(np.int8): "i8", np.dtype(np.int32): "i32"}


def _load_native():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    from photon_ml_tpu.utils.nativelib import build_and_load

    lib = build_and_load(_SRC)
    if lib is not None:
        lib.euler_color.restype = ctypes.c_int
        lib.euler_color.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
    _lib = lib
    return _lib


def _load_layout():
    """The routed layout's native passes (native/routedlayout.cpp: the slot
    permutation, the stage inverses), or None on a host without g++."""
    global _layout_lib, _layout_tried
    if _layout_tried:
        return _layout_lib
    _layout_tried = True
    from photon_ml_tpu.utils.nativelib import build_and_load

    lib = build_and_load(_LAYOUT_SRC)
    if lib is not None:
        lib.fill_slot_perm.restype = ctypes.c_int
        lib.fill_slot_perm.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        for suffix in _INVERT_DTYPES.values():
            lanes = getattr(lib, f"invert_lanes_{suffix}")
            lanes.restype = ctypes.c_int
            lanes.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ]
            sub = getattr(lib, f"invert_sublanes_{suffix}")
            sub.restype = ctypes.c_int
            sub.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int,
            ]
    _layout_lib = lib
    return _layout_lib


def inverse_engine() -> str:
    """What :meth:`PermPlan.invert` runs on this host: ``"native"`` (one
    threaded pass a stage) or ``"numpy"`` (the fancy-index fallback)."""
    return "numpy" if _load_layout() is None else "native"


def _euler_color_numpy(src: np.ndarray, dst: np.ndarray, deg: int,
                       n_src: int, n_dst: int) -> np.ndarray:
    """Fallback colorer: Euler-split halving with a sequential cycle walk.

    Pairings are built vectorized; the alternate 2-coloring walks each
    pairing cycle in Python. Correct at any size; used only when the native
    colorer (eulercolor.cpp) cannot be built, so speed is secondary.
    """
    n_edges = src.shape[0]
    color = np.zeros(n_edges, dtype=np.int32)
    levels = int(deg).bit_length() - 1

    def pair(subset: np.ndarray, key: np.ndarray) -> np.ndarray:
        order = subset[np.argsort(key[subset], kind="stable")]
        partner = np.empty(n_edges, dtype=np.int64)
        partner[order[0::2]] = order[1::2]
        partner[order[1::2]] = order[0::2]
        return partner

    classes = [np.arange(n_edges, dtype=np.int64)]
    for level in range(levels):
        next_classes = []
        for subset in classes:
            ps = pair(subset, src)
            pd = pair(subset, dst)
            visited = np.zeros(n_edges, dtype=bool)
            bit = np.zeros(n_edges, dtype=bool)
            for e0 in subset.tolist():
                if visited[e0]:
                    continue
                e, b, via_src = e0, False, True
                while True:
                    visited[e] = True
                    bit[e] = b
                    e = int(ps[e] if via_src else pd[e])
                    via_src = not via_src
                    b = not b
                    if e == e0:
                        break
            sel = bit[subset]
            color[subset[sel]] |= 1 << (levels - 1 - level)
            next_classes.append(subset[~sel])
            next_classes.append(subset[sel])
        classes = next_classes
    return color


def euler_color(src: np.ndarray, dst: np.ndarray, deg: int, n_src: int,
                n_dst: int) -> np.ndarray:
    """Proper ``deg``-edge-coloring of a regular bipartite multigraph.

    Every src node and dst node must have exactly ``deg`` incident edges;
    ``deg`` must be a power of two. Returns ``color[e] in [0, deg)`` with no
    two edges of equal color sharing a src node or a dst node.
    """
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    n_edges = src.shape[0]
    assert deg > 0 and (deg & (deg - 1)) == 0, "deg must be a power of two"
    assert n_edges == n_src * deg == n_dst * deg
    lib = _load_native()
    if lib is None:
        if n_edges > _NUMPY_COLOR_MAX_EDGES:
            raise RuntimeError(
                f"routing a plan of {n_edges} slots needs the native Euler "
                "colorer (native/eulercolor.cpp), and this host has no g++ "
                "to build it"
            )
        return _euler_color_numpy(src, dst, deg, n_src, n_dst)
    color = np.zeros(n_edges, dtype=np.int32)
    rc = lib.euler_color(
        ctypes.c_int64(n_edges),
        ctypes.c_int32(deg),
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(n_src),
        ctypes.c_int32(n_dst),
        color.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc != 0:
        raise RuntimeError(
            f"native euler_color failed (rc={rc}) on a {deg}-regular graph "
            f"of {n_edges} edges"
        )
    return color


# --------------------------------------------------------------------------
# Stage types. All arrays are host numpy; permute_net converts to device.
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class LaneShuffle:
    """y[r, c] = x[r, idx[r, c]] — within-row 128-lane gather (form B)."""

    idx: np.ndarray  # [rows, 128] int32 in [0, 128)


@dataclass(frozen=True)
class SublaneShuffle:
    """Within consecutive blocks of ``rows`` rows (rows <= 8):
    y[g*rows + i, c] = x[g*rows + idx[g*rows + i, c], c] (form A)."""

    idx: np.ndarray  # [total_rows, 128] int32 in [0, rows)
    rows: int


@dataclass(frozen=True)
class Enter:
    """Relayout into the recursion: view [B, R, 128], transpose the last two
    axes, reshape to [B*128*(R//128), 128]. Pure XLA, ~free."""

    blocks: int
    rows: int


@dataclass(frozen=True)
class Leave:
    """Inverse of :class:`Enter` with the same (blocks, rows)."""

    blocks: int
    rows: int


Stage = Union[LaneShuffle, SublaneShuffle, Enter, Leave]


@dataclass
class PermPlan:
    """Executable decomposition of ``y = x[perm]`` into shuffle stages."""

    size: int  # padded network size (multiple of 128)
    stages: List[Stage]

    def invert(self) -> "PermPlan":
        """Plan for the inverse permutation (stages reversed + inverted):
        each stage in one native pass where :func:`inverse_engine` says so,
        else by numpy."""
        lib = _load_layout()
        inv_stages: List[Stage] = []
        for st in reversed(self.stages):
            if isinstance(st, LaneShuffle):
                inv_stages.append(LaneShuffle(idx=_invert_stage(st.idx, 0, lib)))
            elif isinstance(st, SublaneShuffle):
                inv_stages.append(SublaneShuffle(
                    idx=_invert_stage(st.idx, st.rows, lib), rows=st.rows
                ))
            elif isinstance(st, Enter):
                inv_stages.append(Leave(blocks=st.blocks, rows=st.rows))
            elif isinstance(st, Leave):
                inv_stages.append(Enter(blocks=st.blocks, rows=st.rows))
            else:  # pragma: no cover
                raise TypeError(st)
        return PermPlan(size=self.size, stages=inv_stages)


def _invert_stage(idx: np.ndarray, rows: int, lib) -> np.ndarray:
    """The inverse of one shuffle stage's ``[total_rows, 128]`` indices, of
    the same dtype: a lane shuffle's where ``rows`` is 0, else a sublane
    shuffle's over blocks of ``rows`` rows."""
    if idx.ndim != 2 or idx.shape[1] != LANES:
        raise ValueError(f"stage indices of shape {idx.shape}, not [rows, {LANES}]")
    suffix = _INVERT_DTYPES.get(idx.dtype)
    if lib is None or suffix is None:
        return (
            _invert_lanes_numpy(idx) if not rows
            else _invert_sublanes_numpy(idx, rows)
        )
    idx = np.ascontiguousarray(idx)
    inv = np.empty_like(idx)
    threads = ctypes.c_int(max(1, min(os.cpu_count() or 1, 16)))
    if not rows:
        rc = getattr(lib, f"invert_lanes_{suffix}")(
            idx.shape[0], idx.ctypes.data, inv.ctypes.data, threads
        )
    else:
        rc = getattr(lib, f"invert_sublanes_{suffix}")(
            idx.shape[0], rows, idx.ctypes.data, inv.ctypes.data, threads
        )
    if rc != 0:
        raise ValueError(
            f"stage indices are not a permutation within each "
            f"{'row' if not rows else f'block of {rows} rows'} (rc={rc})"
        )
    return inv


def _invert_lanes_numpy(idx: np.ndarray) -> np.ndarray:
    """``inv[r, idx[r, c]] = c`` by a fancy-index scatter."""
    inv = np.empty_like(idx)
    r = np.arange(idx.shape[0])[:, None]
    inv[r, idx] = np.broadcast_to(np.arange(LANES, dtype=idx.dtype), idx.shape)
    return inv


def _invert_sublanes_numpy(idx: np.ndarray, R: int) -> np.ndarray:
    """``inv[g*R + idx[g*R + i, c], c] = i`` by a fancy-index scatter."""
    total = idx.shape[0]
    blk = idx.reshape(total // R, R, LANES)
    inv = np.empty_like(blk)
    g = np.arange(total // R)[:, None, None]
    c = np.arange(LANES)[None, None, :]
    i = np.broadcast_to(np.arange(R, dtype=idx.dtype)[None, :, None], blk.shape)
    inv[g, blk, c] = i
    return inv.reshape(total, LANES)


def valid_size(n: int) -> int:
    """Smallest routable network size >= n: c * 128**(m+1), c in {1,2,4,8}.

    c is restricted to powers of two so the recursion base emits
    SublaneShuffle stages with rows in {1,2,4,8} — shapes the vectorized
    Pallas sublane kernel handles; a non-power-of-two c would force the
    scalar XLA gather fallback on TPU for that stage.
    """
    if n <= 0:
        raise ValueError("size must be positive")
    base = LANES
    while True:
        for c in (1, 2, 4, 8):
            if c * base >= n:
                return c * base
        base *= LANES


def _route(sigma: np.ndarray, B: int, R: int, stages: List[Stage]) -> None:
    """Emit stages for per-block permutations.

    sigma: [B, R, 128] int64 — for each block, destination position (r, c)
    holds the *source* flat position (rs*128 + cs) within the same block.
    """
    rs, cs = np.divmod(sigma, LANES)  # [B, R, 128]
    b_ids = np.arange(B, dtype=np.int64)[:, None, None]
    rd = np.broadcast_to(np.arange(R, dtype=np.int64)[None, :, None], sigma.shape)
    src_node = (b_ids * R + rs).ravel()
    dst_node = (b_ids * R + rd).ravel()
    color = euler_color(src_node, dst_node, LANES, B * R, B * R).astype(np.int64)

    # First lane shuffle: x1[rs, color] = x[rs, cs]
    la = np.empty(B * R * LANES, dtype=np.int32)
    la[src_node * LANES + color] = cs.ravel().astype(np.int32)
    stages.append(LaneShuffle(idx=la.reshape(B * R, LANES)))

    # Middle stage: per-lane row movement m[rd, color] = rs (block-local).
    m = np.empty(B * R * LANES, dtype=np.int64)
    m[dst_node * LANES + color] = rs.ravel()
    m = m.reshape(B, R, LANES)

    if R <= MAX_SUBLANES:
        stages.append(
            SublaneShuffle(idx=m.reshape(B * R, LANES).astype(np.int32), rows=R)
        )
    else:
        assert R % LANES == 0, f"unroutable row count {R}"
        R1 = R // LANES
        # Relayout: new block (b, lane c); new position (g, j) holds old
        # (b, g*128 + j, c). Element wanted at new (b, c, gd, jd) comes from
        # old row m[b, gd*128+jd, c] = gs*128 + js -> new (b, c, gs, js).
        stages.append(Enter(blocks=B, rows=R))
        m_t = np.transpose(m, (0, 2, 1))  # [B, 128, R] indexed by (b, c, rd)
        sigma2 = m_t.reshape(B * LANES, R1, LANES)  # values are rs = gs*128+js
        _route(sigma2, B * LANES, R1, stages)
        stages.append(Leave(blocks=B, rows=R))

    # Final lane shuffle: y[rd, cd] = x2[rd, color]
    lb = color.astype(np.int32).reshape(B * R, LANES)
    stages.append(LaneShuffle(idx=lb))


def build_plan(perm: Sequence[int] | np.ndarray, size: Optional[int] = None) -> PermPlan:
    """Build a plan computing ``y = x[perm]`` (gather convention).

    ``perm`` must be a bijection over [0, len(perm)). The network size is
    padded up to :func:`valid_size`; padded positions map identically.
    """
    perm = np.asarray(perm, dtype=np.int64)
    n = perm.shape[0]
    S = valid_size(max(n, 1) if size is None else size)
    if S < n:
        raise ValueError(f"requested size {size} < permutation length {n}")
    full = np.arange(S, dtype=np.int64)
    full[:n] = perm
    # sanity: bijection
    if np.unique(perm).shape[0] != n or (n and perm.max() >= n):
        raise ValueError("perm is not a bijection over its domain")
    stages: List[Stage] = []
    _route(full.reshape(1, S // LANES, LANES), 1, S // LANES, stages)
    return PermPlan(size=S, stages=stages)


def host_apply(plan: PermPlan, x: np.ndarray) -> np.ndarray:
    """Reference execution of a plan on host (numpy). Returns the full
    padded [size] result (input and output live in different layouts whose
    real lengths may differ; callers slice what they need). For tests."""
    S = plan.size
    v = np.zeros(S, dtype=x.dtype)
    v[: x.shape[0]] = x
    v = v.reshape(S // LANES, LANES)
    for st in plan.stages:
        if isinstance(st, LaneShuffle):
            v = np.take_along_axis(v, st.idx, axis=1)
        elif isinstance(st, SublaneShuffle):
            rows = v.shape[0]
            blk = v.reshape(rows // st.rows, st.rows, LANES)
            idx = st.idx.reshape(rows // st.rows, st.rows, LANES)
            v = np.take_along_axis(blk, idx, axis=1).reshape(rows, LANES)
        elif isinstance(st, Enter):
            B, R = st.blocks, st.rows
            v = (
                v.reshape(B, R, LANES)
                .transpose(0, 2, 1)
                .reshape(B * LANES * (R // LANES), LANES)
            )
        elif isinstance(st, Leave):
            B, R = st.blocks, st.rows
            v = (
                v.reshape(B, LANES, R)
                .transpose(0, 2, 1)
                .reshape(B * R, LANES)
            )
        else:  # pragma: no cover
            raise TypeError(st)
    return v.reshape(S)
