"""The routed layout's host passes in their plain forms, as test oracles.

``coalesce_coo`` sums duplicates with ``np.add.at``; ``split_spill_entries``
sorts by (col, row) and returns no order; ``build_slot_perm`` sorts again
and fills the pads with five numpy passes over the slots;
``make_row_block_k`` counts with ``np.unique``; ``invert_plan`` inverts
every stage by a fancy-index scatter. ``oracle_layout`` is ``from_coo``'s
whole flow in those forms: the matrix coalesced, its hot side split, the
layout planned, then every column block sent through the whole prologue
again, capped and laid out. ``ops/sparse_perm.py`` must give what these
give, bit for bit.
"""

from __future__ import annotations

import hashlib

import numpy as np

from photon_ml_tpu.ops import fused_perm, routing, sparse_perm


def coalesce_coo(rows, cols, vals, n, d):
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    if rows.size:
        if rows.min() < 0 or rows.max() >= n:
            raise ValueError(f"row index out of range [0, {n})")
        if cols.min() < 0 or cols.max() >= d:
            raise ValueError(f"column index out of range [0, {d})")
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        boundary = np.empty(rows.size, dtype=bool)
        boundary[0] = True
        boundary[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
        seg = np.cumsum(boundary) - 1
        summed = np.zeros(int(boundary.sum()), dtype=np.float64)
        np.add.at(summed, seg, vals)
        rows, cols = rows[boundary], cols[boundary]
        vals = summed.astype(np.float32)
    return rows, cols, vals


def split_spill_entries(rows, cols, vals, col_counts, cap):
    nnz = rows.size
    corder = np.lexsort((rows, cols))
    col_starts = np.zeros(col_counts.size + 1, dtype=np.int64)
    np.cumsum(col_counts, out=col_starts[1:])
    rank = np.arange(nnz, dtype=np.int64) - col_starts[cols[corder]]
    spill = np.zeros(nnz, dtype=bool)
    spill[corder] = rank >= cap
    keep = ~spill
    return (
        rows[keep], cols[keep], vals[keep],
        rows[spill], cols[spill], vals[spill],
    )


def make_row_block_k(rows, cols, n, d, pow2=False):
    cache = {}

    def row_block_k(t):
        if t not in cache:
            d_b = -(-d // t)
            key = rows * t + (cols // d_b)
            if key.size:
                _, counts = np.unique(key, return_counts=True)
                k = int(counts.max())
            else:
                k = 1
            if pow2:
                k = 1 << max(int(k) - 1, 0).bit_length()
            cache[t] = max(k, 1)
        return cache[t]

    return row_block_k


def build_slot_perm(rows, cols, n, d, K, KP, S, row_counts, col_counts):
    """(ell_pos, csc_pos, perm), csc_pos in the entries' own order."""
    nnz = rows.size
    row_starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(row_counts, out=row_starts[1:])
    ell_pos = rows * K + (np.arange(nnz, dtype=np.int64) - row_starts[rows])
    corder = np.lexsort((rows, cols))
    col_starts = np.zeros(d + 1, dtype=np.int64)
    np.cumsum(col_counts, out=col_starts[1:])
    csc_slot = np.arange(nnz, dtype=np.int64) - col_starts[cols[corder]]
    csc_pos = np.empty(nnz, dtype=np.int64)
    csc_pos[corder] = cols[corder] * KP + csc_slot
    perm = np.full(S, -1, dtype=np.int64)
    perm[csc_pos] = ell_pos
    free_dst = np.flatnonzero(perm < 0)
    used_src = np.zeros(S, dtype=bool)
    used_src[ell_pos] = True
    perm[free_dst] = np.flatnonzero(~used_src)
    return ell_pos, csc_pos, perm


def invert_plan(plan):
    stages = []
    for st in reversed(plan.stages):
        if isinstance(st, routing.LaneShuffle):
            inv = np.empty_like(st.idx)
            r = np.arange(st.idx.shape[0])[:, None]
            inv[r, st.idx] = np.broadcast_to(
                np.arange(routing.LANES, dtype=st.idx.dtype), st.idx.shape
            )
            stages.append(routing.LaneShuffle(idx=inv))
        elif isinstance(st, routing.SublaneShuffle):
            total, R = st.idx.shape[0], st.rows
            blk = st.idx.reshape(total // R, R, routing.LANES)
            inv = np.empty_like(blk)
            g = np.arange(total // R)[:, None, None]
            c = np.arange(routing.LANES)[None, None, :]
            i = np.broadcast_to(
                np.arange(R, dtype=st.idx.dtype)[None, :, None], blk.shape
            )
            inv[g, blk, c] = i
            stages.append(routing.SublaneShuffle(
                idx=inv.reshape(total, routing.LANES), rows=R
            ))
        elif isinstance(st, routing.Enter):
            stages.append(routing.Leave(blocks=st.blocks, rows=st.rows))
        else:
            stages.append(routing.Enter(blocks=st.blocks, rows=st.rows))
    return routing.PermPlan(size=plan.size, stages=stages)


def _prologue(rows, cols, vals, shape, max_nnz_row, hot_col_threshold,
              max_hot_cols):
    n, d = shape
    rows, cols, vals = coalesce_coo(rows, cols, vals, n, d)
    nnz = rows.size
    if max_nnz_row is not None and nnz:
        k_orig = int(np.bincount(rows, minlength=n).max())
        if k_orig > int(max_nnz_row):
            raise ValueError(f"row with {k_orig} nnz exceeds max_nnz_row")
    hot_ids = sparse_perm.select_hot_cols(
        rows, cols, n, d, hot_col_threshold, max_hot_cols
    )
    hot_matrix = None
    if hot_ids is not None:
        rows, cols, vals, hot_matrix = sparse_perm.split_hot_entries(
            rows, cols, vals, n, d, hot_ids
        )
        nnz = rows.size
    row_counts = np.bincount(rows, minlength=n) if nnz else np.zeros(n, np.int64)
    col_counts = np.bincount(cols, minlength=d) if nnz else np.zeros(d, np.int64)
    return rows, cols, vals, hot_matrix, hot_ids, row_counts, col_counts


def oracle_layout(rows, cols, vals, shape, engine, max_nnz_row=None,
                  hot_col_threshold=None, max_hot_cols=128, kp_cap="auto",
                  col_split="auto"):
    """What ``<engine>.from_coo`` lays out, on the host: a dict with
    ``blocks`` (one dict a block, None for a block with no entries),
    ``col_bounds`` (None for one block) and the hot side. A block holds K,
    KP, S, the sha1 of its perm, its plan and inverse, its spill and its
    values (``ell_flat`` for the fused engine, ``ell_values`` and
    ``csc_values`` for the stage-by-stage one)."""
    n, d = shape
    fused = engine == "fused"
    size_floor = fused_perm.MIN_FUSED_SIZE if fused else 0
    group = fused_perm._next_pow2 if fused else int
    rows, cols, vals, hot_matrix, hot_ids, row_counts, col_counts = _prologue(
        rows, cols, vals, shape, max_nnz_row, hot_col_threshold, max_hot_cols
    )
    nnz = rows.size
    K = max(group(int(row_counts.max()) if nnz else 1),
            group(int(max_nnz_row)) if max_nnz_row is not None else 1, 1)
    KP = max(group(int(col_counts.max()) if nnz else 1), 1)
    cap, t = None, 1
    if nnz:
        cap, t = sparse_perm.resolve_layout(
            kp_cap, col_split, col_counts, n, d, K, KP, size_floor=size_floor,
            row_block_k=make_row_block_k(rows, cols, n, d, pow2=fused),
        )
    out = {"hot_matrix": hot_matrix, "hot_ids": hot_ids, "col_bounds": None}
    if t > 1:
        d_b = -(-d // t)
        bounds = [min(b * d_b, d) for b in range(t + 1)]
        blocks = []
        for b in range(t):
            m = cols // d_b == b
            width = bounds[b + 1] - bounds[b]
            if width <= 0 or not m.any():
                blocks.append(None)
                continue
            sub = oracle_layout(
                rows[m], cols[m] - bounds[b], vals[m], (n, width), engine,
                max_hot_cols=0, kp_cap=cap, col_split=1,
            )
            blocks.append(sub["blocks"][0])
        out.update(blocks=blocks, col_bounds=bounds)
        return out
    spill = None
    if cap is not None:
        rows, cols, vals, sr, sc, sv = split_spill_entries(
            rows, cols, vals, col_counts, cap
        )
        spill = (sr, sc, sv)
        row_counts = np.bincount(rows, minlength=n)
        col_counts = np.minimum(col_counts, cap)
        KP = cap
    S = routing.valid_size(max(n * K, d * KP, size_floor, 1))
    ell_pos, csc_pos, perm = build_slot_perm(
        rows, cols, n, d, K, KP, S, row_counts, col_counts
    )
    plan = routing.build_plan(perm)
    block = {
        "K": K, "KP": KP, "S": S, "spill": spill, "plan": plan,
        "plan_inv": invert_plan(plan),
        "perm_sha1": hashlib.sha1(perm.tobytes()).hexdigest(),
    }
    if fused:
        ell_flat = np.zeros(S, dtype=np.float32)
        ell_flat[ell_pos] = vals
        block["ell_flat"] = ell_flat
    else:
        ell_values = np.zeros((n, K), dtype=np.float32)
        ell_values.reshape(-1)[ell_pos] = vals
        csc_values = np.zeros((d, KP), dtype=np.float32)
        csc_values.reshape(-1)[csc_pos] = vals
        block.update(ell_values=ell_values, csc_values=csc_values)
    out["blocks"] = [block]
    return out
