"""2-D (data x feature) sharded fixed-effect features: the 1B-coefficient path.

Reference parity: the reference scales the fixed effect by partitioning
examples across executors and broadcasting the full coefficient vector to
every task each evaluation (DistributedObjectiveFunction convertFromVector;
treeAggregate ValueAndGradientAggregator.scala:243-247). That caps the
model at driver/executor heap. Here BOTH axes shard: the example axis over
a "data" mesh axis and the coefficient axis over a "feat" mesh axis, so a
1e9-coefficient vector lives as n_feat-way shards (w, grad, and the L-BFGS
history never materialize on one chip — SURVEY.md §7 hard part (d)).

Collectives per objective evaluation (all ICI, inserted here or by GSPMD):
- matvec:  psum of partial margins over "feat" (each device owns a column
  range; z_tile = X_tile @ w_local).
- rmatvec: psum of partial gradients over "data" (each device reduces its
  row block; output stays feat-sharded — no device ever holds full grad).
- loss sums / w dot products: GSPMD inserts the psums (sharded operands).

Each (data, feat) mesh tile holds its own sparse engine instance — the
permutation-routed Benes engine (TPU) or the ELL gather layout (CPU tests)
— routed with identical paddings so one compiled program serves the grid.
"""

from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.ops import routing
from photon_ml_tpu.utils.nativesort import lexsort_pairs
from photon_ml_tpu.ops.features import EllFeatures
from photon_ml_tpu.ops.sparse_perm import (
    _assemble,
    _hot_arrays,
    coalesce_coo,
    select_hot_cols,
    split_hot_entries,
)
from photon_ml_tpu.parallel.mesh import place as place_global, shard_map
from photon_ml_tpu.telemetry.span import span, upload

DATA_AXIS = "data"
FEAT_AXIS = "feat"
# A feat shard's width is a multiple of this. The solvers keep their history
# as [m, d / 128, 128] (opt/lbfgs.py history_zeros); reshaping a feat-sharded
# [d] vector to [d / 128, 128] stays on its device only where every shard
# holds whole rows of 128, and costs four collective-permutes of a row's
# halo a solve where it does not (docs/SCALING.md).
COLUMN_MULTIPLE = 128
# How many tiles route (or read their plans back) and upload at once: the
# native colorer and numpy's bulk passes run outside the interpreter lock,
# and a tile in flight holds a few GB of host arrays.
_MAX_TILE_WORKERS = 4


def grid_mesh(
    n_data: int, n_feat: int, devices=None
) -> Mesh:
    """(n_data x n_feat) mesh over the flat device list."""
    if devices is None:
        devices = jax.devices()
    need = n_data * n_feat
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_data, n_feat)
    return Mesh(grid, (DATA_AXIS, FEAT_AXIS))


@struct.dataclass
class GridShardedFeatures:
    """[n, d] sparse matrix tiled over a (data, feat) mesh.

    FeatureMatrix protocol over GLOBAL logical shapes with sharded layouts:
    ``matvec`` maps a feat-sharded ``w`` [d_pad] to data-sharded margins
    [n_pad]; ``rmatvec`` maps data-sharded coefficients to a feat-sharded
    gradient. Use :func:`shard_vector_feat` / :func:`shard_vector_data` to
    place vectors accordingly.
    """

    shards: object  # per-tile engine pytree; array leaves [n_dd, n_df, ...]
    mesh: Mesh = struct.field(pytree_node=False)
    num_rows_: int = struct.field(pytree_node=False)  # padded global rows
    num_cols_: int = struct.field(pytree_node=False)  # padded global cols

    @property
    def num_rows(self) -> int:
        return self.num_rows_

    @property
    def dim(self) -> int:
        return self.num_cols_

    def _n_dd(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    def _n_df(self) -> int:
        return self.mesh.shape[FEAT_AXIS]

    def matvec(self, w: jax.Array) -> jax.Array:
        w2 = w.reshape(self._n_df(), -1)

        def local_mv(shards, w_blk):
            tile = jax.tree.map(lambda a: a[0, 0], shards)
            z = tile.matvec(w_blk[0])
            return jax.lax.psum(z, FEAT_AXIS)[None]

        out = shard_map(
            local_mv,
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS, FEAT_AXIS), P(FEAT_AXIS)),
            out_specs=P(DATA_AXIS),
        )(self.shards, w2)
        return out.reshape(-1)

    def rmatvec(self, c: jax.Array) -> jax.Array:
        return self._rmatvec(c, squared=False)

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        return self._rmatvec(c, squared=True)

    def _rmatvec(self, c: jax.Array, squared: bool) -> jax.Array:
        c2 = c.reshape(self._n_dd(), -1)

        def local_rmv(shards, c_blk):
            tile = jax.tree.map(lambda a: a[0, 0], shards)
            g = tile.rmatvec_sq(c_blk[0]) if squared else tile.rmatvec(c_blk[0])
            return jax.lax.psum(g, DATA_AXIS)[None]

        out = shard_map(
            local_rmv,
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS, FEAT_AXIS), P(DATA_AXIS)),
            out_specs=P(FEAT_AXIS),
        )(self.shards, c2)
        return out.reshape(-1)

    def zero_coefficients(self) -> jax.Array:
        """The zero model, feat-sharded as a solve hands its result back:
        a solve from nothing and a warm-started one are then one program
        (an uncommitted start lowers a second)."""
        return jnp.zeros(
            (self.num_cols_,), jnp.float32,
            device=jax.sharding.NamedSharding(self.mesh, P(FEAT_AXIS)),
        )

    def row_norms_sq(self) -> jax.Array:
        def local_rn(shards):
            tile = jax.tree.map(lambda a: a[0, 0], shards)
            return jax.lax.psum(tile.row_norms_sq(), FEAT_AXIS)[None]

        out = shard_map(
            local_rn,
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS, FEAT_AXIS),),
            out_specs=P(DATA_AXIS),
        )(self.shards)
        return out.reshape(-1)


def shard_vector_feat(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Place a [d_pad] vector sharded over the feat axis (replicated over
    data) — the layout for w, grad, and optimizer history rows."""
    return place_global(x, mesh, P(FEAT_AXIS))


def shard_vector_data(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Place an [n_pad] vector sharded over the data axis (labels, offsets,
    weights, margins)."""
    return place_global(x, mesh, P(DATA_AXIS))


def grid_from_coo(
    rows,
    cols,
    vals,
    shape: Tuple[int, int],
    mesh: Mesh,
    engine: str = "benes",
    plan_cache: Optional[str] = None,
    hot_col_threshold: Optional[int] = None,
    max_hot_cols: int = 128,
    kp_cap="auto",
    col_split="auto",
    payload_dtype: str = "float32",
) -> GridShardedFeatures:
    """Tile COO entries over the (data, feat) mesh and route each tile
    identically.

    Rows pad to a multiple of the data-axis size, columns to a multiple of
    ``COLUMN_MULTIPLE`` x the feat-axis size; callers padding labels/weights
    must give padding rows weight 0 (padded columns are simply never
    touched). The planning before the tiles is a ``route/layout`` span;
    tiles are built a few at a time, each with its own device as the
    default one: span ``grid/build_tile``, whose ``route/*`` and
    ``data/upload`` children are the tile's own.
    """
    if engine not in ("benes", "ell", "fused"):
        raise ValueError(f"unknown engine {engine!r}; expected benes/ell/fused")
    if payload_dtype != "float32" and engine != "fused":
        raise ValueError(
            "payload_dtype applies to the fused engine only (the stage-by-"
            "stage and ELL engines have no half-width payload path)"
        )
    n, d = shape
    n_dd = mesh.shape[DATA_AXIS]
    n_df = mesh.shape[FEAT_AXIS]

    if n_dd == 1 and n_df == 1 and engine in ("benes", "fused"):
        # Single-tile grid: delegate to the full single-device builder so
        # the automatic KP-cap + column-split layout planner applies (the
        # 1B-coef chip tile's d*KP would otherwise overshoot the valid-size
        # ladder by up to 16x). Multi-tile grids pin shapes across tiles
        # and keep the flat layout below.
        if engine == "benes":
            from photon_ml_tpu.ops.sparse_perm import from_coo as _single
        else:
            from photon_ml_tpu.ops.fused_perm import from_coo as _single

        single_kw = (
            {"payload_dtype": payload_dtype} if engine == "fused" else {}
        )
        tile = _single(
            rows, cols, vals, (n, d), plan_cache=plan_cache,
            hot_col_threshold=hot_col_threshold, max_hot_cols=max_hot_cols,
            kp_cap=kp_cap, col_split=col_split, **single_kw,
        )
        stacked = upload("tile", lambda: jax.tree.map(
            lambda a: place_global(
                np.asarray(a)[None, None], mesh,
                P(DATA_AXIS, FEAT_AXIS, *([None] * np.asarray(a).ndim)),
            ),
            tile,
        ))
        return GridShardedFeatures(
            shards=stacked, mesh=mesh, num_rows_=int(n), num_cols_=int(d)
        )

    with span("route/layout", nnz=int(np.size(rows))) as laying:
        (n_loc, d_loc, tiles_cold, tile_hot, h_common, K, KP, col_blocks, k_blk,
         block_spill, tile_spill, tile_corder) = _plan_tiles(
            rows, cols, vals, n, d, n_dd, n_df, engine, hot_col_threshold,
            max_hot_cols, kp_cap, col_split,
        )
        laying.set_attrs(blocks=col_blocks)

    # In a multi-process cluster, only build (route!) the tiles whose device
    # belongs to this process — the expensive per-tile routing is O(local
    # share), not O(global). Non-addressable grid positions reuse one built
    # tile as a shape template: their content never reaches any device (the
    # placement callback only reads addressable blocks). K/KP/h_common come
    # from the GLOBAL degree loop above, so all processes agree on shapes.
    multiproc = jax.process_count() > 1
    if multiproc:
        pidx = jax.process_index()
        addressable = {
            (dd, df)
            for dd in range(n_dd)
            for df in range(n_df)
            if mesh.devices[dd, df].process_index == pidx
        }
        if not addressable:
            addressable = {(0, 0)}  # off-mesh process: one template tile
    else:
        addressable = None  # build everything

    def _build_tile(dd, df):
        tr, tc, tv, hm = tiles_cold[dd, df]
        hot_ids = tile_hot[dd, df] if h_common else None
        if engine in ("benes", "fused"):
            assembler = _assemble
            asm_kw = {}
            if engine == "fused":
                from photon_ml_tpu.ops import fused_perm

                assembler = fused_perm.assemble
                asm_kw = {"payload_dtype": payload_dtype}
            if col_blocks > 1:
                # pinned per-block layout: every (tile, block) shares
                # (k_blk, KP, S_b, spill length), so tiles stack
                # leaf-by-leaf; k_blk is the per-block ELL width (each
                # block holds only its columns' entries, so it is smaller
                # than the full-tile K — the planner priced it this way)
                from photon_ml_tpu.ops.sparse_perm import ColumnSplitFeatures

                d_bb = -(-d_loc // col_blocks)
                S_b = routing.valid_size(max(n_loc * k_blk, d_bb * KP, 1))
                blocks = []
                for b, (btr, btc, btv, spill, corder) in enumerate(
                    block_spill[dd, df]
                ):
                    blocks.append(assembler(
                        btr, btc, btv, n_loc, d_bb, k_blk, KP, None, None,
                        plan_cache, size_floor=S_b, spill=spill,
                        corder=corder, **asm_kw,
                    ))
                hot_side = (None, None) if hot_ids is None else upload(
                    "tile", lambda: _hot_arrays(hm, hot_ids)
                )
                return ColumnSplitFeatures(
                    blocks=tuple(blocks),
                    hot_matrix=hot_side[0],
                    hot_cols=hot_side[1],
                    col_bounds=tuple(
                        min(b * d_bb, d_loc) for b in range(col_blocks + 1)
                    ),
                    num_rows_=int(n_loc),
                    num_cols_=int(d_loc),
                )
            S = routing.valid_size(max(n_loc * K, d_loc * KP, 1))
            return assembler(
                tr, tc, tv, n_loc, d_loc, K, KP, hm, hot_ids,
                plan_cache, size_floor=S, spill=tile_spill[dd, df],
                corder=tile_corder[dd, df], **asm_kw,
            )
        ell = _ell_tile(tr, tc, tv, n_loc, d_loc, K)
        if h_common:
            hot_matrix, hot_cols = upload("tile", lambda: _hot_arrays(hm, hot_ids))
            return _EllWithHot(ell=ell, hot_matrix=hot_matrix, hot_cols=hot_cols)
        return ell

    # Each tile is built with its own device as the default one, so what its
    # builder uploads lands there: the global array is assembled from the
    # per-device pieces, no tile ever sits on the first device and none is
    # stacked with its siblings on the host.
    # Grid positions of other processes' devices are never built; a process
    # with no device on the mesh builds one tile for its shapes alone.
    positions = sorted(addressable) if addressable is not None else [
        (dd, df) for dd in range(n_dd) for df in range(n_df)
    ]

    def _build_and_upload(pos):
        dd, df = pos
        device = mesh.devices[dd, df]
        local = device.process_index == jax.process_index()
        with span("grid/build_tile", dd=dd, df=df) as building, (
            jax.default_device(device) if local else contextlib.nullcontext()
        ):
            tile = _build_tile(dd, df)
            building.set_attrs(slots=_tile_slots(tile))
            leaves, treedef = jax.tree.flatten(tile)
            del tile
            pieces = jax.block_until_ready(
                [jnp.asarray(leaf)[None, None] for leaf in leaves]
            )
        return treedef, pieces

    if engine in ("benes", "fused"):
        # once, before any worker asks for them
        routing._load_native()
        routing._load_layout()
    workers = max(1, min(len(positions), _MAX_TILE_WORKERS, os.cpu_count() or 1))
    with ThreadPoolExecutor(workers, thread_name_prefix="grid-tile") as pool:
        built = list(pool.map(_build_and_upload, positions))

    treedef = built[0][0]
    if any(t != treedef for t, _ in built):
        raise AssertionError("grid tiles built to different structures")
    global_leaves = []
    for i, first in enumerate(built[0][1]):
        shape = (n_dd, n_df) + first.shape[2:]
        sharding = jax.sharding.NamedSharding(
            mesh, P(DATA_AXIS, FEAT_AXIS, *([None] * (len(shape) - 2)))
        )
        global_leaves.append(jax.make_array_from_single_device_arrays(
            shape, sharding,
            [pieces[i] for (dd, df), (_, pieces) in zip(positions, built)
             if mesh.devices[dd, df].process_index == jax.process_index()],
        ))
    return GridShardedFeatures(
        shards=jax.tree.unflatten(treedef, global_leaves),
        mesh=mesh,
        num_rows_=int(n_loc * n_dd),
        num_cols_=int(d_loc * n_df),
    )


def _plan_tiles(rows, cols, vals, n, d, n_dd, n_df, engine, hot_col_threshold,
                max_hot_cols, kp_cap, col_split):
    """:func:`grid_from_coo`'s planning before any tile is built: the
    entries coalesced and cut into tiles, each tile's hot side, the common
    paddings and the column layout with its spills. Returns ``(n_loc,
    d_loc, tiles_cold, tile_hot, h_common, K, KP, col_blocks, k_blk,
    block_spill, tile_spill, tile_corder)``: a capped tile's or block's
    entries come with their (col, row) order from the cap's sort, or None
    (``sparse_perm.split_spill_entries``)."""
    rows, cols, vals = coalesce_coo(rows, cols, vals, n, d)
    n_loc = -(-n // n_dd)
    d_loc = -(-d // (n_df * COLUMN_MULTIPLE)) * COLUMN_MULTIPLE
    dd_of = rows // n_loc
    df_of = cols // d_loc

    # One sort by (tile id) then slice: O(nnz log nnz) once instead of one
    # full boolean-mask pass per tile (matters at 1e8+ nnz on big grids).
    tile_id = dd_of * n_df + df_of
    order = lexsort_pairs(tile_id)
    rows, cols, vals, tile_id = (
        rows[order], cols[order], vals[order], tile_id[order]
    )
    bounds = np.searchsorted(tile_id, np.arange(n_dd * n_df + 1))

    # Per-tile hot sets must stack: find each tile's hot columns, then pad
    # every tile to the common H with repeats of its first id and an
    # all-zero dense column (an exact no-op in every linear map).
    tile_entries = {}
    tile_hot = {}
    tile_col_counts = {}
    h_common = 0
    for dd in range(n_dd):
        for df in range(n_df):
            lo, hi = bounds[dd * n_df + df], bounds[dd * n_df + df + 1]
            tr = rows[lo:hi] - dd * n_loc
            tc = cols[lo:hi] - df * d_loc
            tv = vals[lo:hi]
            counts = np.bincount(tc, minlength=d_loc)
            hot = select_hot_cols(
                tr, tc, n_loc, d_loc, hot_col_threshold, max_hot_cols, counts
            )
            if hot is not None:
                counts[hot] = 0  # every entry of a hot column goes dense
            tile_col_counts[dd, df] = counts
            tile_entries[dd, df] = (tr, tc, tv)
            tile_hot[dd, df] = hot
            if hot is not None:
                h_common = max(h_common, hot.size)

    # Common paddings across tiles.
    K = 1
    KP = 1
    tiles_cold = {}
    for key, (tr, tc, tv) in tile_entries.items():
        hot = tile_hot[key]
        hm = None
        if h_common:
            if hot is None:
                hot = np.zeros(0, dtype=np.int64)
            tr, tc, tv, hm_real = (
                split_hot_entries(tr, tc, tv, n_loc, d_loc, hot)
                if hot.size
                else (tr, tc, tv, np.zeros((n_loc, 0), np.float32))
            )
            hm = np.zeros((n_loc, h_common), dtype=np.float32)
            hm[:, : hm_real.shape[1]] = hm_real
            pad_id = int(hot[0]) if hot.size else 0
            hot_full = np.full(h_common, pad_id, dtype=np.int64)
            hot_full[: hot.size] = hot
            tile_hot[key] = hot_full
        tiles_cold[key] = (tr, tc, tv, hm)
        if tr.size:
            K = max(K, int(np.bincount(tr).max()))
            KP = max(KP, int(tile_col_counts[key].max()))

    if engine == "fused":
        # fused kernels need power-of-two slot groups
        from photon_ml_tpu.ops.fused_perm import _next_pow2

        K = _next_pow2(K)
        KP = _next_pow2(KP)

    # Layout planning (sparse_perm.plan_column_layout) evaluated over the
    # WHOLE grid's degree distribution so every tile keeps pinned shapes:
    # thin column-degree tails — the 1B-coef layout's ~1 nnz/col shards —
    # would otherwise pad every tile's network by max/mean degree AND the
    # valid-size ladder. A KP cap spills per-tile over-cap entries; a
    # column split turns each tile into a ColumnSplitFeatures of
    # identically-shaped sub-blocks.
    tile_spill = {key: (None, None, None) for key in tiles_cold}
    tile_corder = {key: None for key in tiles_cold}
    col_blocks = 1
    k_blk = K  # per-block pinned ELL width when the columns split
    block_spill: dict = {}
    if engine in ("benes", "fused") and (kp_cap or col_split != 1):
        from photon_ml_tpu.ops.sparse_perm import (
            max_block_row_count,
            resolve_layout,
            split_spill_entries,
        )

        all_counts = np.concatenate(
            [tile_col_counts[key] for key in sorted(tile_col_counts)]
        )

        block_k: dict = {}

        def _grid_row_block_k(t: int) -> int:
            """Pinned per-block ELL width for a t-way column split: the max
            nnz any tile-local row holds within one column block, over ALL
            tiles (blocks stack across tiles, so the pin is the global
            max). Same refinement as sparse_perm.make_row_block_k: memoized
            per t (the planner asks once a candidate cap)."""
            if t in block_k:
                return block_k[t]
            d_bb_t = -(-d_loc // t)
            k_max = 1
            for tr, tc, _tv, _hm in tiles_cold.values():
                k_max = max(k_max, max_block_row_count(
                    tr.astype(np.int64), tc, n_loc, d_bb_t, t
                ))
            if engine == "fused":
                k_max = 1 << max(k_max - 1, 0).bit_length()
            block_k[t] = k_max
            return k_max

        # all_counts spans every tile while n_loc/d_loc describe one tile:
        # scale the spill cost to per-tile units to match the network size
        cap, col_blocks = resolve_layout(
            kp_cap, col_split, all_counts, n_loc, d_loc, K, KP,
            row_block_k=_grid_row_block_k,
            spill_scale=1.0 / max(len(tiles_cold), 1),
        )
        if col_blocks > 1:
            k_blk = _grid_row_block_k(col_blocks)
        if col_blocks > 1:
            # partition each tile's cold entries into column blocks; apply
            # the cap per (tile, block); pad spills to ONE stackable length
            d_bb = -(-d_loc // col_blocks)
            m_max = 0
            tile_blocks = {}
            for key, (tr, tc, tv, hm) in tiles_cold.items():
                blocks = []
                blk_of = tc // d_bb
                for b in range(col_blocks):
                    m = blk_of == b
                    btr, btc, btv = tr[m], tc[m] - b * d_bb, tv[m]
                    counts_b = (
                        np.bincount(btc, minlength=d_bb) if btr.size
                        else np.zeros(d_bb, np.int64)
                    )
                    corder = None
                    if cap is not None and btr.size and counts_b.max() > cap:
                        btr, btc, btv, sr, sc, sv, corder = (
                            split_spill_entries(btr, btc, btv, counts_b, cap)
                        )
                    else:
                        sr = np.zeros(0, np.int64)
                        sc = np.zeros(0, np.int64)
                        sv = np.zeros(0, np.float32)
                    blocks.append((btr, btc, btv, sr, sc, sv, corder))
                    m_max = max(m_max, sr.size)
                tile_blocks[key] = blocks
            for key, blocks in tile_blocks.items():
                block_spill[key] = []
                for btr, btc, btv, sr, sc, sv, corder in blocks:
                    pad = m_max - sr.size
                    spill = (
                        (np.pad(sr, (0, pad)), np.pad(sc, (0, pad)),
                         np.pad(sv, (0, pad)))
                        if m_max else (None, None, None)
                    )
                    block_spill[key].append((btr, btc, btv, spill, corder))
            if cap is not None:
                KP = cap
        elif cap is not None:
            m_max = 0
            for key, (tr, tc, tv, hm) in tiles_cold.items():
                counts = tile_col_counts[key]
                if tr.size and counts.max() > cap:
                    tr, tc, tv, sr, sc, sv, tile_corder[key] = (
                        split_spill_entries(tr, tc, tv, counts, cap)
                    )
                    tiles_cold[key] = (tr, tc, tv, hm)
                else:
                    sr = np.zeros(0, np.int64)
                    sc = np.zeros(0, np.int64)
                    sv = np.zeros(0, np.float32)
                tile_spill[key] = (sr, sc, sv)
                m_max = max(m_max, sr.size)
            KP = cap
            if m_max:
                # pad every tile's spill to one stackable length; padding
                # entries carry value 0 at (row 0, col 0) — exact no-ops
                for key, (sr, sc, sv) in tile_spill.items():
                    pad = m_max - sr.size
                    tile_spill[key] = (
                        np.pad(sr, (0, pad)),
                        np.pad(sc, (0, pad)),
                        np.pad(sv, (0, pad)),
                    )
            else:
                tile_spill = {key: (None, None, None) for key in tiles_cold}
    return (n_loc, d_loc, tiles_cold, tile_hot, h_common, K, KP, col_blocks,
            k_blk, block_spill, tile_spill, tile_corder)


def _tile_slots(tile) -> int:
    """Routed slots of one tile, summed over its column blocks (0 for the
    ELL engine, which routes nothing)."""
    blocks = getattr(tile, "blocks", (tile,))
    return sum(int(b.plan.size) for b in blocks if hasattr(b, "plan"))


def _ell_tile(tr, tc, tv, n_loc: int, d_loc: int, K: int) -> EllFeatures:
    """One tile in padded ELL layout with pinned row width K."""
    order = np.argsort(tr, kind="stable")
    tr, tc, tv = tr[order], tc[order], tv[order]
    counts = np.bincount(tr, minlength=n_loc)
    starts = np.zeros(n_loc + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    slots = np.arange(tr.size, dtype=np.int64) - starts[tr]
    values = np.zeros((n_loc, K), dtype=np.float32)
    indices = np.zeros((n_loc, K), dtype=np.int32)
    values[tr, slots] = tv
    indices[tr, slots] = tc
    values, indices = upload(
        "tile", lambda: (jnp.asarray(values), jnp.asarray(indices))
    )
    return EllFeatures(values=values, indices=indices, num_cols=d_loc)


@struct.dataclass
class _EllWithHot:
    """ELL tile + dense hot side (mirrors BenesSparseFeatures hot-split
    semantics for the CPU/test engine)."""

    ell: EllFeatures
    hot_matrix: jax.Array
    hot_cols: jax.Array

    def matvec(self, w: jax.Array) -> jax.Array:
        return self.ell.matvec(w) + self.hot_matrix @ w[self.hot_cols]

    def rmatvec(self, c: jax.Array) -> jax.Array:
        g = self.ell.rmatvec(c)
        return g.at[self.hot_cols].add(self.hot_matrix.T @ c)

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        g = self.ell.rmatvec_sq(c)
        hm2 = self.hot_matrix * self.hot_matrix
        return g.at[self.hot_cols].add(hm2.T @ c)

    def row_norms_sq(self) -> jax.Array:
        return self.ell.row_norms_sq() + jnp.sum(
            self.hot_matrix * self.hot_matrix, axis=-1
        )
