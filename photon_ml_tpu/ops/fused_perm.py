"""Fused execution of Benes permutation plans: the large-d GLM fast path.

``ops/permute_net.py`` executes a routed plan stage by stage: every lane or
sublane shuffle and every enter/leave relayout is its own device pass, so one
permutation of S elements costs ~11 full HBM round-trips at production sizes
(7 shuffles + 4 relayouts), and the surrounding GLM algebra (broadcast w over
column slots, multiply by stored values, segment-reduce) adds several more.

This module fuses the same plan into ``2m+1`` Pallas kernels (m = recursion
depth, so 3 or 5 at realistic sizes) by folding each enter/leave transpose
into the adjacent lane shuffle's block layout, and folding the GLM prologue/
epilogue into the first/last kernel:

- descend kernel: lane-shuffle a [128u, 128] tile, transpose it, write it
  into the entered layout — the relayout becomes the kernel's output
  BlockSpec instead of a separate pass.
- base kernel: innermost (lane, sublane, lane) triple in one row-local pass.
- ascend kernel: read a tile from the entered layout (transposed read = the
  leave relayout), lane-shuffle, write.
- prologue (first descend): build the network input in-kernel from the
  small operand — broadcast w over each column's KP slots (matvec), or
  multiply the stored ELL values by the row-broadcast coefficient vector
  (rmatvec) — instead of materializing a [S] array first.
- epilogue (last ascend): reduce each row/column's slot group to the output
  vector (margins z or gradient g) in-kernel.

Per linear map this is ~3x less HBM traffic than the stage-by-stage path.
Reference parity: this implements the same per-example sparse axpy math as
ValueAndGradientAggregator.scala:132-153; only the execution strategy is
TPU-specific.

Slot-group sizes K (ELL, max nnz/row) and KP (CSC, max nnz/col) are rounded
up to powers of two so slot groups tile the 128-lane axis evenly (group <=
128) or span whole rows (group = 128q): both make the prologue/epilogue a
dense in-kernel reshape/matmul instead of a gather.

The executor is chosen from the backend: on a TPU the Pallas kernels, always
— one that Mosaic refuses is an error, not a reason to run something else.
Elsewhere (the CPU tests) the class runs the same plan through plain XLA
(broadcast -> apply_plan -> reduce) with identical semantics; the kernels
themselves are covered on CPU through the interpreter (tests set
``_INTERPRET``) and compiled for the TPU without a chip in
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from photon_ml_tpu.ops import routing
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.pallas_kernels import pallas_available
from photon_ml_tpu.ops.permute_net import DevicePlan, apply_plan, device_plan
from photon_ml_tpu.ops.routing import LANES
from photon_ml_tpu.telemetry.span import span, upload

from jax.experimental import pallas as pl

# Test hook: run the fused kernels through the Pallas interpreter (CPU).
_INTERPRET = False

_MAX_BASE_BLOCK = 1024  # rows per base-kernel block (VMEM budget)

# Largest slot group (K or KP) the fused prologue/epilogue can address. For
# group = 128*q the operand BlockSpec height is LANES*u//q with u as small as
# 1, so q > LANES would silently produce a zero-height block and an obscure
# Mosaic failure at production shapes (a row/column with more than
# LANES*LANES nonzeros after hot-column splitting). Guarded in ``assemble``.
MAX_FUSED_GROUP = LANES * LANES

# Smallest plan with a recursion level. The fused kernels fold that level's
# relayouts, so every fused plan is padded to at least this many slots.
MIN_FUSED_SIZE = LANES * LANES


class FusedGroupTooLarge(ValueError):
    """A slot group exceeds what the fused executor can tile. The
    stage-by-stage engine (``engine="benes"``) has no such limit."""


# --------------------------------------------------------------------------
# Plan parsing: recover the canonical (descend* base ascend*) shape that
# routing._route always emits.
# --------------------------------------------------------------------------


class ParsedPlan(NamedTuple):
    descents: Tuple[Tuple[int, int, int], ...]  # (idx slot, B, R) per level
    base: Tuple[int, Optional[int], int, int]   # (idx_a, idx_s or None, rows, idx_b)
    ascents: Tuple[Tuple[int, int, int], ...]   # (idx slot, B, R), outermost last


def parse_plan(dplan: DevicePlan) -> ParsedPlan:
    kinds = dplan.kinds
    pos = 0   # position in kinds
    ai = 0    # position in idx tuple
    descents = []
    while pos + 1 < len(kinds) and kinds[pos][0] == "lane" and kinds[pos + 1][0] == "enter":
        _, b, r = kinds[pos + 1]
        descents.append((ai, b, r))
        ai += 1
        pos += 2
    if not (
        pos + 2 < len(kinds)
        and kinds[pos][0] == "lane"
        and kinds[pos + 1][0] == "sublane"
        and kinds[pos + 2][0] == "lane"
    ):
        raise ValueError(f"unrecognized plan structure at {pos}: {kinds}")
    rows = kinds[pos + 1][1]
    base = (ai, ai + 1, rows, ai + 2)
    ai += 3
    pos += 3
    ascents = []
    for _ in range(len(descents)):
        if not (pos + 1 < len(kinds) and kinds[pos][0] == "leave" and kinds[pos + 1][0] == "lane"):
            raise ValueError(f"unrecognized plan structure at {pos}: {kinds}")
        _, b, r = kinds[pos]
        ascents.append((ai, b, r))
        ai += 1
        pos += 2
    if pos != len(kinds):
        raise ValueError(f"trailing plan stages at {pos}: {kinds}")
    return ParsedPlan(tuple(descents), base, tuple(ascents))


# --------------------------------------------------------------------------
# Prologue / epilogue specs (all group sizes are powers of two).
# --------------------------------------------------------------------------


class Broadcast(NamedTuple):
    """Network input[col*KP + k] = vec[col] — matvec's w expansion."""

    vec: jax.Array  # [S // group]
    group: int      # KP


class MulBroadcast(NamedTuple):
    """input[row*K + k] = t(values[row*K + k]) * vec[row] — rmatvec's c
    expansion. ``transform`` applies elementwise to the stored values in the
    kernel: "id", "sq" (Hessian diagonal), "abs" / "nnz" (summary stats)."""

    values: jax.Array  # [S] flat slot values (ELL layout)
    vec: jax.Array     # [S // group]
    group: int         # K
    transform: str = "id"


class MulReduce(NamedTuple):
    """out[row] = sum_k values[row*K+k] * permuted[row*K+k] — matvec's z."""

    values: jax.Array  # [S]
    group: int         # K


class Reduce(NamedTuple):
    """out[col] = sum_k permuted[col*KP+k] — rmatvec's g."""

    group: int  # KP


def _group_mats(group: int, dtype=jnp.float32):
    """(expand [g2, 128], reduce [128, g2]) 0/1 matrices for a slot group of
    ``group`` lanes, where g2 = 128 // group; built in-kernel via iota."""
    g2 = LANES // group
    lane = jax.lax.broadcasted_iota(jnp.int32, (g2, LANES), 1) // group
    slot = jax.lax.broadcasted_iota(jnp.int32, (g2, LANES), 0)
    expand = (lane == slot).astype(dtype)
    return expand, expand.T


def _apply_transform(vals: jax.Array, transform: str) -> jax.Array:
    if transform == "id":
        return vals
    if transform == "sq":
        return vals * vals
    if transform == "abs":
        return jnp.abs(vals)
    if transform == "nnz":
        return (vals != 0).astype(vals.dtype)
    raise ValueError(f"unknown value transform {transform!r}")


def _build_input_block(pro, w_ref, v_ref, rows: int):
    """Materialize a [rows, 128] network-input tile inside a kernel.

    ``w_ref`` is the small-operand block; ``v_ref`` the values block (or None).
    For group <= 128 the operand block is [rows, 128//group]; for group =
    128*q it is [rows//q, 1] and each operand element spans q rows.
    """
    group = pro.group
    if group <= LANES:
        wb = w_ref[...]  # [rows, 128//group]
        expand, _ = _group_mats(group, wb.dtype)
        x = jax.lax.dot_general(
            wb, expand, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [rows, 128]
    else:
        q = group // LANES
        wb = w_ref[...]  # [rows//q, 1]
        # row r of the tile takes operand element r//q: select matrix
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, rows // q), 0) // q
        s_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, rows // q), 1)
        sel = (r_ids == s_ids).astype(wb.dtype)
        col = jax.lax.dot_general(
            sel, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [rows, 1]
        x = jnp.broadcast_to(col, (rows, LANES))
    if isinstance(pro, MulBroadcast):
        x = _apply_transform(v_ref[...], pro.transform) * x
    return x


def _pro_specs(pro, R1: int, u: int):
    """(extra inputs, extra in_specs) the prologue adds to a descend call."""
    group = pro.group
    if group <= LANES:
        g2 = LANES // group
        op = pro.vec.reshape(-1, g2)
        specs = [pl.BlockSpec((LANES * u, g2), lambda b, g: (b * R1 // u + g, 0))]
        inputs = [op]
    else:
        q = group // LANES
        op = pro.vec.reshape(-1, 1)
        specs = [pl.BlockSpec((LANES * u // q, 1), lambda b, g: (b * R1 // u + g, 0))]
        inputs = [op]
    if isinstance(pro, MulBroadcast):
        vals = pro.values.reshape(-1, LANES)
        specs.insert(
            0, pl.BlockSpec((LANES * u, LANES), lambda b, g: (b * R1 // u + g, 0))
        )
        inputs.insert(0, vals)
    return inputs, specs


# --------------------------------------------------------------------------
# Fused kernels.
# --------------------------------------------------------------------------


def _tile_cap() -> int:
    """Rows-of-128 per kernel block (the pipeline tile height).

    Default 8 → [1024, 128] f32 blocks (~0.5 MB payload). VMEM holds far
    larger tiles; PHOTON_FUSED_TILE_U raises the cap (power of two) so the
    hardware session can A/B whether per-grid-step overhead — not HBM
    bandwidth — is what binds the kernels (VERDICT r4 weak #3)."""
    try:
        cap = int(os.environ.get("PHOTON_FUSED_TILE_U", "8"))
    except ValueError:
        return 8
    if cap < 8 or cap & (cap - 1):
        return 8
    return cap


def _tile_rows(R1: int) -> int:
    """Sublane tile count u for the 3-D entered layout [B*128, R1, 128].

    Mosaic's lowering requires the middle block dim be divisible by 8 or
    equal to the full array dim R1, so u is the largest power-of-two
    divisor of R1 within the tile cap (>= 8 whenever 8 | R1), and u = R1
    below that (plans are power-of-two sized, making R1 < 8 exact)."""
    cap = _tile_cap()
    u = 8
    while R1 % u:
        u //= 2
    if u < 8 and u != R1:
        raise ValueError(
            f"R1={R1} admits no Mosaic-legal sublane tile (need 8 | u or "
            "u == R1); plan sizes must be powers of two"
        )
    while u * 2 <= cap and R1 % (u * 2) == 0:
        u *= 2
    return u


def _descend_call(
    v, idx, B: int, R: int, pro, interpret: bool, payload_dtype=jnp.float32
) -> jax.Array:
    """(lane shuffle; enter relayout) in one pass; optional input prologue.

    Input layout [B*R, 128]; output entered layout [B*128*R1, 128] returned
    as a 3-D [B*128, R1, 128] array (the caller treats it as opaque).
    ``payload_dtype`` is the storage dtype of the permuted intermediates:
    bfloat16 halves the network's HBM traffic at one entry rounding (the
    prologue math and the final reductions stay f32).
    """
    R1 = R // LANES
    u = _tile_rows(R1)
    if pro is not None and pro.group > LANES:
        # the q-path prologue builds an O(u^2) in-kernel selection matrix;
        # keep the default tile height there regardless of the A/B cap
        u = min(u, 8)

    def kernel(*refs):
        o_ref = refs[-1]
        i_ref = refs[-2]
        if pro is None:
            # shuffle in f32 regardless of the storage dtype: Mosaic's
            # dynamic_gather needs data/index bitwidths to match, and the
            # converts are VMEM-local (HBM load/store stay payload-width)
            x = refs[0][...].astype(jnp.float32)
        elif isinstance(pro, MulBroadcast):
            x = _build_input_block(pro, refs[1], refs[0], LANES * u)
        else:
            x = _build_input_block(pro, refs[0], None, LANES * u)
        sel = i_ref[...].astype(jnp.int32)
        y = jnp.take_along_axis(x, sel, axis=1)
        # y row (t*128 + j) lane c -> out[c, t, j]: a single 2-D transpose
        # ([128u,128] -> [128,128u]) then a minor-dim split — the rank-3
        # transpose equivalent, expressed in ops Mosaic lowers well
        o_ref[...] = y.T.reshape(LANES, u, LANES).astype(o_ref.dtype)

    if pro is None:
        inputs = [v.reshape(B * R, LANES)]
        specs = [pl.BlockSpec((LANES * u, LANES), lambda b, g: (b * R1 // u + g, 0))]
    else:
        inputs, specs = _pro_specs(pro, R1, u)
    inputs.append(idx)
    specs.append(pl.BlockSpec((LANES * u, LANES), lambda b, g: (b * R1 // u + g, 0)))

    return pl.pallas_call(
        kernel,
        grid=(B, R1 // u),
        in_specs=specs,
        out_specs=pl.BlockSpec((LANES, u, LANES), lambda b, g: (b, g, 0)),
        out_shape=jax.ShapeDtypeStruct((B * LANES, R1, LANES), payload_dtype),
        interpret=interpret,
        name="fused_descend",
    )(*inputs)


def _ascend_call(v3, idx, B: int, R: int, epi, interpret: bool):
    """(leave relayout; lane shuffle) in one pass; optional output epilogue.

    Input: entered layout as 3-D [B*128, R1, 128]. Output: [B*R, 128] plain
    rows, or the epilogue's reduced vector.
    """
    R1 = R // LANES
    u = _tile_rows(R1)
    if epi is not None and epi.group > LANES:
        # the q-path epilogue builds an O(u^2) selection matrix (see
        # _descend_call); keep the default tile height there
        u = min(u, 8)

    def _shuffled(x_ref, i_ref):
        # f32 in-VMEM shuffle (see _descend_call): converts are local, the
        # HBM read keeps the payload width
        t = x_ref[...].astype(jnp.float32)
        # t [128, u, 128]: t[c, t_, j] = row (g*u+t_)*128+j lane c;
        # minor-dim merge then one 2-D transpose: y[t_*128+j, c] = t[c, t_, j]
        y = t.reshape(LANES, u * LANES).T
        sel = i_ref[...].astype(jnp.int32)
        return jnp.take_along_axis(y, sel, axis=1)

    def _reduced(y):
        y = y.astype(jnp.float32)  # accumulate reductions in f32 always
        group = epi.group
        if group <= LANES:
            _, reduce = _group_mats(group, y.dtype)
            return jax.lax.dot_general(
                y, reduce, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )  # [128u, 128//group]
        q = group // LANES
        rowsum = jnp.sum(y, axis=1, keepdims=True)  # [128u, 1]
        nrow = LANES * u
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (nrow // q, nrow), 1) // q
        s_ids = jax.lax.broadcasted_iota(jnp.int32, (nrow // q, nrow), 0)
        sel2 = (r_ids == s_ids).astype(y.dtype)
        return jax.lax.dot_general(
            sel2, rowsum, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [128u//q, 1]

    def kernel_plain(x_ref, i_ref, o_ref):
        o_ref[...] = _shuffled(x_ref, i_ref).astype(o_ref.dtype)

    def kernel_reduce(x_ref, i_ref, o_ref):
        o_ref[...] = _reduced(_shuffled(x_ref, i_ref))

    def kernel_mul_reduce(x_ref, v_ref, i_ref, o_ref):
        o_ref[...] = _reduced(_shuffled(x_ref, i_ref) * v_ref[...])

    in_specs = [
        pl.BlockSpec((LANES, u, LANES), lambda b, g: (b, g, 0)),
        pl.BlockSpec((LANES * u, LANES), lambda b, g: (b * R1 // u + g, 0)),
    ]
    inputs = [v3, idx]
    if epi is None:
        body = kernel_plain
    elif isinstance(epi, MulReduce):
        in_specs.insert(
            1, pl.BlockSpec((LANES * u, LANES), lambda b, g: (b * R1 // u + g, 0))
        )
        inputs.insert(1, epi.values.reshape(-1, LANES))
        body = kernel_mul_reduce
    else:
        body = kernel_reduce

    if epi is None:
        out_specs = pl.BlockSpec((LANES * u, LANES), lambda b, g: (b * R1 // u + g, 0))
        out_shape = jax.ShapeDtypeStruct((B * R, LANES), v3.dtype)
    else:
        group = epi.group
        if group <= LANES:
            g2 = LANES // group
            out_specs = pl.BlockSpec((LANES * u, g2), lambda b, g: (b * R1 // u + g, 0))
            out_shape = jax.ShapeDtypeStruct((B * R, g2), jnp.float32)
        else:
            q = group // LANES
            out_specs = pl.BlockSpec(
                (LANES * u // q, 1), lambda b, g: (b * R1 // u + g, 0)
            )
            out_shape = jax.ShapeDtypeStruct((B * R // q, 1), jnp.float32)

    out = pl.pallas_call(
        body,
        grid=(B, R1 // u),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_ascend",
    )(*inputs)
    if epi is None:
        return out
    return out.reshape(-1)


def _base_call(v, idx_a, idx_s, rows: int, idx_b, interpret: bool) -> jax.Array:
    """Innermost (lane, sublane, lane) triple, row-local, one pass."""
    M = v.shape[0]
    # base blocks grow with the tile cap but stay clamped at 4x: the
    # sublane stage materializes [rb/rows, rows, 128] accumulators per
    # step, and an oversized base kernel failing to compile would wipe the
    # whole engine's A/B (the descend/ascend knob is the experiment)
    rb = _MAX_BASE_BLOCK * min(_tile_cap() // 8, 4)
    while M % rb or rb % max(rows, 1):
        rb //= 2

    def kernel(x_ref, ia_ref, *rest):
        o_ref = rest[-1]
        # f32 in-VMEM shuffles (see _descend_call)
        x = x_ref[...].astype(jnp.float32)
        x = jnp.take_along_axis(x, ia_ref[...].astype(jnp.int32), axis=1)
        if rows > 1:
            is_ref, ib_ref = rest[0], rest[1]
            blk = x.reshape(rb // rows, rows, LANES)
            sel = is_ref[...].astype(jnp.int32).reshape(rb // rows, rows, LANES)
            acc = jnp.zeros_like(blk)
            for k in range(rows):
                src = jax.lax.broadcast_in_dim(blk[:, k, :], blk.shape, (0, 2))
                acc = jnp.where(sel == k, src, acc)
            x = acc.reshape(rb, LANES)
        else:
            ib_ref = rest[0]
        x = jnp.take_along_axis(x, ib_ref[...].astype(jnp.int32), axis=1)
        o_ref[...] = x.astype(o_ref.dtype)

    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    inputs = [v, idx_a] + ([idx_s] if rows > 1 else []) + [idx_b]
    return pl.pallas_call(
        kernel,
        grid=(M // rb,),
        in_specs=[spec] * len(inputs),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, LANES), v.dtype),
        interpret=interpret,
        name="fused_base",
    )(*inputs)


def fused_execute(
    dplan: DevicePlan, pro, epi, interpret: Optional[bool] = None,
    payload_dtype=jnp.float32,
):
    """Run a full permutation plan with fused prologue/epilogue.

    pro: Broadcast | MulBroadcast — builds the [S]-layout network input.
    epi: MulReduce | Reduce — reduces the permuted output to a vector; None
    leaves it as it is.
    Returns the epilogue's [S // epi.group] vector, or the permuted [S]
    values as [S // 128, 128].

    ``payload_dtype=bfloat16`` stores the permuted intermediates half-size
    (one rounding at network entry; permutes are exact; reductions
    accumulate f32) — ~2x less HBM traffic through the network stages.
    """
    if interpret is None:
        interpret = _INTERPRET
    parsed = parse_plan(dplan)
    if not parsed.descents:
        raise ValueError("plan too small for fused execution (no recursion)")
    v = None
    for j, (ai, B, R) in enumerate(parsed.descents):
        v = _descend_call(
            v, dplan.idx[ai], B, R, pro if j == 0 else None, interpret,
            payload_dtype=payload_dtype,
        )
        v = v.reshape(B * LANES * (R // LANES), LANES)
    ia, isl, rows, ib = parsed.base
    idx_s = dplan.idx[isl] if rows > 1 else None
    v = _base_call(v, dplan.idx[ia], idx_s, rows, dplan.idx[ib], interpret)
    last = len(parsed.ascents) - 1
    for j, (ai, B, R) in enumerate(parsed.ascents):
        v3 = v.reshape(B * LANES, R // LANES, LANES)
        v = _ascend_call(v3, dplan.idx[ai], B, R, epi if j == last else None, interpret)
    return v


def unfused_execute(dplan: DevicePlan, pro, epi, payload_dtype=jnp.float32) -> jax.Array:
    """Same semantics via plain XLA (stage-by-stage apply_plan): the path off
    the TPU and the reference for the fused kernels (including the
    payload-dtype entry rounding)."""
    S = dplan.size
    if isinstance(pro, Broadcast):
        x = jnp.broadcast_to(
            pro.vec[:, None], (pro.vec.shape[0], pro.group)
        ).reshape(-1)
    else:
        vals = _apply_transform(pro.values, pro.transform)
        x = vals * jnp.repeat(pro.vec, pro.group, total_repeat_length=S)
    x = x.astype(payload_dtype)
    y = apply_plan(dplan, x).astype(jnp.float32)
    if epi is None:
        return y
    if isinstance(epi, MulReduce):
        y = y * epi.values
    return y.reshape(-1, epi.group).sum(axis=1)


# --------------------------------------------------------------------------
# The feature-matrix engine built on fused execution.
# --------------------------------------------------------------------------


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


@struct.dataclass
class FusedBenesFeatures:
    """Sparse [n, d] matrix with fused Benes-routed linear maps.

    Same FeatureMatrix protocol as ``BenesSparseFeatures``; stores one flat
    [S] ELL-slot value array instead of separate ELL/CSC copies. K and KP
    are power-of-two slot-group sizes; hot columns split to a dense MXU side
    exactly as in the unfused engine.
    """

    ell_flat: jax.Array       # [S] float32, p = row*K + k layout, 0 in pads
    plan: DevicePlan          # ELL -> CSC direction
    plan_inv: DevicePlan      # CSC -> ELL direction
    hot_matrix: Optional[jax.Array]
    hot_cols: Optional[jax.Array]
    num_rows_: int = struct.field(pytree_node=False)
    num_cols_: int = struct.field(pytree_node=False)
    ell_k: int = struct.field(pytree_node=False)   # K
    csc_k: int = struct.field(pytree_node=False)   # KP
    # Spill side (KP cap, sparse_perm.auto_kp_cap): over-cap entries
    # evaluated by gather/scatter-add; bounded by max(nnz/128, 4096)
    spill_rows: Optional[jax.Array] = None   # [M] int32
    spill_cols: Optional[jax.Array] = None   # [M] int32
    spill_vals: Optional[jax.Array] = None   # [M] float32
    # Storage dtype of the permuted network intermediates: "bfloat16"
    # halves the network's HBM traffic at one entry rounding per map
    # (stored values / reductions stay f32). Opt-in; relative error per
    # margin/gradient component is ~2^-8/sqrt(K).
    payload_dtype: str = struct.field(pytree_node=False, default="float32")

    @property
    def num_rows(self) -> int:
        return self.num_rows_

    @property
    def dim(self) -> int:
        return self.num_cols_

    @property
    def size(self) -> int:
        return self.plan.size

    def _fused_ok(self) -> bool:
        """Pallas kernels on a TPU (and under the tests' interpreter hook);
        plain XLA on any other backend."""
        return _INTERPRET or pallas_available()

    def _run(self, dplan, pro, epi) -> jax.Array:
        pdt = jnp.dtype(self.payload_dtype)
        if self._fused_ok():
            return fused_execute(dplan, pro, epi, payload_dtype=pdt)
        return unfused_execute(dplan, pro, epi, payload_dtype=pdt)

    def matvec(self, w: jax.Array) -> jax.Array:
        S, KP, K = self.size, self.csc_k, self.ell_k
        wp = jnp.zeros((S // KP,), w.dtype).at[: self.num_cols_].set(w)
        z = self._run(
            self.plan_inv, Broadcast(wp, KP), MulReduce(self.ell_flat, K)
        )[: self.num_rows_]
        if self.hot_matrix is not None:
            z = z + self.hot_matrix @ w[self.hot_cols]
        if self.spill_rows is not None:
            z = z.at[self.spill_rows].add(self.spill_vals * w[self.spill_cols])
        return z

    def rmatvec(self, c: jax.Array) -> jax.Array:
        return self._rmatvec_impl(c, transform="id")

    def rmatvec_sq(self, c: jax.Array) -> jax.Array:
        return self._rmatvec_impl(c, transform="sq")

    def _rmatvec_impl(self, c: jax.Array, transform: str) -> jax.Array:
        """X^T c with the stored values elementwise-transformed first
        ("id" / "sq" / "abs" / "nnz" — the latter two feed summary stats)."""
        S, KP, K = self.size, self.csc_k, self.ell_k
        cp = jnp.zeros((S // K,), c.dtype).at[: self.num_rows_].set(c)
        g = self._run(
            self.plan,
            MulBroadcast(self.ell_flat, cp, K, transform=transform),
            Reduce(KP),
        )[: self.num_cols_]
        if self.hot_matrix is not None:
            hot = _apply_transform(self.hot_matrix, transform)
            g = g.at[self.hot_cols].add(hot.T @ c)
        if self.spill_rows is not None:
            sv = _apply_transform(self.spill_vals, transform)
            g = g.at[self.spill_cols].add(sv * c[self.spill_rows])
        return g

    def routed_values(self, row_scale: jax.Array) -> jax.Array:
        """The stored values, each times its row's entry of ``row_scale``
        [n], routed to the column-grouped side: column ``c``'s ``csc_k``
        slots are entries ``[c*csc_k, (c+1)*csc_k)`` of the [S] result, pads
        0. An rmatvec without its reduction: the stats path reads per-column
        minima and maxima off it."""
        cp = jnp.zeros((self.size // self.ell_k,), row_scale.dtype)
        cp = cp.at[: self.num_rows_].set(row_scale)
        return self._run(
            self.plan, MulBroadcast(self.ell_flat, cp, self.ell_k), None
        ).reshape(-1)

    def row_norms_sq(self) -> jax.Array:
        sq = (self.ell_flat * self.ell_flat).reshape(-1, self.ell_k).sum(axis=1)
        sq = sq[: self.num_rows_]
        if self.hot_matrix is not None:
            sq = sq + jnp.sum(self.hot_matrix * self.hot_matrix, axis=-1)
        if self.spill_rows is not None:
            sq = sq.at[self.spill_rows].add(self.spill_vals * self.spill_vals)
        return sq

    def to_dense(self) -> DenseFeatures:
        eye = jnp.eye(self.num_cols_, dtype=self.ell_flat.dtype)
        cols = jax.vmap(self.matvec, in_axes=1, out_axes=1)(eye)
        return DenseFeatures(matrix=cols)


def from_coo(
    rows,
    cols,
    vals,
    shape,
    max_nnz_row: Optional[int] = None,
    plan_cache: Optional[str] = None,
    hot_col_threshold: Optional[int] = None,
    max_hot_cols: int = 128,
    size_floor: int = 0,
    pin_k: int = 0,
    pin_kp: int = 0,
    kp_cap="auto",
    col_split="auto",
    payload_dtype: str = "float32",
):
    """Build from COO triplets; same contract as ``sparse_perm.from_coo``
    (including the default per-uid routing-plan cache and the ``kp_cap``
    spill side — see that docstring).

    ``pin_k`` / ``pin_kp`` / ``size_floor`` force common paddings across
    shards of one dataset (the grid builder stacks tiles under one compiled
    program); pins must be powers of two and at least the shard's actual
    degree (a too-small pin raises rather than silently diverging from the
    sibling shards). An explicit ``pin_kp`` disables the auto cap.
    """
    from photon_ml_tpu.ops.sparse_perm import (
        build_column_split,
        make_row_block_k,
        prepare_cold_entries,
        resolve_layout,
        split_spill_entries,
    )

    if (pin_k or pin_kp) and (
        kp_cap not in ("auto", None, 0)
        or col_split not in ("auto", None, 0, 1)
    ):
        raise ValueError(
            "pin_k/pin_kp force the flat layout across sibling shards; an "
            "explicit kp_cap/col_split cannot be honored alongside them "
            "(drop the pins or the explicit layout)"
        )
    n, d = shape
    size_floor = max(size_floor, MIN_FUSED_SIZE)
    with span("route/layout", nnz=int(np.size(rows)), blocks=1) as laying:
        rows, cols, vals, hot_matrix, hot_ids, row_counts, col_counts = (
            prepare_cold_entries(
                rows, cols, vals, shape, max_nnz_row, hot_col_threshold,
                max_hot_cols,
            )
        )
        nnz = rows.size
        K = max(
            _next_pow2(int(row_counts.max()) if nnz else 1),
            _next_pow2(int(max_nnz_row)) if max_nnz_row is not None else 1,
            1,
        )
        KP = max(_next_pow2(int(col_counts.max()) if nnz else 1), 1)
        spill = (None, None, None)
        cap, t = None, 1
        # pinned paddings promise shape stability across sibling shards: the
        # layout planner must not replace the flat layout behind them
        if nnz and not pin_k and not pin_kp:
            cap, t = resolve_layout(
                kp_cap, col_split, col_counts, n, d, K, KP,
                size_floor=size_floor,
                row_block_k=make_row_block_k(rows, cols, n, d, pow2=True),
            )
        laying.set_attrs(blocks=t)
        if t == 1 and cap is not None:
            rows, cols, vals, sr, sc, sv = split_spill_entries(
                rows, cols, vals, col_counts, cap
            )
            spill = (sr, sc, sv)
            row_counts = np.bincount(rows, minlength=n)
            col_counts = np.minimum(col_counts, cap)
            KP = cap
    if t > 1:
        import functools

        return build_column_split(
            functools.partial(from_coo, payload_dtype=payload_dtype),
            rows, cols, vals, n, d, t, cap,
            hot_matrix, hot_ids, plan_cache,
        )
    for name, pin, needed in (("pin_k", pin_k, K), ("pin_kp", pin_kp, KP)):
        if not pin:
            continue
        if pin & (pin - 1):
            raise ValueError(f"{name}={pin} must be a power of two")
        if pin < needed:
            raise ValueError(f"{name}={pin} below required group size {needed}")
    K = max(K, pin_k)
    KP = max(KP, pin_kp)
    return assemble(
        rows, cols, vals, n, d, K, KP, hot_matrix, hot_ids, plan_cache,
        size_floor=size_floor, row_counts=row_counts, col_counts=col_counts,
        spill=spill, payload_dtype=payload_dtype,
    )


def assemble(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n: int,
    d: int,
    K: int,
    KP: int,
    hot_matrix: Optional[np.ndarray],
    hot_ids: Optional[np.ndarray],
    plan_cache: Optional[str],
    size_floor: int = 0,
    row_counts: Optional[np.ndarray] = None,
    col_counts: Optional[np.ndarray] = None,
    spill=(None, None, None),
    payload_dtype: str = "float32",
) -> FusedBenesFeatures:
    """Route + lay out prepared cold entries with pinned power-of-two
    paddings — the fused twin of ``sparse_perm._assemble`` (the grid builder
    stacks identically-shaped tiles built through this)."""
    assert K & (K - 1) == 0 and KP & (KP - 1) == 0, "group sizes must be pow2"
    size_floor = max(size_floor, MIN_FUSED_SIZE)
    for name, group in (("K", K), ("KP", KP)):
        if group > MAX_FUSED_GROUP:
            raise FusedGroupTooLarge(
                f"slot group {name}={group} exceeds the fused executor's "
                f"limit of {MAX_FUSED_GROUP} (a row/column with more nonzeros "
                "than that after hot-column splitting, or a pin_k/pin_kp/"
                "cross-tile pad that large); use engine='benes' for this shard"
            )

    from photon_ml_tpu.ops.sparse_perm import _hot_arrays, _spill_arrays, route_layout

    ell_pos, _, plan, plan_inv, S = route_layout(
        rows, cols, n, d, K, KP, plan_cache, size_floor, row_counts, col_counts
    )

    with span("route/place"):
        ell_flat = np.zeros(S, dtype=np.float32)
        ell_flat[ell_pos] = vals

    def features():
        hm, hc = _hot_arrays(hot_matrix, hot_ids)
        sr, sc, sv = _spill_arrays(*spill)
        return dict(
            ell_flat=jnp.asarray(ell_flat), hot_matrix=hm, hot_cols=hc,
            spill_rows=sr, spill_cols=sc, spill_vals=sv,
        )

    return FusedBenesFeatures(
        **upload("features", features),
        **upload("plan", lambda: dict(
            plan=device_plan(plan), plan_inv=device_plan(plan_inv)
        )),
        num_rows_=int(n),
        num_cols_=int(d),
        ell_k=int(K),
        csc_k=int(KP),
        payload_dtype=payload_dtype,
    )
