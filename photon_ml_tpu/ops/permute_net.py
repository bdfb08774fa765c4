"""Device execution of static-permutation plans (see ops/routing.py).

A plan is a sequence of within-row 128-lane shuffles (``tpu.dynamic_gather``
via Pallas), within-tile sublane shuffles, and free XLA relayouts. All
stages are dense vector work — this is how the framework runs the sparse
GLM gather/scatter at vector speed instead of XLA's scalar ~10ns/element
loop (the TPU replacement for the reference's per-partition sparse axpy,
ValueAndGradientAggregator.scala:132-153).

Execution modes, chosen from the backend:
- TPU: Pallas kernels (one program launch amortized over the whole solve).
- elsewhere (the tests' 8-virtual-device CPU harness): XLA
  ``take_along_axis`` with identical semantics.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.pallas_kernels import pallas_available
from photon_ml_tpu.ops.routing import (
    LANES,
    Enter,
    LaneShuffle,
    Leave,
    PermPlan,
    SublaneShuffle,
)


@struct.dataclass
class DevicePlan:
    """Jit-friendly plan: shuffle index arrays are pytree leaves (runtime
    inputs, not baked-in constants), stage structure is static metadata."""

    idx: Tuple[jax.Array, ...]
    kinds: Tuple[tuple, ...] = struct.field(pytree_node=False)
    size: int = struct.field(pytree_node=False)


def device_plan(plan: PermPlan) -> DevicePlan:
    idx = []
    kinds = []
    for st in plan.stages:
        if isinstance(st, LaneShuffle):
            # lane indices are < 128, sublane indices < 8: int8 on device
            # halves the plan's HBM footprint and per-pass index traffic
            # (kernels upcast in VMEM, which is free next to the loads)
            idx.append(jnp.asarray(st.idx, dtype=jnp.int8))
            kinds.append(("lane",))
        elif isinstance(st, SublaneShuffle):
            idx.append(jnp.asarray(st.idx, dtype=jnp.int8))
            kinds.append(("sublane", st.rows))
        elif isinstance(st, Enter):
            kinds.append(("enter", st.blocks, st.rows))
        elif isinstance(st, Leave):
            kinds.append(("leave", st.blocks, st.rows))
        else:  # pragma: no cover
            raise TypeError(st)
    return DevicePlan(idx=tuple(idx), kinds=tuple(kinds), size=plan.size)


def _row_block(m: int) -> int:
    for rb in (4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if m % rb == 0:
            return rb
    return m


# Test hook: run the Pallas kernels through the interpreter (CPU) so their
# semantics are covered by the 8-virtual-device harness, not just on TPU.
_INTERPRET = False


def _lane_shuffle_pallas(v: jax.Array, idx: jax.Array) -> jax.Array:
    m = v.shape[0]
    rb = _row_block(m)

    def kernel(x_ref, i_ref, o_ref):
        sel = i_ref[:].astype(jnp.int32)
        o_ref[:] = jnp.take_along_axis(x_ref[:], sel, axis=1)

    return pl.pallas_call(
        kernel,
        grid=(m // rb,),
        in_specs=[
            pl.BlockSpec((rb, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, LANES), v.dtype),
        interpret=_INTERPRET,
    )(v, idx)


def _sublane_shuffle_pallas(v: jax.Array, idx: jax.Array, rows: int) -> jax.Array:
    m = v.shape[0]
    rb = _row_block(m)
    assert rb % rows == 0

    def kernel(x_ref, i_ref, o_ref):
        # Loop-free within-group row movement: rows <= 8 source rows per
        # group, so materialize each group-constant source row and select.
        # (A fori_loop of tiny dynamic slices compiles pathologically in
        # Mosaic at rb/rows ~ hundreds of steps; 'rows' selects vectorize.)
        x = x_ref[:].reshape(rb // rows, rows, LANES)
        sel = i_ref[:].astype(jnp.int32).reshape(rb // rows, rows, LANES)
        acc = jnp.zeros_like(x)
        for k in range(rows):
            src_row = jax.lax.broadcast_in_dim(
                x[:, k, :], x.shape, (0, 2)
            )
            acc = jnp.where(sel == k, src_row, acc)
        o_ref[:] = acc.reshape(rb, LANES)

    return pl.pallas_call(
        kernel,
        grid=(m // rb,),
        in_specs=[
            pl.BlockSpec((rb, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, LANES), v.dtype),
        interpret=_INTERPRET,
    )(v, idx)


def _lane_shuffle_xla(v: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take_along_axis(v, idx.astype(jnp.int32), axis=1)


def _sublane_shuffle_xla(v: jax.Array, idx: jax.Array, rows: int) -> jax.Array:
    m = v.shape[0]
    blk = v.reshape(m // rows, rows, LANES)
    sel = idx.astype(jnp.int32).reshape(m // rows, rows, LANES)
    return jnp.take_along_axis(blk, sel, axis=1).reshape(m, LANES)


def _use_pallas(m: int) -> bool:
    """Pallas on a TPU for every plan of at least 32 rows. A smaller plan
    (<= 1024 slots) is one sublane tile, too short for the (32, 128) tile an
    int8 index block needs, and runs as an XLA gather. Every larger
    ``routing.valid_size`` is c*128^k rows, so its row block is a multiple
    of 32 and of every sublane group size."""
    return pallas_available() and m >= 32


def apply_plan(dplan: DevicePlan, x: jax.Array, kernels: bool = True) -> jax.Array:
    """Apply the permutation plan to ``x`` (length must equal plan size).

    Returns the permuted array of the same length. Safe under jit/vmap-free
    contexts; all stage shapes are static. ``kernels=False`` keeps every
    stage in XLA on a TPU too: for operands split over devices, which GSPMD
    cannot hand to a Pallas call.
    """
    assert x.shape[-1] == dplan.size, (x.shape, dplan.size)
    v = x.reshape(-1, LANES)
    ai = 0
    for kind in dplan.kinds:
        if kind[0] == "lane":
            idx = dplan.idx[ai]
            ai += 1
            if kernels and _use_pallas(v.shape[0]):
                v = _lane_shuffle_pallas(v, idx)
            else:
                v = _lane_shuffle_xla(v, idx)
        elif kind[0] == "sublane":
            idx = dplan.idx[ai]
            ai += 1
            rows = kind[1]
            if rows == 1:
                continue  # single-row groups: identity movement
            if kernels and _use_pallas(v.shape[0]):
                v = _sublane_shuffle_pallas(v, idx, rows)
            else:
                v = _sublane_shuffle_xla(v, idx, rows)
        elif kind[0] == "enter":
            _, b, r = kind
            v = v.reshape(b, r, LANES).transpose(0, 2, 1).reshape(-1, LANES)
        elif kind[0] == "leave":
            _, b, r = kind
            v = v.reshape(b, LANES, r).transpose(0, 2, 1).reshape(-1, LANES)
        else:  # pragma: no cover
            raise ValueError(kind)
    return v.reshape(-1)
