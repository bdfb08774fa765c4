"""Device mesh + sharding helpers: the communication layer.

Reference parity: §2.6 of the survey — the reference's "distributed backend"
is Spark (treeAggregate all-reduce-to-driver + broadcast of coefficients per
evaluation, ValueAndGradientAggregator.scala:243-247,
DistributedObjectiveFunction.scala). The TPU-native replacement is sharding
annotations over a ``jax.sharding.Mesh``: batches are sharded over the "data"
axis, coefficients are replicated, and XLA inserts the all-reduces (psum over
ICI) inside the jit'd solver program wherever ``rmatvec``/loss-sum reductions
cross the batch axis. There is no per-step broadcast — coefficients live
resident on device.

Multi-host: the same annotations scale to DCN-attached slices via
jax.distributed; data loading feeds per-host shards (io/ pipeline).
"""

from __future__ import annotations

from typing import Optional, Sequence

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures, EllFeatures

DATA_AXIS = "data"

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the feature engines
    mix Pallas calls and psums the checker can't type."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def mesh_attrs(mesh: Optional[Mesh]) -> dict:
    """What a span says of the mesh its work runs on: ``mesh`` (the axis
    sizes, "2x2") and ``devices``; nothing where there is no mesh."""
    if mesh is None:
        return {}
    return {
        "mesh": "x".join(str(n) for n in mesh.devices.shape),
        "devices": int(mesh.devices.size),
    }


def data_parallel_mesh(
    num_devices: Optional[int] = None, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """1-D mesh over the batch ("data") axis."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def pad_batch_to_multiple(data: LabeledData, multiple: int) -> LabeledData:
    """Pad the batch with weight-0 rows so it divides evenly across devices.

    Padding rows have features=0, label=0, offset=0, weight=0 — exact
    algebraic no-ops in the objective (see losses/objective.py _wmask).
    """
    n = data.num_rows
    rem = n % multiple
    if rem == 0:
        return data
    pad = multiple - rem

    def pad0(a):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths)

    feats = data.features
    if isinstance(feats, DenseFeatures):
        feats = DenseFeatures(matrix=pad0(feats.matrix))
    else:
        feats = EllFeatures(
            values=pad0(feats.values),
            indices=pad0(feats.indices),
            num_cols=feats.num_cols,
        )
    return LabeledData(
        features=feats,
        labels=pad0(data.labels),
        offsets=pad0(data.offsets),
        weights=pad0(data.weights),
        norm=data.norm,
    )


def place(x, mesh: Mesh, spec: P):
    """Place a host-global array onto a mesh sharding, working in BOTH
    runtime models: plain device_put under a single controller, and
    per-process addressable-shard placement in a multi-process cluster
    (device_put cannot reach other hosts' devices there). Every process
    must hold the same GLOBAL value of ``x``."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() <= 1:
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array):
        try:
            if x.sharding.is_equivalent_to(sharding, x.ndim):
                return x  # already placed (re-placing buckets is common)
        except Exception:
            pass
        x = fetch_global(x)  # may itself span processes
    else:
        x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


def shard_batch(data: LabeledData, mesh: Mesh) -> LabeledData:
    """Place batch-axis arrays sharded over the mesh's data axis; the
    normalization context (feature-axis arrays) is replicated."""
    n_dev = mesh.shape[DATA_AXIS]
    data = pad_batch_to_multiple(data, n_dev)

    def put_rows(a):
        return place(a, mesh, P(DATA_AXIS))

    def put_mat(a):
        return place(a, mesh, P(DATA_AXIS, None))

    feats = data.features
    if isinstance(feats, DenseFeatures):
        feats = DenseFeatures(matrix=put_mat(feats.matrix))
    else:
        feats = EllFeatures(
            values=put_mat(feats.values),
            indices=put_mat(feats.indices),
            num_cols=feats.num_cols,
        )
    norm = data.norm
    if norm is not None:
        norm = replicate(norm, mesh)
    return LabeledData(
        features=feats,
        labels=put_rows(data.labels),
        offsets=put_rows(data.offsets),
        weights=put_rows(data.weights),
        norm=norm,
    )


def replicate(x, mesh: Mesh):
    """Fully replicate a pytree over the mesh."""
    return jax.tree.map(lambda a: place(a, mesh, P()), x)


@functools.lru_cache(maxsize=64)
def _gather_fn(sharding: NamedSharding):
    """One cached all-gather program per target sharding (a fresh jit per
    call would retrace + recompile on every fetch)."""
    return jax.jit(lambda x: x, out_shardings=sharding)


# Device->host fetch observers: callbacks invoked with the byte size of
# every array fetch_global materializes on host. The zero-row-transfer
# steady-state tests of the device score plane install one to prove no code
# path (driver OR coordinate internals) silently pulls a row-length score
# array; fetches of genuinely-host numpy inputs are not device transfers and
# are only observed when the input was a jax.Array.
_FETCH_OBSERVERS: list = []


def add_fetch_observer(callback) -> None:
    """Register ``callback(nbytes)`` to fire on every device->host fetch."""
    _FETCH_OBSERVERS.append(callback)


def remove_fetch_observer(callback) -> None:
    _FETCH_OBSERVERS.remove(callback)


def fetch_global(a):
    """``np.asarray`` for device arrays that may span processes: a sharded
    global array is all-gathered to a replicated layout first (every shard
    becomes addressable), then fetched. A plain no-op fetch everywhere else
    — host numpy code (the coordinate-descent driver's residual algebra)
    calls this instead of np.asarray.

    In a multi-host run this is a cross-process COLLECTIVE: every process
    must call it in the same order (never behind data-dependent branches).
    """
    was_device = isinstance(a, jax.Array)
    if (
        was_device
        and jax.process_count() > 1
        and not a.is_fully_addressable
    ):
        a = _gather_fn(NamedSharding(a.sharding.mesh, P()))(a)
    out = np.asarray(a)
    if was_device and _FETCH_OBSERVERS:
        for cb in list(_FETCH_OBSERVERS):
            cb(out.nbytes)
    return out
