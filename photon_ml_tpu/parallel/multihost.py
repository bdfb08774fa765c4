"""Multi-host (DCN) runtime helpers: process init + host-sharded input.

Reference parity: the reference's multi-node story is Spark/YARN — executors
pull partitions over the network, the driver coordinates (SURVEY.md §2.6).
The TPU-pod analog: one python process per host, `jax.distributed`
establishes the global device view, training-step collectives ride ICI
inside jit'd programs, and DCN carries only the input pipeline and
checkpoint IO.

These are the runtime seams, called from the CLIs (initialize) and usable
by multi-host input pipelines (file sharding, global batch assembly). They
degrade to the identity in single-process runs — which is also all the
in-repo tests can exercise; the multi-process branches follow the
documented jax.distributed contracts.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Bring this process into the cluster. Returns True when a multi-process
    cluster is (or already was) established.

    A process joins a cluster only when ``coordinator_address`` /
    ``num_processes`` / ``process_id`` ask for it; without them this is a
    single-process run and ``jax.distributed.initialize`` is never called.
    (Its no-argument auto-detection asks the GCE metadata server for the
    worker number on any host where JAX sees TPU chips, which raises on a
    machine without network.) An explicit request MUST run before anything
    initializes an XLA backend (first jnp op, ``jax.devices()``, ...) — the
    CLIs call this first thing.

    Also enables JAX's persistent compilation cache
    (:func:`photon_ml_tpu.utils.cachedir.enable_compilation_cache`): every
    training CLI funnels through here, so repeat runs skip first-compile cost.
    """
    from photon_ml_tpu.utils.cachedir import enable_compilation_cache

    requested = not (
        coordinator_address is None
        and num_processes is None
        and process_id is None
    )
    if requested and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    enable_compilation_cache()  # looks at the backend: after the join
    return jax.process_count() > 1


def barrier(name: str = "photon-ml-tpu-barrier") -> None:
    """Block until every process reaches this point (no-op single-process).

    Use after single-writer persistence (process 0 writes, everyone then
    reads) and before tearing down shared resources.
    """
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def add_distributed_args(parser) -> None:
    """CLI flags for a cluster launch (torchrun-style): every process of
    the job runs the same command with its own --process-id. Without them
    the process runs alone."""
    parser.add_argument(
        "--coordinator-address", default=None,
        help="host:port of process 0 (explicit multi-host launch)",
    )
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)


def initialize_from_args(args) -> bool:
    """``initialize_distributed`` from parsed CLI args (the CLIs call this
    first thing, before any jax device use)."""
    return initialize_distributed(
        coordinator_address=getattr(args, "coordinator_address", None),
        num_processes=getattr(args, "num_processes", None),
        process_id=getattr(args, "process_id", None),
    )


def host_shard_files(paths: Sequence[str]) -> List[str]:
    """This host's slice of the input files (deterministic round-robin over
    the sorted list, so every host computes the same assignment)."""
    ordered = sorted(paths)
    n = jax.process_count()
    if n <= 1:
        return ordered
    i = jax.process_index()
    return [p for k, p in enumerate(ordered) if k % n == i]


def global_batch_from_host_rows(
    rows: np.ndarray, mesh, spec, global_rows: Optional[int] = None
):
    """Assemble a globally-sharded batch array from this host's row block.

    ``rows`` is the process-local data; ``spec`` a PartitionSpec placing the
    global batch over ``mesh``. Each process's block must be exactly the
    slice its own devices address — ``global_rows * local_devices /
    global_devices`` rows (devices cannot hold rows another host has, and
    this helper never moves data between hosts). File sharding
    (:func:`host_shard_files`) generally produces unequal row counts, so
    input pipelines equalize first: fixed-size per-host batches, with
    zero-weight padding rows for the remainder (weight-0 rows are exact
    no-ops in every objective). A too-small/too-large block raises with
    that instruction rather than tripping deep inside jax. On one process
    this is a plain device_put.
    """
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    if jax.process_count() <= 1:
        return jax.device_put(rows, sharding)
    global_shape = None
    if global_rows is not None:
        global_shape = (int(global_rows),) + tuple(rows.shape[1:])
    try:
        return jax.make_array_from_process_local_data(
            sharding, rows, global_shape=global_shape
        )
    except ValueError as e:
        # jax's shard-shape validation covers every spec (sharded over any
        # axis subset, partially sharded, replicated); we add the remedy
        raise ValueError(
            f"{e}\nEach host must supply exactly the rows its own devices "
            "address under the given spec (or the full global batch when "
            "the batch dimension is replicated); this helper never moves "
            "rows between hosts. Equalize per-host batches first — pad "
            "with zero-weight rows (exact no-ops in every objective) or "
            "trim to the share."
        ) from None
