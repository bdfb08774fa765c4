"""Unit tests for the parallel plane's bottom layers (parallel/mesh.py,
parallel/multihost.py): mesh construction, batch padding + shard
placement, and the single-process degenerate paths of the multi-host
runtime seams. conftest forces 8 host-platform devices, so placement is
exercised on a real multi-device mesh without any cluster.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures, EllFeatures
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    add_fetch_observer,
    data_parallel_mesh,
    fetch_global,
    pad_batch_to_multiple,
    place,
    remove_fetch_observer,
    replicate,
    shard_batch,
    shard_map,
)
from photon_ml_tpu.parallel.multihost import (
    barrier,
    global_batch_from_host_rows,
    host_shard_files,
    initialize_distributed,
)


def _dense_batch(n=10, d=4, seed=3):
    rng = np.random.default_rng(seed)
    return LabeledData(
        features=DenseFeatures(
            matrix=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        ),
        labels=jnp.asarray(rng.integers(0, 2, n).astype(np.float32)),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
    )


# ===================================================================== mesh


class TestMeshConstruction:
    def test_default_mesh_spans_all_devices(self):
        mesh = data_parallel_mesh()
        assert mesh.axis_names == (DATA_AXIS,)
        assert mesh.shape[DATA_AXIS] == len(jax.devices())

    def test_num_devices_takes_a_prefix(self):
        mesh = data_parallel_mesh(num_devices=4)
        assert mesh.shape[DATA_AXIS] == 4
        assert list(mesh.devices.flat) == jax.devices()[:4]

    def test_single_device_mesh_is_valid(self):
        mesh = data_parallel_mesh(num_devices=1)
        assert mesh.shape[DATA_AXIS] == 1

    def test_shard_map_psum_is_global_sum(self):
        mesh = data_parallel_mesh(num_devices=4)
        x = jnp.arange(8, dtype=jnp.float32)
        xs = place(x, mesh, P(DATA_AXIS))

        def local_sum(block):
            return jax.lax.psum(jnp.sum(block), DATA_AXIS)

        got = shard_map(
            local_sum, mesh, in_specs=P(DATA_AXIS), out_specs=P()
        )(xs)
        assert float(got) == float(x.sum())


# ================================================================== padding


class TestPadBatch:
    def test_divisible_batch_is_untouched(self):
        data = _dense_batch(n=8)
        assert pad_batch_to_multiple(data, 4) is data

    def test_padding_rows_are_algebraic_noops(self):
        data = _dense_batch(n=10)
        padded = pad_batch_to_multiple(data, 4)
        assert padded.num_rows == 12
        np.testing.assert_array_equal(padded.weights[10:], 0.0)
        np.testing.assert_array_equal(padded.labels[10:], 0.0)
        np.testing.assert_array_equal(
            np.asarray(padded.features.matrix[10:]), 0.0
        )
        # the real rows are untouched
        np.testing.assert_array_equal(
            np.asarray(padded.features.matrix[:10]),
            np.asarray(data.features.matrix),
        )

    def test_ell_features_pad_values_and_indices(self):
        n, k = 6, 3
        data = LabeledData(
            features=EllFeatures(
                values=jnp.ones((n, k), jnp.float32),
                indices=jnp.zeros((n, k), jnp.int32),
                num_cols=5,
            ),
            labels=jnp.ones(n, jnp.float32),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32),
        )
        padded = pad_batch_to_multiple(data, 4)
        assert padded.features.values.shape == (8, k)
        assert padded.features.indices.shape == (8, k)
        assert padded.features.num_cols == 5
        np.testing.assert_array_equal(
            np.asarray(padded.features.values[6:]), 0.0
        )


# ================================================================ placement


class TestPlacement:
    def test_place_rows_shards_over_data_axis(self):
        mesh = data_parallel_mesh(num_devices=4)
        x = np.arange(12, dtype=np.float32)
        placed = place(x, mesh, P(DATA_AXIS))
        assert placed.sharding.is_equivalent_to(
            NamedSharding(mesh, P(DATA_AXIS)), placed.ndim
        )
        # 3 rows per device
        assert {s.data.shape for s in placed.addressable_shards} == {(3,)}
        np.testing.assert_array_equal(np.asarray(placed), x)

    def test_replicate_puts_full_copy_on_every_device(self):
        mesh = data_parallel_mesh(num_devices=4)
        tree = {"w": np.arange(5, dtype=np.float32)}
        rep = replicate(tree, mesh)
        assert {s.data.shape for s in rep["w"].addressable_shards} == {(5,)}

    def test_shard_batch_pads_then_places(self):
        mesh = data_parallel_mesh(num_devices=4)
        data = _dense_batch(n=10, d=4)
        sharded = shard_batch(data, mesh)
        assert sharded.num_rows == 12
        assert sharded.labels.sharding.is_equivalent_to(
            NamedSharding(mesh, P(DATA_AXIS)), 1
        )
        assert sharded.features.matrix.sharding.is_equivalent_to(
            NamedSharding(mesh, P(DATA_AXIS, None)), 2
        )
        # weights of the pad rows stay exact zeros after placement
        np.testing.assert_array_equal(
            np.asarray(sharded.weights)[10:], 0.0
        )


# ============================================================== fetch_global


class TestFetchGlobal:
    def test_numpy_passthrough(self):
        x = np.arange(4.0)
        np.testing.assert_array_equal(fetch_global(x), x)

    def test_sharded_array_roundtrips(self):
        mesh = data_parallel_mesh(num_devices=4)
        x = np.arange(8, dtype=np.float32)
        placed = place(x, mesh, P(DATA_AXIS))
        np.testing.assert_array_equal(fetch_global(placed), x)

    def test_observer_sees_device_fetches_only(self):
        seen = []
        add_fetch_observer(seen.append)
        try:
            fetch_global(np.zeros(4))  # host input: not a device fetch
            assert seen == []
            fetch_global(jnp.zeros(4, jnp.float32))
            assert seen == [16]
        finally:
            remove_fetch_observer(seen.append)


# ======================================================== multihost seams


class TestMultihostDegeneratePaths:
    """Single-process: every seam must degrade to the identity (the
    multi-process branches are exercised by tests/test_multiprocess.py)."""

    def test_host_shard_files_returns_all_sorted(self):
        files = ["b.avro", "a.avro", "c.avro"]
        assert host_shard_files(files) == sorted(files)

    def test_barrier_is_noop(self):
        barrier("unit-test")  # must simply return

    def test_global_batch_is_plain_device_put(self):
        mesh = data_parallel_mesh(num_devices=4)
        rows = np.arange(8, dtype=np.float32).reshape(4, 2)
        got = global_batch_from_host_rows(rows, mesh, P(DATA_AXIS, None))
        assert got.sharding.is_equivalent_to(
            NamedSharding(mesh, P(DATA_AXIS, None)), 2
        )
        np.testing.assert_array_equal(np.asarray(got), rows)

    def test_initialize_without_cluster_flags_is_single_process(self):
        assert initialize_distributed() is False

    def test_explicit_cluster_request_after_backend_up_raises(self):
        with pytest.raises(RuntimeError, match="before any JAX call"):
            initialize_distributed(coordinator_address="127.0.0.1:1234")
