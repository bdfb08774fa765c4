"""The factored coordinate's item-tiled slot layout (``KronTiles``): the
Kronecker maps of the matrix solve over it against the explicit ``[n, d*k]``
design matrix, on one device and on a mesh, and the layout built once per
coordinate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.algorithm.factored_random_effect import (
    FactoredRandomEffectCoordinate,
    KronFeatures,
    MFOptimizationConfiguration,
    build_kron_tiles,
)
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, RegularizationContext
from photon_ml_tpu.telemetry.metrics import jit_trace_counts
from photon_ml_tpu.types import RegularizationType, TaskType

ITEMS = 37  # the last item is never drawn: a column with no slot
K = 3


def _ratings(n=700, users=30, seed=0):
    """Rows of (user, item one-hot): users with 6 - 40 rows, items Zipf(1.1)
    over all but the last, so the hottest item spans several tiles of 4 and
    a user holds fewer distinct items than the widest: padding slots."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(6, 41, users)
    user = np.repeat(np.arange(users), counts)[:n]
    p = 1.0 / np.arange(1, ITEMS) ** 1.1
    item = rng.choice(ITEMS - 1, user.size, p=p / p.sum())
    labels = rng.standard_normal(user.size).astype(np.float32)
    return user, item, labels


def _dataset(num_buckets=2, seed=0):
    user, item, labels = _ratings(seed=seed)
    rows = np.arange(user.size)
    return build_random_effect_dataset(
        entity_ids=np.array([f"u{u}" for u in user]),
        feature_rows=rows, feature_cols=item,
        feature_vals=np.ones(user.size, np.float32), global_dim=ITEMS,
        labels=labels,
        config=RandomEffectDataConfiguration(random_effect_type="u", num_buckets=num_buckets),
    )


def _tiles(ds, tile):
    return build_kron_tiles([np.asarray(b.proj_indices) for b in ds.buckets],
                            [np.asarray(b.proj_valid) for b in ds.buckets], tile)


def _latents(ds, seed=1):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b.num_entities, K)).astype(np.float32))
            for b in ds.buckets]


def _explicit(ds, latents):
    """The [n, d*k] design matrix, bucket by bucket, row (e, s) holding
    kron(x at global columns, latent[e])."""
    mats = []
    for bucket, v in zip(ds.buckets, latents):
        x, pidx, v = np.asarray(bucket.X), np.asarray(bucket.proj_indices), np.asarray(v)
        e_n, s_n, d_n = x.shape
        out = np.zeros((e_n * s_n, ITEMS * K), np.float64)
        for e in range(e_n):
            xg = np.zeros((s_n, ITEMS))
            for j in range(d_n):
                xg[:, pidx[e, j]] += x[e, :, j]
            out[e * s_n:(e + 1) * s_n] = np.einsum("sd,k->sdk", xg, v[e]).reshape(s_n, -1)
        mats.append(out)
    return np.concatenate(mats)


def _features(ds, latents, tiles):
    return KronFeatures.build([b.X for b in ds.buckets], latents, tiles, ITEMS, K)


def test_the_data_has_what_the_layout_must_handle():
    ds = _dataset()
    assert len(ds.buckets) == 2
    tiles = _tiles(ds, 4)
    valid = sum(int(np.asarray(b.proj_valid).sum()) for b in ds.buckets)
    total = sum(int(np.asarray(b.proj_valid).size) for b in ds.buckets)
    items = np.asarray(tiles.item_of_tile)
    assert np.all(np.diff(items) >= 0)
    assert np.bincount(items).max() >= 3  # an item over several tiles
    assert ITEMS - 1 not in items  # a column with no slot has no tile
    slot_of = np.asarray(tiles.slot_of).ravel()
    live = slot_of < total
    # every real slot once, and the padding slots left out of the layout
    assert live.sum() == valid < total and len(np.unique(slot_of[live])) == valid
    # every live position is the slot whose position it is
    pos = np.asarray(tiles.pos_of_slot)
    np.testing.assert_array_equal(pos[slot_of[live]], np.nonzero(live)[0])
    assert np.all(pos[np.setdiff1d(np.arange(total), slot_of[live])] == slot_of.size)


@pytest.mark.parametrize("tile", [4, 128])
def test_tiled_maps_match_the_explicit_matrix(tile):
    ds = _dataset()
    latents = _latents(ds)
    feats = _features(ds, latents, _tiles(ds, tile))
    M = _explicit(ds, latents)
    rng = np.random.default_rng(2)
    w = rng.standard_normal(ITEMS * K).astype(np.float32)
    c = rng.standard_normal(M.shape[0]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(feats.matvec(jnp.asarray(w))), M @ w,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(feats.rmatvec(jnp.asarray(c))), M.T @ c,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(feats.rmatvec_sq(jnp.asarray(c))), (M * M).T @ c,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(feats.row_norms_sq()), np.sum(M * M, axis=1),
                               rtol=1e-5, atol=1e-5)
    # the column no slot names takes no gradient
    grad = np.asarray(feats.rmatvec(jnp.asarray(c))).reshape(ITEMS, K)
    assert np.all(grad[ITEMS - 1] == 0)


def test_the_tiled_maps_repeat_bit_for_bit():
    """Two calls of one jitted program give the same bits: the layout fixes
    the order every map sums in."""
    ds = _dataset()
    latents = _latents(ds)
    tiles = _tiles(ds, 4)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal(ITEMS * K).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(sum(b.X.shape[0] * b.X.shape[1]
                                            for b in ds.buckets)).astype(np.float32))

    @jax.jit
    def maps(xs, latents, tiles, w, c):
        f = KronFeatures.build(xs, latents, tiles, ITEMS, K)
        return f.matvec(w), f.rmatvec(c), f.rmatvec_sq(c)

    xs = [b.X for b in ds.buckets]
    for a, b in zip(maps(xs, latents, tiles, w, c), maps(xs, latents, tiles, w, c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _coordinate(ds, mesh=None, axes=None):
    ridge = GlmOptimizationConfiguration(
        regularization=RegularizationContext(RegularizationType.L2), regularization_weight=1.0)
    return FactoredRandomEffectCoordinate(
        dataset=ds,
        task=TaskType.LINEAR_REGRESSION,
        re_configuration=ridge,
        matrix_configuration=ridge,
        mf_configuration=MFOptimizationConfiguration(num_latent_factors=K, num_iterations=2),
        base_offsets=np.zeros(ds.num_rows, np.float32),
        mesh=mesh, mesh_axes=axes,
    )


def test_the_layout_is_built_once_and_the_solve_traced_once():
    """Two updates of two alternations each: one layout, kept, and one trace
    of the matrix solve."""
    ds = _dataset(seed=4)
    coord = _coordinate(ds)
    traces = jit_trace_counts().get("mf_matrix_solve/lbfgs", 0)
    zeros = np.zeros(ds.num_rows, np.float32)
    model = coord.update_model(None, zeros)
    tiles = coord._kron_tiles
    after_first = jit_trace_counts()["mf_matrix_solve/lbfgs"]
    model = coord.update_model(model, zeros)
    assert tiles is not None and coord._kron_tiles is tiles
    assert after_first - traces == 1
    assert jit_trace_counts()["mf_matrix_solve/lbfgs"] == after_first
    assert np.all(np.isfinite(np.asarray(model.projection_matrix)))


def test_the_kept_layout_refuses_buckets_of_another_slot_count():
    ds = _dataset(seed=4)
    coord = _coordinate(ds)
    tiles = coord._layout(ds)
    assert coord._layout(ds) is tiles
    with pytest.raises(ValueError, match="slots"):
        coord._layout(_dataset(num_buckets=1, seed=7))


def test_a_mesh_solves_over_the_layout_for_the_same_matrix():
    """On a mesh the matrix solve runs over the same layout, built from the
    sharded buckets; given the same latents it solves for the matrix the
    one-device solve finds."""
    import dataclasses
    import types

    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from photon_ml_tpu.data.random_effect import pad_entities_to_multiple, place_dataset

    axes = ("data", "feat")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), axes)
    padded = pad_entities_to_multiple(_dataset(num_buckets=1, seed=5), 4)
    sharded = _coordinate(
        dataclasses.replace(padded, buckets=place_dataset(padded, mesh, axes).buckets),
        mesh, axes)
    single = _coordinate(padded)
    latents = _latents(padded, seed=6)
    on_devices = [jax.device_put(v, NamedSharding(mesh, PartitionSpec(axes, None)))
                  for v in latents]
    B0 = single._init_matrix()
    on_mesh = sharded._solve_matrix(sharded.dataset,
                                    types.SimpleNamespace(coefficients=on_devices), B0)
    alone = single._solve_matrix(single.dataset,
                                 types.SimpleNamespace(coefficients=latents), B0)
    for a, b in zip(jax.tree.leaves(sharded._kron_tiles), jax.tree.leaves(single._kron_tiles)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # both stop where the float32 objective stops moving: the objectives
    # agree to its last places, the matrices as far as that flat a valley says
    M = _explicit(padded, latents)
    y = np.concatenate([np.asarray(b.labels).ravel() for b in padded.buckets])
    wt = np.concatenate([np.asarray(b.weights).ravel() for b in padded.buckets])

    def objective(B):
        w = np.asarray(B, np.float64).ravel()
        return 0.5 * np.sum(wt * (M @ w - y) ** 2) + 0.5 * np.sum(w * w)

    assert abs(objective(on_mesh) - objective(alone)) <= 1e-5 * objective(alone)
    np.testing.assert_allclose(np.asarray(on_mesh), np.asarray(alone), atol=1e-2)
