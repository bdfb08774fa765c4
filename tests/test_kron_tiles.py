"""The factored coordinate's item-tiled slot layout (``KronTiles``): the
Kronecker maps of the matrix solve over it against the explicit ``[n, d*k]``
design matrix, on one device and on a mesh; the layout's routed moves
against the gathers they replaced, through XLA and through the fused kernels
in the Pallas interpreter; and the layout and its plans built once per
coordinate."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import struct

from photon_ml_tpu.algorithm.factored_random_effect import (
    FactoredRandomEffectCoordinate,
    KronFeatures,
    MFOptimizationConfiguration,
    TILE,
    build_kron_tiles,
    slot_permutation,
    tile_layout,
)
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
    pad_entities_to_multiple,
)
from photon_ml_tpu.ops import fused_perm
from photon_ml_tpu.ops.permute_net import apply_plan
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, RegularizationContext
from photon_ml_tpu.telemetry import disable_tracing, enable_tracing
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


def _dataset(num_buckets=2, seed=0, n=700, users=30):
    user, item, labels = _ratings(n=n, users=users, seed=seed)
    rows = np.arange(user.size)
    return build_random_effect_dataset(
        entity_ids=np.array([f"u{u}" for u in user]),
        feature_rows=rows, feature_cols=item,
        feature_vals=np.ones(user.size, np.float32), global_dim=ITEMS,
        labels=labels,
        config=RandomEffectDataConfiguration(random_effect_type="u", num_buckets=num_buckets),
    )


def _index_maps(ds):
    return ([np.asarray(b.proj_indices) for b in ds.buckets],
            [np.asarray(b.proj_valid) for b in ds.buckets])


def _tiles(ds, tile):
    return build_kron_tiles(*_index_maps(ds), tile)


def _layout(ds, tile):
    return tile_layout(*_index_maps(ds), tile)


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
    layout = _layout(ds, 4)
    valid = sum(int(np.asarray(b.proj_valid).sum()) for b in ds.buckets)
    total = sum(int(np.asarray(b.proj_valid).size) for b in ds.buckets)
    items = layout.item_of_tile
    assert np.all(np.diff(items) >= 0)
    assert np.bincount(items).max() >= 3  # an item over several tiles
    assert ITEMS - 1 not in items  # a column with no slot has no tile
    slot_of = layout.slot_of
    live = slot_of < total
    # every real slot once, and the padding slots left out of the layout
    assert live.sum() == valid < total and len(np.unique(slot_of[live])) == valid
    # every live position is the slot whose position it is
    pos = layout.pos_of_slot
    np.testing.assert_array_equal(pos[slot_of[live]], np.nonzero(live)[0])
    assert np.all(pos[np.setdiff1d(np.arange(total), slot_of[live])] == slot_of.size)
    # the device's plans move as the layout says: a live position reads its
    # slot and a real slot its position; a dead position reads a dead slot
    tiles = _tiles(ds, 4)
    assert tiles.slots == total and np.asarray(tiles.entity_of).size == slot_of.size
    ids = jnp.arange(1, tiles.to_slots.size + 1, dtype=jnp.float32)  # exact
    read = np.asarray(apply_plan(tiles.to_positions, ids)).astype(np.int64) - 1
    np.testing.assert_array_equal(read[:slot_of.size][live], slot_of[live])
    assert not np.isin(read[:slot_of.size][~live], slot_of[live]).any()
    read = np.asarray(apply_plan(tiles.to_slots, ids)).astype(np.int64) - 1
    np.testing.assert_array_equal(read[slot_of[live]], np.nonzero(live)[0])


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


def _take(flat, idx):
    """The move the routed plans replaced: a gather of 128-lane rows and a
    one-hot select of the lane. An index one past ``flat`` reads 0."""
    lanes = 128
    flat = jnp.append(flat, 0.0)
    rows = jnp.pad(flat, (0, -flat.shape[0] % lanes)).reshape(-1, lanes)
    hit = (idx % lanes)[..., None] == jnp.arange(lanes)
    return jnp.sum(jnp.where(hit, rows[idx // lanes], 0), axis=-1)


@struct.dataclass
class _Gathered(KronFeatures):
    """The maps with the gathers in place of the routed moves: the oracle."""

    pos_of_slot: jax.Array = None
    slot_of: jax.Array = None

    def _to_slots(self, u):
        return _take(u, self.pos_of_slot)

    def _to_positions(self, a):
        return _take(a, self.slot_of).reshape(self.tiles.entity_of.shape)


def _one_tile():
    """Five users who rated the one item: one tile of 128 positions, five of
    them live, and three padded users whose slots are dead."""
    ds = build_random_effect_dataset(
        entity_ids=np.array([f"u{u}" for u in range(5)]),
        feature_rows=np.arange(5), feature_cols=np.full(5, 7),
        feature_vals=np.ones(5, np.float32), global_dim=ITEMS,
        labels=np.arange(5, dtype=np.float32),
        config=RandomEffectDataConfiguration(random_effect_type="u"),
    )
    return pad_entities_to_multiple(ds, 8)


LAYOUTS = {  # (data set, tile)
    "buckets": (lambda: _dataset(), 4),        # two buckets, items over several tiles
    "tile128": (lambda: _dataset(), TILE),
    "one_tile": (_one_tile, TILE),
    "wide": (lambda: _dataset(n=24000, users=1000), 4),  # over 2^14 slots: a 2^15 plan
}


@pytest.mark.parametrize("moves", ["xla", "routed"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_routed_moves_equal_the_gathers_bit_for_bit(layout, moves, monkeypatch):
    """Each map with the routed moves (XLA's stages; the fused kernels in
    the Pallas interpreter) against the same map with the gathers, on
    layouts with dead positions and dead slots: the same bits. A permutation
    moves values exactly, and the gathers added zeros."""
    monkeypatch.setattr(fused_perm, "_INTERPRET", moves == "routed")
    make, tile = LAYOUTS[layout]
    ds = make()
    host = _layout(ds, tile)
    tiles = _tiles(ds, tile)
    dead_positions = host.slot_of == host.pos_of_slot.size
    dead_slots = host.pos_of_slot == host.slot_of.size
    assert dead_positions.any() and dead_slots.any()
    size = tiles.to_slots.size
    if layout == "wide":
        assert size == 2 ** 15 and len(ds.buckets) > 1
    else:
        assert size == 2 ** 14
    if layout == "one_tile":
        assert host.item_of_tile.size == 1
    latents = _latents(ds)
    xs = [b.X for b in ds.buckets]
    feats = KronFeatures.build(xs, latents, tiles, ITEMS, K)
    assert feats.moves == moves
    oracle = _Gathered.build(xs, latents, tiles, ITEMS, K).replace(
        pos_of_slot=jnp.asarray(host.pos_of_slot),
        slot_of=jnp.asarray(host.slot_of.reshape(-1, tile)),
    )
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.standard_normal(ITEMS * K).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(feats.num_rows).astype(np.float32))
    for op, arg in (("matvec", w), ("rmatvec", c), ("rmatvec_sq", c)):
        got = np.asarray(getattr(feats, op)(arg))
        want = np.asarray(getattr(oracle, op)(arg))
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32), op)


def test_a_dead_slot_and_a_dead_position_hold_zero():
    """What the moves rely on, a direction each: the block is 0 at a local
    column that is not real, padded entities included (so a dead slot's
    part is 0, which ``rmatvec``'s dead positions read), and the latents
    laid into tile order are 0 at a dead position (so its value is 0, which
    ``matvec``'s dead slots read)."""
    ds = pad_entities_to_multiple(_dataset(), 8)
    assert sum(b.num_entities for b in ds.buckets) > 30
    for b in ds.buckets:
        x, real = np.asarray(b.X), np.asarray(b.proj_valid)
        assert (~real).any() and np.all(x.transpose(0, 2, 1)[~real] == 0)
    host = _layout(ds, 4)
    feats = _features(ds, _latents(ds), _tiles(ds, 4))
    dead = (host.slot_of == host.pos_of_slot.size).reshape(-1, 4)
    vs = np.asarray(feats.vs).transpose(0, 2, 1)  # [tiles, TILE, k]
    assert dead.any() and np.all(vs[dead] == 0) and np.all(vs[~dead].any(axis=-1))


def test_the_dead_pairing_gives_one_plan_cache_key(tmp_path, monkeypatch):
    """Two builds of one layout make one permutation, so one key of the plan
    cache: the second reads back what the first routed."""
    monkeypatch.setenv("PHOTON_ML_TPU_PLAN_CACHE", str(tmp_path))
    ds = _dataset()
    perms = [slot_permutation(_layout(ds, 4)) for _ in range(2)]
    np.testing.assert_array_equal(perms[0], perms[1])
    assert hashlib.sha1(perms[0].tobytes()).digest() == hashlib.sha1(perms[1].tobytes()).digest()
    np.testing.assert_array_equal(np.sort(perms[0]), np.arange(perms[0].size))
    first, second = _tiles(ds, 4), _tiles(ds, 4)
    assert len(list(tmp_path.iterdir())) == 1
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(second)):
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


def _kron_spans(coord, updates, ds):
    tracer = enable_tracing(device_sync=False)
    try:
        model = None
        for _ in range(updates):
            model = coord.update_model(model, np.zeros(ds.num_rows, np.float32))
        spans = tracer.spans()
    finally:
        disable_tracing()
    return spans


def test_the_kept_layout_routes_its_plan_once_and_a_warm_cache_none(tmp_path, monkeypatch):
    """Two updates of two alternations: the layout's plan routed once, its
    inverse made once and both uploaded once (three ``what="kron"`` spans);
    a second coordinate over the same buckets reads the plan back and routes
    none. Every matrix solve says how its moves ran and over how many
    slots."""
    monkeypatch.setenv("PHOTON_ML_TPU_PLAN_CACHE", str(tmp_path))
    ds = _dataset(seed=4)
    for cached in (False, True):
        spans = _kron_spans(_coordinate(ds), 2, ds)
        kron = [s for s in spans if s.attrs.get("what") == "kron"]
        assert sorted(s.name for s in kron) == ["data/upload", "route/place", "route/plan"]
        plan = next(s for s in kron if s.name == "route/plan")
        assert plan.attrs["cached"] is cached and plan.attrs["slots"] == 2 ** 14
        solves = [s for s in spans if s.name == "mf/solve_matrix"]
        assert len(solves) == 4
        assert all(s.attrs["moves"] == "xla" and s.attrs["move_slots"] == 2 ** 14
                   for s in solves)
    assert len(list(tmp_path.iterdir())) == 1


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
