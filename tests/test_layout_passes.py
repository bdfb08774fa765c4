"""The routed layout's host passes made once each, in linear time: the
arrays, plans, spills, paddings and plan-cache keys are what the plain forms
in ``tests/_layout_oracles.py`` give, bit for bit; the native stage inverses
and slot permutation equal their numpy fallbacks; and a column split
coalesces once and sorts once a block."""

import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from photon_ml_tpu.ops import fused_perm, routing, sparse_perm
from photon_ml_tpu.ops.permute_net import device_plan
from photon_ml_tpu.parallel import grid_features
from photon_ml_tpu.parallel.grid_features import grid_from_coo, grid_mesh
from photon_ml_tpu.telemetry.span import disable_tracing, enable_tracing
from tests import _layout_oracles as oracle

FROM_COO = {"benes": sparse_perm.from_coo, "fused": fused_perm.from_coo}


def _rows(n, k):
    return np.repeat(np.arange(n, dtype=np.int64), k)


def _pattern(name):
    """(rows, cols, vals, shape, from_coo kwargs) of a named pattern."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "repeats_and_hot":
        # runs of one (row, col) up to 13 long, so a pairwise sum would
        # round differently; column 3 in every row goes to the hot side
        n, k, d = 512, 16, 40_000
        cols = rng.integers(0, d, (n, k))
        cols[::5, 2:15] = cols[::5, 1:2]
        cols[:, 0] = 3
        kw = {}
    elif name == "cap_with_spill":
        n, k, d = 1024, 8, 2048
        cols = rng.integers(0, d, (n, k))
        kw = dict(kp_cap=2, col_split=1)
    elif name == "split_with_empty_blocks":
        # the columns fill half the width: two of four blocks hold nothing
        n, k, d = 1024, 8, 64_000
        cols = rng.integers(0, d // 2, (n, k))
        kw = dict(kp_cap=2, col_split=4)
    elif name == "single_block":
        n, k, d = 1024, 8, 4096
        cols = rng.integers(0, d, (n, k))
        kw = dict(kp_cap=None, col_split=1)
    else:  # "planned": the planner picks a cap of 1 and four blocks
        n, k, d = 4096, 16, 1 << 19
        cols = rng.integers(0, d, (n, k))
        kw = {}
    rows = _rows(n, k)
    # shuffled: from_coo must sort and coalesce whatever order it is given
    order = rng.permutation(rows.size)
    vals = rng.standard_normal(rows.size).astype(np.float32)
    return rows[order], cols.reshape(-1)[order], vals, (n, d), kw


PATTERNS = ("repeats_and_hot", "cap_with_spill", "split_with_empty_blocks",
            "single_block", "planned")


def _record_perms(monkeypatch):
    """The sha1 of every perm routed from here on (the plan cache's key)."""
    perms = []
    real = sparse_perm._plan_of

    def recording(perm, cache_dir):
        perms.append(hashlib.sha1(perm.tobytes()).hexdigest())
        return real(perm, cache_dir)

    monkeypatch.setattr(sparse_perm, "_plan_of", recording)
    return perms


def _no_native(monkeypatch):
    monkeypatch.setattr(routing, "_load_layout", lambda: None)


def _assert_plan(dplan, plan):
    want = device_plan(plan)
    assert dplan.kinds == want.kinds and dplan.size == want.size
    for got, ref in zip(dplan.idx, want.idx):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _assert_spill(block, spill):
    if spill is None or spill[0].size == 0:
        assert block.spill_rows is None
        return
    for got, ref in zip((block.spill_rows, block.spill_cols, block.spill_vals),
                        spill):
        np.testing.assert_array_equal(np.asarray(got), ref)


def _assert_block(engine, block, ref):
    _assert_plan(block.plan, ref["plan"])
    _assert_plan(block.plan_inv, ref["plan_inv"])
    _assert_spill(block, ref["spill"])
    if engine == "fused":
        assert (block.ell_k, block.csc_k, block.size) == (
            ref["K"], ref["KP"], ref["S"])
        np.testing.assert_array_equal(np.asarray(block.ell_flat), ref["ell_flat"])
    else:
        assert (block.ell_k, block.csc_k, block.plan.size) == (
            ref["K"], ref["KP"], ref["S"])
        np.testing.assert_array_equal(
            np.asarray(block.ell_values), ref["ell_values"])
        np.testing.assert_array_equal(
            np.asarray(block.csc_values), ref["csc_values"])


def _assert_hot(features, ref):
    if ref["hot_ids"] is None:
        assert features.hot_cols is None
        return
    np.testing.assert_array_equal(np.asarray(features.hot_cols), ref["hot_ids"])
    np.testing.assert_array_equal(
        np.asarray(features.hot_matrix), ref["hot_matrix"])


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("engine", sorted(FROM_COO))
def test_from_coo_lays_out_what_the_plain_passes_lay_out(
        engine, pattern, native, monkeypatch):
    rows, cols, vals, shape, kw = _pattern(pattern)
    ref = oracle.oracle_layout(rows, cols, vals, shape, engine, **kw)
    if not native:
        _no_native(monkeypatch)
    perms = _record_perms(monkeypatch)
    features = FROM_COO[engine](rows, cols, vals, shape, **kw)

    blocks = getattr(features, "blocks", (features,))
    assert len(blocks) == len(ref["blocks"])
    if ref["col_bounds"] is not None:
        assert list(features.col_bounds) == ref["col_bounds"]
    for block, want in zip(blocks, ref["blocks"]):
        if want is None:
            assert isinstance(block, sparse_perm._ZeroColumnsBlock)
        else:
            _assert_block(engine, block, want)
    _assert_hot(features, ref)
    # the plan cache's keys: the same perms, so the same cached plans
    assert perms == [b["perm_sha1"] for b in ref["blocks"] if b is not None]
    if pattern == "repeats_and_hot":
        assert ref["hot_ids"] is not None
    if pattern in ("cap_with_spill", "planned"):
        assert any(b["spill"] is not None and b["spill"][0].size
                   for b in ref["blocks"] if b is not None)
    if pattern == "split_with_empty_blocks":
        assert ref["blocks"][2] is None and ref["blocks"][3] is None
    if pattern == "planned":
        assert len(ref["blocks"]) > 1


def _oracle_forms(monkeypatch):
    """Swap the plain forms in for the passes ``grid_from_coo`` runs."""
    monkeypatch.setattr(grid_features, "coalesce_coo", oracle.coalesce_coo)
    monkeypatch.setattr(
        sparse_perm, "split_spill_entries",
        lambda *a: (*oracle.split_spill_entries(*a), None),
    )

    def slot_perm(rows, cols, n, d, K, KP, S, row_counts, col_counts,
                  corder=None):
        ell_pos, csc_pos, perm = oracle.build_slot_perm(
            rows, cols, n, d, K, KP, S, row_counts, col_counts
        )
        order = np.argsort(csc_pos, kind="stable")
        return ell_pos, csc_pos[order], order, perm

    monkeypatch.setattr(sparse_perm, "build_slot_perm", slot_perm)
    monkeypatch.setattr(
        sparse_perm, "max_block_row_count",
        lambda rows, cols, n, width, t: oracle.make_row_block_k(
            rows, cols, n, width * t)(t),
    )
    monkeypatch.setattr(routing.PermPlan, "invert", oracle.invert_plan)


@pytest.mark.parametrize("layout", ["split", "flat_cap"])
@pytest.mark.parametrize("engine", ["benes", "fused"])
def test_grid_tiles_are_what_the_plain_passes_give(engine, layout, monkeypatch):
    rng = np.random.default_rng(7)
    n, k, d = 1024, 8, 25_600
    rows = _rows(n, k)
    cols = rng.integers(0, d, (n, k))
    cols[::3, 2:] = cols[::3, 1:2]  # repeated entries
    cols[:, 0] = 5  # a hot column in the first feature shard
    cols = cols.reshape(-1)
    vals = rng.standard_normal(rows.size).astype(np.float32)
    kw = (dict(kp_cap=2, col_split=4) if layout == "split"
          else dict(kp_cap=2, col_split=1))
    mesh = grid_mesh(2, 2)
    perms = _record_perms(monkeypatch)

    def build():
        g = grid_from_coo(rows, cols, vals, (n, d), mesh, engine=engine, **kw)
        routed = sorted(perms)  # tiles route on threads, in any order
        perms.clear()
        return g, routed

    got, got_perms = build()
    with monkeypatch.context() as m:
        _oracle_forms(m)
        want, want_perms = build()
    assert got_perms == want_perms
    assert len(got_perms) == 4 * (4 if layout == "split" else 1)
    tiles = getattr(got.shards, "blocks", (got.shards,))
    assert all(t.spill_rows is not None for t in tiles)  # the cap spilled
    assert got.shards.hot_cols is not None

    got_leaves, got_def = jax.tree.flatten(got.shards)
    want_leaves, want_def = jax.tree.flatten(want.shards)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("engine", sorted(FROM_COO))
def test_a_column_split_coalesces_once_and_sorts_once_a_block(
        engine, monkeypatch):
    rows, cols, vals, shape, kw = _pattern("planned")
    counts = {"coalesce": 0, "lexsort": 0}

    def counted(name, fn):
        def wrapper(*a, **k):
            counts[name] += 1
            return fn(*a, **k)
        return wrapper

    monkeypatch.setattr(sparse_perm, "coalesce_coo",
                        counted("coalesce", sparse_perm.coalesce_coo))
    monkeypatch.setattr(sparse_perm, "lexsort_pairs",
                        counted("lexsort", sparse_perm.lexsort_pairs))
    features = FROM_COO[engine](rows, cols, vals, shape, **kw)
    blocks = len(features.blocks)
    assert blocks > 1
    assert counts["coalesce"] == 1
    assert counts["lexsort"] <= 1 + blocks


@pytest.mark.parametrize("repeats", [1, 2, 9, 40])
def test_coalescing_sums_in_order_as_an_unbuffered_add(repeats):
    rng = np.random.default_rng(repeats)
    n, d = 300, 50
    rows = rng.integers(0, n, 4000)
    cols = rng.integers(0, d, 4000)
    rows = np.repeat(rows, repeats)
    cols = np.repeat(cols, repeats)
    # values of wildly different sizes: any other order of summation shows
    vals = (rng.standard_normal(rows.size)
            * 10.0 ** rng.integers(-6, 7, rows.size)).astype(np.float32)
    if repeats > 8:
        # a run of one entry B, 1, 0, ..., 0, -B: in order the 1 is lost
        # in B, summed in blocks of eight B meets -B first and the 1 stays
        vals.reshape(-1, repeats)[:, :] = 0.0
        vals.reshape(-1, repeats)[:, 0] = 1e18
        vals.reshape(-1, repeats)[:, 1] = 1.0
        vals.reshape(-1, repeats)[:, 8] = -1e18
    got = sparse_perm.coalesce_coo(rows, cols, vals, n, d)
    want = oracle.coalesce_coo(rows, cols, vals, n, d)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    empty = sparse_perm.coalesce_coo(np.zeros(0), np.zeros(0), np.zeros(0), n, d)
    assert all(a.size == 0 for a in empty)


@pytest.mark.parametrize("cap", [1, 2, 4])
def test_the_spill_split_hands_on_the_kept_entries_column_order(cap):
    rng = np.random.default_rng(cap)
    n, d = 2000, 700
    rows, cols, vals = sparse_perm.coalesce_coo(
        rng.integers(0, n, 9000), rng.integers(0, d, 9000),
        rng.standard_normal(9000).astype(np.float32), n, d,
    )
    counts = np.bincount(cols, minlength=d)
    *got, corder = sparse_perm.split_spill_entries(rows, cols, vals, counts, cap)
    want = oracle.split_spill_entries(rows, cols, vals, counts, cap)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    kept_rows, kept_cols = got[0], got[1]
    np.testing.assert_array_equal(corder, np.lexsort((kept_rows, kept_cols)))


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
@pytest.mark.parametrize("handed", [True, False], ids=["handed", "sorted"])
def test_the_slot_permutation_is_the_plain_one(handed, native, monkeypatch):
    if not native:
        _no_native(monkeypatch)
    rng = np.random.default_rng(3)
    n, d, K, KP = 1500, 900, 8, 4
    rows, cols, _ = sparse_perm.coalesce_coo(
        rng.integers(0, n, 6000), rng.integers(0, d, 6000),
        np.ones(6000, np.float32), n, d,
    )
    counts = np.bincount(cols, minlength=d)
    rows, cols, _, _, _, _, corder = sparse_perm.split_spill_entries(
        rows, cols, np.ones(rows.size, np.float32), counts, KP
    )
    rc, cc = np.bincount(rows, minlength=n), np.bincount(cols, minlength=d)
    K = max(K, int(rc.max()))
    S = routing.valid_size(max(n * K, d * KP))
    ell, csc, order, perm = sparse_perm.build_slot_perm(
        rows, cols, n, d, K, KP, S, rc, cc, corder if handed else None
    )
    ell_ref, csc_ref, perm_ref = oracle.build_slot_perm(
        rows, cols, n, d, K, KP, S, rc, cc
    )
    np.testing.assert_array_equal(ell, ell_ref)
    np.testing.assert_array_equal(csc, csc_ref[order])
    assert np.all(np.diff(csc) > 0)
    assert perm.dtype == perm_ref.dtype == np.int64
    np.testing.assert_array_equal(perm, perm_ref)


def test_the_native_walk_refuses_slots_that_are_not_a_layout():
    lib = routing._load_layout()
    if lib is None:
        pytest.skip("no g++ on this host")
    S = 256
    ell = np.array([0, 1, 5], np.int64)
    perm = np.empty(S, np.int64)

    def walk(ell_asc, csc_asc, ell_of_csc):
        return lib.fill_slot_perm(
            S, ell_asc.size, ell_asc.ctypes.data, csc_asc.ctypes.data,
            ell_of_csc.ctypes.data, perm.ctypes.data,
        )

    good = np.array([3, 9, 200], np.int64)
    assert walk(ell, good, ell[::-1].copy()) == 0
    assert sorted(perm.tolist()) == list(range(S))
    assert walk(ell, np.array([9, 3, 200], np.int64), ell) == 2   # unsorted
    assert walk(ell, np.array([3, 3, 200], np.int64), ell) == 2   # twice
    assert walk(ell, np.array([3, 9, S], np.int64), ell) == 2     # outside
    assert walk(np.array([0, 5, 1], np.int64), good, ell) == 2    # sources


@pytest.mark.parametrize("width", [1, 3])
def test_the_planner_counts_blocks_rows_as_the_plain_count(width):
    rng = np.random.default_rng(width)
    n, d = 7000, 40_000
    rows, cols, _ = sparse_perm.coalesce_coo(
        rng.integers(0, n, 5000 * width), rng.integers(0, d, 5000 * width),
        np.ones(5000 * width, np.float32), n, d,
    )
    for pow2 in (False, True):
        got = sparse_perm.make_row_block_k(rows, cols, n, d, pow2=pow2)
        want = oracle.make_row_block_k(rows, cols, n, d, pow2=pow2)
        # past four bins an entry (n * t > 4 * nnz) a sort counts: from
        # t = 4 on where width is 1, at t = 16 where it is 3
        for t in (1, 2, 4, 8, 16):
            assert got(t) == want(t), (t, pow2)


def _plan(S, seed):
    return routing.build_plan(np.random.default_rng(seed).permutation(S))


def _sublane_rows(plan):
    return {st.rows for st in plan.stages if isinstance(st, routing.SublaneShuffle)}


# sizes c x 128 and c x 128^2: sublane stages of 1, 2, 4 and 8 rows, with
# and without a level of recursion
SIZES = (128, 256, 512, 1024, 16384, 32768, 65536, 131072)


@pytest.mark.parametrize("dtype", [np.int32, np.int8], ids=["routed", "cached"])
@pytest.mark.parametrize("S", SIZES)
def test_the_native_inverse_is_the_numpy_inverse_stage_for_stage(S, dtype):
    if routing._load_layout() is None:
        pytest.skip("no g++ on this host")
    plan = _plan(S, S)
    assert _sublane_rows(plan) == {S // 128 if S <= 1024 else S // 16384}
    plan = routing.PermPlan(size=plan.size, stages=[
        dataclasses.replace(st, idx=st.idx.astype(dtype))
        if hasattr(st, "idx") else st
        for st in plan.stages
    ])
    inv = plan.invert()
    ref = oracle.invert_plan(plan)
    assert [type(a) for a in inv.stages] == [type(b) for b in ref.stages]
    for a, b in zip(inv.stages, ref.stages):
        if hasattr(a, "idx"):
            assert a.idx.dtype == b.idx.dtype == np.dtype(dtype)
            np.testing.assert_array_equal(a.idx, b.idx)
        assert getattr(a, "rows", None) == getattr(b, "rows", None)
    x = np.random.default_rng(0).standard_normal(S).astype(np.float32)
    np.testing.assert_array_equal(
        routing.host_apply(inv, routing.host_apply(plan, x)), x)


def test_a_stage_that_is_no_permutation_is_refused():
    if routing._load_layout() is None:
        pytest.skip("no g++ on this host")
    idx = np.tile(np.arange(128, dtype=np.int32), (4, 1))
    idx[2, 7] = 128
    with pytest.raises(ValueError, match="not a permutation"):
        routing.PermPlan(size=512, stages=[routing.LaneShuffle(idx=idx)]).invert()
    sub = np.zeros((8, 128), np.int32)
    sub[3, 0] = 4
    with pytest.raises(ValueError, match="block of 4 rows"):
        routing.PermPlan(
            size=1024, stages=[routing.SublaneShuffle(idx=sub, rows=4)]
        ).invert()


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
def test_route_place_says_which_inverse_ran(native, monkeypatch):
    if native and routing._load_layout() is None:
        pytest.skip("no g++ on this host")
    if not native:
        _no_native(monkeypatch)
    assert routing.inverse_engine() == ("native" if native else "numpy")
    rows, cols, vals, shape, kw = _pattern("single_block")
    tracer = enable_tracing(device_sync=False)
    try:
        fused_perm.from_coo(rows, cols, vals, shape, **kw)
        spans = tracer.spans()
    finally:
        disable_tracing()
    inverts = [s.attrs["invert"] for s in spans
               if s.name == "route/place" and "invert" in s.attrs]
    assert inverts == ["native" if native else "numpy"]
    plan = _plan(1024, 5)
    x = np.random.default_rng(1).standard_normal(1024).astype(np.float32)
    np.testing.assert_array_equal(
        routing.host_apply(plan.invert(), routing.host_apply(plan, x)), x)
