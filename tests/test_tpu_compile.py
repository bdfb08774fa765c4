"""Every Pallas kernel in photon_ml_tpu/ops compiles for a TPU v5e — through
Mosaic, on a host that has no chip.

``jax.experimental.topologies`` describes a v5e without one being attached,
and ``jit(...).lower(<shapes placed on its devices>).compile()`` runs the real
TPU compiler, Mosaic included. (``jax.export`` stops at MLIR emission, before
the compiler that actually refuses kernels.) The interpreter tests elsewhere
check what the kernels compute; this file checks that the chip will take
them. ``chip_smoke.py`` then runs them on the chip at full width.

libtpu allows one process per host to load it: do not run this file beside
another process that does (a second pytest session, a chip run).
"""

import functools
import re

import numpy as np
import pytest

pytest.importorskip("libtpu")

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from photon_ml_tpu.estimators import random_effect
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import LogisticLoss, SquaredLoss
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops import fused_perm, permute_net, sparse_perm
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.pallas_kernels import fused_value_grad_single
from photon_ml_tpu.opt.config import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
)
from photon_ml_tpu.opt.lbfgs import (
    history_zeros,
    two_loop_direction,
    update_history,
)
from photon_ml_tpu.opt.solve import solve
from photon_ml_tpu.stat.summary import summarize
from photon_ml_tpu.types import TaskType

ENGINES = {"fused": fused_perm, "benes": sparse_perm}
OPS = ("matvec", "rmatvec", "rmatvec_sq")


@pytest.fixture(scope="module")
def v5e():
    """Sharding on device 0 of a described (not attached) v5e 2x2 host."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the engines choose what they choose on a TPU backend."""
    monkeypatch.setattr(fused_perm, "pallas_available", lambda: True)
    monkeypatch.setattr(permute_net, "pallas_available", lambda: True)


def compile_for_tpu(fn, sharding, *args):
    """AOT-compile ``fn`` for the TPU at the shapes of ``args``; returns the
    number of Mosaic kernels in the program."""
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), args
    )
    lowered = jax.jit(fn).lower(*structs)
    kernels = lowered.as_text().count("tpu_custom_call")
    lowered.compile()
    return kernels


def _uniform_coo(rng, n, d, k):
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    cols = rng.integers(0, d, n * k).astype(np.int64)
    return rows, cols, rng.standard_normal(n * k).astype(np.float32)


def _plan_case(name):
    """(shape, coo, from_coo kwargs, check) for each plan shape the kernels
    must tile. Small: routing prep stays within seconds."""
    rng = np.random.default_rng(0)
    flat = dict(max_hot_cols=0, kp_cap=None, col_split=1)
    if name == "one_level":      # 128^2 slots: descend, base, ascend
        return (256, 200), _uniform_coo(rng, 256, 200, 8), flat
    if name == "two_level":      # 128^3 slots: two descends and ascends
        return (16384, 4096), _uniform_coo(rng, 16384, 4096, 16), flat
    if name == "column_split":   # thin column tail: auto layout splits + spills
        return (
            (4096, 65536), _uniform_coo(rng, 4096, 65536, 16),
            dict(max_hot_cols=0),
        )
    if name == "wide_groups":    # K = KP = 256 > 128 lanes: the q-path
        r = np.repeat(np.arange(256, dtype=np.int64), 256)
        c = np.tile(np.arange(256, dtype=np.int64), 256)
        return (256, 256), (r, c, rng.standard_normal(r.size).astype(np.float32)), flat
    raise KeyError(name)


PLANS = ("one_level", "two_level", "column_split", "wide_groups")
_BUILT = {}


def _features(engine, plan):
    if (engine, plan) not in _BUILT:
        shape, (rows, cols, vals), kw = _plan_case(plan)
        _BUILT[engine, plan] = ENGINES[engine].from_coo(
            rows, cols, vals, shape, plan_cache="", **kw
        )
    return _BUILT[engine, plan]


def _routed_blocks(feats):
    if isinstance(feats, sparse_perm.ColumnSplitFeatures):
        return [b for b in feats.blocks if hasattr(b, "plan")]
    return [feats]


@pytest.mark.parametrize("plan", PLANS)
def test_plan_cases_have_the_shape_they_claim(plan):
    """The cases above only cover the kernels if routing gives them the
    structure their names say."""
    feats = _features("fused", plan)
    blocks = _routed_blocks(feats)
    levels = {len(fused_perm.parse_plan(b.plan).descents) for b in blocks}
    if plan == "two_level":
        assert levels == {2}
    else:
        assert levels == {1}
    if plan == "column_split":
        assert len(blocks) > 1
        assert any(b.spill_rows is not None for b in blocks)
    if plan == "wide_groups":
        assert blocks[0].ell_k > 128 and blocks[0].csc_k > 128


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("engine", list(ENGINES))
def test_linear_maps_compile(v5e, on_tpu, engine, plan, op):
    feats = _features(engine, plan)
    vec = jnp.zeros(
        feats.dim if op == "matvec" else feats.num_rows, jnp.float32
    )
    kernels = compile_for_tpu(lambda f, v: getattr(f, op)(v), v5e, feats, vec)
    blocks = _routed_blocks(feats)
    if engine == "fused":
        # 2m+1 kernels per block: m descends, the base, m ascends
        m = len(fused_perm.parse_plan(blocks[0].plan).descents)
        assert kernels == len(blocks) * (2 * m + 1)
    else:
        # one kernel per shuffle stage (sublane stages of one row move nothing)
        stages = sum(
            1 for k in blocks[0].plan.kinds
            if k[0] == "lane" or (k[0] == "sublane" and k[1] > 1)
        )
        assert kernels == len(blocks) * stages


def test_lbfgs_solve_over_fused_features_compiles(v5e, on_tpu):
    """What the chip runs is the kernels inside the optimizer's loops."""
    feats = _features("fused", "one_level")
    data = LabeledData.create(feats, jnp.zeros(feats.num_rows, jnp.float32))
    objective = make_glm_objective(LogisticLoss)
    cfg = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.lbfgs(max_iterations=5),
        regularization_weight=1.0,
    )
    kernels = compile_for_tpu(
        lambda w0, dd: solve(objective, w0, dd, cfg).w,
        v5e, jnp.zeros(feats.dim, jnp.float32), data,
    )
    assert kernels >= 6  # at least one matvec and one rmatvec


def test_tron_solve_with_normalization_compiles(v5e, on_tpu):
    """The benchmark's ``fe-linear-tron`` solve: Hessian-vector products
    through the routed maps inside TRON's CG loop, a factor in the data."""
    feats = _features("fused", "column_split")
    norm = NormalizationContext(factor=jnp.ones(feats.dim, jnp.float32))
    data = LabeledData.create(feats, jnp.zeros(feats.num_rows, jnp.float32), norm=norm)
    objective = make_glm_objective(SquaredLoss)
    cfg = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.tron(), regularization_weight=1.0,
    )
    kernels = compile_for_tpu(
        lambda w0, dd: solve(objective, w0, dd, cfg).hessian_vecs,
        v5e, jnp.zeros(feats.dim, jnp.float32), data,
    )
    per_map = 3 * len(_routed_blocks(feats))
    # the start's and a step's value-and-gradient, and a product's maps
    assert kernels >= (2 + 2 + 2) * per_map


@pytest.mark.parametrize("plan", ["two_level", "column_split"])
def test_summarize_compiles(v5e, on_tpu, plan):
    """The feature statistics a normalization is built from: the transformed
    maps (abs, nnz, squares) and the column-grouped view for min and max."""
    feats = _features("fused", plan)
    data = LabeledData.create(feats, jnp.zeros(feats.num_rows, jnp.float32))
    assert compile_for_tpu(summarize, v5e, data) > 0


@pytest.mark.parametrize("batched", [False, True], ids=["plain", "vmapped"])
def test_random_effect_kernel_compiles(v5e, batched):
    """``fused_value_grad_single``, alone and under the vmap of the
    per-entity random-effect solve."""
    fn = functools.partial(
        fused_value_grad_single, kind=LogisticLoss, interpret=False
    )
    s, d = 24, 10
    lead = (4,) if batched else ()
    args = tuple(
        jnp.zeros(lead + shape, jnp.float32)
        for shape in ((s, d), (s,), (s,), (s,), (d,))
    )
    assert compile_for_tpu(jax.vmap(fn) if batched else fn, v5e, *args) == 1


def test_adaptive_chunk_program_compiles_at_the_cell_width(v5e):
    """The adaptive random-effect driver's one chunk program of a bucket
    shape, at the benchmark's per-user bucket: a loop over a run-time count
    of tiles that gathers 1,024 of the 16,384 lanes, runs the vmapped L-BFGS
    chunk on them and scatters them back."""
    E, S, D = 16384, 100, 16
    cfg = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.lbfgs(max_iterations=20),
        regularization_weight=1.0,
    )
    progs = random_effect._re_programs(TaskType.LOGISTIC_REGRESSION, cfg, False)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    data = LabeledData(
        features=DenseFeatures(matrix=f32((E, S, D))),
        labels=f32((E, S)), offsets=f32((E, S)), weights=f32((E, S)), norm=None,
    )
    state = jax.eval_shape(progs.init, f32((E, D)), data, f32(()), f32(()))
    T = random_effect._tile_lanes(E, cfg.adaptive.min_lanes)
    assert T == 1024
    live_idx = jax.ShapeDtypeStruct((E,), jnp.int32)
    n_tiles = jax.ShapeDtypeStruct((), jnp.int32)
    # no Mosaic kernel: gathers, scatters and the solver's XLA loops
    assert compile_for_tpu(progs.chunk, v5e, state, data, f32(()), live_idx, n_tiles) == 0


@pytest.mark.parametrize("d", [40_000_000, 40_000_001], ids=["cell_width", "padded_row"])
def test_history_layout_at_the_cell_width(v5e, d):
    """The L-BFGS history of the benchmark's fixed effect (``m = 10`` over 40M
    columns) as the chip lays it out. As ``[10, d]`` the TPU tiled it (8, 128):
    ten rows padded to sixteen (5.28 GB of arguments for 3.36 GB of data), a
    row read moved eight rows, and an insert rewrote the buffer (12.16 GB
    accessed without donation). As ``history_zeros`` makes it, nothing pads,
    an insert into donated buffers moves its two rows, and where ``d`` is a
    multiple of 128 a row's flattening to ``[d]`` is a bitcast.

    One over the width (a row padded to 312,501 x 128) the recursion runs at
    the padded width: ``g`` is padded once and the direction cut once (0.16 GB
    of temporaries), and no row is written out to be sliced."""
    m = 10
    hist = jax.eval_shape(lambda: history_zeros(m, d, jnp.float32))
    assert hist.shape == (m, -(-d // 128), 128)
    struct = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    h = struct(hist.shape, jnp.float32)
    vec, rho, count = struct((d,), jnp.float32), struct((m,), jnp.float32), struct((), jnp.int32)
    data_bytes = (2 * m + 1) * d * 4

    # g is donated as a solver loop's carry is, so q may take its buffer
    direction = jax.jit(two_loop_direction, donate_argnums=0).lower(
        vec, h, h, rho, count
    ).compile()
    args = direction.memory_analysis().argument_size_in_bytes
    assert data_bytes <= args <= 1.05 * data_bytes  # no sublane padding
    width = hist.shape[1] * 128
    hlo = direction.as_text()
    copies = re.findall(rf"= f32\[({d}|{width})\]\S* copy\(", hlo)
    assert not copies, "a row's reshape to a vector did not become a bitcast"
    # no fusion writes a row out to be read again: the loop bodies read a
    # row and read and write a vector (4.80 GB where each row was sliced)
    assert direction.cost_analysis()["bytes accessed"] < 3.2e9
    # what is left beside the arguments is the padded q and r, if any
    assert direction.memory_analysis().temp_size_in_bytes < 1e7 + (width != d) * 3 * width * 4

    update = jax.jit(update_history, donate_argnums=(0, 1)).lower(
        h, h, rho, count, vec, vec
    ).compile()
    # two rows read, two written, the pair read for its two dot products
    assert update.cost_analysis()["bytes accessed"] < 1.5e9
    assert update.memory_analysis().temp_size_in_bytes < 1e7
