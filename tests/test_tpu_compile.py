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

from photon_ml_tpu.algorithm.factored_random_effect import KronFeatures, KronTiles
from photon_ml_tpu.estimators import random_effect
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import LogisticLoss, SquaredLoss
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops import fused_perm, permute_net, routing, sparse_perm
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.pallas_kernels import fused_value_grad_single
from photon_ml_tpu.opt.config import (
    GlmOptimizationConfiguration,
    OptimizerConfig,
)
from photon_ml_tpu.opt.lbfgs import (
    _gram_pass,
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
def v5e_host():
    """The four devices of a described (not attached) v5e 2x2 host."""
    from jax.experimental import topologies

    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    assert devices[0].device_kind == "TPU v5 lite"
    return devices


@pytest.fixture(scope="module")
def v5e(v5e_host):
    """Sharding on device 0 of the described v5e host."""
    return SingleDeviceSharding(v5e_host[0])


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


@pytest.fixture(scope="module")
def kron_plan():
    """A routed plan at the factored coordinate's 2^21 slots (cell 4's
    1,064,960 slots and 1,032,704 tile positions): two recursion levels.
    Any permutation of that size gives the shapes."""
    perm = np.random.default_rng(0).permutation(2 ** 21)
    return permute_net.device_plan(routing.build_plan(perm))


def test_a_plain_move_compiles_at_the_factored_layout_s_size(v5e, kron_plan):
    """``fused_permute``: the plan's 2m + 1 kernels and nothing around them,
    the payload straight into the first descend."""
    x = jnp.zeros(kron_plan.size, jnp.float32)
    assert len(fused_perm.parse_plan(kron_plan).descents) == 2
    assert compile_for_tpu(fused_perm.fused_permute, v5e, kron_plan, x) == 5


def test_the_kronecker_maps_compile_with_routed_moves_at_the_cell_size(v5e, kron_plan):
    """Cell 4's matrix-solve evaluation (one bucket of [16384, 100, 65], 32
    latent factors over 4,096 items, 8,068 tiles) with its two moves routed:
    a plan's five kernels each way, no gather of 128-lane rows."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    tiles = KronTiles(
        entity_of=i32((8068, 128)), item_of_tile=i32((8068,)),
        to_slots=kron_plan, to_positions=kron_plan, slots=16384 * 65,
    )
    xs, latents = [f32((16384, 100, 65))], [f32((16384, 32))]

    def evaluation(xs, latents, tiles, w, c):
        feats = KronFeatures.build(xs, latents, tiles, 4096, 32, moves="routed")
        return feats.matvec(w), feats.rmatvec(c)

    lowered = jax.jit(evaluation).lower(
        *jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            (xs, latents, tiles, f32((4096 * 32,)), f32((16384 * 100,))),
        )
    )
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 10
    assert "1064960x128" not in text and "1032704x128" not in text
    lowered.compile()


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


def _re_chunk_case(E, S, D=16):
    """The adaptive driver's programs of one L-BFGS bucket shape (cell 1's
    solver settings) and the shapes of their operands."""
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
    live_tiles = jax.ShapeDtypeStruct((-(-E // T),), jnp.int32)
    n_tiles = jax.ShapeDtypeStruct((), jnp.int32)
    return progs, data, state, T, (f32(()), live_tiles, n_tiles)


def test_adaptive_chunk_program_compiles_at_the_cell_width(v5e):
    """The adaptive random-effect driver's one chunk program of a bucket
    shape, at the benchmark's per-user bucket: a loop over a run-time count
    of live tiles, each a slice of 1,024 of the 16,384 lanes, the vmapped
    L-BFGS chunk run on it and the slice written back."""
    progs, data, state, T, rest = _re_chunk_case(16384, 100)
    assert T == 1024
    # no Mosaic kernel: slices and the solver's XLA loops
    assert compile_for_tpu(progs.chunk, v5e, state, data, *rest) == 0


def test_member_solver_state_fits_two_kilobytes_an_entity(v5e):
    """A bucket of 2^20 one-row members at d = 16, m = 10, compiled for the
    chip. The solver state as the init program lays it out is its
    ``[E, 10, 16]`` s and y histories with the entity axis minor, in lanes,
    and the vectors beside them: 1,596 bytes an entity, where a history of
    ``[E, 10, 1, 128]`` took 10,556 (every row padded to 128 lanes). The
    chunk program takes the state as it lies and works a tile of 65,536
    lanes at a time, and what it holds beside its arguments is the tile's
    (2.9 KB a tile lane). Two ways of holding the bucket's whole width went
    before this layout could: a gather of live lanes along the minor axis
    (24 KB an entity: the whole state laid out again, padded), and a ring
    buffer read at a slot that differs from lane to lane (25 KB a tile lane:
    each lane's buffer laid out again, padded, at every chunk)."""
    E = 1 << 20
    progs, data, state, T, rest = _re_chunk_case(E, 1)
    struct = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree
    )
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=v5e)
    init = progs.init.lower(
        struct(jax.ShapeDtypeStruct((E, 16), jnp.float32)), struct(data), f32, f32
    ).compile()
    state_bytes = init.memory_analysis().output_size_in_bytes
    assert state_bytes < 2048 * E
    chunk = progs.chunk.lower(struct(state), struct(data), *struct(rest)).compile()
    held = chunk.memory_analysis()
    data_bytes = held.argument_size_in_bytes - state_bytes
    assert data_bytes < 128 * E  # one row of 16 features, its label, offset, weight
    assert held.temp_size_in_bytes < 4096 * T


def _low_precision(lowered, compiled) -> list:
    """The contractions of a traced program below ``HIGHEST`` (an f32 dot at
    default precision may run as bfloat16 passes on the TPU), and a
    compiled program's mention of bfloat16."""
    found = [
        line for line in lowered.as_text().splitlines()
        if re.search(r"stablehlo\.(dot_general|dot|convolution)\b", line) and "HIGHEST" not in line
    ]
    return found + (["bf16"] if "bf16" in compiled.as_text() else [])


@pytest.mark.parametrize("d", [40_000_000, 40_000_001], ids=["cell_width", "padded_row"])
def test_history_layout_at_the_cell_width(v5e, d):
    """The L-BFGS history of the benchmark's fixed effect (``m = 10`` over 40M
    columns) as the chip lays it out. As ``[10, d]`` the TPU tiled it (8, 128):
    ten rows padded to sixteen (5.28 GB of arguments for 3.36 GB of data), a
    row read moved eight rows, and an insert rewrote the buffer (12.16 GB
    accessed without donation). As ``history_zeros`` makes it, nothing pads,
    an insert into donated buffers moves its two rows, and where ``d`` is a
    multiple of 128 a row's flattening to ``[d]`` is a bitcast.

    The direction comes from the carried Gram matrix in one pass that reads
    each row and ``v`` once and writes ``d``: ``(2m+2) d`` floats, where the
    row recursion moved about ``10 m d``. The end of an iteration's Gram
    pass reads each row and the pair and ``v`` once: ``(2m+3) d``. Neither
    holds a contraction below ``HIGHEST`` or anything in bfloat16, and what
    they hold beside their arguments is small. One over the width (a row
    padded to 312,501 x 128) the passes run at the padded width: ``v`` (and
    the Gram pass's pair) padded once, the direction cut once, and no row is
    written out to be sliced."""
    m = 10
    hist = jax.eval_shape(lambda: history_zeros(m, d, jnp.float32))
    assert hist.shape == (m, -(-d // 128), 128)
    struct = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    h = struct(hist.shape, jnp.float32)
    vec, rho, count = struct((d,), jnp.float32), struct((m,), jnp.float32), struct((), jnp.int32)
    gram = struct((2 * m + 1, 2 * m + 1), jnp.float32)
    width = hist.shape[1] * 128
    # a padded copy of a vector, written and read again
    padded = 2 * width * 4 * (width != d)

    # v is donated as a solver loop's carry is
    lowered = jax.jit(two_loop_direction, donate_argnums=0).lower(vec, h, h, rho, count, gram)
    direction = lowered.compile()
    args = direction.memory_analysis().argument_size_in_bytes
    data_bytes = (2 * m + 1) * d * 4
    assert data_bytes <= args <= 1.05 * data_bytes  # no sublane padding
    hlo = direction.as_text()
    copies = re.findall(rf"= f32\[({d}|{width})\]\S* copy\(", hlo)
    assert not copies, "a row's reshape to a vector did not become a bitcast"
    assert direction.cost_analysis()["bytes accessed"] <= 1.1 * (2 * m + 2) * d * 4 + padded
    assert not _low_precision(lowered, direction)
    # what is left beside the arguments is the padded sum, if any
    assert direction.memory_analysis().temp_size_in_bytes < 1e7 + padded

    lowered = jax.jit(_gram_pass).lower(h, h, count, gram, vec, vec, vec)
    gram_pass = lowered.compile()
    assert gram_pass.cost_analysis()["bytes accessed"] <= 1.1 * (2 * m + 3) * d * 4 + 3 * padded
    assert not _low_precision(lowered, gram_pass)
    assert gram_pass.memory_analysis().temp_size_in_bytes < 1e7 + 3 * padded

    update = jax.jit(update_history, donate_argnums=(0, 1)).lower(
        h, h, rho, count, gram, vec, vec, vec
    ).compile()
    # the Gram pass, and the insert: two rows written, each with the new
    # row and the old one read (the guard selects on the row)
    insert = 6 * width * 4
    assert update.cost_analysis()["bytes accessed"] <= (
        1.1 * (2 * m + 3) * d * 4 + 3 * padded + insert
    )
    assert update.memory_analysis().temp_size_in_bytes < 1e7 + 3 * padded


def test_the_grid_s_history_forms_its_direction_locally(v5e_host):
    """The history of the four-chip cell's fixed effect (80M columns on a
    2 x 2 data x feat grid, each row feat-sharded two ways), compiled for
    the described v5e host: the direction pass needs no collective at all,
    and the end of an iteration's Gram pass one all-reduce, of the pass's
    few dozen sums. (The row recursion made one at each of its 2m + 1
    dot products, one after the other.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(v5e_host).reshape(2, 2), ("data", "feat"))
    on = lambda *spec: NamedSharding(mesh, PartitionSpec(*spec))  # noqa: E731
    m, d = 10, 80_000_000
    h = jax.ShapeDtypeStruct((m, d // 128, 128), jnp.float32, sharding=on(None, "feat", None))
    vec = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=on("feat"))
    rho = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=on())
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=on())
    gram = jax.ShapeDtypeStruct((2 * m + 1, 2 * m + 1), jnp.float32, sharding=on())

    def collectives(compiled):
        return re.findall(r" (all-reduce|all-gather|collective-permute|all-to-all)(?:-start)?\(",
                          compiled.as_text())

    direction = jax.jit(two_loop_direction).lower(vec, h, h, rho, count, gram).compile()
    assert collectives(direction) == []
    update = jax.jit(update_history, donate_argnums=(0, 1)).lower(
        h, h, rho, count, gram, vec, vec, vec
    ).compile()
    assert collectives(update) == ["all-reduce"]


@pytest.mark.parametrize("kind", ["index_map", "projected"])
def test_heldout_scoring_holds_no_low_precision_dot(v5e, kind):
    """The held-out scoring programs (``models/game.py``) at the cells'
    size: 2^15 rows of 16 nonzeros against 2^14 entities. An f32 dot or
    einsum at default precision may run as bfloat16 passes on the TPU, the
    error ``scored_auc_gap`` exists to catch: the programs as traced hold no
    contraction below ``HIGHEST`` (they hold none at all), and the compiled
    programs nothing in bfloat16."""
    from photon_ml_tpu.models import game

    rows, K, E, D, k = 1 << 15, 16, 1 << 14, 16, 32
    struct = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    slots = struct((rows, K), jnp.int32)
    vals = struct((rows, K), jnp.float32)
    if kind == "index_map":
        blocks = [struct((E, D), jnp.float32), struct((E // 4, D), jnp.float32)]
        lowered = game._slot_program.lower(blocks, slots, vals, (E, E // 4))
    else:
        blocks = [struct((E, k), jnp.float32)]
        B = struct((4096, k), jnp.float32)
        ents = struct((rows,), jnp.int32)
        lowered = game._projected_program.lower(blocks, B, slots, vals, ents, (E,))
    assert not _low_precision(lowered, lowered.compile())
