"""Worker for the real multi-process cluster test (test_multiprocess.py).

Each worker is one "host": its own process, its own local CPU devices,
joined into one JAX cluster through a local coordinator. Exercises the
REAL multi-process branches of parallel/multihost.py — cluster init, file
sharding, global-batch assembly from unequal per-host blocks — plus a
cross-process data-parallel FE solve (psums over the global mesh).
"""

import os
import sys

proc_id = int(sys.argv[1])
n_procs = int(sys.argv[2])
port = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={8 // n_procs}"
).strip()
os.environ["PHOTON_ML_TPU_PLAN_CACHE"] = ""

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

from photon_ml_tpu.parallel.multihost import (
    global_batch_from_host_rows,
    host_shard_files,
    initialize_distributed,
)

ok = initialize_distributed(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=n_procs,
    process_id=proc_id,
)
assert ok, "cluster did not form"
assert jax.process_count() == n_procs
assert jax.process_index() == proc_id
n_global = len(jax.devices())
n_local = len(jax.local_devices())
assert n_global == 8 and n_local == 8 // n_procs, (n_global, n_local)

# deterministic, disjoint, complete file assignment
files = [f"part-{i:05d}.avro" for i in range(7)]
mine = host_shard_files(files)
assert mine == [p for k, p in enumerate(sorted(files)) if k % n_procs == proc_id]

# global batch from UNEQUAL per-host row blocks
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from photon_ml_tpu.parallel.mesh import DATA_AXIS, data_parallel_mesh

mesh = data_parallel_mesh()  # all global devices
share = 24 * n_local // n_global  # this process's addressable rows
rows = np.full((share, 3), float(proc_id), dtype=np.float32)
garr = global_batch_from_host_rows(
    rows, mesh, P(DATA_AXIS, None), global_rows=24
)
assert garr.shape == (24, 3)
total = float(jax.jit(jnp.sum)(garr))  # cross-process psum via GSPMD
expected = 3.0 * share * sum(range(n_procs))  # sum over hosts of id*share
assert total == expected, (total, expected)

# an unequal block must fail fast with the pad/trim instruction, not trip
# deep inside jax
try:
    global_batch_from_host_rows(
        rows[: share - 1], mesh, P(DATA_AXIS, None), global_rows=24
    )
except ValueError as e:
    assert "zero-weight" in str(e)
else:
    raise AssertionError("unequal host block silently accepted")

# a real data-parallel FE solve over the global mesh: every process runs the
# same program; loss/grad reductions cross the process boundary
from photon_ml_tpu.losses.objective import make_glm_objective
from photon_ml_tpu.losses.pointwise import LogisticLoss
from photon_ml_tpu.ops.data import LabeledData
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.opt.config import GlmOptimizationConfiguration, OptimizerConfig
from photon_ml_tpu.opt.solve import solve

rng = np.random.default_rng(0)  # same data recipe on every host
n, d = 64, 6
X_all = rng.standard_normal((n_procs * n, d)).astype(np.float32)
w_true = (rng.standard_normal(d) * 0.7).astype(np.float32)
y_all = (rng.random(n_procs * n) < 1.0 / (1.0 + np.exp(-(X_all @ w_true)))).astype(
    np.float32
)
n_share = n_procs * n * n_local // n_global
lo = proc_id * n_share
X_g = global_batch_from_host_rows(
    X_all[lo : lo + n_share], mesh, P(DATA_AXIS, None), global_rows=n_procs * n
)
y_g = global_batch_from_host_rows(
    y_all[lo : lo + n_share], mesh, P(DATA_AXIS), global_rows=n_procs * n
)
data = LabeledData.create(DenseFeatures(matrix=X_g), y_g)
cfg = GlmOptimizationConfiguration(
    optimizer_config=OptimizerConfig.lbfgs(max_iterations=25),
    regularization_weight=1.0,
)
objective = make_glm_objective(LogisticLoss)
res = jax.jit(
    lambda w0, dd: solve(objective, w0, dd, cfg, l2_weight=jnp.float32(1.0))
)(jnp.zeros(d, jnp.float32), data)
w = np.asarray(jax.device_get(res.w))  # replicated -> addressable everywhere
assert np.all(np.isfinite(w)) and np.abs(w).max() > 0.05
corr = float(np.corrcoef(w, w_true)[0, 1])
assert corr > 0.8, corr

# --- the 1B-coefficient layout ACROSS PROCESSES: a (data x feat) grid FE
# solve where coefficients stay feat-sharded and tiles live on whichever
# host owns their device. Every host builds from the same global COO; the
# placement helper hands each process only its addressable shards.
from photon_ml_tpu.parallel.grid_features import (
    grid_from_coo,
    grid_mesh,
    shard_vector_data,
    shard_vector_feat,
)

ng, dg, kg = 128, 96, 4
g_rows = np.repeat(np.arange(ng, dtype=np.int64), kg)
g_cols = rng.integers(0, dg, ng * kg)
g_vals = rng.standard_normal(ng * kg).astype(np.float32)
g_dense = np.zeros((ng, dg), np.float32)
np.add.at(g_dense, (g_rows, g_cols), g_vals)
gw_true = (rng.standard_normal(dg) * 0.5).astype(np.float32)
g_y = (rng.random(ng) < 1.0 / (1.0 + np.exp(-(g_dense @ gw_true)))).astype(
    np.float32
)
gmesh = grid_mesh(2, 4)  # spans every process in the cluster
gf = grid_from_coo(g_rows, g_cols, g_vals, (ng, dg), gmesh, engine="benes")
y_pad = np.zeros(gf.num_rows, np.float32)
y_pad[:ng] = g_y
wt_pad = np.zeros(gf.num_rows, np.float32)
wt_pad[:ng] = 1.0
g_data = LabeledData.create(
    gf,
    shard_vector_data(jnp.asarray(y_pad), gmesh),
    weights=shard_vector_data(jnp.asarray(wt_pad), gmesh),
)
g_res = jax.jit(
    lambda w0, dd: solve(objective, w0, dd, cfg, l2_weight=jnp.float32(1.0))
)(shard_vector_feat(jnp.zeros(gf.dim, jnp.float32), gmesh), g_data)
from jax.sharding import NamedSharding

g_w = np.asarray(jax.device_get(
    jax.jit(lambda a: a, out_shardings=NamedSharding(gmesh, P()))(g_res.w)
))  # all-gather the feat-sharded result (replicated -> fetchable anywhere)
# reference: same solve single-host on local dense math
from photon_ml_tpu.ops.features import from_scipy_like

ell_ref = from_scipy_like(g_rows, g_cols, g_vals, (ng, dg))
ref = solve(
    objective, jnp.zeros(dg, jnp.float32),
    LabeledData.create(ell_ref, jnp.asarray(g_y)), cfg,
    l2_weight=jnp.float32(1.0),
)
assert np.allclose(g_w[:dg], np.asarray(ref.w), atol=5e-3), (
    np.abs(g_w[:dg] - np.asarray(ref.w)).max()
)

# --- full GAME training (FE grid + entity-sharded RE) across processes:
# the estimator's multi-chip path under a real multi-controller runtime.
from photon_ml_tpu.data.game_data import FeatureShard, GameData
from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_ml_tpu.estimators.game import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    ParallelConfiguration,
    RandomEffectCoordinateConfiguration,
)
from photon_ml_tpu.types import TaskType

users = [f"u{i % 8}" for i in range(ng)]
game_data = GameData(
    labels=g_y,
    feature_shards={
        "g": FeatureShard(rows=g_rows, cols=g_cols, vals=g_vals, dim=dg)
    },
    id_tags={"userId": users},
    offsets=np.zeros(ng, np.float32),
    weights=np.ones(ng, np.float32),
)
game_coords = {
    "global": FixedEffectCoordinateConfiguration(
        feature_shard="g", optimizer=cfg
    ),
    "per-user": RandomEffectCoordinateConfiguration(
        feature_shard="g",
        data=RandomEffectDataConfiguration(random_effect_type="userId"),
        optimizer=cfg,
    ),
}
est = GameEstimator(
    task=TaskType.LOGISTIC_REGRESSION,
    coordinates=game_coords,
    num_outer_iterations=1,
    parallel=ParallelConfiguration(n_data=2, n_feat=4, engine="benes"),
)
# checkpoint the fit itself: process 0 writes, every host runs the gathers
import tempfile

from photon_ml_tpu.parallel.multihost import barrier

ckdir = os.path.join(tempfile.gettempdir(), f"mp_ckpt_{port}_{os.getppid()}")
if proc_id == 0 and os.path.isdir(ckdir):
    import shutil

    shutil.rmtree(ckdir)
barrier("ckpt-clean")
game_fit = est.fit(game_data, checkpoint_dir=ckdir)
g_scores = np.asarray(game_fit.model.score(game_data))
assert np.all(np.isfinite(g_scores))

# --- model persistence across processes: every host runs the gather
# collectives, only process 0 writes (single-writer contract), then all
# hosts read the shared directory after a barrier
import tempfile

from photon_ml_tpu.io.model_io import load_game_model, save_game_model
from photon_ml_tpu.parallel.multihost import barrier

mdir = os.path.join(tempfile.gettempdir(), f"mp_model_{port}_{os.getppid()}")
if proc_id == 0 and os.path.isdir(mdir):
    import shutil

    shutil.rmtree(mdir)  # stale dir from a crashed run must not mask a save
barrier("model-dir-clean")
save_game_model(game_fit.model, mdir)
barrier("model-saved")
assert os.path.isdir(mdir), "process 0 should have written the shared model"
reloaded, _ = load_game_model(mdir)
from photon_ml_tpu.parallel.mesh import fetch_global

fe0 = fetch_global(game_fit.model.models["global"].coefficients.means)
fe1 = fetch_global(reloaded.models["global"].coefficients.means)
assert fe0.shape == fe1.shape  # dim survives sparse storage (featureShards in metadata)
assert np.allclose(fe0, fe1, atol=1e-6)
r_scores = np.asarray(reloaded.score(game_data))
assert np.allclose(r_scores, g_scores, atol=1e-4), (
    np.abs(r_scores - g_scores).max()
)
barrier("model-reloaded")
if proc_id == 0:
    import shutil

    shutil.rmtree(mdir, ignore_errors=True)

# --- resume across the cluster: a longer run continues from the shared
# checkpoint written during the fit above
barrier("ckpt-written")
assert os.path.isfile(
    os.path.join(ckdir, "training-state.json")
), "process 0 should have written the checkpoint state"
est_resume = GameEstimator(
    task=TaskType.LOGISTIC_REGRESSION,
    coordinates=game_coords,
    num_outer_iterations=2,
    parallel=ParallelConfiguration(n_data=2, n_feat=4, engine="benes"),
)
fit2 = est_resume.fit(game_data, checkpoint_dir=ckdir)  # resumes at iter 2
# the resumed run must splice iteration 1's objective history from the
# checkpoint — exact equality proves it loaded rather than retrained
h1 = game_fit.objective_history
assert fit2.objective_history[: len(h1)] == h1, (
    fit2.objective_history[: len(h1)], h1
)
assert len(fit2.objective_history) > len(h1)  # and trained iteration 2
r2 = np.asarray(fit2.model.score(game_data))
assert np.all(np.isfinite(r2))
barrier("resume-done")
if proc_id == 0:
    import shutil

    shutil.rmtree(ckdir, ignore_errors=True)

print(f"worker {proc_id}: cluster {n_procs} procs x {n_local} devices, "
      f"dp solve corr {corr:.3f}, grid solve matches local, "
      f"GAME estimator fit OK", flush=True)
