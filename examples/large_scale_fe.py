"""Large-d fixed-effect training through the (data x feat) grid engine.

Demonstrates the 1B-coefficient layout (docs/SCALING.md) end to end at a
size that fits wherever it runs: the sparse design matrix is tiled over a
2-D device mesh, coefficients stay feature-sharded for the whole L-BFGS
solve (no chip ever holds the full vector), and the per-tile sparse compute
runs the fused permutation engine (ops/fused_perm.py) on TPU or its XLA
fallback elsewhere.

Run on the 8-virtual-device CPU harness:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/large_scale_fe.py --n-data 2 --n-feat 4

Scale up with --num-rows / --dim / --nnz-per-row on real hardware (the mesh
shape must divide the device count; routing prep is one-time host work).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

# runnable from a fresh checkout without installing the package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-rows", type=int, default=1 << 15)
    ap.add_argument("--dim", type=int, default=1 << 16)
    ap.add_argument("--nnz-per-row", type=int, default=16)
    ap.add_argument("--n-data", type=int, default=2)
    ap.add_argument("--n-feat", type=int, default=4)
    ap.add_argument("--engine", default="fused", choices=["fused", "benes", "ell"])
    ap.add_argument("--max-iterations", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.evaluation.evaluators import area_under_roc_curve
    from photon_ml_tpu.losses.objective import make_glm_objective
    from photon_ml_tpu.losses.pointwise import LogisticLoss
    from photon_ml_tpu.ops.data import LabeledData
    from photon_ml_tpu.opt.config import (
        GlmOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_ml_tpu.opt.solve import solve
    from photon_ml_tpu.parallel.grid_features import (
        grid_from_coo,
        grid_mesh,
        shard_vector_data,
        shard_vector_feat,
    )

    n, d, k = args.num_rows, args.dim, args.nnz_per_row
    rng = np.random.default_rng(args.seed)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    cols = rng.integers(0, d, n * k)
    vals = rng.standard_normal(n * k).astype(np.float32)
    w_true = (rng.standard_normal(d) * 0.3).astype(np.float32)
    z = (vals * w_true[cols]).reshape(n, k).sum(-1)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)

    mesh = grid_mesh(args.n_data, args.n_feat)
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} "
          f"{jax.devices()[0].platform} devices; engine={args.engine}")

    t0 = time.perf_counter()
    gf = grid_from_coo(rows, cols, vals, (n, d), mesh, engine=args.engine)
    print(f"routing/tiling prep: {time.perf_counter() - t0:.1f}s "
          f"(one-time, pattern-keyed cacheable)")

    y_pad = np.zeros(gf.num_rows, np.float32)
    y_pad[:n] = y
    wt_pad = np.zeros(gf.num_rows, np.float32)
    wt_pad[:n] = 1.0
    data = LabeledData.create(
        gf,
        shard_vector_data(jnp.asarray(y_pad), mesh),
        weights=shard_vector_data(jnp.asarray(wt_pad), mesh),
    )

    objective = make_glm_objective(LogisticLoss)
    cfg = GlmOptimizationConfiguration(
        optimizer_config=OptimizerConfig.lbfgs(
            max_iterations=args.max_iterations
        ),
        regularization_weight=1.0,
    )
    solver = jax.jit(
        lambda w0, dd: solve(objective, w0, dd, cfg, l2_weight=jnp.float32(1.0))
    )
    w0 = shard_vector_feat(jnp.zeros(gf.dim, jnp.float32), mesh)

    t0 = time.perf_counter()
    res = solver(w0, data)
    jax.block_until_ready(res.w)
    compile_and_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = solver(w0, data)
    jax.block_until_ready(res.w)
    steady = time.perf_counter() - t0

    iters = int(res.iterations)
    scores = np.asarray(gf.matvec(res.w))[:n]
    auc = float(area_under_roc_curve(jnp.asarray(scores), jnp.asarray(y)))
    print(f"solve: {iters} iterations, loss {float(res.value):.1f}, "
          f"train AUC {auc:.4f}")
    print(f"wall: first(+compile) {compile_and_first:.1f}s, steady {steady:.2f}s "
          f"-> {n * iters / steady / 1e6:.2f}M example-passes/s")
    assert auc > 0.8


if __name__ == "__main__":
    main()
