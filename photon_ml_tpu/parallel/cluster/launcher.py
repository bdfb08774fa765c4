"""Cluster launcher: spawn and supervise the emulated multi-host mesh.

``ClusterPlane.launch`` starts one coordinator (in-process — the trainer
IS the coordinator, like the reference's Spark driver) plus ``num_hosts``
worker subprocesses running ``python -m photon_ml_tpu.parallel.cluster.worker``
pinned to CPU. Worker stdout/stderr go to per-host log FILES, not pipes —
an unread pipe's backpressure can wedge a worker mid-print (same lesson as
tests/test_multiprocess.py).

The same object shape (``set_residual`` / ``distributed_pass`` /
``drain_events``) is what :class:`StreamingFixedEffectCoordinate` accepts
as its ``cluster``, and a bare :class:`ClusterCoordinator` with
thread-hosted workers satisfies it too — tests use that form to exercise
the full wire protocol without subprocess startup cost. On a real pod,
``dev-scripts/run_multihost.py`` starts the same worker module once per
controller instead of this launcher spawning locally.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional, Sequence

import numpy as np

from .coordinator import ClusterCoordinator

STARTUP_TIMEOUT_ENV = "PHOTON_CLUSTER_STARTUP_TIMEOUT_S"
_DEFAULT_STARTUP_TIMEOUT_S = 300.0


class ClusterPlane:
    """A live cluster: in-process coordinator + spawned worker processes."""

    def __init__(
        self,
        coordinator: ClusterCoordinator,
        procs: Sequence[subprocess.Popen],
        log_paths: Sequence[str],
    ):
        self.coordinator = coordinator
        self.procs = list(procs)
        self.log_paths = list(log_paths)
        self._closed = False

    # -- construction ------------------------------------------------------

    @classmethod
    def launch(
        cls,
        num_hosts: int,
        num_blocks: int,
        train_dirs: Sequence[str],
        coordinate_config: str,
        task: str,
        feature_shard: str,
        block_rows: int,
        input_columns_names: Optional[str] = None,
        on_block_error: str = "fail",
        prefetch_depth: int = 2,
        block_cache_dir: Optional[str] = None,
        heartbeat_timeout_s: Optional[float] = None,
        startup_timeout_s: Optional[float] = None,
        log_dir: Optional[str] = None,
        env: Optional[Dict[str, str]] = None,
        telemetry_dir: Optional[str] = None,
    ) -> "ClusterPlane":
        """Spawn ``num_hosts`` workers over the same training files and
        block plan. ``telemetry_dir`` federates observability across the
        mesh: the coordinator profiles every pass (skew/straggler
        attribution) and each worker writes its own ledger to
        ``{telemetry_dir}/worker-{host}-ledger.jsonl``."""
        coordinator = ClusterCoordinator(
            num_hosts, num_blocks, heartbeat_timeout_s=heartbeat_timeout_s
        )
        if telemetry_dir is not None:
            os.makedirs(telemetry_dir, exist_ok=True)
            coordinator.enable_telemetry()
        if log_dir is None:
            log_dir = tempfile.mkdtemp(prefix="photon-cluster-")
        os.makedirs(log_dir, exist_ok=True)
        worker_env = dict(os.environ)
        worker_env.setdefault("JAX_PLATFORMS", "cpu")
        # the emulated mesh shares one box: keep each worker's BLAS pool
        # from oversubscribing it
        worker_env.setdefault("OPENBLAS_NUM_THREADS", "1")
        if env:
            worker_env.update(env)
        addr = f"{coordinator.address[0]}:{coordinator.address[1]}"
        procs: List[subprocess.Popen] = []
        log_paths: List[str] = []
        try:
            for host in range(num_hosts):
                cmd = [
                    sys.executable, "-m",
                    "photon_ml_tpu.parallel.cluster.worker",
                    "--coordinator-address", addr,
                    "--host-id", str(host),
                    "--train-data-dirs", *list(train_dirs),
                    "--coordinate-config", coordinate_config,
                    "--task", task,
                    "--feature-shard", feature_shard,
                    "--block-rows", str(block_rows),
                    "--prefetch-depth", str(prefetch_depth),
                    "--on-block-error", on_block_error,
                ]
                if input_columns_names:
                    cmd += ["--input-columns-names", input_columns_names]
                if block_cache_dir:
                    # per-host subdirs: the decoded entries are identical
                    # but concurrent writers should not share files
                    cmd += [
                        "--block-cache-dir",
                        os.path.join(block_cache_dir, f"host-{host}"),
                    ]
                if telemetry_dir is not None:
                    cmd += [
                        "--telemetry-out",
                        os.path.join(
                            telemetry_dir, f"worker-{host}-ledger.jsonl"
                        ),
                    ]
                log_path = os.path.join(log_dir, f"worker-{host}.log")
                log_paths.append(log_path)
                log_f = open(log_path, "wb")
                try:
                    procs.append(
                        subprocess.Popen(
                            cmd, stdout=log_f, stderr=subprocess.STDOUT,
                            env=worker_env,
                        )
                    )
                finally:
                    log_f.close()
            if startup_timeout_s is None:
                startup_timeout_s = float(
                    os.environ.get(
                        STARTUP_TIMEOUT_ENV, _DEFAULT_STARTUP_TIMEOUT_S
                    )
                )
            coordinator.wait_for_workers(timeout_s=startup_timeout_s)
        except BaseException:
            for p in procs:
                p.kill()
            coordinator.shutdown()
            raise
        return cls(coordinator, procs, log_paths)

    # -- training-plane interface (what the coordinate calls) --------------

    @property
    def num_blocks(self) -> int:
        return self.coordinator.num_blocks

    def set_residual(self, residual: Optional[np.ndarray]) -> None:
        self.coordinator.set_residual(residual)

    def distributed_pass(self, w: np.ndarray):
        return self.coordinator.distributed_pass(w)

    def drain_events(self) -> List[dict]:
        return self.coordinator.drain_events()

    def drain_pass_profiles(self) -> List[dict]:
        return self.coordinator.drain_pass_profiles()

    # -- lifecycle ---------------------------------------------------------

    def worker_logs(self) -> Dict[int, str]:
        out = {}
        for host, path in enumerate(self.log_paths):
            try:
                with open(path, "r", errors="replace") as f:
                    out[host] = f.read()
            except OSError:
                out[host] = ""
        return out

    def close(self, reap_timeout_s: float = 30.0) -> None:
        if self._closed:
            return
        self._closed = True
        self.coordinator.shutdown()
        for p in self.procs:
            try:
                p.wait(timeout=reap_timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def __enter__(self) -> "ClusterPlane":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
