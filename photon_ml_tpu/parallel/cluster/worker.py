"""Worker side of the cluster plane.

Each worker owns a full :class:`StreamingSource` over the SAME training
files as every other host (the plan is rebuilt deterministically from a
sorted file scan, and the hello handshake verifies the block counts
agree), but per pass it streams only the block subset the coordinator
assigned — the ``order=`` seam of :class:`BlockPrefetcher`. For its
blocks it accumulates the donated per-block ``value_and_grad`` exactly
like the single-host solver's ``_full_pass`` (l2=0 — regularization is
finalized once, on the coordinator) and replies with the partial
``(f, g)`` sums plus per-block stats feeding the shared gap ledger.

Failure semantics are deliberately coarse: ANY exception while streaming
a pass (including an armed ``cluster.worker_block`` fault) kills the
worker, whose closed socket is the coordinator's failure signal. Recovery
lives at the CLUSTER level — the dead host's blocks are reassigned, the
pass completes on the survivors — not at the block level, so a worker
never needs its own retry machinery beyond what StreamingSource already
does for IO.

Run as a module for subprocess workers::

    python -m photon_ml_tpu.parallel.cluster.worker \
        --coordinator-address 127.0.0.1:PORT --host-id 0 \
        --train-data-dirs DIR --coordinate-config CFG.json \
        --task LOGISTIC_REGRESSION --feature-shard global --block-rows 4096

or in-thread for tests via :func:`serve_worker_in_thread`.
"""

from __future__ import annotations

import argparse
import logging
import threading
import time
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...losses.objective import make_glm_objective
from ...losses.pointwise import loss_for_task
from ...resilience.faultpoints import FatalInjectedFault, fault_point, register_fault_site
from ...telemetry.metrics import get_registry
from ...telemetry.span import span
from ...streaming.blocks import StreamingSource
from ...streaming.coordinate import _fuse_block_offsets, _pad_residual
from ...streaming.prefetch import BlockPrefetcher
from ...streaming.solver import StreamPrograms
from ...types import TaskType
from .protocol import connect

logger = logging.getLogger(__name__)

FAULT_SITE = "cluster.worker_block"
register_fault_site(
    FAULT_SITE,
    "cluster worker, before streaming each assigned block: an armed fault "
    "kills the worker mid-pass, exercising host-loss reassignment",
)

HEARTBEAT_INTERVAL_S = 2.0


class ClusterWorker:
    """One host's streaming + partial-accumulation loop."""

    def __init__(
        self,
        host_id: int,
        source: StreamingSource,
        shard_id: str,
        task: TaskType,
        prefetch_depth: int = 2,
        chaos_kill_after: Optional[int] = None,
    ):
        self.host_id = int(host_id)
        self.source = source
        self.shard_id = shard_id
        self.objective = make_glm_objective(loss_for_task(task))
        self.programs = StreamPrograms.for_objective(self.objective)
        self.prefetch_depth = int(prefetch_depth)
        self.chaos_kill_after = (
            None if chaos_kill_after is None else int(chaos_kill_after)
        )
        self._blocks_done = 0
        self._residual_padded = None
        self._dim = source.plan.shard_dims[shard_id]

    # -- one pass fragment -------------------------------------------------

    def _partial(
        self, w: np.ndarray, blocks: List[int], telemetry: bool = False
    ) -> dict:
        t0 = time.perf_counter() if telemetry else 0.0
        w_dev = jnp.asarray(w, dtype=jnp.float32)
        f = jnp.zeros((), dtype=w_dev.dtype)
        g = jnp.zeros((self._dim,), dtype=w_dev.dtype)
        stats: List[Tuple[int, object, object, object]] = []
        prefetcher = BlockPrefetcher(
            self.source,
            shards=(self.shard_id,),
            depth=self.prefetch_depth,
            order=[int(b) for b in blocks],
        )
        t_decode = time.perf_counter() if telemetry else 0.0
        for blk in prefetcher:
            fault_point(FAULT_SITE)
            if (
                self.chaos_kill_after is not None
                and self._blocks_done >= self.chaos_kill_after
            ):
                raise FatalInjectedFault(
                    f"chaos: host {self.host_id} killed after "
                    f"{self._blocks_done} blocks"
                )
            data = blk.data[self.shard_id]
            if self._residual_padded is not None:
                data = data.replace(
                    offsets=_fuse_block_offsets(
                        data.offsets,
                        self._residual_padded,
                        jnp.int32(blk.start),
                    )
                )
            f, g, bf, bg, bgap = self.programs.acc_vg_probe(w_dev, data, f, g)
            stats.append((int(blk.index), bf, bg, bgap))
            self._blocks_done += 1
        reply = {
            "f": float(f),
            "g": np.asarray(g, dtype=np.float64),
            "block_stats": [
                {
                    "block": idx,
                    "partial_loss": float(bf),
                    "partial_grad_norm": float(bg),
                    "gap": float(bgap),
                }
                for idx, bf, bg, bgap in stats
            ],
        }
        if telemetry:
            # Piggybacked fragment timing: decode (weight upload +
            # prefetcher setup), solve (the block loop), plus blocks
            # visited and H2D bytes moved. busy_s/reply_s are stamped by
            # run() just before send, where the reply cost is known.
            reply["telemetry"] = {
                "decode_s": t_decode - t0,
                "solve_s": time.perf_counter() - t_decode,
                "blocks": len(stats),
                "h2d_bytes": int(prefetcher.stats.h2d_bytes),
            }
        return reply

    # -- protocol loop -----------------------------------------------------

    def run(self, address: Tuple[str, int], connect_timeout_s: float = 60.0) -> None:
        msock = connect(address, timeout=connect_timeout_s)
        stop_beat = threading.Event()

        def _heartbeat():
            while not stop_beat.wait(HEARTBEAT_INTERVAL_S):
                try:
                    msock.send({"type": "heartbeat", "host": self.host_id})
                except OSError:
                    return

        try:
            msock.send(
                {
                    "type": "hello",
                    "host": self.host_id,
                    "num_blocks": self.source.plan.num_blocks,
                }
            )
            threading.Thread(
                target=_heartbeat, daemon=True,
                name=f"cluster-heartbeat-{self.host_id}",
            ).start()
            while True:
                msg = msock.recv()
                kind = msg.get("type")
                if kind == "stop":
                    break
                if kind == "residual":
                    residual = msg["residual"]
                    self._residual_padded = (
                        None
                        if residual is None
                        else _pad_residual(
                            jnp.asarray(residual, dtype=jnp.float32),
                            self.source.plan.padded_rows,
                        )
                    )
                elif kind == "pass":
                    # The coordinator only sets "telemetry" when its own
                    # telemetry is enabled; without it the reply is
                    # byte-identical to the plain plane.
                    want_tele = bool(msg.get("telemetry"))
                    t_recv = time.perf_counter() if want_tele else 0.0
                    with span(
                        "cluster/fragment",
                        host=self.host_id,
                        pass_id=int(msg["pass_id"]),
                        frag=int(msg["frag"]),
                        blocks=len(msg["blocks"]),
                    ):
                        reply = self._partial(
                            msg["w"], msg["blocks"], telemetry=want_tele
                        )
                    reply.update(
                        type="partial",
                        pass_id=msg["pass_id"],
                        frag=msg["frag"],
                        host=self.host_id,
                    )
                    if want_tele:
                        wt = reply["telemetry"]
                        t_send = time.perf_counter()
                        wt["reply_s"] = max(
                            0.0,
                            t_send - t_recv - wt["decode_s"] - wt["solve_s"],
                        )
                        wt["busy_s"] = t_send - t_recv
                        reg = get_registry()
                        reg.count("cluster.worker.fragments")
                        reg.count("cluster.worker.blocks", wt["blocks"])
                        reg.count("cluster.worker.h2d_bytes", wt["h2d_bytes"])
                        reg.observe("cluster.worker.solve_s", wt["solve_s"])
                    msock.send(reply)
        except EOFError:
            logger.info("host %d: coordinator closed connection", self.host_id)
        finally:
            stop_beat.set()
            msock.close()


def serve_worker_in_thread(
    worker: ClusterWorker, address: Tuple[str, int]
) -> threading.Thread:
    """Run a worker's protocol loop on a daemon thread (tests: exercises
    the full wire protocol without subprocess startup cost). A fatal
    injected fault ends the thread and closes the socket — the same
    death signal a killed process gives."""

    def _run():
        try:
            worker.run(address)
        except FatalInjectedFault as exc:
            logger.info("host %d chaos-killed: %s", worker.host_id, exc)
        except Exception:
            logger.exception("host %d worker died", worker.host_id)

    t = threading.Thread(
        target=_run, daemon=True, name=f"cluster-worker-{worker.host_id}"
    )
    t.start()
    return t


# -- subprocess entry ------------------------------------------------------


def _parse_address(spec: str) -> Tuple[str, int]:
    host, _, port = spec.rpartition(":")
    return host or "127.0.0.1", int(port)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="photon-ml-tpu cluster worker (spawned by the launcher)"
    )
    p.add_argument("--coordinator-address", required=True)
    p.add_argument("--host-id", type=int, required=True)
    p.add_argument("--train-data-dirs", nargs="+", required=True)
    p.add_argument("--coordinate-config", required=True)
    p.add_argument("--task", required=True)
    p.add_argument("--feature-shard", required=True)
    p.add_argument("--block-rows", type=int, default=4096)
    p.add_argument("--input-columns-names", default=None)
    p.add_argument("--prefetch-depth", type=int, default=2)
    p.add_argument("--on-block-error", default="fail")
    p.add_argument("--block-cache-dir", default=None)
    p.add_argument(
        "--telemetry-out",
        default=None,
        metavar="LEDGER.jsonl",
        help="write this worker's own run ledger (fragment spans, "
        "cluster.worker.* counters) to this path; enables span tracing "
        "in the worker process",
    )
    args = p.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format=f"[host {args.host_id}] %(levelname)s %(message)s",
    )
    from ...cli.common import (
        expand_data_dirs,
        id_tags_needed,
        load_game_config,
        parse_input_columns,
    )

    shard_configs, coordinates, _, _ = load_game_config(args.coordinate_config)
    col_names = parse_input_columns(args.input_columns_names)
    train_dirs = expand_data_dirs(args.train_data_dirs, None, None)
    # index_maps=None: the maps rebuild deterministically from the sorted
    # file scan, so every host (and the coordinator) plans identical blocks
    source = StreamingSource.open(
        train_dirs,
        shard_configs,
        index_maps=None,
        block_rows=args.block_rows,
        id_tags=id_tags_needed(coordinates),
        cache_dir=args.block_cache_dir,
        **col_names,
    )
    source.on_block_error = args.on_block_error
    worker = ClusterWorker(
        host_id=args.host_id,
        source=source,
        shard_id=args.feature_shard,
        task=TaskType[args.task],
        prefetch_depth=args.prefetch_depth,
    )
    run = None
    if args.telemetry_out:
        from ...telemetry import start_run

        run = start_run(
            f"cluster-worker-{args.host_id}", ledger_path=args.telemetry_out
        )
    try:
        worker.run(_parse_address(args.coordinator_address))
    except FatalInjectedFault as exc:
        logger.error("chaos-killed: %s", exc)
        return 17
    finally:
        if run is not None:
            try:
                run.finish()
            except Exception:
                logger.exception("worker telemetry finish failed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
