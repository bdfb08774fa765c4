"""GAME training data: per-row responses + feature shards + id tags.

Reference parity: data/GameDatum.scala:38 (response/offset/weight, a
featureShardContainer, and idTagToValueMap naming the entity each row belongs
to for every random-effect type) and data/GameConverters.scala:29 (DataFrame
row -> GameDatum). Struct-of-arrays instead of an RDD of per-row objects:
one numpy column per field, features kept as COO per shard so both the
fixed-effect ELL layout and the random-effect grouped blocks can be built
from the same source without re-reading input.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass
class FeatureShard:
    """One feature bag/shard in COO form over its own feature space
    (reference "feature shards" merged from feature bags,
    AvroDataReader.scala:84-145)."""

    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    dim: int

    def slice_rows(self, row_mask: np.ndarray) -> "FeatureShard":
        """Subset to rows where mask is True, renumbering rows densely."""
        keep = row_mask[self.rows]
        new_index = np.cumsum(row_mask) - 1
        return FeatureShard(
            rows=new_index[self.rows[keep]],
            cols=self.cols[keep],
            vals=self.vals[keep],
            dim=self.dim,
        )

    def take_rows(self, indices: np.ndarray) -> "FeatureShard":
        """Gather rows by index, allowing repeats (bootstrap resampling);
        output row r holds the nonzeros of input row indices[r]."""
        indices = np.asarray(indices, dtype=np.int64)
        order = np.argsort(self.rows, kind="stable")
        r_sorted = self.rows[order]
        starts = np.searchsorted(r_sorted, indices, side="left")
        ends = np.searchsorted(r_sorted, indices, side="right")
        counts = ends - starts
        total = int(counts.sum())
        # positions into `order`, one contiguous run per selected row
        run_offsets = np.repeat(np.cumsum(counts) - counts, counts)
        pos = np.arange(total) - run_offsets + np.repeat(starts, counts)
        nz = order[pos]
        return FeatureShard(
            rows=np.repeat(np.arange(len(indices), dtype=np.int64), counts),
            cols=self.cols[nz],
            vals=self.vals[nz],
            dim=self.dim,
        )


@dataclasses.dataclass
class GameData:
    """All rows of a GAME train/validation set (host container; device
    arrays are built per-coordinate)."""

    labels: np.ndarray                      # [n]
    feature_shards: Dict[str, FeatureShard]
    id_tags: Dict[str, np.ndarray]          # re_type -> per-row entity id (str)
    offsets: Optional[np.ndarray] = None    # [n]
    weights: Optional[np.ndarray] = None    # [n]

    def __post_init__(self) -> None:
        n = len(self.labels)
        self.labels = np.asarray(self.labels, dtype=np.float32)
        self.offsets = (
            np.zeros(n, dtype=np.float32)
            if self.offsets is None
            else np.asarray(self.offsets, dtype=np.float32)
        )
        self.weights = (
            np.ones(n, dtype=np.float32)
            if self.weights is None
            else np.asarray(self.weights, dtype=np.float32)
        )
        for t, ids in self.id_tags.items():
            if len(ids) != n:
                raise ValueError(f"id tag {t} has {len(ids)} rows, expected {n}")

    @property
    def num_rows(self) -> int:
        return len(self.labels)

    def slice_rows(self, row_mask: np.ndarray) -> "GameData":
        """Row-subset view (fresh arrays; ELL cache not carried over)."""
        row_mask = np.asarray(row_mask, dtype=bool)
        return GameData(
            labels=self.labels[row_mask],
            feature_shards={
                sid: s.slice_rows(row_mask)
                for sid, s in self.feature_shards.items()
            },
            id_tags={t: np.asarray(v)[row_mask] for t, v in self.id_tags.items()},
            offsets=self.offsets[row_mask],
            weights=self.weights[row_mask],
        )

    def take_rows(self, indices: np.ndarray) -> "GameData":
        """Gather rows by index with repeats allowed (bootstrap resamples)."""
        indices = np.asarray(indices, dtype=np.int64)
        return GameData(
            labels=self.labels[indices],
            feature_shards={
                sid: s.take_rows(indices)
                for sid, s in self.feature_shards.items()
            },
            id_tags={t: np.asarray(v)[indices] for t, v in self.id_tags.items()},
            offsets=self.offsets[indices],
            weights=self.weights[indices],
        )

    def device_rows(self):
        """(labels, weights, offsets) on the device, uploaded once: a
        held-out evaluation after every coordinate update reads them there."""
        rows = getattr(self, "_device_rows", None)
        if rows is None:
            import jax.numpy as jnp

            from photon_ml_tpu.telemetry.span import upload

            rows = upload("rows", lambda: tuple(
                jnp.asarray(a) for a in (self.labels, self.weights, self.offsets)
            ))
            self._device_rows = rows
        return rows

    def scoring_index(self, key, anchors, build):
        """A sub-model's index into these rows, built once and kept, as
        :meth:`sparse_features` keeps its layouts. ``key`` names the slot
        (kind, shard, entity tag); ``anchors`` are the model-side objects
        the index was built from, matched by identity: every model one
        coordinate's updates produce shares them, so a validation after
        every update finds the index built. The slot holds the newest
        index alone (a model with other anchors replaces it) and keeps its
        anchors alive, so an id is never reused while cached. Returns
        ``(index, built)``."""
        cache = self.__dict__.setdefault("_index_cache", {})
        held = cache.get(key)
        if held is not None and len(held[0]) == len(anchors) and all(
            a is b for a, b in zip(held[0], anchors)
        ):
            return held[1], False
        index = build()
        cache[key] = (tuple(anchors), index)
        return index, True

    def ell_features(self, shard_name: str):
        """Device ELL layout of one shard, built once and cached (validation
        re-scores the same data after every coordinate update)."""
        return self.sparse_features(shard_name, engine="ell")

    def sparse_features(self, shard_name: str, engine: str = "auto"):
        """Device sparse layout of one shard, built once and cached.

        engine:
        - "ell"   — padded row-sparse gather/scatter layout (XLA).
        - "benes" — permutation-routed engine (ops/sparse_perm.py): dense
          vector work instead of gather/scatter on TPU, with a one-time host
          routing cost.
        - "fused" — same routing executed as fused Pallas kernels
          (ops/fused_perm.py): fewer HBM passes per linear map.
        - "auto"  — chosen from what can be observed: "fused" on a TPU
          backend for a shard of at least 2^20 nonzeros (the host routing
          prep is meant to be repaid above that; no benchmark cell sits on
          either side of the threshold yet), "ell" everywhere else. On a
          TPU a fused kernel that fails to compile is an error; nothing
          falls back to another engine.
        """
        if engine not in ("auto", "ell", "benes", "fused"):
            raise ValueError(
                f"unknown sparse engine {engine!r}; expected auto/ell/benes/fused"
            )
        cache = getattr(self, "_feat_cache", None)
        if cache is None:
            cache = {}
            self._feat_cache = cache
        shard = self.feature_shards[shard_name]
        if engine == "auto":
            import jax

            on_tpu = jax.default_backend() == "tpu"
            big = shard.rows.size >= (1 << 20)
            engine = "fused" if on_tpu and big else "ell"
        key = (shard_name, engine)
        if key not in cache:
            if engine in ("benes", "fused"):
                if engine == "benes":
                    from photon_ml_tpu.ops.sparse_perm import from_coo
                else:
                    from photon_ml_tpu.ops.fused_perm import from_coo

                cache[key] = from_coo(
                    shard.rows, shard.cols, shard.vals, (self.num_rows, shard.dim)
                )
            else:
                from photon_ml_tpu.ops.features import from_scipy_like

                cache[key] = from_scipy_like(
                    shard.rows, shard.cols, shard.vals, (self.num_rows, shard.dim)
                )
        return cache[key]
