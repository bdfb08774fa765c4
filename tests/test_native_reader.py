"""Native columnar Avro reader vs the Python codec: exact agreement.

The C++ fast path (native/avrodecode.cpp) must be behaviorally invisible —
same GameData up to feature-index permutation, same errors — with the
Python record-at-a-time codec as the always-available fallback.
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.io import data_reader as dr
from photon_ml_tpu.io import native_reader as nr
from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    read_game_data,
    write_training_examples,
)


@pytest.fixture
def avro_dir(tmp_path, rng):
    recs = []
    for i in range(300):
        feats = [
            ("f", str(j), float(v))
            for j, v in zip(
                rng.choice(40, 4, replace=False), rng.standard_normal(4)
            )
        ]
        rec = {
            "uid": f"r{i}",
            "label": float(rng.integers(0, 2)),
            "features": feats,
            "userFeatures": [("u", "0", 1.0)],
            "metadataMap": {"userId": f"u{i % 7}"},
        }
        if i % 3 == 0:
            rec["weight"] = 2.0
        if i % 4 == 0:
            rec["offset"] = 0.5
        recs.append(rec)
    d = tmp_path / "data"
    d.mkdir()
    write_training_examples(str(d / "part-0.avro"), recs[:200])
    write_training_examples(str(d / "part-1.avro"), recs[200:])
    return str(d)


SHARDS = {
    "g": FeatureShardConfiguration(feature_bags=["features"], add_intercept=True),
    "u": FeatureShardConfiguration(
        feature_bags=["userFeatures"], add_intercept=False
    ),
}


def _densify(shard):
    m = np.zeros((int(shard.rows.max()) + 1, shard.dim), np.float32)
    np.add.at(m, (shard.rows, shard.cols), shard.vals)
    return m


class TestNativeReader:
    def test_native_path_is_taken(self, avro_dir):
        assert nr.native_available()
        got = dr._read_game_data_native(
            [avro_dir], SHARDS, None, ["userId"],
            "label", "offset", "weight", "uid", True,
        )
        assert got is not None

    def test_matches_python_codec(self, avro_dir, monkeypatch):
        native = read_game_data([avro_dir], SHARDS, id_tags=["userId"])
        monkeypatch.setattr(dr, "_read_game_data_native", lambda *a: None)
        python = read_game_data([avro_dir], SHARDS, id_tags=["userId"])

        dn, mn, un = native
        dp, mp, up = python
        np.testing.assert_array_equal(dn.labels, dp.labels)
        np.testing.assert_array_equal(dn.offsets, dp.offsets)
        np.testing.assert_array_equal(dn.weights, dp.weights)
        assert un == up
        np.testing.assert_array_equal(
            dn.id_tags["userId"], dp.id_tags["userId"]
        )
        for sid in SHARDS:
            # feature ids may be permuted between the paths; compare by name
            names_n = [mn[sid].get_feature_name(i) for i in range(len(mn[sid]))]
            names_p = [mp[sid].get_feature_name(i) for i in range(len(mp[sid]))]
            assert sorted(names_n) == sorted(names_p)
            dense_n = _densify(dn.feature_shards[sid])
            dense_p = _densify(dp.feature_shards[sid])
            perm = [names_n.index(k) for k in names_p]
            np.testing.assert_allclose(dense_n[:, perm], dense_p, atol=1e-6)

    def test_scoring_with_fixed_index_map(self, avro_dir):
        # train-style read builds the maps; scoring-style read reuses them
        # and must drop unmapped features identically on both paths
        _, maps, _ = read_game_data([avro_dir], SHARDS, id_tags=["userId"])
        native = read_game_data(
            [avro_dir], SHARDS, index_maps=maps, id_tags=["userId"]
        )
        assert native[0].feature_shards["g"].dim == len(maps["g"])

    def test_missing_tag_raises(self, avro_dir):
        with pytest.raises(ValueError, match="missing id tag"):
            read_game_data([avro_dir], SHARDS, id_tags=["itemId"])

    def test_missing_label_raises(self, tmp_path):
        # nullable-label schema (RESPONSE_PREDICTION-style input)
        from photon_ml_tpu.io.avro import write_avro_file

        schema = {
            "type": "record",
            "name": "ScoredExample",
            "fields": [
                {"name": "label", "type": ["null", "double"], "default": None},
                {
                    "name": "features",
                    "type": {
                        "type": "array",
                        "items": {
                            "type": "record",
                            "name": "FeatureAvro",
                            "fields": [
                                {"name": "name", "type": "string"},
                                {"name": "term", "type": "string"},
                                {"name": "value", "type": "double"},
                            ],
                        },
                    },
                },
            ],
        }
        path = str(tmp_path / "p.avro")
        write_avro_file(
            path, schema,
            [{"label": None,
              "features": [{"name": "f", "term": "1", "value": 1.0}]}],
        )
        with pytest.raises(ValueError, match="has no 'label'"):
            read_game_data([path], {"g": SHARDS["g"]})
        # and the same file reads fine when the response is optional
        data, _, _ = read_game_data(
            [path], {"g": SHARDS["g"]}, is_response_required=False
        )
        assert np.isnan(data.labels[0])

    def test_fallback_on_unsupported_schema(self, tmp_path, rng):
        # a record schema with a nested record field compiles to no program
        from photon_ml_tpu.io.avro import AvroSchema, write_avro_file

        schema = {
            "type": "record",
            "name": "Odd",
            "fields": [
                {"name": "label", "type": "double"},
                {
                    "name": "inner",
                    "type": {
                        "type": "record",
                        "name": "Inner",
                        "fields": [{"name": "x", "type": "double"}],
                    },
                },
                {
                    "name": "features",
                    "type": {
                        "type": "array",
                        "items": {
                            "type": "record",
                            "name": "FeatureAvro",
                            "fields": [
                                {"name": "name", "type": "string"},
                                {"name": "term", "type": "string"},
                                {"name": "value", "type": "double"},
                            ],
                        },
                    },
                },
            ],
        }
        path = str(tmp_path / "odd.avro")
        write_avro_file(
            path, schema,
            [{"label": 1.0, "inner": {"x": 2.0},
              "features": [{"name": "f", "term": "1", "value": 3.0}]}],
        )
        data, maps, _ = read_game_data([path], {"g": SHARDS["g"]})
        assert data.num_rows == 1  # python fallback handled it
        assert data.feature_shards["g"].vals.tolist().count(3.0) == 1

    def test_corrupt_record_count_no_crash(self, tmp_path):
        """A corrupted block record-count must surface as a fallback/skip,
        never a process abort (the decoder's never-UB contract)."""
        import photon_ml_tpu.io.native_reader as nrm
        from photon_ml_tpu.io.avro import AvroSchema, _Reader, _decode, MAGIC

        path = str(tmp_path / "c.avro")
        write_training_examples(
            path, [{"uid": "a", "label": 1.0, "features": [("f", "1", 2.0)]}]
        )
        with open(path, "rb") as f:
            raw = f.read()
        r = _Reader(raw)
        r.read(4)
        meta = _decode(r, {"type": "map", "values": "bytes"})
        root = AvroSchema(meta["avro.schema"].decode()).root
        plan = nr.compile_program(root, ["label"], [], ["features"])
        assert plan is not None
        # lie about the record count: the native decoder must reject, not die
        import ctypes

        lib = nrm._load_native()
        u8p = ctypes.POINTER(ctypes.c_uint8)
        blob = b"\x00" * 4
        h = lib.avro_decode(
            ctypes.cast(ctypes.c_char_p(blob), u8p), len(blob), 1 << 55,
            np.ascontiguousarray(plan.program).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)
            ),
            len(plan.program) // 3, len(plan.num_fields), plan.n_str_cols,
            len(plan.bag_fields),
            ctypes.cast(ctypes.c_char_p(b""), u8p),
            np.zeros(0, np.int32).ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            0, plan.tag_col_base,
        )
        assert not h  # null handle, process alive


class TestChunkedDecode:
    """Container-block-granular decode: the unit of out-of-core streaming.

    ``read_columnar_file(block_start, block_count)`` must decompress only
    the selected container blocks and produce columns bitwise-identical to
    the matching row range of a whole-file read."""

    def _write_multiblock(self, tmp_path, rng, n=400):
        """One Avro file with MANY container blocks (tiny sync interval)."""
        from photon_ml_tpu.io import schemas as _schemas
        from photon_ml_tpu.io.avro import write_avro_file

        recs = [
            {
                "uid": f"r{i}",
                "label": float(rng.integers(0, 2)),
                "weight": 1.0 + (i % 3),
                "features": [
                    {"name": "f", "term": str(j), "value": float(v)}
                    for j, v in zip(
                        rng.choice(30, 3, replace=False),
                        rng.standard_normal(3),
                    )
                ],
                "metadataMap": {"userId": f"u{i % 5}"},
            }
            for i in range(n)
        ]
        path = str(tmp_path / "multiblock.avro")
        write_avro_file(
            path, _schemas.TRAINING_EXAMPLE, recs, sync_interval=1024
        )
        return path, recs

    def _plan(self, path):
        from photon_ml_tpu.io.avro import AvroSchema, MAGIC, _Reader, _decode

        with open(path, "rb") as f:
            raw = f.read()
        r = _Reader(raw)
        assert r.read(4) == MAGIC
        meta = _decode(r, {"type": "map", "values": "bytes"})
        root = AvroSchema(meta["avro.schema"].decode()).root
        plan = nr.compile_program(
            root, ["label", "weight", "offset"], ["uid"], ["features"],
            ["userId"],
        )
        assert plan is not None
        return plan, raw

    def test_container_block_counts_sum_to_rows(self, tmp_path, rng):
        path, recs = self._write_multiblock(tmp_path, rng)
        counts = nr.container_block_counts(path)
        assert len(counts) > 4  # the tiny sync interval made many blocks
        assert sum(counts) == len(recs)
        assert all(c > 0 for c in counts)

    def test_chunked_decode_bitwise_identical(self, tmp_path, rng):
        path, _ = self._write_multiblock(tmp_path, rng)
        plan, raw = self._plan(path)
        counts = nr.container_block_counts(path, data=raw)
        whole = nr.read_columnar_file(path, plan, data=raw)
        assert whole is not None

        def _bag_rows(cf, lo_row):
            rec, val, koff, klen = cf.bags["features"]
            return rec + lo_row, val, koff, klen

        row = 0
        for start in range(len(counts)):
            for count in (1, 2):
                part = nr.read_columnar_file(
                    path, plan, data=raw,
                    block_start=start, block_count=count,
                )
                assert part is not None
                lo, hi = row, row + sum(counts[start:start + count])
                assert part.n_rows == hi - lo
                for name in ("label", "weight"):
                    np.testing.assert_array_equal(
                        part.num[name], whole.num[name][lo:hi]
                    )
                    np.testing.assert_array_equal(
                        part.num_present[name],
                        whole.num_present[name][lo:hi],
                    )
                # bag streams: same per-row features, values bitwise equal
                prec, pval, pkoff, pklen = part.bags["features"]
                wrec, wval, wkoff, wklen = whole.bags["features"]
                sel = (wrec >= lo) & (wrec < hi)
                np.testing.assert_array_equal(prec + lo, wrec[sel])
                np.testing.assert_array_equal(pval, wval[sel])
                # feature KEYS resolve identically through each arena
                pkeys = [
                    part.key_arena[o:o + l]
                    for o, l in zip(pkoff, pklen)
                ]
                wkeys = [
                    whole.key_arena[o:o + l]
                    for o, l in zip(wkoff[sel], wklen[sel])
                ]
                assert pkeys == wkeys
                # string columns (uid + metadataMap tag)
                for col_of in ("strs", "tag_strs"):
                    pcols = getattr(part, col_of)
                    wcols = getattr(whole, col_of)
                    for name in pcols:
                        pa, po, pl = pcols[name]
                        wa, wo, wl = wcols[name]
                        got = [
                            pa[o:o + l] for o, l in zip(po, pl)
                        ]
                        want = [
                            wa[o:o + l]
                            for o, l in zip(wo[lo:hi], wl[lo:hi])
                        ]
                        assert got == want
            row += counts[start]

    def test_chunked_decode_tail_and_bounds(self, tmp_path, rng):
        path, recs = self._write_multiblock(tmp_path, rng)
        plan, raw = self._plan(path)
        counts = nr.container_block_counts(path, data=raw)
        # open-ended read from mid-file covers exactly the tail
        part = nr.read_columnar_file(path, plan, data=raw, block_start=2)
        assert part.n_rows == sum(counts[2:])
        # block_count past the end clamps
        part = nr.read_columnar_file(
            path, plan, data=raw, block_start=len(counts) - 1,
            block_count=99,
        )
        assert part.n_rows == counts[-1]
        # out-of-range start raises (not a silent empty read)
        with pytest.raises(ValueError, match="out of range"):
            nr.read_columnar_file(
                path, plan, data=raw, block_start=len(counts) + 1
            )

    def test_unsupported_codec_counts_raise(self, tmp_path):
        """container_block_counts must refuse (not mis-count) codecs the
        framing scan cannot see through."""
        path = str(tmp_path / "weird.avro")
        # hand-write a container header claiming an unsupported codec
        from photon_ml_tpu.io.avro import MAGIC, SYNC_SIZE, _encode

        with open(path, "wb") as f:
            f.write(MAGIC)
            _encode(
                f, {"type": "map", "values": "bytes"},
                {"avro.schema": b'"null"', "avro.codec": b"snappy"},
            )
            f.write(b"\x00" * SYNC_SIZE)
        with pytest.raises(ValueError, match="unsupported avro codec"):
            nr.container_block_counts(path)


class TestPackedDecodeParallelism:
    """The packed decode entry point (avro_decode_packed) runs inflate +
    columnar decode as ONE foreign call, so the GIL is released for the
    whole per-file decode window — the property that makes the streaming
    decode pool's threads genuinely overlap."""

    def _write_big(self, tmp_path, rng, name, n=12000):
        from photon_ml_tpu.io import schemas as _schemas
        from photon_ml_tpu.io.avro import write_avro_file

        recs = [
            {
                "uid": f"r{i}",
                "label": float(i % 2),
                "weight": 1.0,
                "features": [
                    {"name": "f", "term": str(j), "value": float(v)}
                    for j, v in zip(
                        rng.choice(64, 6, replace=False),
                        rng.standard_normal(6),
                    )
                ],
                "metadataMap": {"userId": f"u{i % 50}"},
            }
            for i in range(n)
        ]
        path = str(tmp_path / name)
        write_avro_file(path, _schemas.TRAINING_EXAMPLE, recs)
        return path

    def _packed_args(self, path, raw, plan, lib):
        import ctypes

        scanned = nr._scan_container_offsets(path, raw)
        assert scanned is not None
        data, offsets, lengths, counts, codec = scanned
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        offs_a = np.asarray(offsets, dtype=np.int64)
        lens_a = np.asarray(lengths, dtype=np.int64)
        cnts_a = np.asarray(counts, dtype=np.int64)
        prog = np.ascontiguousarray(plan.program)
        tag_names = sorted(plan.tags, key=plan.tags.get)
        tag_bytes = b"".join(t.encode() for t in tag_names)
        tag_lens = np.asarray([len(t) for t in tag_names], dtype=np.int32)
        # keep every array alive via the returned closure's cell refs
        def call():
            return lib.avro_decode_packed(
                ctypes.cast(ctypes.c_char_p(data), u8p), len(data),
                offs_a.ctypes.data_as(i64p), lens_a.ctypes.data_as(i64p),
                cnts_a.ctypes.data_as(i64p), len(offsets),
                1 if codec == "deflate" else 0,
                prog.ctypes.data_as(i32p), len(plan.program) // 3,
                len(plan.num_fields), plan.n_str_cols, len(plan.bag_fields),
                ctypes.cast(ctypes.c_char_p(tag_bytes), u8p),
                tag_lens.ctypes.data_as(i32p), len(tag_names),
                plan.tag_col_base,
            )
        return call

    def test_packed_decode_releases_gil(self, tmp_path, rng):
        """Background-counter probe: a pure-Python thread makes progress
        DURING the native call iff the call dropped the GIL. Valid on any
        CPU count (on one core the OS preempts between the two threads
        only when the native thread isn't holding the lock)."""
        import sys
        import threading

        lib = nr._load_native()
        if lib is None or not getattr(lib, "has_packed", False):
            pytest.skip("native packed decoder unavailable")
        path = self._write_big(tmp_path, rng, "gilprobe.avro")
        with open(path, "rb") as f:
            raw = f.read()
        plan, _ = TestChunkedDecode._plan(TestChunkedDecode(), path)
        call = self._packed_args(path, raw, plan, lib)

        ticks = [0]
        stop = threading.Event()

        def counter():
            while not stop.is_set():
                ticks[0] += 1

        old_interval = sys.getswitchinterval()
        sys.setswitchinterval(0.0005)
        t = threading.Thread(target=counter, daemon=True)
        t.start()
        try:
            # only the foreign call runs between the two snapshots, so any
            # counter progress happened while native code was executing
            progressed = 0
            for _ in range(4):
                before = ticks[0]
                handle = call()
                progressed += ticks[0] - before
                assert handle
                lib.res_free(handle)
        finally:
            stop.set()
            t.join(timeout=2.0)
            sys.setswitchinterval(old_interval)
        assert progressed > 0, "GIL held across avro_decode_packed"

    def test_two_thread_decode_overlap(self, tmp_path, rng):
        """Two files decoding on two threads are inside the native call at
        the same time: in the order of events, one thread enters its decode
        before the other has left its own. An ordering, not a ratio of
        wall-clock times, so it holds on a loaded or single-core host."""
        import threading

        lib = nr._load_native()
        if lib is None or not getattr(lib, "has_packed", False):
            pytest.skip("native packed decoder unavailable")
        calls = []
        for name in ("ovl-a.avro", "ovl-b.avro"):
            path = self._write_big(tmp_path, rng, name)
            with open(path, "rb") as f:
                raw = f.read()
            plan, _ = TestChunkedDecode._plan(TestChunkedDecode(), path)
            calls.append(self._packed_args(path, raw, plan, lib))

        events = []  # (thread, "enter" | "exit"), appended under the GIL
        start = threading.Barrier(len(calls))

        def run(who, call, reps=3):
            start.wait()
            for _ in range(reps):
                events.append((who, "enter"))
                h = call()
                events.append((who, "exit"))
                assert h
                lib.res_free(h)

        threads = [
            threading.Thread(target=run, args=(who, c))
            for who, c in enumerate(calls)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(events) == 2 * 3 * len(calls)
        inside = set()
        both_inside = 0
        for who, what in events:
            if what == "enter":
                inside.add(who)
                both_inside += len(inside) == len(calls)
            else:
                inside.discard(who)
        assert both_inside > 0, f"decodes never overlapped: {events}"
