"""Process start-up and selection rules: where the compile cache lives, when
a process joins a cluster, which sparse engine "auto" means, what a failed
native build or a failed kernel does, and what ``chip_smoke.py`` accepts.

Each rule here replaced a fallback that carried on in another mode and said
so, at most, in a log line.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ compile cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.__setitem__(name, value)
    )
    return calls


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_compile_cache_placed_from_outside_is_left_alone(
    monkeypatch, config_updates, tpu_backend, tmp_path
):
    from photon_ml_tpu.utils.cachedir import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, config_updates, tpu_backend
):
    from photon_ml_tpu.utils import cachedir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # the retired variable must no longer move it
    monkeypatch.setenv("PHOTON_ML_TPU_COMPILE_CACHE", "/somewhere/else")
    first = cachedir.enable_compilation_cache()
    assert config_updates["jax_compilation_cache_dir"] == first
    assert cachedir.enable_compilation_cache() == first
    assert first == os.path.join(REPO, ".jax_compile_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_compile_cache_is_off_on_the_cpu(monkeypatch, config_updates, tmp_path):
    """A multi-device XLA:CPU program loaded back from the persistent cache
    deadlocks in jax 0.9.0; wherever it is placed, the CPU does without."""
    from photon_ml_tpu.utils.cachedir import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() is None
    assert config_updates == {"jax_enable_compilation_cache": False}


# ------------------------------------------------------------- distributed


def test_no_cluster_flag_never_reaches_jax_distributed(monkeypatch):
    """Auto-detection asks a metadata server that a sealed TPU host does not
    have; a process joins a cluster only when told to."""
    from photon_ml_tpu.parallel.multihost import initialize_distributed

    def forbidden(**kwargs):
        raise AssertionError(f"jax.distributed.initialize called: {kwargs}")

    monkeypatch.setattr(jax.distributed, "initialize", forbidden)
    assert initialize_distributed() is False


def test_cluster_flags_reach_jax_distributed(monkeypatch):
    from photon_ml_tpu.parallel.multihost import initialize_distributed

    seen = {}
    monkeypatch.setattr(jax.distributed, "initialize", lambda **kw: seen.update(kw))
    initialize_distributed("127.0.0.1:1234", num_processes=2, process_id=1)
    assert seen == {
        "coordinator_address": "127.0.0.1:1234",
        "num_processes": 2,
        "process_id": 1,
    }


# ------------------------------------------------------ sparse engine rule


def _game_data(nnz: int):
    from photon_ml_tpu.data.game_data import FeatureShard, GameData

    return GameData(
        labels=np.zeros(4, np.float32),
        feature_shards={
            "g": FeatureShard(
                rows=np.zeros(nnz, np.int64), cols=np.zeros(nnz, np.int64),
                vals=np.ones(nnz, np.float32), dim=8,
            )
        },
        id_tags={},
    )


@pytest.mark.parametrize(
    "backend,nnz,expected",
    [
        ("tpu", 1 << 20, "fused"),
        ("tpu", (1 << 20) - 1, "ell"),
        ("cpu", 1 << 20, "ell"),
    ],
)
def test_auto_engine_is_chosen_from_backend_and_size(
    monkeypatch, backend, nnz, expected
):
    from photon_ml_tpu.ops import features, fused_perm, sparse_perm

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    built = []
    monkeypatch.setattr(fused_perm, "from_coo", lambda *a, **k: built.append("fused"))
    monkeypatch.setattr(sparse_perm, "from_coo", lambda *a, **k: built.append("benes"))
    monkeypatch.setattr(
        features, "from_scipy_like", lambda *a, **k: built.append("ell")
    )
    _game_data(nnz).sparse_features("g", engine="auto")
    assert built == [expected]


def test_fused_kernel_that_cannot_compile_raises(monkeypatch, rng):
    """Where the engine believes it is on a TPU, a kernel the backend
    refuses is an error — not a quiet switch to the XLA executor. (Here the
    refusal is the CPU backend's: it has no Mosaic.)"""
    from photon_ml_tpu.ops import fused_perm

    n, d = 64, 50
    rows = rng.integers(0, n, 300)
    cols = rng.integers(0, d, 300)
    vals = rng.standard_normal(300).astype(np.float32)
    feats = fused_perm.from_coo(rows, cols, vals, (n, d), plan_cache="")
    w = jnp.ones(d, jnp.float32)
    assert not feats._fused_ok()
    feats.matvec(w)  # off the TPU: the XLA executor, by design

    monkeypatch.setattr(fused_perm, "pallas_available", lambda: True)
    assert feats._fused_ok()
    with pytest.raises(Exception, match="(?i)interpret|mosaic|tpu"):
        jax.block_until_ready(feats.matvec(w))


def test_every_fused_plan_has_a_recursion_level(rng):
    """The fused executor has no unfused branch for plans too small to
    recurse: they are padded to 128*128 slots instead."""
    from photon_ml_tpu.ops import fused_perm

    feats = fused_perm.from_coo(
        np.array([0, 1]), np.array([1, 0]), np.ones(2, np.float32), (2, 2),
        plan_cache="",
    )
    assert feats.size == fused_perm.MIN_FUSED_SIZE
    assert fused_perm.parse_plan(feats.plan).descents


# ----------------------------------------------------------- native builds


def test_native_library_is_keyed_by_source_and_flags(tmp_path):
    from photon_ml_tpu.utils.nativelib import build_and_load, library_path

    src = tmp_path / "answer.cpp"
    src.write_text('extern "C" int answer() { return 41; }\n')
    first = library_path(src)
    assert build_and_load(src).answer() == 41
    assert first.exists()

    src.write_text('extern "C" int answer() { return 42; }\n')
    second = library_path(src)
    assert second != first and second != library_path(src, ldflags=("-lz",))
    # same mtime second or not, the new source is what loads
    assert build_and_load(src).answer() == 42
    assert second.exists() and not first.exists()


def test_native_build_failure_raises_with_compiler_output(tmp_path):
    from photon_ml_tpu.utils.nativelib import NativeBuildError, build_and_load

    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    with pytest.raises(NativeBuildError, match="error"):
        build_and_load(src)
    assert not list(tmp_path.glob("*.so"))


def test_missing_toolchain_is_the_only_fallback(tmp_path, monkeypatch):
    from photon_ml_tpu.utils import nativelib

    def no_gxx(*args, **kwargs):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(nativelib.subprocess, "run", no_gxx)
    src = tmp_path / "answer.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    assert nativelib.build_and_load(src) is None


def test_large_plan_without_native_router_raises(monkeypatch):
    from photon_ml_tpu.ops import routing

    monkeypatch.setattr(routing, "_load_native", lambda: None)
    # small plans still route in numpy
    routing.build_plan(np.random.default_rng(0).permutation(256))
    n = routing._NUMPY_COLOR_MAX_EDGES * 2
    with pytest.raises(RuntimeError, match="native Euler colorer"):
        routing.euler_color(
            np.zeros(n, np.int32), np.zeros(n, np.int32), 128, n // 128, n // 128
        )


# ---------------------------------------------------------- chip_smoke.py


def _chip_smoke(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_chip_smoke_without_a_tpu_fails_and_prints_nothing():
    """An inherited JAX_PLATFORMS=cpu is a failure, not a mode."""
    proc = _chip_smoke(timeout=300)
    assert proc.returncode not in (0, None)
    assert proc.stdout == ""
    assert "not a TPU" in proc.stderr


@pytest.mark.slow
def test_chip_smoke_rehearsal_runs_every_phase():
    proc = _chip_smoke("--platform", "cpu", "--size", "tiny", timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("platform=cpu ")
    assert any(line.startswith("REHEARSAL") for line in lines)
    for phase in ("native", "engine", "train", "cli", "multichip"):
        assert any(line.startswith(f"phase {phase}: ok") for line in lines), phase
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
        "rehearsal": True,
    }
