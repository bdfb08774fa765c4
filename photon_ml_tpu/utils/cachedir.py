"""On-disk cache locations: JAX's compilation cache and per-uid tempdirs.

The compilation cache's path is part of its key, so it must not move between
runs: it is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed
directory inside the checkout. The routing-plan cache (~0.1 GB per large
plan) stays out of the checkout, in a per-uid tempdir.
"""

from __future__ import annotations

import os
import stat
import tempfile
from pathlib import Path
from typing import Optional

# <checkout>/.jax_compile_cache, listed in .gitignore and .chiprunignore
COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_compile_cache"
)


def per_uid_cache_dir(name: str) -> Optional[str]:
    """``$TMPDIR/<name>_<uid>`` created 0700, or None when unavailable.

    Refused if owned by someone else or writable by group/other: a
    pre-planted directory in the sticky shared tempdir must never be
    trusted."""
    uid = os.getuid() if hasattr(os, "getuid") else 0
    path = os.path.join(tempfile.gettempdir(), f"{name}_{uid}")
    try:
        os.makedirs(path, mode=0o700, exist_ok=True)
        st = os.stat(path)
        if st.st_uid != uid or (st.st_mode & (stat.S_IWGRP | stat.S_IWOTH)):
            return None
    except OSError:
        return None
    return path


def enable_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory, or
    None on the CPU backend, where it is turned off.

    When ``JAX_COMPILATION_CACHE_DIR`` is set the caller has placed the
    cache and no directory is set in code (JAX reads the variable itself).
    Otherwise the cache is :data:`COMPILE_CACHE_DIR`. Every CLI and
    ``chip_smoke.py`` call this before their first compile. It looks at
    the backend, so a process that joins a cluster does that first.

    Off on the CPU: in jax 0.9.0 a multi-device XLA:CPU program loaded back
    from the cache deadlocks in its first all-reduce and the process aborts
    (``chip_smoke.py --platform cpu --phases multichip`` twice in one
    directory reproduced it every time), and CPU compiles are cheap.
    """
    import jax

    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compilation that takes meaningful time, not only the very
    # slow ones (the default is 1 s; GLM solves compile in 2-40 s on a TPU)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
