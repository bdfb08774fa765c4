"""One hardened build-and-load path for the in-tree C++ components.

Every native module (Euler-coloring router, off-heap index store, columnar
Avro decoder, radix argsort) needs the same thing: compile ``<name>.cpp``
next to it into a shared library when none matches the source, then ``CDLL``
it. Doing that safely requires building to a temp file and atomically
renaming — concurrent builders (multihost launches, pytest workers) must
never CDLL or cache a half-written .so. This helper is that pattern, once.

The library is named after a hash of its source and build flags
(``_<name>.<hash>.so``), so an artefact copied from another checkout or left
by older source is never loaded in place of the committed ``.cpp``.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Optional, Sequence

logger = logging.getLogger(__name__)

_DEFAULT_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-pthread")


class NativeBuildError(RuntimeError):
    """The toolchain is present and refused the committed source."""


def library_path(
    src: Path,
    flags: Sequence[str] = _DEFAULT_FLAGS,
    ldflags: Sequence[str] = (),
) -> Path:
    """``_<name>.<hash of source + flags>.so`` next to ``src``."""
    h = hashlib.sha1(src.read_bytes())
    h.update("\0".join((*flags, "--", *ldflags)).encode())
    return src.with_name(f"_{src.stem}.{h.hexdigest()[:12]}.so")


def build_and_load(
    src: Path,
    flags: Sequence[str] = _DEFAULT_FLAGS,
    ldflags: Sequence[str] = (),
) -> Optional[ctypes.CDLL]:
    """Compile ``src`` to :func:`library_path` (if missing) and CDLL it.

    ``ldflags`` (e.g. ``("-lz",)``) are placed AFTER the source on the
    command line — with ``--as-needed`` linkers a library named before the
    objects that use it is silently dropped.

    Returns None only when there is no ``g++`` on this host — callers keep a
    pure-Python fallback for that. A compiler that runs and fails raises
    :class:`NativeBuildError` carrying its stderr. Never leaves a
    half-written .so visible at the library path.
    """
    lib_path = library_path(src, flags, ldflags)
    if not lib_path.exists():
        fd, tmp = tempfile.mkstemp(
            suffix=".so", dir=str(lib_path.parent), prefix=f"._{src.stem}_"
        )
        os.close(fd)
        try:
            subprocess.run(
                ["g++", *flags, "-o", tmp, str(src), *ldflags],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, str(lib_path))
        except FileNotFoundError:
            logger.warning(
                "no g++ on this host: %s runs its pure-Python fallback",
                src.name,
            )
            return None
        except subprocess.CalledProcessError as e:
            raise NativeBuildError(
                f"g++ failed on {src.name} (exit {e.returncode}):\n"
                + e.stderr.decode("utf-8", "replace")
            ) from None
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        # libraries built from earlier revisions of this source
        for old in src.parent.glob(f"_{src.stem}.*.so"):
            if old != lib_path:
                old.unlink(missing_ok=True)
    return ctypes.CDLL(str(lib_path))
