"""Robust lazy build of the native engine.

Reference parity: the reference compiles its C++ core at ``pip install``
time (setup.py:244-465).  Here the install-time build (setup.py) is the
primary path; this module is the fallback that makes a source checkout or
a compiler-less install self-healing: the first ``hvd.init()`` (or an
explicit :func:`ensure_native_lib`) compiles ``libhorovod_core.so`` from
the shipped sources with ``make``.

Build location: next to the sources when that directory is writable
(source checkout), else ``$XDG_CACHE_HOME/horovod_tpu`` (installed
site-packages are often read-only).  A file lock serializes concurrent
builders (the launcher starts N ranks at once).

A library is trusted only when the stamp beside it
(``libhorovod_core.so.digest``) equals the digest of the sources on
disk: ``*.so`` and ``*.o`` are not tracked by git, so a copied tree can
carry a library built from other sources, with mtimes ``make`` cannot
judge.  On a mismatch everything is rebuilt (``make -B``) and re-stamped.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess
import tempfile
from typing import Optional

__all__ = ["ensure_native_lib", "native_lib_path"]

_LIB_NAME = "libhorovod_core.so"
_build_failed = False  # per-process: don't retry a failing make on every init


def _cpp_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "cpp"
    )


def _source_digest() -> str:
    """Hash of the shipped C++ sources — keys the cache so an upgraded
    package never loads a stale engine built from older sources."""
    h = hashlib.sha256()
    cpp = _cpp_dir()
    try:
        names = sorted(
            f for f in os.listdir(cpp)
            if f.endswith((".cc", ".h")) or f == "Makefile"
        )
        for name in names:
            with open(os.path.join(cpp, name), "rb") as f:
                h.update(name.encode())
                h.update(f.read())
    except OSError:
        pass
    return h.hexdigest()[:16]


def _cache_dir() -> str:
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    return os.path.join(base, "horovod_tpu", _source_digest())


def _is_current(lib: str) -> bool:
    """True when ``lib`` exists and its stamp names the sources on disk."""
    try:
        with open(lib + ".digest") as f:
            stamp = f.read().strip()
    except OSError:
        return False
    return os.path.exists(lib) and stamp == _source_digest()


def native_lib_path() -> Optional[str]:
    """Path of an engine library built from the current sources, or None."""
    for candidate in (
        os.path.join(_cpp_dir(), _LIB_NAME),
        os.path.join(_cache_dir(), _LIB_NAME),
    ):
        if _is_current(candidate):
            return candidate
    return None


def ensure_native_lib(timeout: float = 300.0) -> Optional[str]:
    """Return the engine library path, building it with ``make`` if needed.

    Returns None when no build is possible (no ``make``/compiler); callers
    fall back to pure-Python single-process mode.
    """
    global _build_failed
    path = native_lib_path()
    if path is not None:
        return path
    if _build_failed or shutil.which("make") is None:
        return None

    cpp = _cpp_dir()
    if os.access(cpp, os.W_OK):
        build_dir, out = cpp, os.path.join(cpp, _LIB_NAME)
    else:
        # Installed read-only: copy sources to the cache and build there.
        cache = _cache_dir()
        os.makedirs(cache, exist_ok=True)
        build_dir = os.path.join(cache, "build")
        if not os.path.isdir(build_dir):
            shutil.copytree(cpp, build_dir)
        out = os.path.join(cache, _LIB_NAME)

    lock_path = os.path.join(
        tempfile.gettempdir(), f"horovod_tpu_build_{os.getuid()}.lock"
    )
    with open(lock_path, "w") as lock:
        try:
            import fcntl

            fcntl.flock(lock, fcntl.LOCK_EX)
        except ImportError:  # non-POSIX: best effort, races rebuild harmlessly
            pass
        # Another rank may have finished the build while we waited.
        path = native_lib_path()
        if path is not None:
            return path
        try:
            # -B: object files beside the sources may predate them with
            # mtimes a copy has reset.
            subprocess.run(
                ["make", "-B", "-C", build_dir],
                check=True,
                capture_output=True,
                timeout=timeout,
            )
        except (OSError, subprocess.CalledProcessError,
                subprocess.TimeoutExpired):
            _build_failed = True
            return None
        built = os.path.join(build_dir, _LIB_NAME)
        if built != out and os.path.exists(built):
            shutil.copy2(built, out)
        # Stamp last, atomically: a reader that sees the stamp sees the
        # finished library.
        with open(out + ".digest.tmp", "w") as f:
            f.write(_source_digest() + "\n")
        os.replace(out + ".digest.tmp", out + ".digest")
    return native_lib_path()
