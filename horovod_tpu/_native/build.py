"""Build the native library on demand.

Parity note: the reference compiles its native core at pip-install time
via setup.py→CMake (SURVEY.md §2.7); this repo has no install step in
the loop, so the equivalent moment is "first import" — we shell out to
g++ directly (or ``make -C csrc``) and cache the result next to this
file. Staleness is mtime-based against the csrc/ sources.
"""

from __future__ import annotations

import os
import subprocess
import tempfile
from typing import List, Optional

from ..common.logging import get_logger

_log = get_logger("native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.normpath(os.path.join(_HERE, "..", "..", "csrc"))
_LIB = os.path.join(_HERE, "libhvd_native.so")

_SOURCES = [
    "timeline.cc",
    "adasum.cc",
    "gp.cc",
    "pack.cc",
    "sha256.cc",
    "kvstore.cc",
    "npyio.cc",
]


def _source_paths() -> List[str]:
    return [os.path.join(_CSRC, s) for s in _SOURCES]


def _stale() -> bool:
    if not os.path.exists(_LIB):
        return True
    lib_mtime = os.path.getmtime(_LIB)
    deps = _source_paths() + [
        os.path.join(_CSRC, "export.h"),
        os.path.join(_CSRC, "sha256.h"),
    ]
    return any(
        os.path.exists(p) and os.path.getmtime(p) > lib_mtime for p in deps
    )


def _build(sources: List[str], out: str, extra: List[str]) -> Optional[str]:
    """Compile ``sources`` into the shared object ``out``; returns the
    path on success, the existing artifact (if any) on failure. Build to
    a temp name then os.replace: concurrent builders (e.g.
    pytest-launched worker processes) each produce a complete .so and
    the last rename wins — nobody ever dlopens a half-written file."""
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    cmd = [
        os.environ.get("CXX", "g++"),
        "-std=c++17", "-O3", "-fPIC", "-Wall", "-pthread",
        "-fvisibility=hidden", "-shared",
        *extra,
        *sources,
        "-o", tmp,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=300, cwd=_CSRC
        )
        os.replace(tmp, out)
        return out
    except (subprocess.SubprocessError, OSError) as e:
        if os.path.exists(tmp):
            os.unlink(tmp)
        stale = os.path.exists(out)
        # a missing compiler (FileNotFoundError) or a failed compile is
        # said out loud: the callers carry on with Python fallbacks, and
        # nobody should have to guess which half they are running
        stderr = getattr(e, "stderr", None) or b""
        _log.warning(
            "building %s with %r failed: %s %s — %s",
            os.path.basename(out), cmd[0], e,
            stderr.decode(errors="replace")[-800:],
            "loading the stale copy" if stale
            else "the pure-Python fallbacks serve",
        )
        return out if stale else None


def lib_path() -> Optional[str]:
    """Path to an up-to-date libhvd_native.so, building it if needed.
    Returns None when the sources are missing or the build fails."""
    if not _stale():
        return _LIB
    if not all(os.path.exists(p) for p in _source_paths()):
        return _LIB if os.path.exists(_LIB) else None
    return _build(_source_paths(), _LIB, [])


# ------------------------------------------------- CPython extension half

def _ext_suffix() -> str:
    """ABI-tagged extension suffix (e.g. .cpython-311-x86_64-linux-gnu.so)
    so checkouts shared between interpreters never load an extension
    compiled against another version's headers."""
    import importlib.machinery

    return importlib.machinery.EXTENSION_SUFFIXES[0]


_EXT = os.path.join(_HERE, "_hvd_cext" + _ext_suffix())
_EXT_SRC = os.path.join(_CSRC, "cext.cc")


def _ext_stale() -> bool:
    if not os.path.exists(_EXT):
        return True
    return (
        os.path.exists(_EXT_SRC)
        and os.path.getmtime(_EXT_SRC) > os.path.getmtime(_EXT)
    )


def ext_path() -> Optional[str]:
    """Path to the up-to-date ``_hvd_cext`` CPython extension module
    (csrc/cext.cc), building it against this interpreter's headers on
    first call. A plain ``.so`` suffix imports fine on Linux
    (``importlib.machinery.EXTENSION_SUFFIXES`` ends with ``.so``);
    undefined Python symbols resolve from the host process at import,
    exactly like a setuptools-built extension."""
    if not _ext_stale():
        return _EXT
    if not os.path.exists(_EXT_SRC):
        return _EXT if os.path.exists(_EXT) else None
    import sysconfig

    include = sysconfig.get_paths().get("include")
    if not include or not os.path.exists(
        os.path.join(include, "Python.h")
    ):
        return _EXT if os.path.exists(_EXT) else None
    return _build([_EXT_SRC], _EXT, ["-I", include])
