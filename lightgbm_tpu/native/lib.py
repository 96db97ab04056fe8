"""ctypes loader for the native C++ helper library.

The reference is a pure C++ program; in this framework the device compute is
XLA and the host runtime keeps native C++ for the text-parsing hot path
(utils/text_reader.h + parser.hpp equivalents).  Built by
``lightgbm_tpu/native/build.sh`` (g++ -O3 -fopenmp -shared).
"""
from __future__ import annotations

import ctypes
import os
from typing import List, Optional

import numpy as np

_LIB = None
_TRIED = False


def _lib_path() -> str:
    return os.path.join(os.path.dirname(__file__), "liblgbm_native.so")


def _build() -> str:
    """Compile the helper at first use (PipelineReader has no Python
    analog fast enough for Higgs-scale CSVs; a one-time ~3 s g++ build
    makes the native path the default).  Returns "" on success, else the
    reason — the caller logs it, and text parsing then takes the slower
    pandas / exact tiers."""
    import shutil
    import subprocess
    if shutil.which("g++") is None:
        return "no g++ on PATH"
    src = _src_path()
    if not os.path.exists(src):
        return "source %s missing" % src
    # compile to a temp path and rename into place: another process may
    # race first use, and a killed build must not leave a corrupt .so
    # that permanently disables the native path
    tmp = _lib_path() + ".%d.tmp" % os.getpid()
    try:
        subprocess.run(
            ["g++", "-O3", "-fopenmp", "-shared", "-fPIC", "-std=c++17",
             src, "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _lib_path())
        return ""
    except Exception as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        stderr = getattr(e, "stderr", b"") or b""
        return ("g++ build failed: %s %s"
                % (type(e).__name__, stderr.decode(errors="replace")[-300:]))


def _src_path() -> str:
    return os.path.join(os.path.dirname(__file__), "src", "lgbm_native.cpp")


def _load():
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        from ..utils import log
        path = _lib_path()
        stale = False
        try:
            # rebuild when the source is newer than the cached .so (new
            # exported symbols must not silently disappear behind a stale
            # binary)
            stale = (os.path.exists(path)
                     and os.path.getmtime(_src_path())
                     > os.path.getmtime(path))
        except OSError:
            pass
        why = ""
        if not os.path.exists(path) or stale:
            why = _build()
        if os.path.exists(path):
            try:
                lib = ctypes.CDLL(path)
                lib.parse_delimited.restype = ctypes.c_int
                lib.parse_delimited.argtypes = [
                    ctypes.c_char_p, ctypes.c_longlong, ctypes.c_char,
                    ctypes.c_longlong, ctypes.c_longlong,
                    ctypes.POINTER(ctypes.c_double),
                ]
                _LIB = lib
            except Exception as e:  # bad/incomplete .so: missing symbols too
                _LIB = None
                why = why or "cannot load %s: %r" % (path, e)
        # say which text-parser tier this process runs on: the slower
        # tiers are correct but a Higgs-scale load on them is a finding
        if _LIB is not None:
            log.info("text parser tier: native (%s)" % path)
        else:
            log.warning("text parser tier: pandas/exact — native helper "
                        "unavailable (%s)" % (why or "no library built"))
    return _LIB


def loaded_path() -> Optional[str]:
    """Path of the loaded helper library, or None on the slower tiers."""
    return _lib_path() if _load() is not None else None


def available() -> bool:
    return _load() is not None


def set_num_threads(n: int) -> None:
    """Cap the native OpenMP pool (Application ctor parity,
    application.cpp:30-34).  No-op when the library is unavailable or the
    cached .so predates the symbol."""
    lib = _load()
    if lib is None or n <= 0:
        return
    try:
        lib.set_num_threads(ctypes.c_int(int(n)))
    except AttributeError:
        pass


def parse_delimited(lines: List[str], delimiter: str) -> Optional[np.ndarray]:
    """Parse uniform delimited lines into a float64 matrix, or None to make
    the caller fall back to the Python path."""
    lib = _load()
    if lib is None or not lines:
        return None
    ncols = lines[0].count(delimiter) + 1
    nrows = len(lines)
    blob = ("\n".join(lines) + "\n").encode()
    out = np.empty((nrows, ncols), dtype=np.float64)
    rc = lib.parse_delimited(
        blob, len(blob), delimiter.encode()[0] if delimiter != "\t" else 9,
        nrows, ncols,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if rc != 0:
        return None
    return out
