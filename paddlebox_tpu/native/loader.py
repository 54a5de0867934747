"""Shared loader for the native C++ helpers.

One place for the build-on-first-use / cache / PBTPU_NO_NATIVE_BUILD logic
used by every binding (slot parser, key index). Each binding supplies the
library filename (also its make target) and a `configure(lib)` that
declares ctypes signatures.

The library is always rebuilt through ``make`` before its first load in a
process — a no-op when the ``.so`` is newer than its source, a rebuild
when it is stale — so a checkout never runs yesterday's binary against
today's ``*.cc``. The ``.so`` files are build outputs (``.gitignore``); a
fresh checkout builds them here, on first use.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Callable

_HERE = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache: dict[str, ctypes.CDLL | None] = {}
_log = logging.getLogger(__name__)


def _make(target: str) -> str | None:
    """Bring `target` up to date; returns why it could not, or None."""
    try:
        r = subprocess.run(["make", "-C", _HERE, "-s", target],
                           capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"make {target}: {e!r}"
    if r.returncode != 0:
        return f"make {target} exited {r.returncode}: {r.stderr[-400:]}"
    return None


def load_native(lib_filename: str,
                configure: Callable[[ctypes.CDLL], None]
                ) -> ctypes.CDLL | None:
    """Load a native lib, building it first when it is missing or older
    than its source. Returns None when it is unavailable — callers then
    take their Python paths — and says so once per library, by name
    (``PBTPU_NO_NATIVE_BUILD=1`` skips the build and the load: the
    opt-out the fallback tests use)."""
    with _lock:
        if lib_filename in _cache:
            return _cache[lib_filename]
        path = os.path.join(_HERE, lib_filename)
        if os.environ.get("PBTPU_NO_NATIVE_BUILD"):
            why: str | None = "PBTPU_NO_NATIVE_BUILD is set"
        else:
            why = _make(lib_filename)
        lib = None
        if why is None:
            try:
                lib = ctypes.CDLL(path)
                configure(lib)
            except (OSError, AttributeError) as e:
                lib, why = None, f"loading {path}: {e!r}"
        if lib is None:
            _log.warning(
                "native_fallback: %s unavailable (%s); the pure-Python "
                "path runs instead — several times slower on the ingest "
                "and pack hot paths", lib_filename, why)
        _cache[lib_filename] = lib
        return lib
