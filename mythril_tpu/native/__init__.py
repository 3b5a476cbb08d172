"""Native runtime components: C++ sources and the shared library built
from them by this directory's Makefile.

`library_path()` is what every ctypes loader asks for the library: it
builds the library when it is missing or older than a source, so a
checkout never loads a stale binary, and never falls back to a slower
pure-Python path without having tried the build.
"""

from __future__ import annotations

import subprocess
import threading
from pathlib import Path

_DIR = Path(__file__).resolve().parent
LIBRARY = _DIR / "libmythril_native.so"
_BUILD_LOCK = threading.Lock()


def _stale() -> bool:
    if not LIBRARY.exists():
        return True
    built = LIBRARY.stat().st_mtime
    sources = [_DIR / "Makefile", *_DIR.glob("*.cpp")]
    return any(src.stat().st_mtime > built for src in sources)


def library_path() -> str:
    """Path of the built library, building it first when needed.
    Raises OSError when the build fails (no compiler, compile error)."""
    with _BUILD_LOCK:
        if _stale():
            try:
                subprocess.run(
                    ["make", "-s", "-C", str(_DIR)],
                    check=True,
                    capture_output=True,
                    text=True,
                )
            except FileNotFoundError as why:
                raise OSError(f"native build needs make: {why}") from why
            except subprocess.CalledProcessError as why:
                raise OSError(
                    f"native build failed: {why.stderr.strip()[-2000:]}"
                ) from why
    return str(LIBRARY)
