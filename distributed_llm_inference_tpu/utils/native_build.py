"""Build the package's C++ helpers (``native/*.cc``) into shared libraries.

One builder for both libraries (``distributed/relay.py``,
``utils/streader.py``). A library is reused only when the SHA-256 of its
source, kept in a ``.sha256`` file beside it, matches the source on disk:
the libraries are git-ignored yet travel with a copied tree, and a copy
need not keep modification times, so an mtime comparison can pass a
library built from another source. Both files are written to a
pid-suffixed temporary and ``os.replace``d in, so a concurrent process
never ``dlopen``s a half-written library.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading

__all__ = ["build_shared"]

_build_lock = threading.Lock()


def build_shared(src: str, so: str, force: bool = False) -> str:
    """Compile ``src`` → ``so`` unless ``so`` was built from this very
    source; returns ``so``. Raises ``OSError`` (no compiler) or
    ``subprocess.CalledProcessError`` (compile error)."""
    stamp = os.path.splitext(so)[0] + ".sha256"
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    with _build_lock:
        if not force and os.path.exists(so):
            try:
                with open(stamp) as f:
                    if f.read().strip() == digest:
                        return so
            except FileNotFoundError:
                pass
        tmp = f"{so}.tmp.{os.getpid()}"
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", src, "-o", tmp,
             "-pthread"],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, so)
        tmp = f"{stamp}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, stamp)
        return so
