"""Build the native runtime (g++ → shared library), keyed by source hash.

Replaces the reference's SCons build of the storage engine
(``SConstruct``); one translation unit keeps it dependency-free.

The library's file name carries the SHA-256 of the source and the
compile command, so the ``.so`` that loads is always the one the
present ``native/<name>.cpp`` produces: a stale or foreign binary left
in ``native/build/`` (git-ignored, but copied along with a working
tree) can never be picked up by mtime.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_lock = threading.Lock()

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_OUT_DIR = os.path.join(_NATIVE_DIR, "build")
_CXX = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread"]


class NativeBuildError(RuntimeError):
    pass


def _source(name: str) -> str:
    return os.path.join(_NATIVE_DIR, f"{name}.cpp")


def library_path(name: str = "pagestore") -> str:
    """Where the library for the PRESENT ``native/<name>.cpp`` lives
    (whether or not it has been built yet)."""
    with open(_source(name), "rb") as f:
        digest = hashlib.sha256(
            " ".join(_CXX).encode() + b"\0" + f.read()).hexdigest()[:16]
    return os.path.join(_OUT_DIR, f"lib{name}-{digest}.so")


def build_library(name: str = "pagestore") -> str:
    """Compile ``native/<name>.cpp`` unless the library for exactly
    this source already exists; returns the .so path."""
    src = _source(name)
    out = library_path(name)
    with _lock:
        if os.path.exists(out):
            return out
        os.makedirs(_OUT_DIR, exist_ok=True)
        # compile to a private name, then rename: a concurrent process
        # (daemon + client on one checkout) never loads a half-written
        # library
        tmp = f"{out}.{os.getpid()}.tmp"
        try:
            proc = subprocess.run(_CXX + [src, "-o", tmp],
                                  capture_output=True, text=True)
        except OSError as e:  # no compiler on PATH
            raise NativeBuildError(f"native build failed: {e}") from e
        if proc.returncode != 0:
            raise NativeBuildError(
                f"native build failed:\n{proc.stderr[-2000:]}")
        os.replace(tmp, out)
        return out
