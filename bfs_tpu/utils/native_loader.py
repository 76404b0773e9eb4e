"""Shared loader for the repo's native C++ libraries (native/*.cpp).

One place for the build-on-demand + ctypes-load logic used by the oracle
(:mod:`bfs_tpu.oracle.native`), the data loader
(:mod:`bfs_tpu.graph.native_gen`) and the Beneš router
(:mod:`bfs_tpu.graph.benes`).  pybind11 is not in the image, so the
native layer is plain C ABI + ctypes.

A library is built from its committed source into
``native/build/lib<name>-<key>.so``, where ``key`` hashes the source, the
compiler and its flags: a ``.so`` left over from another source revision
or another build recipe has another name and is never loaded.  The flags
carry no ``-march=native``, so a library built on one host is valid on
any x86-64 host that shares the checkout.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from collections.abc import Callable

#: Compiler flags of every native library (part of the build key).
CXXFLAGS = ("-O3", "-std=c++17", "-fPIC", "-shared", "-pthread")


class NativeLib:
    """Lazily built, lazily loaded shared library.

    ``so`` names the library without its build key; ``register`` is
    called once with the loaded CDLL to set restype/argtypes.  A failed
    build or load is remembered with its reason: :meth:`load` then returns
    None (callers with a NumPy path fall back) and :meth:`require` raises
    it (callers without one, like the relay layout's router)."""

    def __init__(self, src: str, so: str, register: Callable[[ctypes.CDLL], None]):
        self._src = src
        self._so_stem = so[: -len(".so")] if so.endswith(".so") else so
        self._register = register
        self._lock = threading.Lock()
        self._lib: ctypes.CDLL | None = None
        self._error: str | None = None

    def _compiler(self) -> str:
        return os.environ.get("CXX", "g++")

    def so_path(self) -> str:
        """The keyed library path for the current source and recipe."""
        h = hashlib.sha256()
        with open(self._src, "rb") as fh:
            h.update(fh.read())
        h.update("\0".join((self._compiler(), *CXXFLAGS)).encode())
        return f"{self._so_stem}-{h.hexdigest()[:16]}.so"

    def _build(self, so: str) -> None:
        os.makedirs(os.path.dirname(so), exist_ok=True)
        # Compile to a per-process temp path and publish atomically: the
        # in-process lock does not cover concurrent Python processes
        # (pytest workers), and CDLL-loading a half-written .so fails.
        tmp = f"{so}.tmp.{os.getpid()}"
        cmd = [self._compiler(), *CXXFLAGS, "-o", tmp, self._src]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)

    def load(self) -> ctypes.CDLL | None:
        with self._lock:
            if self._lib is not None or self._error is not None:
                return self._lib
            try:
                so = self.so_path()
                if not os.path.exists(so):
                    self._build(so)
                lib = ctypes.CDLL(so)
                self._register(lib)
            except subprocess.CalledProcessError as exc:
                self._error = (
                    f"building {self._src} failed: "
                    f"{exc.stderr.decode(errors='replace')[-2000:]}"
                )
                return None
            except (OSError, subprocess.SubprocessError, AttributeError) as exc:
                self._error = f"{self._src}: {exc!r}"
                return None
            self._lib = lib
            return self._lib

    def available(self) -> bool:
        return self.load() is not None

    def require(self) -> ctypes.CDLL:
        """The loaded library, or RuntimeError with why it is missing."""
        lib = self.load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {self._error}")
        return lib
