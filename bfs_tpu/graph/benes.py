"""Beneš routing networks: compile a static permutation to butterfly masks.

The relay engine (see :mod:`bfs_tpu.graph.relay`) moves per-edge frontier
bits from src-grouped to dst-grouped order every superstep.  That move is a
fixed permutation, so it is compiled ONCE into a Beneš network — 2·log2(N)-1
stages of conditional pair swaps — whose control masks are computed by the
native router (native/benes.cpp) and applied on device as pure elementwise
ops over bit-packed int32 words (:func:`bfs_tpu.ops.relay.apply_benes`).

Conventions shared with the C++ router and the XLA applier:
  * stage ``s`` of a size-``N=2^k`` network has pair distance
    ``N >> (s+1)`` for ``s < k`` and ``N >> (2k-1-s)`` after;
  * a stage swaps ``x[i] <-> x[i+d]`` iff mask bit ``i`` is set, mask bits
    stored only at the lower index of each pair;
  * bits pack little-endian into uint32 words;
  * the network computes ``y[j] = x[perm[j]]``.
"""

from __future__ import annotations

import atexit
import contextlib
import ctypes
import os
import signal
import threading

import numpy as np

from .. import knobs
from ..utils.native_loader import NativeLib

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _register(lib: ctypes.CDLL) -> None:
    lib.benes_route.restype = ctypes.c_int32
    lib.benes_route.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
        ctypes.c_int32,
    ]
    lib.benes_route_i32.restype = ctypes.c_int32
    lib.benes_route_i32.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
    ]
    lib.benes_route_i32_v2.restype = ctypes.c_int32
    lib.benes_route_i32_v2.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
        ctypes.c_int32,
    ]


_LIB = NativeLib(
    src=os.path.join(_REPO_ROOT, "native", "benes.cpp"),
    so=os.path.join(_REPO_ROOT, "native", "build", "libbenes.so"),
    register=_register,
)


def native_available() -> bool:
    return _LIB.available()


def require_native() -> None:
    """Raise RuntimeError, with the build error, when the router is
    missing: the relay layout has no other router on the host."""
    _LIB.require()


def num_stages(n: int) -> int:
    return 2 * (int(n).bit_length() - 1) - 1


def stage_distance(n: int, s: int) -> int:
    k = int(n).bit_length() - 1
    return n >> (s + 1) if s < k else n >> (2 * k - 1 - s)


def route(perm: np.ndarray, *, bit_major: bool = False) -> np.ndarray:
    """Compute Beneš masks for ``perm`` (``y[j] = x[perm[j]]``).

    ``len(perm)`` must be a power of two >= 2.  Returns
    ``uint32[num_stages, n/32]`` packed masks (``n//32`` >= 1).
    ``bit_major`` packs mask element e at (word ``e % nw``, bit ``e // nw``)
    — the layout :func:`bfs_tpu.ops.relay.apply_benes` consumes; the default
    word-major layout matches :func:`apply_network_numpy`'s default.
    """
    lib = _LIB.require()
    perm = np.ascontiguousarray(perm, dtype=np.int64)
    n = int(perm.shape[0])
    if n < 2 or n & (n - 1):
        raise ValueError(f"network size {n} is not a power of two >= 2")
    words = max(n // 32, 1)
    masks = np.zeros(num_stages(n) * words, dtype=np.uint32)
    if lib.benes_route(n, perm, masks, int(bit_major)) != 0:
        raise ValueError("perm is not a bijection")
    return masks.reshape(num_stages(n), words)


_NR_HUGEPAGES = "/proc/sys/vm/nr_hugepages"


def _reserve_hugepages(n: int) -> int | None:
    """Best-effort explicit 2MB huge-page reservation for the native
    router's working set (a/b/inv = 20 bytes/slot; native/benes.cpp
    ``HugeBuf`` prefers ``mmap(MAP_HUGETLB)``).  The build VM's kernel
    grants ZERO transparent huge pages in madvise mode (verified via
    smaps_rollup), so without an explicit pool the route's pointer chase
    pays a 4KB-page walk on nearly every random access — measured +21-26%
    route throughput with the pool.

    Raises the SYSTEM-WIDE ``/proc/sys/vm/nr_hugepages`` sysctl (~5 GB at
    net 2^28); :func:`route_std` restores the previous value after routing
    (the router's hugetlb mappings are freed by then), with an
    atexit + SIGTERM fallback restore for abnormal exits (ADVICE r4).  A
    SIGKILL / OOM-kill can still strand the reservation — recovery is
    ``echo 0 > /proc/sys/vm/nr_hugepages`` (or the prior value).  Returns
    the prior value when the sysctl was raised, else None.  Set
    ``BFS_TPU_HUGEPAGES=0`` to skip entirely (the router falls back to 4KB
    pages).  Needs root; silently a no-op without it."""
    if not knobs.get("BFS_TPU_HUGEPAGES"):
        return None
    try:
        pages = (20 * n + (2 << 20) - 1) // (2 << 20) + 16
        with open(_NR_HUGEPAGES, "r+") as f:
            prev = int(f.read())
            if prev < pages:
                f.seek(0)
                f.write(str(pages))
                return prev
    except (OSError, ValueError):
        pass
    return None


def _restore_hugepages(prev: int | None) -> None:
    if prev is None:
        return
    try:
        with open(_NR_HUGEPAGES, "w") as f:
            f.write(str(prev))
    except (OSError, ValueError):
        pass


# One outstanding raised-sysctl value per process, guarded by a reentrant
# lock (ADVICE r4: the bare _HOLD_DEPTH/_HOLD_PREV globals were not
# thread-safe, and nothing restored the sysctl on SIGTERM/interpreter
# exit).  _ACTIVE_PREV is the value to write back; the atexit + SIGTERM
# hooks restore it on abnormal exits.
_HP_LOCK = threading.RLock()
_HOLD_DEPTH = 0
_HOLD_ACQUIRED = False  # the hold (not a frame) owns an acquired raise
_ACTIVE_PREV: int | None = None
_EMERGENCY_INSTALLED = False


def _emergency_restore(*_args) -> None:
    # Signal-handler-safe: a plain swap + file write, no locks.
    global _ACTIVE_PREV
    prev, _ACTIVE_PREV = _ACTIVE_PREV, None
    _restore_hugepages(prev)


def _install_emergency_restore() -> None:
    global _EMERGENCY_INSTALLED
    if _EMERGENCY_INSTALLED:
        return
    _EMERGENCY_INSTALLED = True
    atexit.register(_emergency_restore)
    try:
        prev_handler = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            _emergency_restore()
            if callable(prev_handler):
                prev_handler(signum, frame)
            elif prev_handler is signal.SIG_IGN:
                pass  # preserve the process's ignored-TERM disposition
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                signal.raise_signal(signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):
        pass  # signal handlers are only settable from the main thread


def _acquire_hugepages(n: int) -> bool:
    """Raise the sysctl for an ``n``-slot route.  Returns True iff THIS
    call raised it (the caller must then :func:`_release_hugepages`)."""
    global _ACTIVE_PREV
    with _HP_LOCK:
        if _ACTIVE_PREV is not None:
            return False  # another caller in this process holds the raise
        prev = _reserve_hugepages(n)
        if prev is None:
            return False
        _ACTIVE_PREV = prev
        _install_emergency_restore()
        return True


def _release_hugepages() -> None:
    global _ACTIVE_PREV
    with _HP_LOCK:
        prev, _ACTIVE_PREV = _ACTIVE_PREV, None
    _restore_hugepages(prev)


@contextlib.contextmanager
def hugepage_reservation(n: int):
    """Hold ONE huge-page reservation across several :func:`route_std`
    calls (a layout build routes the net and then the vperm): repeated
    reserve/free cycles pay kernel compaction per route and the later
    reservations can fall short on a fragmented allocator, silently losing
    the 2MB-page speedup.  ``route_std`` skips its own per-call
    reservation while a hold is active.  Same ``n >= 2^24`` gate as
    route_std's own reservation: small builds (test graphs) stay sysctl
    no-ops."""
    global _HOLD_DEPTH, _HOLD_ACQUIRED
    with _HP_LOCK:
        if _HOLD_DEPTH == 0 and n >= (1 << 24):
            # Ownership lives in the shared hold state, NOT this frame:
            # with overlapping holds from different threads the acquiring
            # frame may exit first, and whichever frame brings the depth
            # back to zero must do the release.
            _HOLD_ACQUIRED = _acquire_hugepages(n)
        _HOLD_DEPTH += 1
    try:
        yield
    finally:
        with _HP_LOCK:
            _HOLD_DEPTH -= 1
            release = _HOLD_DEPTH == 0 and _HOLD_ACQUIRED
            if release:
                _HOLD_ACQUIRED = False
        if release:
            _release_hugepages()


def route_std(perm: np.ndarray, *, trusted: bool = False) -> np.ndarray:
    """Layout-v4 router: Beneš masks in STANDARD (word-major) packing — mask
    element ``e`` at word ``e >> 5``, bit ``e & 31`` — via the iterative int32
    native router (``benes_route_i32``).  This is the packing the v4 device
    kernels consume directly; no transpose pass.  ``len(perm)`` must be a
    power of two in [32, 2^30]."""
    lib = _LIB.require()
    perm = np.ascontiguousarray(perm, dtype=np.int32)
    n = int(perm.shape[0])
    if n < 32 or n & (n - 1):
        raise ValueError(f"network size {n} is not a power of two >= 32")
    acquired = n >= (1 << 24) and _acquire_hugepages(n)
    try:
        words = n // 32
        masks = np.zeros(num_stages(n) * words, dtype=np.uint32)
        rc = lib.benes_route_i32_v2(n, perm, masks, int(trusted))
    finally:
        if acquired:
            _release_hugepages()
    if rc == -2:
        raise MemoryError(
            f"native router could not allocate its ~{20 * n >> 20} MiB "
            "working set"
        )
    if rc != 0:
        raise ValueError("perm is not a bijection")
    return masks.reshape(num_stages(n), words)


def pad_perm(perm_partial: np.ndarray, n: int, used_inputs: np.ndarray) -> np.ndarray:
    """Complete a partial mapping to a bijection on ``n`` slots.

    ``perm_partial``: int64[n] with -1 at outputs that do not care;
    ``used_inputs``: bool[n] marking inputs already consumed.  Unassigned
    outputs are matched to unused inputs in order.
    """
    perm = np.asarray(perm_partial, dtype=np.int64).copy()
    free_outputs = np.flatnonzero(perm < 0)
    free_inputs = np.flatnonzero(~np.asarray(used_inputs, dtype=bool))
    if free_outputs.shape[0] != free_inputs.shape[0]:
        raise ValueError("partial permutation is not completable")
    perm[free_outputs] = free_inputs
    return perm


def apply_network_numpy(
    masks: np.ndarray, x: np.ndarray, *, bit_major: bool = False
) -> np.ndarray:
    """Reference applier on an element array (testing / fallback)."""
    n = x.shape[0]
    nw = max(n // 32, 1)
    x = x.copy()
    for s in range(masks.shape[0]):
        d = stage_distance(n, s)
        i = np.arange(n)
        if bit_major:
            bits = (masks[s, i % nw] >> (i // nw)) & 1
        else:
            bits = (masks[s, i >> 5] >> (i & 31)) & 1
        swap = ((i & d) == 0) & (bits == 1)
        idx = i[swap]
        x[idx], x[idx + d] = x[idx + d].copy(), x[idx].copy()
    return x


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """bool/int8[n] -> uint32[n/32] little-endian (n must be a multiple of 32)."""
    b = np.asarray(bits, dtype=np.uint8).reshape(-1, 32).astype(np.uint32)
    return (b << np.arange(32, dtype=np.uint32)).sum(axis=1, dtype=np.uint32)


def unpack_bits(words: np.ndarray) -> np.ndarray:
    w = np.asarray(words, dtype=np.uint32)
    return ((w[:, None] >> np.arange(32, dtype=np.uint32)) & 1).astype(np.uint8).reshape(-1)
