"""Framework configuration: the ``service.properties`` layer, TPU-shaped.

Parity with ``ServiceConfiguration`` (ServiceConfiguration.java:30-63): a
``key=value`` properties file loaded once, exposing app name and the
comma-separated problem-file list (``problemFiles``).  TPU-specific keys
replace the Spark master coordinates (ip/port/jar — obsolete: XLA programs
are dispatched to the mesh, not shipped as jars):

    app-name       = BFS with MapReduce, TPU edition
    problemFiles   = test-sets/tinyCG.txt, test-sets/mediumG.txt
    source         = 0
    mesh-batch     = 1
    mesh-graph     = 0            # 0 = all devices
    dump-supersteps = false       # write problemFile_i-style text dumps
    checkpoint-every = 0          # supersteps between .npz checkpoints

Unlike the reference, a missing/corrupt file raises instead of being
swallowed into null getters (ServiceConfiguration.java:40-42 logs and
continues — a latent NPE factory we deliberately do not reproduce).

This module also owns the ARTIFACT-CACHE directory layout (the cold-path
killer, ISSUE 2): every persistent cache — relay/ELL layout bundles, JAX's
persistent compilation cache and the crash-resume run journals (ISSUE 3)
— lives under one root so a driver, a serving process and
``tools/cache_warm.py`` all share warm artifacts.  Resolution order:
explicit env knob per cache, then ``BFS_TPU_CACHE_DIR``, then
``<repo>/.bench_cache``.  JAX's compile cache is the one exception: it
lives where ``JAX_COMPILATION_CACHE_DIR`` says when that is set.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from . import knobs

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_root() -> str:
    """Root directory for all persistent artifact caches
    (``BFS_TPU_CACHE_DIR``; default ``<repo>/.bench_cache``)."""
    v = knobs.raw("BFS_TPU_CACHE_DIR")
    return v if v is not None else os.path.join(_REPO_ROOT, ".bench_cache")


def layout_cache_dir() -> str:
    """On-disk layout-bundle store (:mod:`bfs_tpu.cache.layout`)."""
    return os.path.join(cache_root(), "layout")


def journal_dir() -> str:
    """Run-journal directory (:mod:`bfs_tpu.resilience.journal`):
    ``BFS_TPU_JOURNAL_DIR`` wins when set (tests point it at a tmp dir so
    kill/resume runs can share warm artifact caches but not journals),
    else ``<cache root>/journal`` — resume state lives with the other
    per-config artifacts it must stay consistent with."""
    v = knobs.raw("BFS_TPU_JOURNAL_DIR")
    return v if v is not None else os.path.join(cache_root(), "journal")


def compile_cache_dir() -> str:
    """JAX persistent compilation cache directory: a fixed path (the path
    is part of the cache's key) — ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<repo>/.bench_cache/xla``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".bench_cache", "xla"
    )


def enable_compile_cache(*, min_compile_seconds: float = 5.0) -> dict:
    """Turn on JAX's persistent compile cache in
    :func:`compile_cache_dir`; call before the first trace.  The only
    compile cache of this repo: a cold compile of a bench-scale program
    is paid once per (topology, program).

    Idempotent; returns the resolved directories so callers can log them.
    Entry points that compile anything (the runners, tools) call this at
    startup; importing the ``bfs_tpu`` package itself must NOT (an
    application's global jax config is not ours to mutate).  The one
    historical exception is ``bfs_tpu.bench``, which enables the caches at
    import — every importer of that module (the bench entry point, the
    profiling tools, benchmarks.py) is itself a bench surface that relies
    on it.
    """
    import jax

    cc_dir = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", cc_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_seconds)
    )
    return {
        "jax_compilation_cache_dir": cc_dir,
        "layout_cache_dir": layout_cache_dir(),
    }


def parse_properties(text: str) -> dict[str, str]:
    """Minimal Java-properties subset: ``k=v`` lines, ``#``/``!`` comments,
    whitespace-trimmed keys/values."""
    out: dict[str, str] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#") or line.startswith("!"):
            continue
        if "=" not in line:
            raise ValueError(f"malformed properties line: {raw!r}")
        k, _, v = line.partition("=")
        out[k.strip()] = v.strip()
    return out


@dataclass(frozen=True)
class ServiceConfiguration:
    app_name: str = "BFS with MapReduce, TPU edition"
    problem_files: tuple[str, ...] = ()
    source: int = 0
    mesh_batch: int = 1
    mesh_graph: int = 0  # 0 = use all devices
    dump_supersteps: bool = False
    checkpoint_every: int = 0
    work_dir: str = "."

    @classmethod
    def load(cls, path: str | os.PathLike) -> "ServiceConfiguration":
        with open(path, "r") as f:
            props = parse_properties(f.read())
        files = tuple(
            p.strip() for p in props.get("problemFiles", "").split(",") if p.strip()
        )
        return cls(
            app_name=props.get("app-name", cls.app_name),
            problem_files=files,
            source=int(props.get("source", "0")),
            mesh_batch=int(props.get("mesh-batch", "1")),
            mesh_graph=int(props.get("mesh-graph", "0")),
            dump_supersteps=props.get("dump-supersteps", "false").lower() == "true",
            checkpoint_every=int(props.get("checkpoint-every", "0")),
            work_dir=props.get("work-dir", os.path.dirname(os.fspath(path)) or "."),
        )
