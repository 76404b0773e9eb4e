"""Test harness config: force an 8-device virtual CPU platform BEFORE jax
imports — the single-host multi-device methodology mirroring the reference
benchmark's "master + N workers on one machine" setup
(docs/BigData_Project.pdf §1.5, SURVEY.md §4)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tier-1 compile-budget pin: default the direction knob to the dense
# (pull) program so the many tests that run engines with DEFAULT knobs
# compile the cheapest superstep body on this 2-core container.  Every
# direction/exchange behavior has dedicated coverage that passes
# `direction=`/`exchange=` explicitly (test_direction.py,
# test_direction_sharded.py, test_exchange.py) — explicit arguments win
# over this env default, and a caller-exported BFS_TPU_DIRECTION is
# respected (setdefault).
os.environ.setdefault("BFS_TPU_DIRECTION", "pull")
os.environ.setdefault("BFS_TPU_EXCHANGE", "bitmap")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

from bfs_tpu.graph.csr import Graph

# tinyCG.txt contents (reference test-sets/tinyCG.txt; the paper's worked
# example, docs/BigData_Project.pdf §1.2 Table 1): 6 vertices, 8 edges.
TINY_V = 6
TINY_EDGES = [(0, 5), (2, 4), (2, 3), (1, 2), (0, 1), (3, 4), (3, 5), (0, 2)]
TINY_TEXT = "6\n8\n" + "\n".join(f"{u} {v}" for u, v in TINY_EDGES) + "\n"

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE_TEST_SETS = "/root/reference/test-sets"


@pytest.fixture
def tiny_graph() -> Graph:
    return Graph.from_undirected_edges(TINY_V, np.array(TINY_EDGES))


@pytest.fixture
def medium_graph() -> Graph:
    """A mediumG-shape graph (250 V / 1,273 E — the reference benchmark's
    middle size): the in-repo fixture test-sets/randomG.txt, so no test
    depends on the read-only reference mount.  When the reference's actual
    mediumG.txt is present it is used instead, for closer parity."""
    from bfs_tpu.graph.io import read_sedgewick

    ref = os.path.join(REFERENCE_TEST_SETS, "mediumG.txt")
    if os.path.exists(ref):
        return read_sedgewick(ref)
    return read_sedgewick(os.path.join(REPO_ROOT, "test-sets", "randomG.txt"))
