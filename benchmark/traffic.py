"""The traffic generator of search keys.  A traffic mix is a file of
parameters, ``benchmark/traffic/<mix>.json``; its ``driver`` names the
loop that drives the program (``benchmark/drivers/<driver>.py``) and the
rest sizes it.  Everything drawn here is a function of the run's
``--seed`` (and of a ``structure_seed`` that the file fixes), so one seed
gives the same requests on every run.

``keys`` search keys are drawn uniformly from the vertices of degree at
least 1 (the Graph500 rule) with ``structure_seed``, and requests of
``batch`` keys cycle through them in the order the run's seed gives: a
search's cost depends on its key, so keys drawn anew per seed would
change the work from seed to seed.
"""

from __future__ import annotations

import numpy as np

#: Offsets that separate the streams drawn from one seed.
_KEYS, _ORDER = 1, 2
#: The stream a driver draws its sample of answers to check from.
SAMPLE = 5


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of one run seed (any non-negative integer,
    including ones past 64 bits)."""
    return np.random.default_rng([int(seed), stream])


def search_keys(degree: np.ndarray, keys: int, seed: int) -> np.ndarray:
    """``keys`` distinct search keys of degree at least 1."""
    candidates = np.flatnonzero(degree > 0)
    if candidates.size < keys:
        raise ValueError(f"graph has {candidates.size} vertices with edges, "
                         f"traffic asks for {keys} keys")
    return rng(seed, _KEYS).choice(candidates, size=keys,
                                   replace=False).astype(np.int32)


def closed_requests(params: dict, degree: np.ndarray, seed: int) -> np.ndarray:
    """int32[R, batch]: the requests a closed loop cycles through.  The
    keys and their batches are drawn once from the file's
    ``structure_seed``; the run's seed orders the batches and the keys in
    each, so every seed does the same searches."""
    batch = int(params["batch"])
    keys = int(params["keys"])
    if keys % batch:
        raise ValueError("keys must be a multiple of batch")
    pool = search_keys(degree, keys, int(params["structure_seed"]))
    pool = pool.reshape(keys // batch, batch)
    order = rng(seed, _ORDER)
    pool = pool[order.permutation(pool.shape[0])]
    return order.permuted(pool, axis=1)
