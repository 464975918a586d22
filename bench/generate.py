"""The one traffic generator: turns a traffic file and ``--seed`` into requests.

A traffic file (``bench/traffic/<name>.json``) holds only parameters.  Every
request below is a pure function of (traffic, configuration, seed, index), so
the same seed gives the same inputs and a new cell needs only a new file.
Seeds handed to the program fit 31 bits whatever ``--seed`` is.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        [int(seed) & (2**64 - 1), *(int(x) & (2**32 - 1) for x in stream)])


def dse_request(traffic: dict, seed: int, i: int) -> dict:
    """Request ``i`` of a closed DSE loop: its const_sf values and seeds.

    ``const_sf`` cycles through the traffic's grid ``sf_per_request`` values
    at a time, from the same offset for every seed, so every run does the
    same set of constraint problems; the search seeds come from ``seed``.
    A request's lanes are ``for sf in const_sf: for s in seeds``.
    """
    grid = list(traffic["const_sf_grid"])
    k = int(traffic["sf_per_request"])
    sfs = [grid[(i * k + j) % len(grid)] for j in range(k)]
    seeds = _rng(seed, 1, i).integers(0, 2**31 - 1, int(traffic["seeds_per_request"]))
    return {"const_sf": sfs, "seeds": [int(s) for s in seeds]}


def prompts(traffic: dict, vocab: int, seed: int, i: int) -> np.ndarray:
    """Batch ``i`` of a closed serving loop: (batch, prompt_len) token ids."""
    shape = (int(traffic["batch"]), int(traffic["prompt_len"]))
    return _rng(seed, 2, i).integers(0, vocab, shape, dtype=np.int32)


def sample(n: int, k: int, seed: int, must: tuple = ()) -> list[int]:
    """``k`` of ``range(n)`` drawn from ``seed``, always holding ``must``."""
    rest = [i for i in _rng(seed, 3).permutation(n) if i not in must]
    return sorted(set(must) | set(rest[: max(0, k - len(set(must)))]))


def weight_seed(seed: int) -> int:
    return int(_rng(seed, 4).integers(0, 2**31 - 1))
