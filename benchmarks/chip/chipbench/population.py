"""Client sizes and run seeds.

A configuration fixes its size draw (``population.size_seed``): n_max sets
every packed shape, so a size draw per ``--seed`` would compile new
programs and fill a different amount of memory in every run.  ``--seed``
draws only contents, weights and cohorts (``Seeds``).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


def lognormal_sizes(population: dict) -> np.ndarray:
    """[K] sizes with the configured mean and std (a copy of the program's
    ``repro.data.partition.lognormal_sizes``, same draw for the same seed)."""
    mean, std = float(population["size_mean"]), float(population["size_std"])
    rng = np.random.default_rng(int(population["size_seed"]))
    sigma2 = np.log(1.0 + (std / mean) ** 2)
    mu = np.log(mean) - sigma2 / 2.0
    sizes = rng.lognormal(mu, np.sqrt(sigma2),
                          size=int(population["num_clients"]))
    return np.maximum(sizes.round().astype(int), 2)


class Seeds(NamedTuple):
    """Independent 31-bit seeds drawn from one ``--seed`` (any size)."""
    data: int           # client contents
    weights: int        # initial model
    minibatch: int      # the keyed minibatch draws (dataset seed)
    cohort: int         # cohorts of the checked rounds and the warm-up
    window: int         # cohorts of the measured window
    trace: int          # cohorts of the traced stretch

    @classmethod
    def from_seed(cls, seed: int) -> "Seeds":
        state = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
            len(cls._fields))
        return cls(*(int(s) & 0x7FFFFFFF for s in state))
