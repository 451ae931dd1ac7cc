"""The named parts a cell's program and reference are built from.

Each role's spec sits in the cell's configuration or traffic file and names
a file ``parts/<role>/<name>.py``:

=============  ==================================  ==========================
role           spec                                what it sets
=============  ==================================  ==========================
server_opt     ``config["server_opt"]``            server optimiser
local_opt      ``config["client"]["local_opt"]``   client optimiser
sampler        ``traffic["cohort"]["sampler"]``    cohorts, weights, dropouts
aggregation    ``traffic["aggregation"]``          e.g. a masked ring
client_step    ``traffic["client_step"]``          e.g. a fused kernel hook
=============  ==================================  ==========================

A part module has

* ``KEYS``: the spec keys it reads beside ``name``; any other key raises,
  so a setting the part does not implement is never ignored;
* ``program(spec, ctx) -> {"trainer" | "rcfg" | "plan": {kwargs}}``: what it
  adds to the program's ``FederatedTrainer``, ``RoundConfig`` and
  ``ExecutionPlan``;
* for the roles the reference follows, ``reference(spec, ctx)``:
  ``server_opt`` a ``reference.ServerRule``, ``local_opt`` a
  ``reference.LocalRule``, ``sampler`` a function ``t -> (ids [C],
  weights [C], local steps [C])``.  Aggregation and client-step parts change
  how the program computes, not what: the reference sums the weighted
  deltas in the open and takes the local optimiser's plain steps.

A spec is a dict with ``name``; a name with no file raises.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType
from typing import Dict, Tuple

import numpy as np

from chipbench import registry

ROLES = ("server_opt", "local_opt", "sampler", "aggregation", "client_step")
REFERENCE_ROLES = ("server_opt", "local_opt", "sampler")


@dataclass(frozen=True)
class Ctx:
    """What a part sees: the cell's shape, the client sizes, the seeds."""
    config: dict
    counts: np.ndarray           # [K] client sizes
    clients_per_round: int
    local_steps: int
    local_batch: int
    seeds: "object"              # population.Seeds


def specs(config: dict, traffic: dict) -> Dict[str, dict]:
    """Each role's spec, from where the table above puts it."""
    where = {"server_opt": config["server_opt"],
             "local_opt": config["client"]["local_opt"],
             "sampler": traffic["cohort"]["sampler"],
             "aggregation": traffic["aggregation"],
             "client_step": traffic["client_step"]}
    return {role: where[role] for role in ROLES}


def load(role: str, spec: dict) -> Tuple[ModuleType, dict]:
    """The part file ``spec`` names, and the spec without its name."""
    mod = registry.part(role, spec["name"])
    rest = {k: v for k, v in spec.items() if k != "name"}
    unknown = set(rest) - set(mod.KEYS)
    if unknown:
        raise ValueError(f"{role} part {spec['name']!r} does not implement "
                         f"{sorted(unknown)}")
    return mod, rest


def program(config: dict, traffic: dict, ctx: Ctx) -> Dict[str, dict]:
    """The parts' program kwargs, merged; two parts setting one raises."""
    out: Dict[str, dict] = {"trainer": {}, "rcfg": {}, "plan": {}}
    for role, spec in specs(config, traffic).items():
        mod, rest = load(role, spec)
        for kind, kwargs in mod.program(rest, ctx).items():
            clash = out[kind].keys() & kwargs.keys()
            if clash:
                raise ValueError(f"{role} part sets {sorted(clash)} of the "
                                 f"{kind}, which another part set")
            out[kind].update(kwargs)
    return out


def reference(config: dict, traffic: dict, ctx: Ctx) -> dict:
    """``{role: rule}`` for the roles the reference follows."""
    all_specs = specs(config, traffic)
    out = {}
    for role in REFERENCE_ROLES:
        mod, rest = load(role, all_specs[role])
        out[role] = mod.reference(rest, ctx)
    return out
