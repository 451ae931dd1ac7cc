"""Theory-linked experiments (beyond the paper's own figures):

1. eta sweep — Theorem 3.2 allows eta in [1, K/M] and the bound's first
   term decreases with eta: larger server stepsize should dominate at
   small round counts (the paper uses eta=K/M without an ablation).
2. gamma_t schedules — Corollary 3.3 requires sum gamma_t = inf,
   sum gamma_t^2 < inf; we compare constant / 1/(t+1) / 1/sqrt(t+1)
   schedules (constant satisfies only the rate bound of Cor. 3.4).

    PYTHONPATH=src python -m benchmarks.theory_validation
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.common import femnist_task
from repro.core import RoundConfig, UniformSampler, fedavg, fedmom, round_step
from repro.launch.train import FederatedTrainer
from repro.models import small

import jax


def _train(task, opt, rounds, lr_schedule=None, lr=0.05, seed=9):
    pop = task.dataset.population()
    rcfg = RoundConfig(clients_per_round=2, local_steps=10, lr=lr,
                       placement="mesh", compute_dtype="float32")
    tr = FederatedTrainer(
        loss_fn=task.loss_fn, server_opt=opt, rcfg=rcfg,
        dataset=task.dataset, sampler=UniformSampler(pop, 2, seed=seed),
        state=opt.init(task.init_fn(jax.random.PRNGKey(0))),
        lr_schedule=lr_schedule, local_batch=10)
    hist = tr.run(rounds, log_every=10_000, verbose=False)
    return float(np.mean([h["loss"] for h in hist[-10:]]))


def run(rounds: int = 120, verbose: bool = True) -> dict:
    task = femnist_task()
    K = task.dataset.n_clients
    out = {"eta": {}, "schedule": {}}

    # 1) eta sweep over [1, K/M]
    for eta in (1.0, K / 8, K / 4, K / 2):
        out["eta"][f"{eta:g}"] = _train(task, fedavg(eta=eta), rounds)
    if verbose:
        print("[theory] fedavg eta sweep (K/M =", K / 2, "):",
              {k: round(v, 4) for k, v in out["eta"].items()})

    # 2) gamma_t schedules (Corollary 3.3) under FedMom
    g0 = 0.2
    schedules = {
        "constant": None,
        "1/(t+1)": lambda t: g0 / (t + 1.0),
        "1/sqrt(t+1)": lambda t: g0 / math.sqrt(t + 1.0),
    }
    for name, sched in schedules.items():
        out["schedule"][name] = _train(
            task, fedmom(eta=K / 2, beta=0.9), rounds,
            lr_schedule=sched, lr=(0.05 if sched is None else g0))
    if verbose:
        print("[theory] fedmom gamma_t schedules:",
              {k: round(v, 4) for k, v in out["schedule"].items()})
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
