"""Beyond-paper ablation: the whole biased-gradient server-optimizer family
on the paper's FEMNIST task.

The paper's reformulation (model averaging == gradient step on delta_t)
makes any server optimizer a drop-in; this ablation quantifies the family:
FedSGD / FedAvg / FedMom (paper) vs FedAvgM / FedAdam / FedYogi / FedLaMom
(ours).  Run: PYTHONPATH=src python -m benchmarks.ablation_server_opts
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import femnist_task, run_rounds
from repro.core import server_opt as so


def run(rounds: int = 150, verbose: bool = True) -> dict:
    task = femnist_task()
    K = task.dataset.n_clients
    eta = K / 2
    family = {
        "fedsgd": (so.fedavg(eta=eta), 1),
        "fedavg": (so.fedavg(eta=eta), 10),
        "fedmom": (so.fedmom(eta=eta, beta=0.9), 10),
        "fedavgm": (so.fedavgm(eta=eta, beta=0.9), 10),
        "fedadam": (so.fedadam(eta=0.03), 10),
        "fedyogi": (so.fedyogi(eta=0.03), 10),
        "fedlamom": (so.fedlamom(eta=eta, beta=0.9), 10),
    }
    out = {}
    for name, (opt, H) in family.items():
        r = run_rounds(task, opt, rounds, local_steps=H, lr=0.05, seed=11)
        out[name] = {
            "final_loss": float(np.mean(r["losses"][-10:])),
            "auc": float(np.mean(r["losses"])),   # lower = faster overall
        }
        if verbose:
            print(f"[ablation] {name:9s} final={out[name]['final_loss']:.4f} "
                  f"auc={out[name]['auc']:.4f}")
    if verbose:
        best = min(out, key=lambda k: out[k]["auc"])
        print(f"[ablation] fastest (auc): {best}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
