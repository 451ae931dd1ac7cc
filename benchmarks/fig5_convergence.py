"""Paper Figure 5: convergence comparison — FedMom > FedAvg > FedSGD in
rounds-to-loss on both tasks (same gamma, beta=0.9, eta=K/M, M=2)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import femnist_task, run_rounds, shakespeare_task
from repro.core import fedavg, fedmom


def run(rounds: int = 200, verbose: bool = True) -> dict:
    out = {}
    for task_fn, lr in ((femnist_task, 0.05), (shakespeare_task, 0.8)):
        task = task_fn()
        K = task.dataset.n_clients
        runs = {
            "fedsgd": (fedavg(eta=K / 2), 1),
            "fedavg": (fedavg(eta=K / 2), 10),
            "fedmom": (fedmom(eta=K / 2, beta=0.9), 10),
        }
        res = {}
        for name, (opt, H) in runs.items():
            r = run_rounds(task, opt, rounds, local_steps=H, lr=lr, seed=5)
            res[name] = float(np.mean(r["losses"][-10:]))
        # rounds to reach the fedavg final loss
        out[task.name] = res
        if verbose:
            order = " > ".join(sorted(res, key=res.get))
            print(f"[fig5:{task.name}] final losses: " +
                  " ".join(f"{k}={v:.4f}" for k, v in res.items()) +
                  f"  (fastest first: {order}; paper: fedmom fastest)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
