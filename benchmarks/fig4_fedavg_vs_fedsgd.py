"""Paper Figure 4: why FedAvg (H=10 local steps) converges faster than
FedSGD (H=1): its biased gradient has a larger inner product with
w_t - w*, and its loss curve dominates."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import femnist_task, inner_products, run_rounds
from repro.core import fedavg


def run(rounds: int = 200, verbose: bool = True) -> dict:
    task = femnist_task()
    K = task.dataset.n_clients
    out = {}
    results = {}
    for name, H in (("fedsgd", 1), ("fedavg", 10)):
        res = run_rounds(task, fedavg(eta=K / 2), rounds,
                         local_steps=H, seed=4, record_states=True)
        results[name] = res
    # use the better run's final point as the common w*
    w_star = results["fedavg"]["final_w"]
    for name, res in results.items():
        ips = inner_products(res["states"], res["deltas"], w_star)
        probe = ips[: int(rounds * 0.9)]
        out[name] = {
            "inner_mean": float(probe.mean()),
            "lossT": float(np.mean(res["losses"][-10:])),
        }
    out["inner_ratio_avg_over_sgd"] = (
        out["fedavg"]["inner_mean"] / max(out["fedsgd"]["inner_mean"], 1e-12))
    out["loss_gap"] = out["fedsgd"]["lossT"] - out["fedavg"]["lossT"]
    if verbose:
        print(f"[fig4] inner product: FedAvg {out['fedavg']['inner_mean']:.4g}"
              f" vs FedSGD {out['fedsgd']['inner_mean']:.4g} "
              f"(ratio {out['inner_ratio_avg_over_sgd']:.2f}); final loss "
              f"FedAvg {out['fedavg']['lossT']:.4f} vs FedSGD "
              f"{out['fedsgd']['lossT']:.4f} (paper: FedAvg dominates both)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
