"""Paper Figure 3: the biased gradient g_t points toward the target —
E<g_t, w_t - w*> stays positive over the course of optimization.

w* is the model after the full run (the paper uses w_2000); the probe
replays training and reports the positive fraction + windowed averages.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import (
    femnist_task,
    inner_products,
    run_rounds,
    shakespeare_task,
    smooth,
)
from repro.core import fedavg


def run(rounds: int = 200, verbose: bool = True) -> dict:
    out = {}
    for task_fn in (femnist_task, shakespeare_task):
        task = task_fn()
        K = task.dataset.n_clients
        opt = fedavg(eta=K / 2)
        t0 = time.time()
        res = run_rounds(task, opt, rounds, record_states=True, seed=3)
        ips = inner_products(res["states"], res["deltas"], res["final_w"])
        # exclude the tail (w_t ~ w* trivially shrinks the product)
        probe = ips[: int(rounds * 0.9)]
        frac_pos = float((probe > 0).mean())
        early = float(probe[: len(probe) // 3].mean())
        late = float(probe[-len(probe) // 3:].mean())
        out[task.name] = {
            "frac_positive": frac_pos,
            "early_mean": early,
            "late_mean": late,
            "loss0": res["losses"][0],
            "lossT": float(np.mean(res["losses"][-10:])),
            "secs": time.time() - t0,
        }
        if verbose:
            print(f"[fig3:{task.name}] <g_t, w_t-w*> positive "
                  f"{frac_pos:.0%} of rounds; early mean {early:.4g} -> "
                  f"late mean {late:.4g} (paper: positive, shrinking)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
