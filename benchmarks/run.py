"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * us_per_call = wall microseconds per federated round (or per record);
  * derived     = the figure's headline quantity (see each module).

Fast defaults (~5 min CPU); ``--full`` restores paper-scale round counts.
    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig5,...]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


def bench_table2_statistics() -> None:
    """Paper Table 2: dataset statistics — the synthetic generators must
    reproduce the per-client mean/std of LEAF FEMNIST & Shakespeare."""
    from repro.data import synthetic_femnist, synthetic_shakespeare
    t0 = time.time()
    _, c1 = synthetic_femnist(n_clients=500, seed=0)
    _, c2 = synthetic_shakespeare(n_clients=125, seed=0)
    us = (time.time() - t0) * 1e6 / (500 + 125)
    _row("table2_femnist", us,
         f"mean={c1.mean():.1f}/224.5 std={c1.std():.1f}/87.8")
    _row("table2_shakespeare", us,
         f"mean={c2.mean():.0f}/4136.9 std={c2.std():.0f}/7226.2")


def bench_fig3(rounds: int) -> None:
    from benchmarks import fig3_inner_product
    t0 = time.time()
    out = fig3_inner_product.run(rounds=rounds, verbose=False)
    us = (time.time() - t0) * 1e6 / (2 * rounds)
    for task, r in out.items():
        _row(f"fig3_{task}", us,
             f"frac_positive={r['frac_positive']:.2f} "
             f"early={r['early_mean']:.3g} late={r['late_mean']:.3g}")


def bench_fig4(rounds: int) -> None:
    from benchmarks import fig4_fedavg_vs_fedsgd
    t0 = time.time()
    out = fig4_fedavg_vs_fedsgd.run(rounds=rounds, verbose=False)
    us = (time.time() - t0) * 1e6 / (2 * rounds)
    _row("fig4_fedavg_vs_fedsgd", us,
         f"inner_ratio={out['inner_ratio_avg_over_sgd']:.2f} "
         f"loss_gap={out['loss_gap']:.4f}")


def bench_fig5(rounds: int) -> None:
    from benchmarks import fig5_convergence
    t0 = time.time()
    out = fig5_convergence.run(rounds=rounds, verbose=False)
    us = (time.time() - t0) * 1e6 / (6 * rounds)
    for task, res in out.items():
        order = "<".join(sorted(res, key=res.get))
        _row(f"fig5_{task}", us,
             " ".join(f"{k}={v:.4f}" for k, v in res.items())
             + f" order={order}")


def bench_fig6(rounds: int) -> None:
    from benchmarks import fig6_robustness
    t0 = time.time()
    out = fig6_robustness.run(rounds=rounds, verbose=False)
    us = (time.time() - t0) * 1e6 / (14 * rounds)
    _row("fig6_robustness", us,
         f"gamma_spread fedavg={out['gamma']['fedavg_spread']:.4f} "
         f"fedmom={out['gamma']['fedmom_spread']:.4f}; "
         f"H_spread fedavg={out['H']['fedavg_spread']:.4f} "
         f"fedmom={out['H']['fedmom_spread']:.4f}")


def bench_roofline() -> None:
    import os
    from benchmarks import roofline
    if not os.path.exists(roofline.DEFAULT_PATH):
        _row("roofline", 0.0, "no dryrun_baseline.jsonl (run "
             "repro.launch.dryrun --all --both-meshes first)")
        return
    t0 = time.time()
    out = roofline.run(verbose=False)
    ok = [r for r in out["records"] if r["status"] == "ok"]
    us = (time.time() - t0) * 1e6 / max(len(out["records"]), 1)
    _row("roofline_table", us,
         f"{len(ok)} lowered combos; picks={out['picks']}")


def bench_kernels() -> None:
    """Microbench: interpret-mode kernels vs oracles (correctness-gated
    timing; wall time on CPU is NOT a TPU claim)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.fedmom_update import kernel as fm
    w = {"p": jnp.ones((256 * 128,))}
    v = {"p": jnp.zeros((256 * 128,))}
    d = {"p": jnp.full((256 * 128,), 0.01)}
    fm.fused_update_tree(w, v, d, eta=1.0, beta=0.9)   # warm
    t0 = time.time()
    for _ in range(10):
        jax.block_until_ready(
            fm.fused_update_tree(w, v, d, eta=1.0, beta=0.9))
    _row("kernel_fedmom_interpret", (time.time() - t0) * 1e5,
         "fused server update, 32k params, interpret mode")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale round counts")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig5,roofline")
    args = ap.parse_args()
    rounds = 400 if args.full else 80
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    benches = [
        ("table2", lambda: bench_table2_statistics()),
        ("fig3", lambda: bench_fig3(rounds)),
        ("fig4", lambda: bench_fig4(rounds)),
        ("fig5", lambda: bench_fig5(rounds)),
        ("fig6", lambda: bench_fig6(max(rounds // 2, 40))),
        ("roofline", bench_roofline),
        ("kernels", bench_kernels),
    ]
    # opt-in extras (slow): --only theory / ablation
    extras = {
        "theory": lambda: _run_extra("theory_validation"),
        "ablation": lambda: _run_extra("ablation_server_opts"),
    }
    for name, fn in benches:
        if only and name not in only:
            continue
        fn()
    for name, fn in (extras.items() if only else ()):
        if name in only:
            fn()


def _run_extra(module: str):
    import importlib
    import time as _t
    mod = importlib.import_module(f"benchmarks.{module}")
    t0 = _t.time()
    out = mod.run(verbose=False)
    _row(module, (_t.time() - t0) * 1e6, str(out)[:160])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
