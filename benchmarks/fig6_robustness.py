"""Paper Figure 6: FedMom is more robust than FedAvg to the stepsize gamma
and the number of local iterations H (loss varies less across the grid).

Runs under the plan-based driver (``FederatedTrainer.run(plan=...)``,
scanned plane) — the same keyed trajectory contract as the tests.

Scenario lane (``--scenario``): the production-conditions extension of the
same robustness question.  A provider-backed Zipf corpus (hundreds of
thousands of lazily-synthesized clients — host RAM holds the [K] count
vector, never the corpus) trains under the streaming plane while a
``ScenarioSpec`` applies mid-round dropouts at a swept rate plus
round-deadline stragglers; eq. (3) partial-work aggregation keeps a
fully-dropped client's weight mass on w_t, so FedMom's final loss should
move less across the dropout grid than FedAvg's:

    PYTHONPATH=src python -m benchmarks.fig6_robustness --scenario \\
        [--smoke] [--emit-bench BENCH_7.json]

Trace lane (``--trace``): the same robustness question on RECORDED reality
— each dropout cell's synthetic scenario is recorded into a ``FleetTrace``
(saved and re-loaded from disk) and replayed via
``ScenarioSpec(trace=TraceSpec(...))`` over a disk-backed, mmap-read
corpus (``DiskShardProvider``); one cell is certified bit-equal to its
originating synthetic run (``replay_drift_bits == 0`` in the snapshot):

    PYTHONPATH=src python -m benchmarks.fig6_robustness --trace \\
        [--smoke] [--emit-bench BENCH_9.json]

``--smoke`` shrinks to a CI-sized pass (smaller corpus, fewer rounds);
``--emit-bench PATH`` writes the sweep as the committed per-PR snapshot
(``BENCH_<pr>.json`` — CI regenerates the smoke shape against it).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import femnist_task, run_plan
from repro.core import fedavg, fedmom


def run(rounds: int = 120, verbose: bool = True) -> dict:
    task = femnist_task()
    K = task.dataset.n_clients
    gammas = [0.01, 0.03, 0.05, 0.1]
    hs = [5, 10, 20]
    out = {"gamma": {}, "H": {}}
    for label, opt_fn in (("fedavg", lambda: fedavg(eta=K / 2)),
                          ("fedmom", lambda: fedmom(eta=K / 2, beta=0.9))):
        g_losses = []
        for g in gammas:
            r = run_plan(task, opt_fn(), rounds, local_steps=10, lr=g,
                         seed=6)
            g_losses.append(float(np.mean(r["losses"][-10:])))
        h_losses = []
        for H in hs:
            r = run_plan(task, opt_fn(), rounds, local_steps=H, lr=0.05,
                         seed=6)
            h_losses.append(float(np.mean(r["losses"][-10:])))
        out["gamma"][label] = dict(zip(map(str, gammas), g_losses))
        out["H"][label] = dict(zip(map(str, hs), h_losses))
        out["gamma"][label + "_spread"] = max(g_losses) - min(g_losses)
        out["H"][label + "_spread"] = max(h_losses) - min(h_losses)
    if verbose:
        print(f"[fig6] loss spread across gamma grid: "
              f"fedavg {out['gamma']['fedavg_spread']:.4f} vs "
              f"fedmom {out['gamma']['fedmom_spread']:.4f} "
              f"(paper: fedmom tighter)")
        print(f"[fig6] loss spread across H grid:     "
              f"fedavg {out['H']['fedavg_spread']:.4f} vs "
              f"fedmom {out['H']['fedmom_spread']:.4f}")
    return out


def _scenario_run(opt, provider, rounds: int, rate: float, *, m: int,
                  local_steps: int, deadline_s: float, chunk_rounds: int,
                  seed: int) -> dict:
    """One dropout-sweep cell: provider-backed streaming run under a
    dropout + straggler ScenarioSpec; returns final loss + completion."""
    import jax
    import jax.numpy as jnp

    from repro.core import DeviceUniformSampler, RoundConfig
    from repro.data import StreamingFederatedDataset
    from repro.launch.plan import CacheSpec, ExecutionPlan
    from repro.launch.train import FederatedTrainer
    from repro.scenario import (LatencyStragglers, ScenarioSpec,
                                UniformDropout)

    ds = StreamingFederatedDataset.from_provider(provider, seed=seed + 7)
    rcfg = RoundConfig(clients_per_round=m, local_steps=local_steps,
                       lr=0.05, placement="mesh", compute_dtype="float32")
    d = provider.fields["x"][0][0]
    tr = FederatedTrainer(
        loss_fn=_linreg_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=DeviceUniformSampler(ds.population(), m, seed=seed),
        state=opt.init({"w": jnp.zeros(d), "b": jnp.zeros(())}),
        local_batch=4)
    spec = ScenarioSpec(
        dropout=UniformDropout(rate=rate) if rate > 0 else None,
        stragglers=LatencyStragglers(deadline_s=deadline_s,
                                     mean_step_s=1.0),
        seed=seed + 11)
    plan = ExecutionPlan(plane="streaming", chunk_rounds=chunk_rounds,
                         cache=CacheSpec(clients=m * chunk_rounds),
                         scenario=spec)
    hist = [r for r in tr.run(rounds, plan=plan, verbose=False)
            if "event" not in r]
    jax.tree.leaves(tr.state.w)[0].block_until_ready()
    cache = tr.stream_cache
    return {
        "final_loss": float(np.mean([r["loss"] for r in hist[-10:]])),
        "completed_mean": float(np.mean([r["completed"] for r in hist])),
        "cache_nbytes": int(cache.nbytes),
    }


def scenario_lane(rounds: int = 60, n_clients: int = 1_000_000,
                  smoke: bool = False, verbose: bool = True) -> dict:
    """Dropout-rate sweep on a provider-backed Zipf corpus: eq. (3) keeps
    FedMom's final loss stable as the dropout rate climbs (the spread
    stays at or below FedAvg's), while the lazily-synthesized corpus never
    materializes on host.  Returns the BENCH_7 snapshot dict."""
    from repro.scenario import zipf_linreg_provider

    if smoke:
        rounds, n_clients = min(rounds, 24), min(n_clients, 100_000)
    m, local_steps, chunk_rounds, deadline_s = 8, 10, 8, 11.0
    rates = [0.0, 0.3, 0.6] if smoke else [0.0, 0.2, 0.4, 0.6]
    provider = zipf_linreg_provider(n_clients, dim=16, n_min=4, n_max=64,
                                    seed=0)
    # what a materialized corpus would pin on host vs what the provider
    # declares: the [K] count vector only
    row_nbytes = (16 + 1) * 4
    materialized_mb = float(provider.counts.sum() * row_nbytes / 2**20)
    declared_mb = float(provider.counts.nbytes / 2**20)
    eta = n_clients / m                 # the paper's eta = K/M unbiasing
    out = {"bench": "scenario_dropout_sweep",
           "config": {"model": "linreg", "n_clients": n_clients,
                      "rounds": rounds, "m": m, "local_steps": local_steps,
                      "chunk_rounds": chunk_rounds,
                      "deadline_s": deadline_s, "rates": rates,
                      "smoke": smoke},
           "corpus_materialized_mb": round(materialized_mb, 2),
           "corpus_declared_mb": round(declared_mb, 4),
           "rates": {}}
    cache_mb = None
    for label, opt_fn in (("fedavg", lambda: fedavg(eta=eta)),
                          ("fedmom", lambda: fedmom(eta=eta, beta=0.9))):
        finals = []
        for rate in rates:
            cell = _scenario_run(opt_fn(), provider, rounds, rate, m=m,
                                 local_steps=local_steps,
                                 deadline_s=deadline_s,
                                 chunk_rounds=chunk_rounds, seed=6)
            cache_mb = round(cell.pop("cache_nbytes") / 2**20, 3)
            out["rates"].setdefault(str(rate), {})[label] = cell
            finals.append(cell["final_loss"])
            if verbose:
                print(f"[fig6-scenario] {label} rate={rate}: "
                      f"loss={cell['final_loss']:.4f} "
                      f"completed={cell['completed_mean']:.2f}/{m}")
        out[label + "_spread"] = float(max(finals) - min(finals))
    out["cache_mb"] = cache_mb
    if verbose:
        print(f"[fig6-scenario] final-loss spread across dropout grid: "
              f"fedavg {out['fedavg_spread']:.4f} vs "
              f"fedmom {out['fedmom_spread']:.4f} (eq. (3) partial work; "
              f"paper: fedmom tighter)")
        print(f"[fig6-scenario] corpus: {n_clients} clients, "
              f"{materialized_mb:.1f} MB materialized vs "
              f"{declared_mb:.2f} MB declared + {cache_mb} MB device cache")
    return out


def _linreg_loss(params, b):
    import jax.numpy as jnp

    pred = b["x"] @ params["w"] + params["b"]
    return jnp.mean(jnp.square(pred - b["y"])), {}


def _trace_run(opt, provider, scenario, rounds: int, *, m: int,
               local_steps: int, chunk_rounds: int, seed: int):
    """One trace-lane cell: disk-backed streaming run under ``scenario``
    (a synthetic spec or a trace replay — same code path, same sampler);
    returns final loss + completion + the flattened final params (for the
    bit-drift certification)."""
    import jax
    import jax.numpy as jnp

    from repro.core import DeviceUniformSampler, RoundConfig
    from repro.data import StreamingFederatedDataset
    from repro.launch.plan import CacheSpec, ExecutionPlan
    from repro.launch.train import FederatedTrainer

    ds = StreamingFederatedDataset.from_provider(provider, seed=seed + 7)
    rcfg = RoundConfig(clients_per_round=m, local_steps=local_steps,
                       lr=0.05, placement="mesh", compute_dtype="float32")
    d = provider.fields["x"][0][0]
    tr = FederatedTrainer(
        loss_fn=_linreg_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=DeviceUniformSampler(ds.population(), m, seed=seed),
        state=opt.init({"w": jnp.zeros(d), "b": jnp.zeros(())}),
        local_batch=4)
    plan = ExecutionPlan(plane="streaming", chunk_rounds=chunk_rounds,
                         cache=CacheSpec(clients=m * chunk_rounds),
                         scenario=scenario)
    hist = [r for r in tr.run(rounds, plan=plan, verbose=False)
            if "event" not in r]
    flat = np.concatenate([np.ravel(np.asarray(x))
                           for x in jax.tree.leaves(tr.state.w)])
    return {
        "final_loss": float(np.mean([r["loss"] for r in hist[-10:]])),
        "completed_mean": float(np.mean([r["completed"] for r in hist])),
        "losses": [float(r["loss"]) for r in hist],
        "flat_w": flat,
    }


def trace_lane(rounds: int = 48, n_clients: int = 50_000,
               smoke: bool = False, verbose: bool = True) -> dict:
    """Trace-replay robustness lane (BENCH_9): the dropout sweep re-run on
    RECORDED reality instead of live rate draws — a synthetic
    ``ScenarioSpec`` per dropout rate is recorded into a ``FleetTrace``
    (save/load round-tripped through disk), then FedAvg vs FedMom replay
    the trace via ``ScenarioSpec(trace=TraceSpec(...))`` over a
    DISK-BACKED corpus (``write_disk_corpus`` -> mmap ``DiskShardProvider``).
    One cell is certified bit-equal against its originating synthetic run
    (``replay_drift_bits`` must be 0).  Returns the BENCH_9 snapshot dict.
    """
    import tempfile

    from repro.core import DeviceUniformSampler
    from repro.data import DiskShardProvider, write_disk_corpus
    from repro.scenario import (LatencyStragglers, ScenarioSpec,
                                UniformDropout, zipf_linreg_provider)
    from repro.traces import FleetTrace, TraceRecorder, TraceSpec

    if smoke:
        rounds, n_clients = min(rounds, 16), min(n_clients, 5_000)
    m, local_steps, chunk_rounds, deadline_s = 8, 10, 8, 11.0
    rates = [0.0, 0.3, 0.6]
    seed = 6
    src = zipf_linreg_provider(n_clients, dim=16, n_min=4, n_max=64,
                               seed=0)
    tmp = tempfile.mkdtemp(prefix="repro-trace-lane-")
    corpus = write_disk_corpus(os.path.join(tmp, "corpus"), src,
                               layout="npy-packed")
    provider = DiskShardProvider(corpus)
    disk_mb = sum(os.path.getsize(os.path.join(corpus, f))
                  for f in os.listdir(corpus)) / 2**20
    if verbose:
        print(f"[fig6-trace] disk corpus: {n_clients} clients, "
              f"{disk_mb:.1f} MB packed (mmap-backed)")
    eta = n_clients / m
    out = {"bench": "trace_replay_dropout",
           "config": {"model": "linreg", "n_clients": n_clients,
                      "rounds": rounds, "m": m, "local_steps": local_steps,
                      "chunk_rounds": chunk_rounds,
                      "deadline_s": deadline_s, "rates": rates,
                      "smoke": smoke},
           "corpus": {"layout": "npy-packed",
                      "disk_mb": round(disk_mb, 2)},
           "rates": {}}
    # record one trace per dropout rate — pure host work, then round-trip
    # each through FleetTrace.save/load so the replayed object is the
    # deserialized one (persistence is part of what the lane certifies)
    traces, syn_specs = {}, {}
    from repro.data import StreamingFederatedDataset
    pop = StreamingFederatedDataset.from_provider(
        provider, seed=seed + 7).population()
    for rate in rates:
        spec = ScenarioSpec(
            dropout=UniformDropout(rate=rate) if rate > 0 else None,
            stragglers=LatencyStragglers(deadline_s=deadline_s,
                                         mean_step_s=1.0),
            seed=seed + 11)
        sampler = DeviceUniformSampler(pop, m, seed=seed)
        trace = TraceRecorder(spec, local_steps).record(sampler, rounds)
        path = trace.save(os.path.join(tmp, f"trace_rate{rate}"))
        traces[rate] = FleetTrace.load(path)
        syn_specs[rate] = spec
    out["trace"] = {"rounds": rounds,
                    "events_per_trace": int(traces[rates[0]].n_events),
                    "peak_m": int(traces[rates[0]].peak_m)}
    # per-trace fleet analytics (FleetTrace.summarize): completion
    # histogram + churn/round summary, printed per dropout rate and kept
    # on the snapshot so the recorded conditions are auditable
    out["trace"]["summaries"] = {}
    for rate in rates:
        summ = traces[rate].summarize()
        out["trace"]["summaries"][str(rate)] = summ
        if verbose:
            hist = summ["completion_hist"]
            jpr = summ["joined_per_round"]
            print(f"[fig6-trace] rate={rate}: {summ['participants']} "
                  f"participants over {summ['n_events']} events — "
                  f"complete/mixed/partial = {hist['all_complete']}/"
                  f"{hist['mixed']}/{hist['all_partial']}, "
                  f"joined/round {jpr['mean']:.1f} "
                  f"[{jpr['min']}, {jpr['max']}], "
                  f"complete-frac {summ['complete_frac_mean']:.3f}, "
                  f"turnover {summ['turnover_mean']:.3f}")
    drift_bits = None
    for label, opt_fn in (("fedavg", lambda: fedavg(eta=eta)),
                          ("fedmom", lambda: fedmom(eta=eta, beta=0.9))):
        finals = []
        for rate in rates:
            replay = ScenarioSpec(trace=TraceSpec(trace=traces[rate]))
            cell = _trace_run(opt_fn(), provider, replay, rounds, m=m,
                              local_steps=local_steps,
                              chunk_rounds=chunk_rounds, seed=seed)
            if label == "fedmom" and rate == rates[1]:
                # certify: the replayed trajectory is bit-equal to the
                # originating synthetic run on the same disk corpus
                syn = _trace_run(opt_fn(), provider, syn_specs[rate],
                                 rounds, m=m, local_steps=local_steps,
                                 chunk_rounds=chunk_rounds, seed=seed)
                drift_bits = int((cell["flat_w"].view(np.uint32)
                                  != syn["flat_w"].view(np.uint32)).sum())
                drift_bits += sum(a != b for a, b
                                  in zip(cell["losses"], syn["losses"]))
            cell.pop("flat_w")
            cell.pop("losses")
            out["rates"].setdefault(str(rate), {})[label] = cell
            finals.append(cell["final_loss"])
            if verbose:
                print(f"[fig6-trace] {label} rate={rate}: "
                      f"loss={cell['final_loss']:.4f} "
                      f"completed={cell['completed_mean']:.2f}/{m}")
        out[label + "_spread"] = float(max(finals) - min(finals))
    out["replay_drift_bits"] = drift_bits
    if verbose:
        print(f"[fig6-trace] final-loss spread under replayed dropout "
              f"traces: fedavg {out['fedavg_spread']:.4f} vs "
              f"fedmom {out['fedmom_spread']:.4f}; "
              f"replay drift {drift_bits} bits (must be 0)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--scenario", action="store_true",
                    help="run the dropout-sweep scenario lane instead of "
                         "the gamma/H grids")
    ap.add_argument("--trace", action="store_true",
                    help="run the trace-replay lane: record each dropout "
                         "cell's scenario into a FleetTrace and replay it "
                         "over a disk-backed corpus (BENCH_9)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized pass (smaller corpus, short run)")
    ap.add_argument("--emit-bench", metavar="PATH", default=None,
                    help="write the sweep as a JSON snapshot "
                         "(the committed BENCH_<pr>.json perf record)")
    args = ap.parse_args(argv)
    if args.trace or args.scenario or args.emit_bench:
        if args.trace:
            snap = trace_lane(rounds=args.rounds or 48, smoke=args.smoke)
        else:
            snap = scenario_lane(rounds=args.rounds or 60, smoke=args.smoke)
        if args.emit_bench:
            with open(args.emit_bench, "w") as f:
                json.dump(snap, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"  bench snapshot -> {args.emit_bench}")
        return snap
    return run(rounds=args.rounds or 120)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
