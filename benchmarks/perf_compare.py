"""§Perf helper: compare dry-run records (baseline vs variant) — per-kind
collective deltas and the three roofline terms side by side.

    PYTHONPATH=src python -m benchmarks.perf_compare results/hillclimb.jsonl

Driver lane: measure the per-round host overhead the scanned plane
(``run(n, plan="scanned")``) removes relative to the per-round Python loop,
at the paper's small round sizes:

    PYTHONPATH=src python -m benchmarks.perf_compare --drivers \
        [--model lenet|linreg] [--rounds 100] [--chunk-rounds 25]

Data-plane lane: prefetch-queue (host-assembled chunks, ``plan="scanned"``)
vs device-resident corpus (``plan="device"``: sampling + minibatch gather
fused into the scan, zero host round-trips per chunk) vs shard-cached
streaming (``plan="streaming"``: bounded device LRU of client shards, chunk
i+1's H2D uploads overlapped with chunk i's compute) — the same trajectory,
only the data plane differs.  The streaming row also reports cache hit-rate
and the cache-vs-packed footprint (the plane-choice decision numbers), a
warm-session row reruns the streaming lane on the SAME ``TrainSession``: the
persistent shard cache makes the second ``run()`` re-upload nothing for
already-resident clients (measured upload savings), and a tiered-vs-uniform
row trains one Zipfian-n_k corpus under both slot layouts
(``CacheSpec(tiers=None)`` vs ``tiers=1``) at equal trajectory, reporting
cache device bytes + hit-rate (the n_k-tiered footprint win).  A
bucketed-vs-padded row trains the same Zipfian corpus under
``CacheSpec(bucketed=True)`` (one sized launch per n_k tier,
``scan_rounds_bucketed``) vs the padded switch-under-vmap gather, asserting
the bucketed compute is no slower at equal trajectory:

    PYTHONPATH=src python -m benchmarks.perf_compare --data-plane \
        [--model lenet|linreg] [--rounds 100] [--chunk-rounds 25] \
        [--cache-clients N] [--smoke] [--emit-bench BENCH_6.json]

``--smoke`` shrinks the config to a seconds-long CI sanity pass (with a
cache smaller than the corpus, so the streaming lane actually streams).
``--emit-bench PATH`` writes the bucketed-vs-padded numbers as a JSON
snapshot — the per-PR perf record (``BENCH_<pr>.json``, committed; CI
regenerates and fails the lane when the snapshot is missing or the
bucketed lane regresses to slower-than-padded).

Secure-aggregation lane: plain fp32 reduction vs open uint32 ring vs
masked pairwise transport (``ExecutionPlan(secure=SecureAggSpec(...))``)
on the scanned plane — the masked-vs-open ms/round overhead at equal
trajectory (equal meaning BIT-equal: the lane asserts zero drift between
masked and open final params, the ring-cancellation guarantee):

    PYTHONPATH=src python -m benchmarks.perf_compare --secure \
        [--rounds 60] [--m 8] [--smoke] [--emit-bench BENCH_8.json]

Mesh lane: the mesh-sharded round engine (``ExecutionPlan(mesh=
MeshSpec(devices=n))``) at increasing data-parallel device counts —
ms/round per count at equal trajectory, on forced host devices.  The
``--mesh`` branch merges ``--xla_force_host_platform_device_count=8`` and
the XLA latency-hiding-scheduler flags into ``XLA_FLAGS`` before jax
initializes (user-set force counts are respected), so the lane runs on any
host.  Host-CPU collectives are emulation, not hardware interconnect, so
the snapshot records ms/round per device count without asserting a
speedup — the numbers are the scaling SHAPE record, the trajectory-drift
field is the correctness record:

    PYTHONPATH=src python -m benchmarks.perf_compare --mesh \
        [--rounds 60] [--m 8] [--smoke] [--emit-bench BENCH_10.json]
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict


def load(paths):
    recs = {}
    for path in paths:
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                key = (r["arch"], r["shape"], r["mesh"], r["variant"])
                recs[key] = r
    return recs


def fmt(r):
    if r["status"] != "ok":
        return f"   {r['variant']:14s} {r['status']}: {r.get('error','')[:90]}"
    t = r["roofline"]
    coll = ", ".join(
        f"{k}:{v['bytes']:.2e}B x{int(v['count'])}"
        for k, v in sorted(r.get("collectives", {}).items()))
    return (f"   {r['variant']:14s} compute={t['compute_s']:.3e}s "
            f"memory={t['memory_s']:.3e}s coll={t['collective_s']:.3e}s "
            f"dom={t['dominant']:10s} bound={t['bound_s']:.3e}s\n"
            f"     args/dev={r.get('arg_bytes_per_dev', 0)/2**30:.2f}GiB "
            f"[{coll}]")


def main(paths):
    recs = load(paths)
    groups = defaultdict(list)
    for (arch, shape, mesh, variant), r in recs.items():
        groups[(arch, shape, mesh)].append(r)
    for (arch, shape, mesh), rs in sorted(groups.items()):
        print(f"{arch} x {shape} @ {mesh}")
        base = next((r for r in rs if r["variant"] == "zero"), None)
        for r in sorted(rs, key=lambda r: r["variant"] != "zero"):
            print(fmt(r))
            if (base and r is not base and r["status"] == "ok"
                    and base["status"] == "ok"):
                b0 = base["roofline"]["bound_s"]
                b1 = r["roofline"]["bound_s"]
                if b0 > 0:
                    print(f"     -> bound {b0:.3e}s -> {b1:.3e}s "
                          f"({(b0 - b1) / b0:+.1%} vs zero)")
        print()


def _driver_setup(model: str, m: int, local_steps: int, batch: int,
                  fused: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (DeviceUniformSampler, RoundConfig, fedmom)
    from repro.data import FederatedDataset, synthetic_femnist
    from repro.launch.train import FederatedTrainer

    if model == "lenet":
        from repro.models import small
        clients, _ = synthetic_femnist(n_clients=20, seed=0)
        loss_fn = small.lenet_loss
        w0 = small.lenet_init(jax.random.PRNGKey(0))
    else:
        rng = np.random.default_rng(0)
        d = 32
        clients = []
        for _ in range(20):
            n = int(rng.integers(60, 120))
            x = rng.normal(size=(n, d)).astype(np.float32)
            y = (x @ rng.normal(size=d)).astype(np.float32)
            clients.append({"x": x, "y": y})

        def loss_fn(params, b):
            pred = b["x"] @ params["w"] + params["b"]
            return jnp.mean(jnp.square(pred - b["y"])), {}

        w0 = {"w": jnp.zeros(d), "b": jnp.zeros(())}

    ds = FederatedDataset(clients, seed=1)
    rcfg = RoundConfig(clients_per_round=m, local_steps=local_steps,
                       lr=0.05, placement="mesh", compute_dtype="float32")
    opt = fedmom(eta=2.0, beta=0.9, use_fused_kernel=fused)

    def make():
        return FederatedTrainer(
            loss_fn=loss_fn, server_opt=opt, rcfg=rcfg,
            dataset=FederatedDataset(list(ds.data), seed=1),
            sampler=DeviceUniformSampler(ds.population(), m, seed=2),
            state=opt.init(w0), local_batch=batch)
    return make


def _lane_args(argv, flag: str, smoke: bool = False):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(flag, action="store_true")
    ap.add_argument("--model", choices=("lenet", "linreg"), default="lenet")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=10)
    ap.add_argument("--chunk-rounds", type=int, default=25)
    ap.add_argument("--cache-clients", type=int, default=None,
                    help="shard-cache capacity for the streaming lane "
                         "(default: one chunk's worst case, m*chunk_rounds)")
    ap.add_argument("--fused-server", action="store_true",
                    help="route FedMom through the fused Pallas update")
    if smoke:
        ap.add_argument("--smoke", action="store_true",
                        help="tiny config for the fast CI lane (sanity, "
                             "not numbers)")
        ap.add_argument("--emit-bench", metavar="PATH", default=None,
                        help="write the bucketed-vs-padded numbers as a "
                             "JSON snapshot (the committed BENCH_<pr>.json "
                             "perf record)")
    return ap.parse_args(argv)


def _time_lanes(args, lanes):
    """Warmup + timed pass per lane; returns (ms/round, final-loss) dicts.

    ``lanes``: ordered {name: run_fn(trainer, n_rounds)}.  jit caches live
    on the trainer's own wrappers, so warmup and the timed pass must share
    ONE trainer (reset state between); the warmup covers the full schedule
    because a ragged last chunk is its own compile.
    """
    import time

    import jax

    make = _driver_setup(args.model, args.m, args.local_steps, args.batch,
                         args.fused_server)
    width = max(len(n) for n in lanes)
    ms, final, trainers = {}, {}, {}
    for name, run_fn in lanes.items():
        def go(tr, n):
            run_fn(tr, n)
            jax.tree.leaves(tr.state.w)[0].block_until_ready()
        tr = make()
        init_state = tr.server_opt.init(tr.state.w)
        go(tr, args.rounds)
        tr.state, tr.history = init_state, []
        t0 = time.perf_counter()
        go(tr, args.rounds)
        ms[name] = (time.perf_counter() - t0) / args.rounds
        final[name] = tr.history[-1]["loss"]
        trainers[name] = tr
        print(f"  {name:{width}s} {ms[name] * 1e3:8.3f} ms/round "
              f"({args.rounds} rounds, {args.model}, M={args.m}, "
              f"H={args.local_steps}, b={args.batch})")
    return ms, final, trainers


def bench_drivers(argv):
    """Python-loop driver vs scanned multi-round driver, wall-clock/round."""
    from repro.launch.plan import ExecutionPlan

    args = _lane_args(argv, "--drivers")
    scanned = ExecutionPlan(plane="scanned", chunk_rounds=args.chunk_rounds)
    ms, _, _ = _time_lanes(args, {
        "python-loop": lambda tr, n: tr.run(n, verbose=False),
        "scanned": lambda tr, n: tr.run(n, plan=scanned, verbose=False),
    })
    py, sc = ms["python-loop"], ms["scanned"]
    print(f"  scanned removes {(py - sc) * 1e3:.3f} ms/round of host "
          f"overhead ({py / sc:.2f}x speedup at this round size)")


def bench_data_plane(argv):
    """Prefetch-queue vs device-resident vs shard-cached streaming data
    planes, ms/round at equal trajectory (+ cache hit-rate), plus the
    warm-TrainSession rerun (cross-call cache persistence)."""
    import time

    from repro.launch.plan import CacheSpec, ExecutionPlan

    args = _lane_args(argv, "--data-plane", smoke=True)
    if args.smoke:
        args.model, args.rounds, args.chunk_rounds = "linreg", 12, 4
    streaming = ExecutionPlan(plane="streaming",
                              chunk_rounds=args.chunk_rounds,
                              cache=CacheSpec(clients=args.cache_clients))

    def run_streaming_cold(tr, n):
        # the session cache persists across run() calls now, so the timed
        # pass would otherwise be warm from the warmup run — drop residency
        # to keep this row the COLD plane-choice number (the warm-session
        # row below isolates the persistence win)
        tr.session.shard_cache = None
        tr.run(n, plan=streaming, verbose=False)

    ms, final, trainers = _time_lanes(args, {
        "prefetch-queue": lambda tr, n: tr.run(
            n, plan=ExecutionPlan(plane="scanned",
                                  chunk_rounds=args.chunk_rounds),
            verbose=False),
        "device-resident": lambda tr, n: tr.run(
            n, plan=ExecutionPlan(plane="device",
                                  chunk_rounds=args.chunk_rounds),
            verbose=False),
        "shard-cached": run_streaming_cold,
    })
    # all lanes run (seed, t, client_id)-keyed draws => one trajectory
    drift = max(abs(final[a] - final[b])
                for a in final for b in final if a < b)
    assert drift < 1e-4, f"data planes diverged: {final}"
    pq, dev = ms["prefetch-queue"], ms["device-resident"]
    print(f"  device-resident removes {(pq - dev) * 1e3:.3f} ms/round of "
          f"host data-plane work ({pq / dev:.2f}x at this round size; "
          f"trajectories identical, final-loss drift {drift:.2e})")
    cache = trainers["shard-cached"].stream_cache
    sds = trainers["shard-cached"].streaming_dataset()
    print(f"  shard-cached   {cache.slots} slots "
          f"({cache.nbytes / 2**20:.2f} MiB of "
          f"{sds.packed_nbytes / 2**20:.2f} MiB packed), "
          f"hit-rate {cache.hit_rate:.1%}, {cache.evictions} evictions, "
          f"{ms['shard-cached'] / dev:.2f}x device-resident ms/round at "
          f"equal trajectory")

    # warm TrainSession: a fresh trainer, one cold run() (uploads + compile)
    # then a rerun on the same session — the persistent cache re-uploads
    # nothing for already-resident clients
    make = _driver_setup(args.model, args.m, args.local_steps, args.batch,
                         args.fused_server)
    tr = make()
    init_state = tr.server_opt.init(tr.state.w)
    t0 = time.perf_counter()
    tr.run(args.rounds, plan=streaming, verbose=False)
    cold_s = time.perf_counter() - t0
    cache = tr.stream_cache
    cold_up = cache.misses
    tr.state, tr.history = init_state, []
    t0 = time.perf_counter()
    tr.run(args.rounds, plan=streaming, verbose=False)
    warm_s = time.perf_counter() - t0
    warm_up = cache.misses - cold_up
    saved = 1.0 - warm_up / max(cold_up, 1)
    print(f"  warm-session   rerun on one TrainSession: {cold_up} shard "
          f"uploads cold -> {warm_up} warm ({saved:.0%} upload savings), "
          f"{cold_s / args.rounds * 1e3:.3f} -> "
          f"{warm_s / args.rounds * 1e3:.3f} ms/round (cold includes "
          f"compile)")
    bench_tiered_cache(args)
    snap = bench_bucketed(args)
    if getattr(args, "emit_bench", None):
        with open(args.emit_bench, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"  bench snapshot -> {args.emit_bench}")


def _zipf_clients(args, K=None, d=None, n_top=None):
    """Zipfian-n_k linreg corpus — the skew the n_k-tiered cache (and the
    bucketed compute) target.  Returns (clients, counts, d)."""
    import numpy as np

    rng = np.random.default_rng(0)
    smoke = getattr(args, "smoke", False)
    K = K if K is not None else (24 if smoke else 60)
    d = d if d is not None else (16 if smoke else 32)
    n_top = n_top if n_top is not None else (256 if smoke else 1024)
    counts = [max(2, int(n_top / (r + 1) ** 1.2)) for r in range(K)]
    clients = []
    for n in counts:
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (x @ rng.normal(size=d)).astype(np.float32)
        clients.append({"x": x, "y": y})
    return clients, counts, d


def _zipf_trainer(args, clients, d, m=None, local_batch=2):
    import jax.numpy as jnp

    from repro.core import DeviceUniformSampler, RoundConfig, fedmom
    from repro.data import FederatedDataset
    from repro.launch.train import FederatedTrainer

    m = m if m is not None else args.m
    ds = FederatedDataset([dict(c) for c in clients], seed=1)
    rcfg = RoundConfig(clients_per_round=m,
                       local_steps=args.local_steps, lr=0.05,
                       placement="mesh", compute_dtype="float32")
    opt = fedmom(eta=2.0, beta=0.9)
    w0 = {"w": jnp.zeros(d), "b": jnp.zeros(())}
    return FederatedTrainer(
        loss_fn=_linreg_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=DeviceUniformSampler(ds.population(), m, seed=2),
        state=opt.init(w0), local_batch=local_batch)


def _linreg_loss(params, b):
    import jax.numpy as jnp

    pred = b["x"] @ params["w"] + params["b"]
    return jnp.mean(jnp.square(pred - b["y"])), {}


def bench_bucketed(args):
    """n_max-padded vs n_k-shaped streaming PIPELINE at one equal cache
    byte budget, on a Zipfian-n_k corpus with K >> cache capacity.

    The padded lane is ``CacheSpec(tiers=1)``: every cache slot is padded
    to n_max rows, so the byte budget holds only a handful of clients and
    every LRU miss re-uploads an n_max-row shard; the compute is the
    C-wide switch-under-vmap gather.  The bucketed lane gives the SAME
    byte budget to the n_k-tiered layout (``CacheSpec(bucketed=True)``):
    slots are tier-sized, the same bytes hold an order of magnitude more
    clients (under skew, usually the whole population), and each round
    runs sized per-tier launches over staged minibatch indices
    (``scan_rounds_bucketed`` fused-concat form) — no in-scan PRNG, no
    switch, no n_max-shaped fills.  That is the paper's on-device claim:
    a 4-sample crowdsensing client should never move or compute
    n_max-shaped data.  Asserts equal trajectory and that the n_k-shaped
    pipeline is no slower (with timing slack for the smoke sizes);
    returns the snapshot dict ``--emit-bench`` records."""
    import time

    import jax

    from repro.launch.plan import CacheSpec, ExecutionPlan

    smoke = bool(getattr(args, "smoke", False))
    # corpus/budget knobs are the lane's own (not the lenet driver args):
    # K >> padded capacity so the uniform layout churns, n_top a power of
    # two so the uniform slot is exactly n_max rows, and the budget is one
    # chunk's worst-case PADDED working set — the least memory the uniform
    # layout can run with, handed identically to both lanes
    K, d, n_top, m, cr = ((96, 32, 1024, 4, 4) if smoke
                          else (512, 64, 8192, 8, 8))
    clients, counts, d = _zipf_clients(args, K=K, d=d, n_top=n_top)
    row_nbytes = d * 4 + 4                     # one x row + one y scalar
    budget = m * cr * max(counts) * row_nbytes
    results = {}
    for name, tiers, bucketed in (("padded", 1, False),
                                  ("bucketed", None, True)):
        tr = _zipf_trainer(args, clients, d, m=m,
                           local_batch=4 if smoke else 8)
        plan = ExecutionPlan(
            plane="streaming", chunk_rounds=cr,
            cache=CacheSpec(bytes=budget, tiers=tiers, bucketed=bucketed))

        def go(n):
            tr.run(n, plan=plan, verbose=False)
            jax.tree.leaves(tr.state.w)[0].block_until_ready()

        init_state = tr.server_opt.init(tr.state.w)
        go(args.rounds)                     # warmup: compiles + uploads
        tr.state, tr.history = init_state, []
        up0 = tr.stream_cache.misses
        t0 = time.perf_counter()
        go(args.rounds)
        results[name] = ((time.perf_counter() - t0) / args.rounds,
                         tr.history[-1]["loss"], tr.stream_cache,
                         (tr.stream_cache.misses - up0) / args.rounds)
    (pms, ploss, pcache, pup) = results["padded"]
    (bms, bloss, bcache, bup) = results["bucketed"]
    drift = abs(ploss - bloss)
    assert drift < 1e-4, \
        f"bucketed/padded trajectories diverged: {ploss} {bloss}"
    # "no slower" with slack for single-shot wall-clock noise; the real
    # win is the removed n_max-shaped fill traffic + in-scan PRNG/switch,
    # which dwarfs timer jitter at the non-smoke sizes
    assert bms <= pms * 1.25, \
        (f"n_k-shaped pipeline slower than padded: {bms * 1e3:.3f} vs "
         f"{pms * 1e3:.3f} ms/round")
    print(f"  bucketed       Zipfian n_k (K={K}, n_max={max(counts)}, "
          f"{len(bcache.tier_sizes)} tiers, "
          f"{budget / 2**20:.1f} MiB budget): "
          f"{pms * 1e3:.3f} ms/round padded -> {bms * 1e3:.3f} "
          f"n_k-shaped ({pms / bms:.2f}x); uploads/round "
          f"{pup:.1f} -> {bup:.1f}, hit-rate {pcache.hit_rate:.1%} -> "
          f"{bcache.hit_rate:.1%}, final-loss drift {drift:.2e}")
    return {
        "bench": "bucketed_vs_padded_zipf",
        "config": {"model": "linreg", "n_clients": K,
                   "n_max": max(counts), "d": d, "rounds": args.rounds,
                   "chunk_rounds": cr, "m": m,
                   "local_steps": args.local_steps,
                   "cache_budget_bytes": budget, "smoke": smoke},
        "tiers": len(bcache.tier_sizes),
        "padded_ms_per_round": round(pms * 1e3, 4),
        "bucketed_ms_per_round": round(bms * 1e3, 4),
        "speedup": round(pms / bms, 4),
        "padded_uploads_per_round": round(pup, 2),
        "bucketed_uploads_per_round": round(bup, 2),
        "padded_hit_rate": round(pcache.hit_rate, 4),
        "bucketed_hit_rate": round(bcache.hit_rate, 4),
        "final_loss_drift": float(drift),
    }


def bench_tiered_cache(args):
    """Tiered vs uniform slot sizing on one Zipfian-n_k corpus: the same
    keyed trajectory, strictly smaller cache device bytes under skew (the
    n_k-tiered ShardCache row; asserts the footprint win so the CI smoke
    lane catches a regression)."""
    from repro.launch.plan import CacheSpec, ExecutionPlan

    clients, counts, d = _zipf_clients(args)
    K = len(counts)
    results = {}
    for name, tiers in (("tiered", None), ("uniform", 1)):
        tr = _zipf_trainer(args, clients, d)
        tr.run(args.rounds,
               plan=ExecutionPlan(plane="streaming",
                                  chunk_rounds=args.chunk_rounds,
                                  cache=CacheSpec(tiers=tiers)),
               verbose=False)
        results[name] = (tr.stream_cache, tr.history[-1]["loss"])
    (tc, tl), (uc, ul) = results["tiered"], results["uniform"]
    drift = abs(tl - ul)
    assert drift < 1e-4, f"tiered/uniform trajectories diverged: {tl} {ul}"
    assert tc.nbytes < uc.nbytes, \
        f"tiered cache not smaller: {tc.nbytes} vs {uc.nbytes}"
    print(f"  tiered-slots   Zipfian n_k (K={K}, n_max={max(counts)}): "
          f"cache {tc.nbytes / 2**20:.3f} MiB over {len(tc.tier_sizes)} "
          f"tiers vs {uc.nbytes / 2**20:.3f} MiB uniform "
          f"({1 - tc.nbytes / uc.nbytes:.0%} smaller), hit-rate "
          f"{tc.hit_rate:.1%} vs {uc.hit_rate:.1%}, final-loss drift "
          f"{drift:.2e}")


def bench_secure(argv):
    """Plain fp32 vs open-ring vs masked secure aggregation, ms/round at
    equal trajectory on the scanned plane.

    The three lanes train the same keyed trajectory; only step 4's
    reduction differs.  ``open`` is the fixed-point ring with no masks
    (the certification reference), ``masked`` adds the [C, C, ...]
    pairwise PRG grid — the full transport simulation.  The lane asserts
    masked == open BIT-equal (drift exactly 0.0 bits — the ring
    cancellation guarantee, not a tolerance) and plain-vs-ring within
    quantization tolerance; returns/emits the snapshot with the
    masked-over-open overhead, the per-PR BENCH_8.json record."""
    import numpy as np

    import jax

    from repro.core.secure_agg import SecureAggSpec
    from repro.launch.plan import ExecutionPlan

    args = _lane_args(argv, "--secure", smoke=True)
    if args.smoke:
        args.model, args.rounds, args.chunk_rounds = "linreg", 12, 4
    specs = {"plain": None,
             "open": SecureAggSpec(masked=False, seed=0),
             "masked": SecureAggSpec(masked=True, seed=0)}

    def lane(spec):
        plan = ExecutionPlan(plane="scanned",
                             chunk_rounds=args.chunk_rounds, secure=spec)
        return lambda tr, n: tr.run(n, plan=plan, verbose=False)

    ms, final, trainers = _time_lanes(
        args, {name: lane(spec) for name, spec in specs.items()})

    def wflat(tr):
        return np.concatenate([np.ravel(np.asarray(x))
                               for x in jax.tree.leaves(tr.state.w)])

    # masked == open is the guarantee this whole PR certifies: exact ring
    # cancellation, zero drift in BITS, not "close"
    drift_bits = int((wflat(trainers["masked"])
                      != wflat(trainers["open"])).sum())
    assert drift_bits == 0, \
        f"masked diverged from open ring in {drift_bits} params"
    quant_drift = float(abs(final["plain"] - final["open"]))
    assert quant_drift < 1e-3, \
        f"ring quantization drift too large: {quant_drift}"
    overhead = ms["masked"] / ms["open"]
    ring_overhead = ms["open"] / ms["plain"]
    print(f"  masked transport costs {overhead:.2f}x the open ring "
          f"({(ms['masked'] - ms['open']) * 1e3:.3f} ms/round for the "
          f"[C, C, ...] pair grid at M={args.m}); ring-vs-plain "
          f"{ring_overhead:.2f}x, quantization drift {quant_drift:.2e}, "
          f"masked-vs-open drift {drift_bits} bits")
    snap = {
        "bench": "secure_masked_vs_open",
        "config": {"model": args.model, "rounds": args.rounds,
                   "chunk_rounds": args.chunk_rounds, "m": args.m,
                   "local_steps": args.local_steps,
                   "frac_bits": specs["masked"].frac_bits,
                   "smoke": bool(getattr(args, "smoke", False))},
        "plain_ms_per_round": round(ms["plain"] * 1e3, 4),
        "open_ms_per_round": round(ms["open"] * 1e3, 4),
        "masked_ms_per_round": round(ms["masked"] * 1e3, 4),
        "masked_overhead_x": round(overhead, 4),
        "ring_overhead_x": round(ring_overhead, 4),
        "masked_open_drift_bits": drift_bits,
        "quantization_drift": quant_drift,
    }
    if getattr(args, "emit_bench", None):
        with open(args.emit_bench, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"  bench snapshot -> {args.emit_bench}")
    return snap


def bench_mesh(argv):
    """ms/round vs data-parallel device count on the mesh-sharded device
    plane, at equal trajectory.

    The 1-device row is ``mesh=None`` — the pre-mesh engine, the baseline
    every sharded row's final loss is drift-checked against (the psum
    reassociates the fp32 cohort reduction, so the check is a tolerance,
    not bitwise).  No speedup assert: on forced host devices the psum is
    a CPU-emulated collective whose cost swamps the tiny per-shard
    compute — the lane records the scaling shape, real wins need real
    chips.  Returns/emits the BENCH_10.json snapshot."""
    import os

    import jax

    from repro.launch.mesh import MeshSpec
    from repro.launch.plan import ExecutionPlan

    args = _lane_args(argv, "--mesh", smoke=True)
    if args.m == 2:
        args.m = 8              # parser default is the tiny driver lane's;
        # this lane wants a cohort every tested mesh size divides
    if args.smoke:
        args.model, args.rounds, args.chunk_rounds = "linreg", 12, 4
    counts = [n for n in (1, 2, 4, 8)
              if n <= jax.device_count() and args.m % n == 0]

    def lane(n):
        plan = ExecutionPlan(
            plane="device", chunk_rounds=args.chunk_rounds,
            mesh=None if n == 1 else MeshSpec(devices=n))
        return lambda tr, k: tr.run(k, plan=plan, verbose=False)

    ms, final, _ = _time_lanes(args, {f"{n}-dev": lane(n) for n in counts})
    drift = max(abs(final[f"{n}-dev"] - final["1-dev"]) for n in counts)
    assert drift < 1e-4, f"sharded trajectories diverged: {final}"
    base = ms["1-dev"]
    rel = {n: ms[f"{n}-dev"] / base for n in counts}
    print(f"  mesh-sharded   cohort M={args.m} over {counts} device(s): "
          + ", ".join(f"{n}-dev {rel[n]:.2f}x" for n in counts)
          + f" vs 1-dev ms/round; final-loss drift {drift:.2e} "
          f"(host-emulated collectives — shape record, not a speedup "
          f"claim)")
    snap = {
        "bench": "mesh_sharded_round",
        "config": {"model": args.model, "rounds": args.rounds,
                   "chunk_rounds": args.chunk_rounds, "m": args.m,
                   "local_steps": args.local_steps,
                   "device_counts": counts,
                   "xla_flags": os.environ.get("XLA_FLAGS", ""),
                   "smoke": bool(getattr(args, "smoke", False))},
        "ms_per_round": {str(n): round(ms[f"{n}-dev"] * 1e3, 4)
                         for n in counts},
        "relative_to_1dev": {str(n): round(rel[n], 4) for n in counts},
        "final_loss_drift": float(drift),
    }
    if getattr(args, "emit_bench", None):
        with open(args.emit_bench, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"  bench snapshot -> {args.emit_bench}")
    return snap


if __name__ == "__main__":
    if "--mesh" in sys.argv[1:]:
        # XLA_FLAGS must be final before jax initializes its backends: force
        # 8 host devices when the user didn't pin a count, and turn on the
        # latency-hiding scheduler so the psum overlaps with per-shard
        # compute where XLA can manage it (async collectives are default-on
        # in this XLA; its old opt-in flag no longer parses)
        import os

        _flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in _flags:
            _flags += " --xla_force_host_platform_device_count=8"
        _f = "--xla_gpu_enable_latency_hiding_scheduler=true"
        if _f not in _flags:
            _flags += " " + _f
        os.environ["XLA_FLAGS"] = _flags.strip()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--drivers" in sys.argv[1:]:
        bench_drivers(sys.argv[1:])
    elif "--data-plane" in sys.argv[1:]:
        bench_data_plane(sys.argv[1:])
    elif "--secure" in sys.argv[1:]:
        bench_secure(sys.argv[1:])
    elif "--mesh" in sys.argv[1:]:
        bench_mesh(sys.argv[1:])
    else:
        main(sys.argv[1:] or ["results/hillclimb.jsonl"])
