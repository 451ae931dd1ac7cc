"""Quickstart: the paper's setting in miniature.

Trains LeNet on synthetic non-IID FEMNIST with M=2 active clients per round
(exactly §5.1's configuration) and compares FedAvg vs FedMom server
optimizers.  Runs in ~1 minute on CPU.

    PYTHONPATH=src python examples/quickstart.py [--rounds 150] [--plan auto]

Execution is declared with ``--plan`` (see the table in ``--help``): every
plane trains the SAME trajectory, only the engine/data placement differs.
``--plan auto`` lets the system resolve the plane from the memory budget
(``--memory-budget-mb``) vs the packed corpus and the chunk working set —
the decision is printed and logged.  The legacy ``--scanned`` /
``--device-data`` / ``--stream-data`` flags remain as aliases.
``--fused-server`` independently routes FedMom through the fused Pallas
server update (compiled on a TPU; interpret mode on the CPU).  ``--hetero``
additionally gives each client a random H_k <= H of local work per round
(the straggler / partial-work scenario).

Production-fleet conditions are declared with the scenario flags
(``--dropout`` / ``--deadline`` / ``--adaptive-cohort``; see the scenario
table in ``--help``) and run identically on every plane; ``--provider``
swaps the materialized FEMNIST corpus for a lazily-synthesized Zipf
linear-regression fleet of that many clients (streaming plane — host RAM
holds a count vector, never the corpus).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DeviceUniformSampler,
    RoundConfig,
    SecureAggSpec,
    UniformSampler,
    dp,
    fedavg,
    fedmom,
)
from repro.data import (FederatedDataset, StreamingFederatedDataset,
                        synthetic_femnist)
from repro.launch.mesh import MeshSpec
from repro.launch.plan import CacheSpec, ExecutionPlan
from repro.launch.train import FederatedTrainer
from repro.models import small
from repro.data.stream import DiskShardProvider
from repro.scenario import (AdaptiveCohort, LatencyStragglers, ScenarioSpec,
                            UniformDropout, zipf_linreg_provider)
from repro.traces import TraceSpec, record_trace

PLAN_TABLE = """\
plan selection (--plan):
  value       engine                        data plane           pick when
  ---------   ---------------------------   ------------------   --------------------------------------------
  auto        resolved at run time          resolved             let the budget rule decide (decision logged)
  per-round   one jitted round_step/round   host assembly        every round needs an eval / a host decision
  scanned     chunked lax.scan + prefetch   host assembly        corpus unbounded, or a host-only sampler
  device      fused sample+gather scan      device-resident      packed K*n_max corpus fits device memory
  streaming   fused scan over shard cache   n_k-tiered LRU cache corpus > device memory, chunk set fits cache

auto rule: packed_nbytes <= budget -> device; else chunk working set
(clients_per_round * chunk_rounds clients, priced at the ACTUAL tiered
cache bytes) <= budget -> streaming; else scanned.  Fused planes need a
Device* sampler (DeviceSampleable / KeyedReplayable capabilities).

--mesh-devices N shards any fused plane over an N-way data mesh
(ExecutionPlan(mesh=MeshSpec(devices=N))): the round cohort, its step
masks/weights and the minibatch index stacks split across devices, the
weighted delta aggregates with a psum (server state replicated), the
streaming plane runs one full-capacity cache shard per device
(client -> shard by cid % N), and the auto rule re-prices the device
plane at ceil(packed/N) per device — the flip is audited in the plan
log with mesh_shape / per_device_nbytes.  Same trajectory within fp32
reduction-order tolerance (secure-agg stays bit-exact: uint32 ring).
Needs N visible devices: on CPU, set
XLA_FLAGS=--xla_force_host_platform_device_count=N.  Scaling-shape
record: benchmarks/perf_compare.py --mesh --emit-bench BENCH_10.json.

streaming cache slots are n_k-TIERED (CacheSpec.tiers / --cache-tiers):
clients bucket into power-of-two size tiers so small clients never pay
n_max-row padding — several-fold fewer cache device bytes under skewed
n_k, same trajectory bit for bit.  Default: one tier per natural
power-of-two bucket; --cache-tiers 1 forces the uniform n_max-slot
layout; --cache-tiers m caps the tier count (smallest buckets merge
upward).

--bucketed additionally makes the COMPUTE n_k-shaped (streaming plane
only): each round's cohort is regrouped by tier and dispatched as one
sized launch per occupied tier, so small clients stop paying
n_max-shaped gathers and the cache fills n_k-sized slots instead of
n_max ones.  Same trajectory (bit-equal at one occupied tier,
fp32-reduction-order tolerance across several).  --chunk-rounds auto
sizes the scan chunk from the measured per-dispatch overhead instead of
a fixed guess.  Perf snapshots: benchmarks/perf_compare.py --data-plane
--emit-bench BENCH_<pr>.json records the bucketed-vs-padded pipeline
win at Zipf-skewed n_k (committed per PR; CI re-checks a smoke run).

scenario simulation (repro.scenario; composable, plane-agnostic,
bit-reproducible — every fate is keyed by (seed, tag, round, client)):
  flag                    fleet condition                aggregation effect
  ---------------------   ----------------------------   -------------------------------------------
  --dropout RATE          i.i.d. mid-round dropouts      dropped client keeps its partial H_k steps;
                                                         a 0-step dropout contributes zero (eq. 3)
  --deadline SECONDS      round deadline + lognormal     slow device contributes floor(deadline/step)
                          per-device step latency        of its H steps, never stalls the round
  --adaptive-cohort GOAL  server over-selection toward   active cohort m_t grows when observed
                          GOAL completed clients/round   completion drops (EMA; resumable state)
  --provider K            lazily-synthesized Zipf fleet  identical trajectory to the same corpus
                          of K clients (ShardProvider)   materialized; host holds [K] counts only
Scenario runs log a per-round "completed" metric (clients that finished
any work).  The dropout sweep benchmark: benchmarks/fig6_robustness.py
--scenario --emit-bench BENCH_7.json (eq. (3) keeps FedMom's final loss
stable as the dropout rate climbs).

fleet traces (repro.traces; record reality once, replay it anywhere):
  flag                    what it does
  ---------------------   -------------------------------------------
  --record-trace PATH     record the declared scenario's per-round
                          cohorts / step caps / cutoffs into a
                          versioned FleetTrace (PATH.npz + PATH.json)
                          before training starts
  --replay-trace PATH     replay a recorded trace through the same
                          eq. (3) step-mask machinery — bit-equal to
                          the originating run on every plane; rounds
                          past the recorded horizon raise (explicit
                          wrap/clamp policies live on TraceSpec)
  --leaf-dir PATH         train from an on-disk corpus directory via
                          DiskShardProvider (mmap-backed npy-packed /
                          npz-per-client manifests, or a raw LEAF json
                          directory; streaming plane)
Trace snapshot: benchmarks/fig6_robustness.py --trace --emit-bench
BENCH_9.json (record-on-synthetic -> replay-on-disk-corpus, drift must
be 0 bits; CI re-checks a smoke run).

privacy (--secure-agg / --dp-clip / --dp-noise): --secure-agg runs the
round's aggregation through the compiled uint32-ring pairwise-masking
layer (repro.core.SecureAggSpec) — the server only materializes masked
per-client messages and their dropout-recovered sum, and the masked
trajectory is BIT-equal to the open one (masks cancel exactly in the
ring; --secure-frac-bits sets the fixed-point precision).  Composes
with every plane and with the scenario dropouts above.  --dp-clip C
[--dp-noise Z] additionally wraps the server optimizer in central DP:
the aggregate is clipped to L2 norm C and seeded Gaussian noise of
stddev C*Z is added before the update (DP-FedAvg / DP-FedMom; noise is
a pure function of (seed, round), so DP runs stay plane-independent
and resumable).  Overhead record: benchmarks/perf_compare.py --secure
--emit-bench BENCH_8.json (masked-vs-open ms/round at equal — bit-equal
— trajectory; CI re-checks a smoke run)."""


def main():
    ap = argparse.ArgumentParser(
        epilog=PLAN_TABLE,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--clients", type=int, default=60)
    ap.add_argument("--m", type=int, default=2, help="active clients/round")
    ap.add_argument("--local-steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--plan", default=None,
                    choices=("auto", "per-round", "scanned", "device",
                             "streaming"),
                    help="execution plan (see table below); default: "
                         "per-round, or whatever a legacy flag selects")
    ap.add_argument("--memory-budget-mb", type=float, default=None,
                    help="device memory budget for --plan auto (default: "
                         "what the backend reports; unbounded on CPU)")
    ap.add_argument("--scanned", action="store_true",
                    help="legacy alias for --plan scanned")
    ap.add_argument("--device-data", action="store_true",
                    help="legacy alias for --plan device")
    ap.add_argument("--stream-data", action="store_true",
                    help="legacy alias for --plan streaming")
    ap.add_argument("--cache-clients", type=int, default=None,
                    help="shard-cache capacity in clients (default: one "
                         "chunk's worst case, m * chunk_rounds)")
    ap.add_argument("--cache-tiers", type=int, default=None,
                    help="max n_k slot-size tiers for the shard cache "
                         "(default: every natural power-of-two bucket; "
                         "1 = uniform n_max slots)")
    ap.add_argument("--bucketed", action="store_true",
                    help="n_k-bucketed compute: one sized launch per "
                         "occupied cache tier (streaming plane only)")
    ap.add_argument("--mesh-devices", type=int, default=None, metavar="N",
                    help="shard the fused planes over an N-way data mesh "
                         "(cohort split + psum aggregation; needs N "
                         "visible devices — on CPU force them with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--fused-server", action="store_true",
                    help="route FedMom through the fused Pallas update "
                         "(compiled on TPU; interpret mode — slower — on "
                         "CPU)")
    ap.add_argument("--chunk-rounds", default=25,
                    type=lambda s: s if s == "auto" else int(s),
                    help="rounds per jitted scan chunk, or 'auto' to size "
                         "from the measured dispatch overhead")
    ap.add_argument("--hetero", action="store_true",
                    help="random per-client local work H_k <= H per round")
    ap.add_argument("--dropout", type=float, default=None, metavar="RATE",
                    help="scenario: i.i.d. mid-round dropout rate in [0,1]")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="scenario: round deadline in seconds (lognormal "
                         "per-device step latency around 1s/step)")
    ap.add_argument("--adaptive-cohort", type=int, default=None,
                    metavar="GOAL",
                    help="scenario: grow/shrink the active cohort toward "
                         "GOAL completed clients per round")
    ap.add_argument("--scenario-seed", type=int, default=0,
                    help="seed keying every scenario fate draw")
    ap.add_argument("--provider", type=int, default=None, metavar="K",
                    help="train a lazily-synthesized Zipf linreg fleet of "
                         "K clients via a ShardProvider (streaming plane) "
                         "instead of materialized FEMNIST")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="record the declared scenario's per-round "
                         "cohorts/caps into a versioned FleetTrace at "
                         "PATH (.npz + .json) before training")
    ap.add_argument("--replay-trace", default=None, metavar="PATH",
                    help="replay a recorded FleetTrace through the eq. "
                         "(3) step masks (bit-equal to the originating "
                         "run on every plane)")
    ap.add_argument("--leaf-dir", default=None, metavar="PATH",
                    help="train from an on-disk corpus / LEAF json "
                         "directory via DiskShardProvider (mmap-backed; "
                         "streaming plane)")
    ap.add_argument("--secure-agg", action="store_true",
                    help="aggregate under compiled secure aggregation "
                         "(uint32-ring pairwise masks; bit-equal to the "
                         "open plane)")
    ap.add_argument("--secure-frac-bits", type=int, default=20,
                    help="fixed-point fractional bits for the masking "
                         "ring (values exact on a 2^-frac_bits grid)")
    ap.add_argument("--dp-clip", type=float, default=None, metavar="C",
                    help="central DP: clip the aggregate to L2 norm C "
                         "before the server update (DP-FedAvg/DP-FedMom)")
    ap.add_argument("--dp-noise", type=float, default=0.0, metavar="Z",
                    help="central DP noise multiplier: Gaussian stddev "
                         "C*Z added to the clipped aggregate (needs "
                         "--dp-clip; seeded per round)")
    args = ap.parse_args()

    plane = args.plan or ("streaming" if args.stream_data or args.provider
                          or args.leaf_dir
                          else "device" if args.device_data
                          else "scanned" if args.scanned else "per-round")
    budget = (int(args.memory_budget_mb * 2**20)
              if args.memory_budget_mb is not None else None)
    scenario = None
    if (args.dropout is not None or args.deadline is not None
            or args.adaptive_cohort is not None
            or args.replay_trace is not None):
        scenario = ScenarioSpec(
            dropout=(UniformDropout(rate=args.dropout)
                     if args.dropout is not None else None),
            stragglers=(LatencyStragglers(deadline_s=args.deadline)
                        if args.deadline is not None else None),
            cohort=(AdaptiveCohort(goal=args.adaptive_cohort)
                    if args.adaptive_cohort is not None else None),
            trace=(TraceSpec(path=args.replay_trace)
                   if args.replay_trace is not None else None),
            seed=args.scenario_seed)
    secure = (SecureAggSpec(masked=True, seed=0,
                            frac_bits=args.secure_frac_bits)
              if args.secure_agg else None)
    mesh = (MeshSpec(devices=args.mesh_devices)
            if args.mesh_devices is not None else None)
    plan = ExecutionPlan(plane=plane, chunk_rounds=args.chunk_rounds,
                         cache=CacheSpec(clients=args.cache_clients,
                                         tiers=args.cache_tiers,
                                         bucketed=args.bucketed),
                         memory_budget_bytes=budget, scenario=scenario,
                         secure=secure, mesh=mesh)

    if args.provider or args.leaf_dir:
        provider = (DiskShardProvider(args.leaf_dir) if args.leaf_dir
                    else zipf_linreg_provider(args.provider, dim=16,
                                              n_min=4, n_max=64, seed=0))
        ds = StreamingFederatedDataset.from_provider(provider, seed=1)
        pop = ds.population()
        K, M = pop.n_clients, args.m
        d = provider.fields["x"][0][0]

        def loss_fn(params, b):
            pred = b["x"] @ params["w"] + params["b"]
            return jnp.mean(jnp.square(pred - b["y"])), {}

        # held-out eval: a handful of synthesized shards (never cached)
        ev = [provider.shard(cid) for cid in range(min(K, 8))]
        ex = jnp.asarray(np.concatenate([s["x"] for s in ev]))
        ey = jnp.asarray(np.concatenate([s["y"] for s in ev]))

        def eval_fn(state):
            mse = jnp.mean(jnp.square(
                ex @ state.w["w"] + state.w["b"] - ey))
            return {"eval_mse": float(mse)}

        w0 = {"w": jnp.zeros(d), "b": jnp.zeros(())}
    else:
        clients, counts = synthetic_femnist(n_clients=args.clients, seed=0)
        ds = FederatedDataset(clients, seed=1)
        pop = ds.population()
        K, M = pop.n_clients, args.m
        loss_fn = small.lenet_loss

        # held-out eval set: a slice of every client's data
        ex = np.concatenate([c["x"][:5] for c in clients])
        ey = np.concatenate([c["y"][:5] for c in clients])

        def eval_fn(state):
            logits = small.lenet_apply(
                jax.tree.map(lambda x: x.astype(jnp.float32), state.w),
                jnp.asarray(ex))
            acc = float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(ey)))
            return {"eval_acc": acc}

        w0 = small.lenet_init(jax.random.PRNGKey(0))
    rcfg = RoundConfig(clients_per_round=M, local_steps=args.local_steps,
                       lr=args.lr, placement="mesh",
                       compute_dtype="float32")

    if args.record_trace:
        # record what the declared scenario does to the exact cohorts the
        # run below will sample (same keyed sampler: pop, M, seed=2)
        rec = record_trace(scenario if scenario is not None
                           else ScenarioSpec(seed=args.scenario_seed),
                           DeviceUniformSampler(pop, M, seed=2),
                           args.rounds, args.local_steps)
        out = rec.save(args.record_trace)
        print(f"recorded fleet trace: {rec.n_rounds} rounds x m={M} "
              f"({rec.n_events} events, peak m={rec.peak_m}) -> {out}")

    hetero_fn = None
    if args.hetero:
        def hetero_fn(t):
            return np.random.default_rng(1000 + t).integers(
                1, args.local_steps + 1, size=M)

    scen_tag = ""
    if scenario is not None:
        parts = [f"dropout={args.dropout}" if args.dropout is not None
                 else None,
                 f"deadline={args.deadline}s" if args.deadline is not None
                 else None,
                 f"cohort->{args.adaptive_cohort}"
                 if args.adaptive_cohort is not None else None,
                 f"replay={args.replay_trace}"
                 if args.replay_trace is not None else None]
        scen_tag = f" [scenario: {', '.join(p for p in parts if p)}]"
    priv = []
    if args.secure_agg:
        priv.append(f"secure-agg frac_bits={args.secure_frac_bits}")
    if args.dp_clip is not None:
        priv.append(f"dp clip={args.dp_clip} noise={args.dp_noise}")
    if priv:
        scen_tag += f" [{', '.join(priv)}]"

    def privatize(opt):
        if args.dp_clip is None:
            return opt
        return dp(opt, clip=args.dp_clip,
                  noise_multiplier=args.dp_noise, seed=0)

    for name, opt in [("FedAvg (eta=K/M)", privatize(fedavg(eta=K / M))),
                      ("FedMom (eta=K/M, beta=0.9)",
                       privatize(fedmom(eta=K / M, beta=0.9,
                                        use_fused_kernel=args.fused_server))
                       )]:
        print(f"\n=== {name} [plan={plan.plane}]"
              f"{' [hetero H_k]' if args.hetero else ''}{scen_tag} ===")
        # the per-round plane works with the paper's stateful sampler; the
        # compiled/fused planes (and auto, which may resolve to one) need
        # the keyed Device* capabilities — as do trace record/replay runs,
        # whose cohorts must be replayable as pure functions of (seed, t)
        sampler = (UniformSampler(pop, M, seed=2)
                   if plan.plane == "per_round"
                   and not (args.record_trace or args.replay_trace)
                   else DeviceUniformSampler(pop, M, seed=2))
        trainer = FederatedTrainer(
            loss_fn=loss_fn, server_opt=opt, rcfg=rcfg,
            dataset=ds, sampler=sampler, hetero_steps_fn=hetero_fn,
            state=opt.init(w0), local_batch=4 if args.provider else 10)
        hist = trainer.run(args.rounds, plan=plan, log_every=25,
                           eval_fn=eval_fn)
        cache = trainer.stream_cache
        if cache is not None:
            sds = trainer.streaming_dataset()
            print(f"shard cache: {len(cache.resident())}/{K} clients "
                  f"resident in {cache.slots} slots over "
                  f"{len(cache.tier_sizes)} size tier(s) "
                  f"{list(cache.tier_sizes)} "
                  f"({cache.nbytes / 2**20:.2f} MiB of "
                  f"{sds.packed_nbytes / 2**20:.2f} MiB packed), "
                  f"hit-rate {cache.hit_rate:.1%}, "
                  f"{cache.evictions} evictions")
        final = hist[-1]
        quality = (f"mse={final['eval_mse']:.4f}" if "eval_mse" in final
                   else f"acc={final['eval_acc']:.3f}")
        done = (f" completed={final['completed']}/{M}"
                if "completed" in final else "")
        print(f"final: loss={final['loss']:.4f} {quality}{done}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
