"""One run of one cell.

Set-up (everything before the first timed round, and ``setup_s``):

1. JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
   refuse to run without a TPU, or with fewer chips than the cell asks.
2. ``checked_first_chunk``: the clients' data from ``--seed`` (the model's
   generator, sizes fixed by the configuration), the initial weights in
   one jitted call, ``FederatedTrainer`` built from the cell's named parts
   (``chipbench.parts``), and its first chunk through ``run()`` -- the
   window's own call and compiled program.  Those rounds are what the
   reference follows.
3. One warm chunk through ``run()`` that times a round, so that the
   window's round count (whole chunks) fills ``--seconds``.

The window is one ``run(n)`` on the same trainer, rounds back to back, on
cohorts drawn from a seed of their own (new clients, not the warm-up's).
With ``--trace 1`` a short stretch after it runs under the profiler, and
its trace is split by the program's named scopes (``chipbench.scopes``).
Then the program's state is freed and the reference follows the first
chunk.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from types import ModuleType
from typing import List, Optional

import numpy as np

from chipbench import parts, reference, registry, scopes
from chipbench import trace as trace_lib
from chipbench.peaks import Peaks, peaks
from chipbench.population import Seeds

# a traced stretch lasts about this long (whole chunks, at least one)
TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class CompileCounter:
    """Executables loaded (compiled or read from the persistent cache) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.loaded = self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.loaded += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.loaded, self.cache_hits, self.seconds)

    def since(self, snap) -> dict:
        return {"programs_loaded": self.loaded - snap[0],
                "cache_hits": self.cache_hits - snap[1],
                "compiled": (self.loaded - snap[0])
                - (self.cache_hits - snap[1]),
                "seconds": self.seconds - snap[2]}


@dataclass
class Cell:
    """A cell's pieces, found by name (``registry``)."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    model: ModuleType

    @classmethod
    def load(cls, name: str, bench: dict) -> "Cell":
        wl = registry.cell(name, bench)
        config = registry.config(wl["config"])
        return cls(name=name, chips=wl["chips"], config=config,
                   traffic=registry.traffic(wl["traffic"]),
                   limits=registry.limits(name),
                   model=registry.model(config["model"]))


@dataclass
class Context:
    """What a metric reader sees (``metrics/<name>.py``: ``read(ctx)``).
    Traced, ``layers`` is the stretch's split by named scope and ``trace``
    its ``base``; untraced, both are None.  ``events`` are the program's
    event records of the first chunk (``{"event": "pack", ...}``)."""
    cell: str
    config: dict
    traffic: dict
    model: ModuleType
    chips: int
    peaks: Optional[Peaks]
    setup_s: float
    window_s: float
    window_rounds: int
    window_history: List[dict]
    events: List[dict]
    trace: Optional[trace_lib.Reduction] = None
    layers: Optional[scopes.Layers] = None


def configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(registry.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices


def make_plan(plan: dict, **extra):
    from repro.launch.mesh import MeshSpec
    from repro.launch.plan import CacheSpec, ExecutionPlan

    mesh = plan.get("mesh_devices")
    return ExecutionPlan(plane=plan["plane"],
                         chunk_rounds=plan["chunk_rounds"],
                         cache=CacheSpec(**plan.get("cache", {})),
                         mesh=MeshSpec(devices=mesh) if mesh else None,
                         **extra)


def build(cell: Cell, ctx: parts.Ctx, clients, params):
    """The system under test, built through its public API from the
    cell's named parts; (trainer, plan)."""
    from repro.core import RoundConfig
    from repro.data import FederatedDataset
    from repro.launch.train import FederatedTrainer

    got = parts.program(cell.config, cell.traffic, ctx)
    rcfg = RoundConfig(clients_per_round=ctx.clients_per_round,
                       local_steps=ctx.local_steps,
                       lr=cell.config["client"]["lr"],
                       compute_dtype=cell.config["precision"]["dtype"],
                       **got["rcfg"])
    opt = got["trainer"].pop("server_opt")
    tr = FederatedTrainer(
        loss_fn=cell.model.program_loss(), server_opt=opt, rcfg=rcfg,
        dataset=FederatedDataset(clients, seed=ctx.seeds.minibatch),
        state=opt.init(params), local_batch=ctx.local_batch,
        **got["trainer"])
    return tr, make_plan(cell.traffic["plan"], **got["plan"])


def _host(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _run(tr, n: int, plan) -> List[dict]:
    """``tr.run(n)``; the records it appended, once the state is ready."""
    import jax

    start = len(tr.history)
    tr.run(n, plan=plan, verbose=False)
    jax.block_until_ready(tr.state.w)
    return [r for r in tr.history[start:] if "event" not in r]


@dataclass
class FirstChunk:
    """Set-up through the first chunk: the trainer the window drives, the
    clients' data, and what the program read in that chunk."""
    trainer: object
    plan: object
    clients: list
    counts: np.ndarray
    seeds: Seeds
    program: dict        # "loss", "dnorm" per round; "w0", "w" (host)
    events: List[dict]   # the program's event records, pack among them


def checked_first_chunk(cell: Cell, seed: int, log) -> FirstChunk:
    """The clients' data from ``seed`` (sizes fixed by the configuration),
    the initial weights in one jitted call, the trainer, and its first
    chunk through ``run()``: the window's own call and compiled program,
    whose rounds the reference follows."""
    import jax

    seeds = Seeds.from_seed(seed)
    shape = cell.traffic["cohort"]
    t = time.perf_counter()
    clients, counts = cell.model.make_data(cell.config, seeds.data)
    params = jax.jit(lambda k: cell.model.init(
        k, cell.config["model_config"]))(jax.random.PRNGKey(seeds.weights))
    w0 = _host(params)
    log(f"data: {len(clients)} clients, {int(counts.sum())} samples, n_max "
        f"{int(counts.max())}, {time.perf_counter() - t:.3f} s")
    ctx = parts.Ctx(config=cell.config, counts=counts,
                    clients_per_round=shape["clients_per_round"],
                    local_steps=shape["local_steps"],
                    local_batch=shape["local_batch"], seeds=seeds)
    tr, plan = build(cell, ctx, clients, params)
    t = time.perf_counter()
    hist = _run(tr, plan.chunk_rounds, plan)
    log(f"first chunk ({plan.chunk_rounds} rounds, packs and compiles): "
        f"{time.perf_counter() - t:.3f} s")
    program = {"loss": [r["loss"] for r in hist],
               "dnorm": [r["delta_norm"] for r in hist],
               "w0": w0, "w": _host(tr.state.w)}
    events = [r for r in tr.history if "event" in r]
    return FirstChunk(tr, plan, clients, counts, seeds, program, events)


def run_cell(cell: Cell, bench: dict, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)):
    """Run one cell; returns (result dict, check lines).  Without
    ``require_chip`` (the tests) it runs on whatever JAX has."""
    import jax

    devices = check_devices(cell.chips) if require_chip else jax.devices()
    used = devices[:cell.chips]
    clock = CompileCounter()
    first = checked_first_chunk(cell, seed, log)
    tr, plan, seeds = first.trainer, first.plan, first.seeds
    R = plan.chunk_rounds
    log(f"compiles in set-up: {clock.since((0, 0, 0))}")

    t = time.perf_counter()
    _run(tr, R, plan)
    per_round = (time.perf_counter() - t) / R
    n_window = R * max(1, round(seconds / (per_round * R)))
    log(f"warm chunk: {per_round * 1e3:.4f} ms/round -> window of "
        f"{n_window} rounds")

    tr.sampler.seed = seeds.window
    snap = clock.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    hist = _run(tr, n_window, plan)
    window_s = time.perf_counter() - t_window
    print(f"compiles in the window: {json.dumps(clock.since(snap))}",
          flush=True)
    failed = sum(not math.isfinite(r["loss"]) for r in hist)

    layers = None
    if trace:
        n_trace = R * max(1, math.ceil(min(seconds, TRACE_SECONDS)
                                       / (per_round * R)))
        tr.sampler.seed = seeds.trace
        layers = _traced(tr, n_trace, plan, [d.id for d in used], log)

    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in used) \
        if require_chip else 0
    print(f"memory_peak_bytes {peak}", flush=True)
    first.trainer = tr = None
    gc.collect()

    t = time.perf_counter()
    ref = reference.follow(cell, first.clients, first.counts, seeds,
                           rounds=R, shards=cell.chips)
    log(f"reference ({R} rounds, blocks of {ref['block']} clients): "
        f"{time.perf_counter() - t:.3f} s")
    numbers = reference.gaps(first.program, ref, cell.limits["loss_rounds"])
    log(f"numbers, compared or not: {json.dumps(numbers)}")
    lim = cell.limits["limits"]
    correct = failed == 0 and all(numbers[k] <= lim[k] for k in lim)

    dev = used[0]
    ctx = Context(cell=cell.name, config=cell.config, traffic=cell.traffic,
                  model=cell.model, chips=cell.chips,
                  peaks=peaks(dev.device_kind) if require_chip else None,
                  setup_s=setup_s, window_s=window_s,
                  window_rounds=n_window, window_history=hist,
                  events=first.events,
                  trace=layers.base if layers else None, layers=layers)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in registry.metrics_for(cell.name, bench, kind):
        value = registry.metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": n_window,
              "failed": failed, "metrics": metrics, "device": device}
    if layers is not None:
        reduction = layers.base
        device["busy_s"] = reduction.mean_busy_s
        device["window_s"] = reduction.window_s
        top = sorted(reduction.op_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [list(kv) for kv in top],
                               "idle_gaps": [list(g) for g in
                                             reduction.gaps[:10]]}
    result["check"] = {k: {"value": numbers[k], "limit": lim[k]}
                       for k in lim}
    lines = [f"check {k} {numbers[k]!r} limit {lim[k]!r}" for k in lim]
    lines.append(f"check failed_rounds {failed} limit 0")
    return result, lines


def _traced(tr, n: int, plan, device_ids, log) -> scopes.Layers:
    """``run(n)`` under the profiler; its split by named scope."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                _run(tr, n, plan)
        finally:
            jax.profiler.stop_trace()
        t = time.perf_counter()
        data = scopes.load(trace_lib.find_xplane(trace_dir))
        layers = scopes.reduce(data, rounds=n, devices=device_ids)
        log(f"trace of {n} rounds read in {time.perf_counter() - t:.3f} s: "
            f"window {layers.base.window_s:.6f} s, busy "
            f"{layers.base.busy_s}, ms per round {layers.ms_per_round()}")
        return layers
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(args, t_start: float) -> int:
    bench = registry.benchmark()
    configure_jax()
    try:
        result, lines = run_cell(Cell.load(args.workload, bench), bench,
                                 args.seed, args.seconds, bool(args.trace),
                                 t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    return 0
