"""Federated training driver: one ``run()``, a declarative ``ExecutionPlan``.

Couples the host-side scheduler (client sampling, round-batch assembly,
checkpointing, logging) with the jitted round engine.  Used by the examples
and the paper-reproduction benchmarks; the same driver scales from the
paper's LeNet to the assigned-architecture reduced configs.

The entry point is ``FederatedTrainer.run(n_rounds, plan=...)``.  ``plan``
is a plane name or an ``ExecutionPlan`` (``launch/plan.py``); all four
execution tiers train the SAME algorithm — trajectory-equivalent bit for
bit, certified on the shared ``tests/_trajectory.py`` harness:

* ``plan="per_round"`` (the default when ``plan`` is omitted) — one jitted
  ``round_step`` per round, host Python between rounds.  Simple, observable,
  and the right tool when every round needs an eval or an external
  scheduling decision (``EvalSpec.cadence`` is honored exactly).
* ``plan="scanned"`` — chunks of ``chunk_rounds`` rounds execute as a single
  jitted ``lax.scan`` (``core/multiround.scan_rounds``) with the
  ``ServerState`` donated between chunks, while a background producer thread
  assembles the next chunk's round batches (a bounded prefetch queue,
  depth ``prefetch``).  Host work per round drops to ~zero.
* ``plan="device"`` — the corpus is packed once into a device-resident
  ``DeviceFederatedDataset`` and each chunk runs
  ``core/multiround.scan_rounds_ondevice``: client sampling AND minibatch
  gather fused into the scan, zero host round-trips per chunk.  Needs the
  ``DeviceSampleable`` sampler capability.
* ``plan="streaming"`` — the corpus stays on HOST as per-client shards and a
  bounded device-side LRU ``ShardCache`` (``cache=CacheSpec(...)``) holds
  only upcoming participants' shards in n_k-tiered slots (power-of-two size
  buckets, ``CacheSpec.tiers``; small clients never pay n_max-row padding),
  with chunk i+1's uploads dispatched right after chunk i's compute
  (double-buffered staging).  Needs the ``KeyedReplayable`` capability (the
  host replay is what names chunk i+1's participants ahead of time).
  ``CacheSpec(bucketed=True)`` extends the tiering to the COMPUTE: the
  cohort is staged per size tier and each tier runs one launch of its own
  extent (optionally through the fused ``kernels/client_step`` Pallas
  kernel via ``client_step_fn``).
* ``plan="auto"`` — the system resolves the plane from the memory budget vs
  ``packed_nbytes`` and the chunk working-set rule (``launch/plan.py:
  resolve``); the decision is logged into ``session.plan_log``, the history
  and the metrics jsonl, and the resolved run is bit-equal to requesting
  that plane directly.

A ``TrainSession`` (created implicitly, shareable via ``session=``) owns the
packed/streaming datasets, the persistent ``ShardCache`` and the jit caches
across ``run()`` calls: a second ``run()``, an eval loop, or a resumed run
re-uploads nothing for already-resident clients and recompiles nothing.

The legacy ``run_scanned`` / ``run_device`` / ``run_streaming`` drivers
remain as thin deprecated shims over ``run(plan=...)`` (kept bit-equal by a
dedicated CI lane until removal).

Checkpointing in every tier goes through ``checkpoint.AsyncCheckpointWriter``
(device-to-host copy + npz write on a background thread, flushed before
``run`` returns, tmp+rename atomic).  Every run takes ``resume=True`` —
``checkpoint.latest_round`` + ``restore_state`` pick the trajectory up at
the round after the last durable save; keyed sampling/minibatch draws make
the resumed run bit-equal to an uninterrupted one.  Heterogeneous local work
(stragglers): ``hetero_steps_fn(t) -> [C] H_k`` runs each client's first H_k
of the H staged local steps in every tier identically.  Time-varying
participation (``DeviceDiurnalSampler``) works in all tiers via the padded-C
convention (``rcfg.clients_per_round`` must equal ``sampler.lowered_clients``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (AsyncCheckpointWriter, append_metrics,
                              latest_round, prune_metrics, restore_state)
from repro.core import RoundConfig, round_step, scan_rounds
from repro.core.multiround import scan_rounds_bucketed, scan_rounds_ondevice
from repro.core.sampling import (KeyedReplayable, UniformSampler,
                                 participants_in_span)
from repro.core.server_opt import ServerOpt, ServerState
from repro.data.device import DeviceFederatedDataset
from repro.data.federated import FederatedDataset, minibatch_indices
from repro.data.stream import ShardCache, StreamingFederatedDataset
from repro.kernels._device import kernel_platform
from repro.launch.mesh import MeshSpec
from repro.launch.plan import (CacheSpec, CkptSpec, ExecutionPlan, PlanError,
                               TrainSession, _IdKey, as_plan, resolve)
from repro.scenario.spec import ScenarioRuntime
from repro.sharding import FED_MESH_RULES, axis_rules


def _cache_counters(cache: Optional[ShardCache]):
    return None if cache is None else (cache.hits, cache.misses,
                                       cache.evictions,
                                       tuple(cache.tier_hits),
                                       tuple(cache.tier_misses),
                                       tuple(cache.tier_evictions))


def _cache_stats(before, cache: Optional[ShardCache]):
    """Per-chunk delta of the cache counters (+ cumulative hit rate), the
    durable form of the stats that used to live only on the live cache
    object.  Staging overlaps compute, so uploads dispatched for chunk i+1
    during chunk i land on chunk i's record; the per-run sums are exact.
    The ``cache_tier_*`` lists attribute the same deltas to the cache's
    n_k size tiers (index = tier, smallest slot rows first), so churn at
    skewed corpora can be pinned to the tier causing it."""
    if cache is None:
        return None
    return {"cache_hits": cache.hits - before[0],
            "cache_misses": cache.misses - before[1],
            "cache_evictions": cache.evictions - before[2],
            "cache_hit_rate": round(cache.hit_rate, 6),
            "cache_tier_hits": [a - b for a, b
                                in zip(cache.tier_hits, before[3])],
            "cache_tier_misses": [a - b for a, b
                                  in zip(cache.tier_misses, before[4])],
            "cache_tier_evictions": [a - b for a, b
                                     in zip(cache.tier_evictions,
                                            before[5])]}


def _eval_spans(t0: int, n_rounds: int, chunk_rounds: int,
                eval_every: Optional[int] = None) -> list:
    """Chunk spans ``[s, e)`` of at most ``chunk_rounds`` rounds, shared by
    every chunked plane.  Chunked planes eval at chunk ends (the
    ``_seal_chunk`` hook sees the state right after round ``e - 1``), so an
    ``EvalSpec`` cadence FINER than the chunk size is honored by adding a
    boundary at every eval round: a span ends early at ``e`` whenever round
    ``e - 1`` is an eval round (``(e - 1) % eval_every == 0`` — the same
    rounds the per-round plane evals).  ``eval_every=None`` (no eval_fn)
    keeps the uniform chunking: sub-chunking costs one compiled chunk shape
    per distinct length, pointless without an eval to run."""
    spans = []
    s = t0
    while s < n_rounds:
        e = min(s + chunk_rounds, n_rounds)
        if eval_every:
            # earliest eval round at or after s → desired end t_ev + 1
            t_ev = -(-s // eval_every) * eval_every
            if t_ev + 1 < e:
                e = t_ev + 1
        spans.append((s, e))
        s = e
    return spans


# eager host replay of the keyed minibatch draws for a whole chunk at once:
# one jitted dispatch over the flattened [R*C] (t, cid, n_k) lanes (threefry
# is counter-based, so the staged values are bit-equal to the in-scan draw
# the padded planes make) — the bucketed plane ships these as scan xs so its
# compiled chunk carries no PRNG ops at all
_staged_indices = jax.jit(
    jax.vmap(minibatch_indices, in_axes=(None, 0, 0, 0, None)),
    static_argnums=(4,))


def _warn_shim(old: str, plane: str):
    warnings.warn(
        f"FederatedTrainer.{old}(...) is deprecated: use "
        f"run(n_rounds, plan=ExecutionPlan(plane={plane!r}, ...)) — the shim "
        f"stays bit-equal until removal (CI certifies it)",
        DeprecationWarning, stacklevel=3)


@dataclass
class FederatedTrainer:
    loss_fn: Callable                  # (params, batch) -> (loss, metrics)
    server_opt: ServerOpt
    rcfg: RoundConfig
    dataset: FederatedDataset
    sampler: UniformSampler
    state: ServerState
    param_axes: Optional[Any] = None
    lr_schedule: Optional[Callable] = None   # round t -> gamma_t
                                             # (Corollary 3.3 schedules)
    hetero_steps_fn: Optional[Callable] = None  # round t -> [C] ints H_k
    client_step_fn: Optional[Callable] = None   # fused gather+local-SGD hook
                                                # (kernels/client_step) for
                                                # the bucketed streaming plane
    ckpt_path: Optional[str] = None
    ckpt_every: int = 0
    metrics_path: Optional[str] = None       # durable per-round jsonl log
    local_batch: int = 10                    # b, the client minibatch size
    session: Optional[TrainSession] = None   # warm resources across run()s
    history: list = field(default_factory=list)

    def __post_init__(self):
        if int(self.local_batch) < 1:
            raise PlanError(
                f"local_batch must be a positive int, got "
                f"{self.local_batch!r}")
        self.local_batch = int(self.local_batch)
        if self.session is None:
            self.session = TrainSession()
        # the active ScenarioRuntime, scoped to one run() call (set when the
        # resolved plan carries a non-null ScenarioSpec, cleared after)
        self._scenario: Optional[ScenarioRuntime] = None
        # the active MeshSpec, scoped to one run() call like _scenario.
        # It keys _sig() (a sharded and an unsharded run must never alias a
        # compiled executable) and the session's dataset/cache lookups.
        self._mesh_spec: Optional[MeshSpec] = None

    # ------------------------------------------------------------------
    # jitted engines (lazily built, cached on the session so a fresh
    # trainer sharing the session — e.g. rebuilt for a resume or an eval
    # loop — reuses the compiled executables)
    # ------------------------------------------------------------------
    def _sig(self):
        return (_IdKey(self.loss_fn), _IdKey(self.server_opt), self.rcfg,
                _IdKey(self.param_axes), self._mesh_spec)

    def _step_fn(self, masked: bool):
        rcfg, axes = self.rcfg, self.param_axes
        loss_fn, opt = self.loss_fn, self.server_opt

        def build():
            if masked:
                @jax.jit
                def step(state, batches, weights, lr, mask):
                    return round_step(loss_fn, opt, state, batches, weights,
                                      rcfg, param_axes=axes, lr=lr,
                                      step_mask=mask)
            else:
                @jax.jit
                def step(state, batches, weights, lr):
                    return round_step(loss_fn, opt, state, batches, weights,
                                      rcfg, param_axes=axes, lr=lr)
            return step

        return self.session.jit_fn(("step", masked) + self._sig(), build)

    def _scan_chunk_fn(self, masked: bool):
        rcfg, axes = self.rcfg, self.param_axes
        loss_fn, opt = self.loss_fn, self.server_opt

        def build():
            if masked:
                @partial(jax.jit, donate_argnums=(0,))
                def chunk(state, batches, weights, lrs, masks):
                    return scan_rounds(loss_fn, opt, state, batches, weights,
                                       rcfg, param_axes=axes, lrs=lrs,
                                       step_masks=masks)
            else:
                @partial(jax.jit, donate_argnums=(0,))
                def chunk(state, batches, weights, lrs):
                    return scan_rounds(loss_fn, opt, state, batches, weights,
                                       rcfg, param_axes=axes, lrs=lrs)
            return chunk

        return self.session.jit_fn(("scan_chunk", masked) + self._sig(),
                                   build)

    def _device_chunk_fn(self, n_rounds: int, masked: bool):
        """Jitted fused chunk, cached per (R, masked, b) — the ragged last
        chunk is its own compile, like the scanned plane.  Shared by the
        device and streaming planes: ``dds`` is any gather-contract pytree
        (jit keys on argument structure, so the packed dataset and a
        streaming ``CacheView`` each get their own trace under one
        wrapper)."""
        rcfg, axes = self.rcfg, self.param_axes
        loss_fn, opt, sampler = self.loss_fn, self.server_opt, self.sampler
        b = self.local_batch

        def build():
            if masked:
                @partial(jax.jit, donate_argnums=(0,))
                def fn(state, dds, sample_key, data_key, t0, lrs, masks):
                    return scan_rounds_ondevice(
                        loss_fn, opt, state, dds, sampler, data_key,
                        sample_key, t0, n_rounds, rcfg, b, param_axes=axes,
                        lrs=lrs, step_masks=masks)
            else:
                @partial(jax.jit, donate_argnums=(0,))
                def fn(state, dds, sample_key, data_key, t0, lrs):
                    return scan_rounds_ondevice(
                        loss_fn, opt, state, dds, sampler, data_key,
                        sample_key, t0, n_rounds, rcfg, b, param_axes=axes,
                        lrs=lrs)
            return fn

        key = (("ondevice_chunk", n_rounds, masked, b, _IdKey(sampler))
               + self._sig())
        return self.session.jit_fn(key, build)

    # ------------------------------------------------------------------
    # host-side round assembly (shared by the drivers and the prefetcher)
    # ------------------------------------------------------------------
    def _check_client_extent(self):
        """The engine is lowered for rcfg.clients_per_round slots; a sampler
        with a different extent (e.g. a diurnal sampler's m_max) would pair
        weights with the wrong batch rows — fail loudly instead."""
        ext = getattr(self.sampler, "lowered_clients", None)
        if ext is not None and ext != self.rcfg.clients_per_round:
            raise ValueError(
                f"sampler lowers {ext} client slots but "
                f"rcfg.clients_per_round={self.rcfg.clients_per_round}; for "
                f"time-varying M use clients_per_round = m_max (padded-C, "
                f"zero-weight tail)")

    def _round_knobs(self, t: int):
        """Per-round lr + optional [C, H] step mask (host scalars only)."""
        lr_t = (self.rcfg.lr if self.lr_schedule is None
                else float(self.lr_schedule(t)))
        mask = None
        if self.hetero_steps_fn is not None:
            h_k = np.asarray(self.hetero_steps_fn(t))
            mask = (np.arange(self.rcfg.local_steps)[None, :]
                    < h_k[:, None]).astype(np.float32)
        return lr_t, mask

    def _scenario_mask(self, t: int, client_ids, mask):
        """Fold the active scenario's completed-step caps for round ``t``'s
        cohort into the (possibly None) hetero mask — both are prefix
        masks, so elementwise min composes them.  The engine sees one
        ``step_mask`` either way: eq. (3) partial-work weighting does not
        care whether a client stopped early by configuration (H_k) or by
        simulated fate (dropout/straggler/availability)."""
        if self._scenario is None:
            return mask
        sm = self._scenario.masks_for(t, np.asarray(client_ids))
        return sm if mask is None else np.minimum(mask, sm)

    def _round_inputs(self, t: int):
        """Sample S_t and assemble its [C, H, b, ...] batches + knobs."""
        idx, weights = self.sampler.sample(t)
        batches = self.dataset.round_batches(
            idx, self.rcfg.local_steps, self.local_batch, t=t)
        lr_t, mask = self._round_knobs(t)
        mask = self._scenario_mask(t, idx, mask)
        return batches, np.asarray(weights, np.float32), lr_t, mask

    def _assemble_chunk(self, t_lo: int, t_hi: int):
        """Stack rounds [t_lo, t_hi) into [R, C, H, ...] scan inputs."""
        bs, ws, lrs, ms = [], [], [], []
        for t in range(t_lo, t_hi):
            b, w, lr_t, m = self._round_inputs(t)
            bs.append(b)
            ws.append(w)
            lrs.append(lr_t)
            ms.append(m)
        batches = jax.tree.map(lambda *x: np.stack(x), *bs)
        masks = None if ms[0] is None else np.stack(ms)
        return (batches, np.stack(ws), np.asarray(lrs, np.float32), masks)

    def _chunk_knobs(self, t_lo: int, t_hi: int):
        """[R] lrs + optional [R, C, H] masks for the device data plane.

        With an active scenario the cohort ids matter (scenario fates are
        keyed per client), so each round's in-scan draw is replayed on host
        (``KeyedReplayable``, gated at plan resolution) — the same replay
        the streaming prefetch already relies on."""
        lrs, ms = [], []
        for t in range(t_lo, t_hi):
            lr_t, m = self._round_knobs(t)
            if self._scenario is not None:
                idx, _ = self.sampler.sample(t)
                m = self._scenario_mask(t, idx, m)
            lrs.append(lr_t)
            ms.append(m)
        masks = None if ms[0] is None else np.stack(ms)
        return np.asarray(lrs, np.float32), masks

    def _resume_round(self, resume: bool) -> int:
        """First round this run should execute: 0 normally; with
        ``resume=True``, restore the latest durable checkpoint and continue
        at the round after it.  Keyed sampling/minibatch draws make the
        continued trajectory bit-equal to an uninterrupted one — which is
        why a sampler without the ``KeyedReplayable`` capability (sequential
        numpy RNG that would restart at its seed) is rejected here.  An
        absent or unreadable checkpoint (``latest_round`` == -1) means a
        fresh start, not an error — first launch and resume-after-crash
        share one code path.  The metrics jsonl is rewound to the restored
        round so the re-run rounds are never double-logged."""
        if not resume:
            return self._scenario_start(0)
        if not self.ckpt_path:
            raise ValueError("resume=True needs ckpt_path")
        if not isinstance(self.sampler, KeyedReplayable):
            raise PlanError(
                "resume=True needs the KeyedReplayable capability — a keyed "
                "Device* sampler (host replay of the (seed, t)-keyed device "
                "draw): a stateful sampler's RNG stream restarts at its "
                "seed, so resumed rounds would silently replay round-0 "
                "client sets", missing="KeyedReplayable")
        t_ck = latest_round(self.ckpt_path)
        if t_ck < 0:
            return self._scenario_start(0)
        self.state, _ = restore_state(self.ckpt_path, self.state)
        if self.metrics_path:
            prune_metrics(self.metrics_path, t_ck)
        return self._scenario_start(t_ck + 1)

    def _scenario_start(self, t0: int) -> int:
        """Prime the scenario runtime for a run starting at ``t0``: an
        adaptive-cohort scenario replays rounds [0, t0) on host to rebuild
        its completion-rate EMA (pure keyed hashing — resume stays bit-equal
        to uninterrupted; t0 > 0 implies resume, whose gate already
        guarantees the KeyedReplayable replay this needs).  Stateless
        scenarios need no history."""
        if self._scenario is not None:
            self._scenario.warmup(t0, self.sampler)
        return t0

    @contextlib.contextmanager
    def _writer(self):
        """Async checkpoint writer scoped to one run call: joined and
        flushed on normal exit; on an in-flight exception the writer is
        still retired but its own failures never mask the primary error."""
        writer = AsyncCheckpointWriter() if self.ckpt_path else None
        try:
            yield writer
        except BaseException:
            if writer:
                writer.close(raise_failure=False)
            raise
        else:
            if writer:
                writer.close()

    # ------------------------------------------------------------------
    # THE entry point: declarative plan -> resolved plane -> one trajectory
    # ------------------------------------------------------------------
    def run(self, n_rounds: int,
            plan: Union[None, str, ExecutionPlan] = None, *,
            log_every: Optional[int] = None,
            eval_fn: Optional[Callable] = None, verbose: bool = True,
            resume: bool = False):
        """Train ``n_rounds`` federated rounds under ``plan``.

        ``plan``: ``None`` (historical per-round behavior), a plane name
        (``"auto" | "per_round" | "scanned" | "device" | "streaming"``), or
        a full ``ExecutionPlan``.  The trajectory is a function of the
        config alone — every plane (and ``"auto"``, whichever it resolves
        to) trains the same model bit for bit.  A plan's ``local_batch`` /
        ``ckpt`` overrides are scoped to THIS call: the trainer's own
        fields are restored afterwards, so a one-off plan never leaks into
        later runs.  ``log_every`` overrides ``plan.eval.cadence``; with an
        ``eval_fn``, chunked planes split their scan chunks at eval rounds
        (see ``_eval_spans``) so a cadence finer than ``chunk_rounds`` is
        honored exactly, same rounds as the per-round plane.
        ``resume=True`` continues from the latest durable checkpoint.  Auto
        resolutions are appended to the history and metrics jsonl as
        ``{"event": "plan", ...}`` records.  ``plan.secure`` is scoped the
        same way as ``local_batch``/``ckpt``: it lands on ``self.rcfg``
        for this call only (RoundConfig keys the jit caches, so secure and
        open runs never share a compiled executable).
        """
        plan = as_plan(plan)
        saved = (self.local_batch, self.ckpt_path, self.ckpt_every,
                 self.rcfg)
        if plan.local_batch is not None:
            self.local_batch = plan.local_batch
        if plan.ckpt is not None:
            if plan.ckpt.path is not None:
                self.ckpt_path = plan.ckpt.path
            if plan.ckpt.every is not None:
                self.ckpt_every = plan.ckpt.every
        if plan.secure is not None:
            self.rcfg = dataclasses.replace(self.rcfg, secure=plan.secure)
        self._mesh_spec = plan.mesh
        try:
            self._check_client_extent()
            decision = resolve(plan, self, n_rounds)
            self._scenario = (
                ScenarioRuntime(plan.scenario, self.rcfg.local_steps)
                if decision.scenario else None)
            self.session.plan_log.append(decision.record())
            if decision.auto:
                rec = decision.record()
                self.history.append(rec)
                if self.metrics_path:
                    append_metrics(self.metrics_path, [rec])
                if verbose:
                    print(f"  plan: auto -> {decision.plane} "
                          f"({decision.reason})")
            cadence = (log_every if log_every is not None
                       else plan.eval.cadence)
            # a plan-carried mesh activates the logical-axis rules for the
            # whole plane dispatch: packing, cache uploads and tracing all
            # see the same mesh context, so the cohort axis shards (GSPMD
            # constraints everywhere, the explicit shard_map+psum plane in
            # round_step when the mesh is pure data-parallel).  mesh=None
            # activates nothing — the pre-mesh code path, bit for bit.
            mesh = (self.session.mesh_for(plan.mesh)
                    if plan.mesh is not None else None)
            mesh_ctx = (axis_rules(mesh, FED_MESH_RULES)
                        if mesh is not None else contextlib.nullcontext())
            # the Pallas kernels' mode follows the platform this run places
            # on (compiled on a TPU, interpreted on the CPU), decided here
            # once for every program the planes trace
            device = (mesh.devices.flat[0] if mesh is not None
                      else jax.devices()[0])
            with mesh_ctx, kernel_platform(device.platform):
                if decision.plane == "per_round":
                    return self._run_per_round(n_rounds, cadence, eval_fn,
                                               verbose, resume)
                # chunked planes take the RESOLVED chunk size — a literal
                # plan value, or the measured-overhead auto pick (see
                # plan.resolve)
                chunk_rounds = decision.chunk_rounds
                eval_every = cadence if eval_fn is not None else None
                if decision.plane == "scanned":
                    return self._run_scanned(n_rounds, chunk_rounds,
                                             int(plan.prefetch), eval_fn,
                                             eval_every, verbose, resume)
                if decision.plane == "device":
                    return self._run_device(n_rounds, chunk_rounds, eval_fn,
                                            eval_every, verbose, resume)
                return self._run_streaming(
                    n_rounds, chunk_rounds, plan.cache.clients,
                    plan.cache.bytes, plan.cache.tiers, decision.bucketed,
                    bool(plan.prefetch), eval_fn, eval_every, verbose,
                    resume)
        finally:
            (self.local_batch, self.ckpt_path, self.ckpt_every,
             self.rcfg) = saved
            self._scenario = None
            self._mesh_spec = None

    # ------------------------------------------------------------------
    # plane: per_round — one dispatch per round
    # ------------------------------------------------------------------
    def _run_per_round(self, n_rounds: int, log_every: int, eval_fn,
                       verbose: bool, resume: bool):
        t0 = self._resume_round(resume)
        t_start = time.time()
        with self._writer() as writer:
            for t in range(t0, n_rounds):
                batches, weights, lr_t, mask = self._round_inputs(t)
                batches = jax.tree.map(jnp.asarray, batches)
                if mask is None:
                    self.state, metrics = self._step_fn(False)(
                        self.state, batches, jnp.asarray(weights),
                        jnp.float32(lr_t))
                else:
                    self.state, metrics = self._step_fn(True)(
                        self.state, batches, jnp.asarray(weights),
                        jnp.float32(lr_t), jnp.asarray(mask))
                rec = {"round": t, "loss": float(metrics["loss"]),
                       "delta_norm": float(metrics["delta_norm"])}
                if self._scenario is not None:
                    rec["completed"] = int(metrics["completed"])
                if eval_fn is not None and (t % log_every == 0
                                            or t == n_rounds - 1):
                    rec.update(eval_fn(self.state))
                self.history.append(rec)
                if self.metrics_path:
                    append_metrics(self.metrics_path, [rec])
                if verbose and (t % log_every == 0 or t == n_rounds - 1):
                    extra = " ".join(f"{k}={v:.4f}" for k, v in rec.items()
                                     if k not in ("round",))
                    print(f"  round {t:5d}  {extra}  "
                          f"({time.time() - t_start:.1f}s)")
                if (writer and self.ckpt_every
                        and t % self.ckpt_every == 0 and t > 0):
                    writer.submit(self.ckpt_path, self.state, {"round": t})
        return self.history

    # ------------------------------------------------------------------
    # plane: scanned — chunked lax.scan with host prefetch
    # ------------------------------------------------------------------
    def _run_scanned(self, n_rounds: int, chunk_rounds: int, prefetch: int,
                     eval_fn, eval_every: Optional[int], verbose: bool,
                     resume: bool):
        t0 = self._resume_round(resume)
        spans = _eval_spans(t0, n_rounds, chunk_rounds, eval_every)
        q: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
        failure: list = []
        stop = threading.Event()

        def produce():
            try:
                for s, e in spans:
                    item = self._assemble_chunk(s, e)
                    while not stop.is_set():     # never block past a dead
                        try:                     # consumer (see finally:)
                            q.put(item, timeout=0.2)
                            break
                        except queue.Full:
                            pass
                    if stop.is_set():
                        return
            except BaseException as exc:   # surface in the consumer
                failure.append(exc)
                stop.set()

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        t_start = time.time()
        try:
            with self._writer() as writer:
                for s, e in spans:
                    while True:
                        if failure:
                            raise failure[0]
                        try:
                            item = q.get(timeout=0.2)
                            break
                        except queue.Empty:
                            pass
                    batches, weights, lrs, masks = item
                    batches = jax.tree.map(jnp.asarray, batches)
                    if masks is None:
                        self.state, metrics = self._scan_chunk_fn(False)(
                            self.state, batches, jnp.asarray(weights),
                            jnp.asarray(lrs))
                    else:
                        self.state, metrics = self._scan_chunk_fn(True)(
                            self.state, batches, jnp.asarray(weights),
                            jnp.asarray(lrs), jnp.asarray(masks))
                    self._finish_chunk(s, e, n_rounds, metrics, eval_fn,
                                       verbose, writer, t_start)
        finally:
            stop.set()                   # unblock + retire the producer
            producer.join()
        return self.history

    # ------------------------------------------------------------------
    # plane: device — device-resident data (zero host round-trips/chunk)
    # ------------------------------------------------------------------
    def device_dataset(self,
                       shard_clients: bool = True) -> DeviceFederatedDataset:
        """The packed corpus (built once, owned by the session; see
        data/device.py for the K * n_max memory ceiling this implies).
        Keyed by the active mesh spec: packing places the client axis
        under the live mesh context, so a sharded and an unsharded run
        never share a packed corpus.

        A call that packs appends one ``{"event": "pack", "seconds",
        "bytes", "clients"}`` record to the history, like the ``plan``
        event: ``seconds`` on the host clock covers filling the padded
        arrays and handing them to ``device_put``, not the transfer's end.
        Waiting for the transfer would serialize it with the first chunk's
        compile and could lengthen set-up."""
        before = self.session.device_ds
        t = time.perf_counter()
        dds = self.session.device_dataset(self.dataset,
                                          shard_clients=shard_clients,
                                          mesh=self._mesh_spec)
        if dds is not before and dds is not self.dataset:
            self.history.append({"event": "pack",
                                 "seconds": time.perf_counter() - t,
                                 "bytes": dds.nbytes,
                                 "clients": dds.n_clients})
        return dds

    def _sample_key(self):
        return (self.sampler.base_key()
                if isinstance(self.sampler, KeyedReplayable)
                else jax.random.PRNGKey(self.sampler.seed))

    def _run_device(self, n_rounds: int, chunk_rounds: int, eval_fn,
                    eval_every: Optional[int], verbose: bool, resume: bool):
        t0 = self._resume_round(resume)
        dds = self.device_dataset()
        spans = _eval_spans(t0, n_rounds, chunk_rounds, eval_every)
        return self._run_fused_chunks(
            spans, n_rounds, dds, dds.base_key(), prepare=None, upload=None,
            prefetch=True, eval_fn=eval_fn, verbose=verbose)

    # ------------------------------------------------------------------
    # plane: streaming — shard-cached data (corpus larger than device)
    # ------------------------------------------------------------------
    def streaming_dataset(self) -> StreamingFederatedDataset:
        """The host-resident shard set (built once, owned by the session).
        Costs no device memory by itself; ``packed_nbytes`` reports what the
        device-RESIDENT plane would pay — the plane-choice comparison."""
        return self.session.streaming_dataset(self.dataset)

    @property
    def stream_cache(self) -> Optional[ShardCache]:
        """The session's persistent ``ShardCache`` (None before the first
        streaming run).  Lives across ``run()`` calls: a second run with the
        same capacity re-uploads nothing for already-resident clients."""
        return self.session.shard_cache

    def _run_streaming(self, n_rounds: int, chunk_rounds: int,
                       cache_clients: Optional[int],
                       cache_bytes: Optional[int],
                       cache_tiers: Optional[int], bucketed: bool,
                       prefetch: bool, eval_fn, eval_every: Optional[int],
                       verbose: bool, resume: bool):
        t0 = self._resume_round(resume)
        sds = self.streaming_dataset()
        if cache_clients is None and cache_bytes is None:
            cache_clients = self.rcfg.clients_per_round * chunk_rounds
        cache = self.session.shard_cache_for(sds, cache_clients, cache_bytes,
                                             cache_tiers,
                                             mesh=self._mesh_spec)
        spans = _eval_spans(t0, n_rounds, chunk_rounds, eval_every)
        if bucketed:
            return self._run_streaming_bucketed(spans, n_rounds, sds, cache,
                                                prefetch, eval_fn, verbose)

        def prepare(i):
            # raw per-round sequence (dedup=False): ensure() refreshes LRU
            # recency from it in last-use order, so cross-chunk eviction
            # never targets a client the chunk's final round just used
            return participants_in_span(self.sampler, *spans[i],
                                        dedup=False)

        def upload(parts):
            cache.ensure(parts)
            return cache.view()

        return self._run_fused_chunks(
            spans, n_rounds, None, sds.base_key(), prepare, upload,
            prefetch, eval_fn=eval_fn, verbose=verbose, cache=cache)

    # ------------------------------------------------------------------
    # plane: streaming + cache.bucketed — n_k-shaped per-tier dispatch
    # ------------------------------------------------------------------
    def _bucket_chunk(self, t_lo: int, t_hi: int, tier_of, counts,
                      data_key):
        """Host staging for one bucketed chunk: replay each round's cohort
        (``KeyedReplayable`` host sample — the same draw the device planes
        make), group the C slots by cache size tier and right-pad every
        round's per-tier cohort to the chunk-wide tier width with a
        SAME-TIER chunk participant at weight 0 (the diurnal padded-C
        convention: zero weight => zero delta, excluded from the loss
        metric; same tier because ``gather_tier_batch`` row-indexes the
        tier's own corpus, and chunk participant so the pad row is
        guaranteed cache-resident).  Padding rows carry all-ones H_k masks
        so their effective weight stays exactly 0.

        With no ``client_step_fn``, the chunk's minibatch index draws are
        staged here too (one jitted host replay over the flattened cohort —
        bit-equal to the in-scan draw), so the dispatched chunk runs in
        fused-concat form: switch-free per-tier gathers, one concatenated
        ``round_step`` launch per round, zero in-scan PRNG.  Padding rows
        get index 0 — any in-range row works, their weight is 0.

        Returns ``(participants, tiers_present, tier_cids, tier_weights,
        lrs, tier_idx, tier_masks)`` — the raw round-order cid sequence
        (the ``participants_in_span(dedup=False)`` form
        ``ShardCache.ensure`` wants, so the span's sampler replay happens
        exactly once), the static tier tuple, then [R, C_i]-stacked arrays
        per occupied tier (``tier_idx`` None under the fused hook, which
        draws its own keyed indices; ``tier_masks`` None when
        ``hetero_steps_fn`` is)."""
        R = t_hi - t_lo
        rounds, lrs, participants = [], [], []
        for t in range(t_lo, t_hi):
            idx, weights = self.sampler.sample(t)
            idx = np.asarray(idx)
            participants.extend(int(c) for c in idx)
            lr_t, mask = self._round_knobs(t)
            mask = self._scenario_mask(t, idx, mask)
            lrs.append(lr_t)
            by_tier: dict = {}
            for j, cid in enumerate(idx):
                by_tier.setdefault(int(tier_of[cid]), []).append(j)
            rounds.append((idx, np.asarray(weights, np.float32), mask,
                           by_tier))
        tiers_present = tuple(sorted(
            {tier for (_, _, _, bt) in rounds for tier in bt}))
        # chunk-wide tier widths, rounded UP to the next power of two
        # (capped at C): the jitted chunk fn re-traces on every new width
        # signature, and raw per-chunk maxima almost never repeat across
        # chunks — quantized widths collapse the signature space so the
        # compile amortizes over the whole run.  Extra columns are plain
        # weight-0 padding, excluded from delta and loss like any other.
        C = self.rcfg.clients_per_round
        widths = {tier: min(C, 1 << (max(len(bt.get(tier, ()))
                                         for (_, _, _, bt) in rounds)
                                     - 1).bit_length())
                  for tier in tiers_present}
        pad_cid: dict = {}          # any chunk participant of the tier
        for (idx, _, _, bt) in rounds:
            for tier, js in bt.items():
                pad_cid.setdefault(tier, int(idx[js[0]]))
        H = self.rcfg.local_steps
        masked = (self.hetero_steps_fn is not None
                  or self._scenario is not None)
        need = H * self.local_batch
        idx_all = None
        if self.client_step_fn is None:
            # one host replay of every (t, cid) draw in the chunk — the
            # concat-form chunk consumes these as xs instead of running
            # fold-in/randint chains per tier per round in-scan
            cid_flat = np.concatenate([idx for (idx, _, _, _) in rounds])
            t_flat = np.repeat(np.arange(t_lo, t_hi, dtype=np.int32),
                               [len(idx) for (idx, _, _, _) in rounds])
            idx_all = np.asarray(_staged_indices(
                data_key, t_flat, cid_flat.astype(np.int32),
                np.asarray(counts)[cid_flat].astype(np.int32), need))
            splits = np.cumsum([len(idx) for (idx, _, _, _) in rounds])[:-1]
            idx_all = np.split(idx_all, splits)
        tier_cids, tier_ws, tier_ms, tier_ix = [], [], [], []
        for tier in tiers_present:
            C_i = widths[tier]
            cids = np.full((R, C_i), pad_cid[tier], np.int32)
            ws = np.zeros((R, C_i), np.float32)
            ms = np.ones((R, C_i, H), np.float32)
            ix = np.zeros((R, C_i, need), np.int32)
            for r, (idx, weights, mask, bt) in enumerate(rounds):
                js = np.asarray(bt.get(tier, []), np.intp)
                k = len(js)
                if k == 0:
                    continue           # all-padding round for this tier
                cids[r, :k] = idx[js]
                ws[r, :k] = weights[js]
                if mask is not None:
                    ms[r, :k] = mask[js]
                if idx_all is not None:
                    ix[r, :k] = idx_all[r][js]
            tier_cids.append(cids)
            tier_ws.append(ws)
            tier_ms.append(ms)
            tier_ix.append(ix)
        return (participants, tiers_present, tuple(tier_cids),
                tuple(tier_ws), np.asarray(lrs, np.float32),
                tuple(tier_ix) if idx_all is not None else None,
                tuple(tier_ms) if masked else None)

    def _bucketed_chunk_fn(self, n_rounds: int, tiers_present: tuple,
                           masked: bool):
        """Jitted bucketed chunk, cached per (R, occupied tiers, masked, b,
        hook) — per-tier widths need no key of their own (jit retraces on
        the staged array shapes), but ``tiers_present`` and the fused hook
        are closure constants, so they key the cache."""
        rcfg, axes = self.rcfg, self.param_axes
        loss_fn, opt = self.loss_fn, self.server_opt
        b = self.local_batch
        hook = self.client_step_fn

        def build():
            if masked:
                @partial(jax.jit, donate_argnums=(0,))
                def fn(state, view, data_key, t0, lrs, cids, ws, ixs, ms):
                    return scan_rounds_bucketed(
                        loss_fn, opt, state, view, tiers_present, cids, ws,
                        data_key, t0, n_rounds, rcfg, b, param_axes=axes,
                        lrs=lrs, tier_idx=ixs, tier_masks=ms,
                        client_step_fn=hook)
            else:
                @partial(jax.jit, donate_argnums=(0,))
                def fn(state, view, data_key, t0, lrs, cids, ws, ixs):
                    return scan_rounds_bucketed(
                        loss_fn, opt, state, view, tiers_present, cids, ws,
                        data_key, t0, n_rounds, rcfg, b, param_axes=axes,
                        lrs=lrs, tier_idx=ixs, client_step_fn=hook)
            return fn

        key = (("bucketed_chunk", n_rounds, tiers_present, masked, b,
                _IdKey(hook)) + self._sig())
        return self.session.jit_fn(key, build)

    def _run_streaming_bucketed(self, spans, n_rounds: int, sds, cache,
                                prefetch: bool, eval_fn, verbose: bool):
        """The streaming chunk loop with n_k-shaped compute: ``prepare(i)``
        stages span i's tier-bucketed cohorts alongside the usual residency
        lookahead, and each dispatch runs one sized launch per occupied
        tier (``scan_rounds_bucketed``) instead of the C-wide padded
        gather.  Same trajectory as the padded plane (bit-equal with one
        occupied tier, fp32-reduction-order tolerance across tiers)."""
        if self.client_step_fn is not None:
            if self._mesh_spec is not None:
                # the hook sees the whole cohort and the composed corpus:
                # under a mesh every device would gather every tier and run
                # every client, so the cohort would not split at all
                raise PlanError(
                    "client_step_fn (the fused kernels/client_step hook) "
                    "does not split the cohort over a mesh; run it without "
                    "plan.mesh, or drop the hook to shard the XLA local "
                    "update", plane="streaming")
            if (self.rcfg.local_opt != "sgd"
                    or jnp.dtype(self.rcfg.compute_dtype)
                    != jnp.dtype(jnp.float32)):
                raise PlanError(
                    f"client_step_fn (the fused kernels/client_step hook) "
                    f"covers plain-SGD fp32 local updates; got local_opt="
                    f"{self.rcfg.local_opt!r}, compute_dtype="
                    f"{self.rcfg.compute_dtype!r}", plane="streaming")
        tier_of = cache.layout.tier_of
        data_key = sds.base_key()
        staged: dict = {}

        def prepare(i):
            # one host replay per span: _bucket_chunk both stages the
            # per-tier cohorts (+ minibatch index draws) and yields the raw
            # participant sequence (the dedup=False form ensure() wants for
            # LRU recency)
            s, e = spans[i]
            parts, *rest = self._bucket_chunk(s, e, tier_of, sds.counts,
                                              data_key)
            staged[i] = tuple(rest)
            return parts

        def upload(parts):
            cache.ensure(parts)
            return cache.view()

        def dispatch(i, s, e, view):
            tiers_present, cids, ws, lrs, ixs, ms = staged.pop(i)
            fn = self._bucketed_chunk_fn(e - s, tiers_present,
                                         ms is not None)
            args = (self.state, view, data_key, jnp.int32(s),
                    jnp.asarray(lrs), jax.tree.map(jnp.asarray, cids),
                    jax.tree.map(jnp.asarray, ws),
                    jax.tree.map(jnp.asarray, ixs))
            if ms is not None:
                args += (jax.tree.map(jnp.asarray, ms),)
            return fn(*args)

        return self._run_fused_chunks(
            spans, n_rounds, None, data_key, prepare, upload, prefetch,
            eval_fn=eval_fn, verbose=verbose, cache=cache,
            dispatch=dispatch)

    # ------------------------------------------------------------------
    # the chunk loop shared by the fused on-device planes
    # ------------------------------------------------------------------
    def _run_fused_chunks(self, spans, n_rounds, view, data_key,
                          prepare, upload, prefetch, eval_fn, verbose,
                          cache=None, dispatch=None):
        """Per-chunk knobs, one dispatch, shared bookkeeping for the device
        and streaming planes.  ``view`` is the gather-contract pytree of
        every span (the device plane); with staging hooks it is None, and
        ``prepare(i)`` does the host-side lookahead for span i (called
        BEFORE span i-1's dispatch, so its eager replay ops never queue
        behind the in-flight chunk) and ``upload(prepared)`` makes span
        i's data resident and returns its view — dispatched right after
        the chunk when ``prefetch`` (overlapping its compute), after the
        metrics sync otherwise.
        ``dispatch(i, s, e, view) -> (state, metrics)`` overrides the
        default ondevice-chunk launch (the bucketed plane supplies its own
        staged per-tier launch); it must donate/consume ``self.state``
        exactly like the default.

        The host-blocking metrics d2h sync for chunk i is deferred until
        chunk i+1 is in flight (the last per-chunk host-blocking step, now
        overlapped with compute); chunk-boundary eval and the async
        checkpoint snapshot still run *before* the next dispatch donates the
        chunk's state.  Per-chunk ``ShardCache`` counter deltas ride on each
        chunk's last metrics record (history + jsonl).

        Host spans on the profiler's clock (``jax.profiler.TraceAnnotation``,
        about a microsecond each while no trace runs), each with a
        ``chunk`` stat naming the first round of the chunk it serves:
        ``fed.stage`` (``prepare``), ``fed.upload`` (``upload``),
        ``fed.dispatch`` (the jitted chunk call; a compile shows as a long
        one), ``fed.seal`` and ``fed.drain`` (``_seal_chunk`` /
        ``_drain_chunk``)."""
        def stage(i):
            with jax.profiler.TraceAnnotation("fed.stage",
                                              chunk=spans[i][0]):
                return prepare(i)

        def put(i, prepared):
            with jax.profiler.TraceAnnotation("fed.upload",
                                              chunk=spans[i][0]):
                return upload(prepared)

        sample_key = self._sample_key()
        t_start = time.time()
        stats0 = _cache_counters(cache)
        if prepare is not None and spans:
            view = put(0, stage(0))
        pending = None        # chunk dispatched but not yet drained
                              # (last element: sealed yet?)
        with self._writer() as writer:
            try:
                for i, (s, e) in enumerate(spans):
                    if dispatch is None:
                        lrs, masks = self._chunk_knobs(s, e)
                        fn = self._device_chunk_fn(e - s, masks is not None)
                    nxt = (stage(i + 1)
                           if prepare and i + 1 < len(spans) else None)
                    if pending is not None:
                        # the previous chunk's state is live only until
                        # this dispatch donates it: eval + ckpt snapshot
                        # now, the blocking metrics sync after the dispatch
                        pending = self._seal_chunk(pending, n_rounds,
                                                   eval_fn, writer)
                    with jax.profiler.TraceAnnotation("fed.dispatch",
                                                      chunk=s):
                        if dispatch is None:
                            args = (self.state, view, sample_key, data_key,
                                    jnp.int32(s), jnp.asarray(lrs))
                            if masks is not None:
                                args += (jnp.asarray(masks),)
                            self.state, metrics = fn(*args)  # async
                        else:
                            self.state, metrics = dispatch(i, s, e, view)
                    if nxt is not None and prefetch:
                        # double-buffered staging: span i+1's H2D scatters
                        # are dispatched now and overlap chunk i's scanned
                        # compute; chunk i's view snapshot stays valid
                        # (functional updates never touch captured arrays)
                        view = put(i + 1, nxt)
                    if pending is not None:
                        done, pending = pending, None
                        self._drain_chunk(done, verbose, t_start,
                                          writer)
                    pending = (s, e, metrics,
                               _cache_stats(stats0, cache), None,
                               None, False)
                    stats0 = _cache_counters(cache)
                    if nxt is not None and not prefetch:
                        # serialized A/B arm: retire THIS chunk first (the
                        # metrics drain blocks until its compute finishes),
                        # so the upload genuinely never overlaps compute —
                        # this arm forgoes the deferred-sync optimization
                        pending = self._seal_chunk(pending, n_rounds,
                                                   eval_fn, writer)
                        done, pending = pending, None
                        self._drain_chunk(done, verbose, t_start, writer)
                        view = put(i + 1, nxt)
                if pending is not None:
                    pending = self._seal_chunk(pending, n_rounds, eval_fn,
                                               writer)
                    done, pending = pending, None
                    self._drain_chunk(done, verbose, t_start, writer)
            except BaseException:
                # retire the completed-but-unretired chunk before
                # propagating: its compute finished and its checkpoint may
                # already be durable, so append its metrics too — the jsonl
                # and the checkpoint must stay one trajectory prefix.
                # Best-effort: never mask the primary error.
                if pending is not None:
                    try:
                        if not pending[-1]:
                            # the next dispatch never happened, so
                            # self.state is still this chunk's output —
                            # safe to checkpoint (eval skipped on the
                            # error path)
                            pending = self._seal_chunk(pending, n_rounds,
                                                       None, writer)
                        self._drain_chunk(pending, verbose, t_start,
                                          writer)
                    except BaseException:
                        pass
                raise
        return self.history

    # ------------------------------------------------------------------
    # per-chunk bookkeeping, split at the donation boundary
    # ------------------------------------------------------------------
    def _seal_chunk(self, pending, n_rounds: int, eval_fn,
                    writer: Optional[AsyncCheckpointWriter]):
        """The bookkeeping that must see the chunk's own state before the
        next dispatch donates it: chunk-boundary eval + a device-side state
        snapshot for the due checkpoint.  The snapshot is only *submitted*
        in ``_drain_chunk``, after the chunk's metrics are appended — the
        durable checkpoint must never run ahead of the metrics log (resume
        prunes the log back to the checkpointed round, so rounds missing
        below it could never be re-logged).  Save cadence matches the
        per-round plane: when a round t > 0 with t % ckpt_every == 0 falls
        inside the chunk, plus one final save so a chunked run always ends
        restorable."""
        s, e, metrics, cstats, _, _, _ = pending
        with jax.profiler.TraceAnnotation("fed.seal", chunk=s):
            ev = eval_fn(self.state) if eval_fn is not None else None
            due = self.ckpt_every and any(
                t > 0 and t % self.ckpt_every == 0 for t in range(s, e))
            snap = None
            if writer and (due or e == n_rounds):
                # async device copy, dispatched before the next chunk's
                # donation invalidates these buffers
                snap = jax.tree.map(jnp.copy, self.state)
        return (s, e, metrics, cstats, ev, snap, True)

    def _drain_chunk(self, pending, verbose: bool, t_start: float,
                     writer: Optional[AsyncCheckpointWriter]):
        """The host-blocking half: one metrics d2h sync per chunk, history +
        jsonl append, progress line, then the checkpoint submit (after the
        append — see ``_seal_chunk``)."""
        s, e, metrics, cstats, ev, snap, _ = pending
        with jax.profiler.TraceAnnotation("fed.drain", chunk=s):
            losses = np.asarray(metrics["loss"])  # one sync per chunk
            dnorms = np.asarray(metrics["delta_norm"])
            recs = [{"round": t, "loss": float(losses[i]),
                     "delta_norm": float(dnorms[i])}
                    for i, t in enumerate(range(s, e))]
            if self._scenario is not None and "completed" in metrics:
                done = np.asarray(metrics["completed"])
                for i, rec in enumerate(recs):
                    rec["completed"] = int(done[i])
            if ev is not None:
                recs[-1].update(ev)
            if cstats is not None:
                recs[-1].update(cstats)
            self.history.extend(recs)
            if self.metrics_path:
                append_metrics(self.metrics_path, recs)
            if verbose:
                print(f"  rounds {s:5d}..{e - 1:5d}  "
                      f"loss={recs[-1]['loss']:.4f} "
                      f"delta_norm={recs[-1]['delta_norm']:.4f}  "
                      f"({time.time() - t_start:.1f}s)")
            if writer and snap is not None:
                writer.submit(self.ckpt_path, snap, {"round": e - 1},
                              copy=False)

    def _finish_chunk(self, s: int, e: int, n_rounds: int, metrics,
                      eval_fn, verbose: bool,
                      writer: Optional[AsyncCheckpointWriter],
                      t_start: float):
        """Serialized seal + drain (the scanned plane has no in-flight next
        chunk to overlap the sync with)."""
        pending = self._seal_chunk(
            (s, e, metrics, None, None, None, False), n_rounds,
            eval_fn, writer)
        self._drain_chunk(pending, verbose, t_start, writer)

    # ------------------------------------------------------------------
    # deprecated shims over run(plan=...) — bit-equal until removal (the
    # CI legacy-shim lane re-runs the trajectory matrix through them)
    # ------------------------------------------------------------------
    def run_scanned(self, n_rounds: int, chunk_rounds: int = 25,
                    prefetch: int = 2, eval_fn: Optional[Callable] = None,
                    verbose: bool = True, resume: bool = False):
        """Deprecated: ``run(n, plan=ExecutionPlan(plane="scanned", ...))``."""
        _warn_shim("run_scanned", "scanned")
        return self.run(n_rounds,
                        plan=ExecutionPlan(plane="scanned",
                                           chunk_rounds=chunk_rounds,
                                           prefetch=prefetch),
                        eval_fn=eval_fn, verbose=verbose, resume=resume)

    def run_device(self, n_rounds: int, chunk_rounds: int = 25,
                   eval_fn: Optional[Callable] = None, verbose: bool = True,
                   resume: bool = False):
        """Deprecated: ``run(n, plan=ExecutionPlan(plane="device", ...))``."""
        _warn_shim("run_device", "device")
        return self.run(n_rounds,
                        plan=ExecutionPlan(plane="device",
                                           chunk_rounds=chunk_rounds),
                        eval_fn=eval_fn, verbose=verbose, resume=resume)

    def run_streaming(self, n_rounds: int, chunk_rounds: int = 25,
                      cache_clients: Optional[int] = None,
                      cache_bytes: Optional[int] = None,
                      prefetch: bool = True,
                      eval_fn: Optional[Callable] = None,
                      verbose: bool = True, resume: bool = False):
        """Deprecated: ``run(n, plan=ExecutionPlan(plane="streaming",
        cache=CacheSpec(...)))``."""
        _warn_shim("run_streaming", "streaming")
        return self.run(n_rounds,
                        plan=ExecutionPlan(plane="streaming",
                                           chunk_rounds=chunk_rounds,
                                           cache=CacheSpec(
                                               clients=cache_clients,
                                               bytes=cache_bytes),
                                           prefetch=int(bool(prefetch))),
                        eval_fn=eval_fn, verbose=verbose, resume=resume)

    def local_batch_size(self) -> int:
        """Deprecated accessor for the ``local_batch`` field."""
        return self.local_batch

    def set_local_batch(self, b: int):
        """Deprecated: pass ``local_batch=b`` to the constructor (or set it
        on an ``ExecutionPlan``)."""
        warnings.warn(
            "set_local_batch is deprecated: pass local_batch= to "
            "FederatedTrainer (or ExecutionPlan(local_batch=...))",
            DeprecationWarning, stacklevel=2)
        self.local_batch = int(b)
        return self
