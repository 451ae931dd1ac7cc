"""Declarative execution plans for ``FederatedTrainer.run`` (the driver API).

One algorithm (FedMom, eq. (3)), one trajectory, four execution tiers.  The
repo used to expose the tiers as four divergent ``run_*`` entry points whose
knobs and capability rules lived in docstrings; this module makes the choice
*declarative*: callers say **what** to train and (optionally) the budget, and
the system picks **how**:

    trainer.run(n_rounds, plan="auto")                # resolved + audited
    trainer.run(n_rounds, plan=ExecutionPlan(
        plane="streaming", chunk_rounds=50,
        cache=CacheSpec(bytes=1 << 30),
        ckpt=CkptSpec(every=100, path="ck.npz")))

Pieces:

* ``ExecutionPlan`` — frozen dataclass naming the plane (``"auto" |
  "per_round" | "scanned" | "device" | "streaming"``) plus the knobs every
  tier shares (``chunk_rounds``, ``prefetch``, ``cache=CacheSpec``,
  ``eval=EvalSpec``, ``ckpt=CkptSpec``, ``memory_budget_bytes``,
  ``local_batch``).  Validated eagerly (``PlanError`` on bad values).
* ``resolve`` — turns ``plane="auto"`` into a concrete plane via the
  ROADMAP decision rule: packed corpus (``packed_nbytes``) fits the device
  memory budget -> **device**; otherwise one chunk's participant working set
  fits -> **streaming**; otherwise (or when the sampler lacks the needed
  capability) -> **scanned**.  Every resolution returns a ``PlanDecision``
  that the trainer logs into ``TrainSession.plan_log`` (and, for auto runs,
  into history + the metrics jsonl) so runs are auditable.
* Capability checks are explicit ``Protocol``s (``DeviceSampleable``,
  ``KeyedReplayable`` in ``core/sampling.py``), not ``hasattr`` duck-typing;
  a plane whose capability is missing raises a structured ``PlanError``
  naming the missing capability and the nearest viable plane.
* ``TrainSession`` — the long-lived resources one logical training workload
  owns across ``run()`` calls: the packed ``DeviceFederatedDataset``, the
  host ``StreamingFederatedDataset``, the persistent ``ShardCache`` (warm
  across calls: an eval loop or a resumed run re-uploads nothing for
  already-resident clients) and the jit caches.  Trainers create one
  implicitly; pass ``session=`` to share it between trainer instances.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Union

from repro.core.sampling import DeviceSampleable, KeyedReplayable
from repro.core.secure_agg import SecureAggSpec
from repro.data.device import DeviceFederatedDataset
from repro.data.stream import (MeshShardedCache, ShardCache,
                               StreamingFederatedDataset)
from repro.launch.mesh import MeshSpec
from repro.scenario.spec import ScenarioSpec

PLANES = ("per_round", "scanned", "device", "streaming")
_PLANE_ALIASES = {"per-round": "per_round", "python-loop": "per_round"}


class PlanError(ValueError):
    """A plan that cannot run as declared.

    Structured: ``plane`` is the requested plane, ``missing`` names the
    absent sampler capability (``"DeviceSampleable"`` / ``"KeyedReplayable"``,
    or ``None`` for plain validation errors) and ``nearest`` names the
    closest plane that *would* run with the given sampler/dataset.
    """

    def __init__(self, message: str, plane: Optional[str] = None,
                 missing: Optional[str] = None,
                 nearest: Optional[str] = None):
        super().__init__(message)
        self.plane = plane
        self.missing = missing
        self.nearest = nearest


@dataclass(frozen=True)
class CacheSpec:
    """Shard-cache budget for the streaming plane (and the working-set term
    of the auto rule): capacity in ``clients`` (a per-chunk distinct-client
    guarantee) and/or ``bytes`` (tighter wins); both ``None`` means one
    chunk's worst-case working set, ``clients_per_round * chunk_rounds``.

    ``tiers`` controls n_k-tiered slot sizing: ``None`` (default) buckets
    clients into every natural power-of-two size tier so small clients
    never pay n_max-row padding; ``1`` recovers the uniform single-tier
    layout; ``m`` caps the tier count, merging the smallest buckets upward.
    Tiering changes only the cache footprint, never the trajectory.

    ``bucketed`` extends the tiering from the cache FOOTPRINT to the
    COMPUTE: the chunk's cohort is staged on host grouped by size tier and
    each tier runs one launch of its own extent
    (``core.multiround.scan_rounds_bucketed``) instead of the padded
    switch-under-vmap gather.  Streaming plane + ``placement="mesh"`` only;
    trajectory-equivalent to the padded path (bit-equal with one occupied
    tier, fp32-reduction-order tolerance otherwise — see
    ``core.round.bucketed_round_step``)."""
    clients: Optional[int] = None
    bytes: Optional[int] = None
    tiers: Optional[int] = None
    bucketed: bool = False


@dataclass(frozen=True)
class EvalSpec:
    """Eval cadence in rounds.  Only the per-round plane can honor it
    exactly; chunked planes eval once per chunk boundary (rounds inside a
    chunk execute in one compiled scan)."""
    cadence: int = 50


@dataclass(frozen=True)
class CkptSpec:
    """Checkpoint sink: save every ``every`` rounds to ``path`` (async,
    tmp+rename atomic).  Unset fields keep the trainer's configured values
    (``path=None`` keeps ``ckpt_path``, ``every=None`` keeps
    ``ckpt_every``); an explicit ``every=0`` disables periodic saves."""
    every: Optional[int] = None
    path: Optional[str] = None


@dataclass(frozen=True)
class ExecutionPlan:
    """What to run and under which budget — the engine picks the rest.

    ``plane="auto"`` resolves against ``memory_budget_bytes`` (default: the
    backend's reported device memory, unlimited when the backend reports
    none — pass an explicit budget to constrain CPU runs).  ``prefetch`` is
    the host prefetch-queue depth on the scanned plane and the
    overlap-uploads-with-compute switch (truthiness) on the streaming plane.
    ``local_batch`` overrides the trainer's ``local_batch`` field when set.
    ``chunk_rounds="auto"`` sizes chunks from the MEASURED per-dispatch
    overhead at resolve time (amortize it to ~``_AUTO_CHUNK_TARGET_S`` per
    round, clamped to [8, 256] and to ``n_rounds``); the chosen size is
    audited on the ``PlanDecision``.

    ``scenario`` declares simulated production-FL conditions
    (``repro.scenario.ScenarioSpec``: mid-round dropouts, round-deadline
    stragglers, availability schedules, adaptive cohort sizing) — compiled
    by the driver into eq. (3) partial-work step masks, identically on
    every plane.  ``None`` (and a spec with no models) is bit-equal to no
    scenario at all.

    ``secure`` turns on secure aggregation
    (``repro.core.SecureAggSpec``): eq. (3)'s reduction runs through the
    uint32-ring pairwise-masking layer on whichever plane resolves, so the
    server only materializes masked per-client messages and their
    (dropout-recovered) sum.  ``SecureAggSpec(masked=False)`` is the open
    ring reference the masked run is bit-equal to.  Requires
    ``rcfg.placement == "mesh"``; composes with ``scenario`` dropouts
    (non-reporting clients' pairwise terms are recovered).
    """
    plane: str = "auto"
    chunk_rounds: Union[int, str] = 25
    prefetch: int = 2
    cache: CacheSpec = CacheSpec()
    eval: EvalSpec = EvalSpec()
    ckpt: Optional[CkptSpec] = None
    memory_budget_bytes: Optional[int] = None
    local_batch: Optional[int] = None
    scenario: Optional[ScenarioSpec] = None
    secure: Optional[SecureAggSpec] = None
    # data-parallel device mesh (repro.launch.mesh.MeshSpec): the resolved
    # plane's cohort splits across devices under shard_map, the
    # weighted-delta aggregation becomes a psum (server state replicated),
    # and the auto rule re-prices memory per device — a corpus that
    # overflows one device may fit the mesh, flipping the auto decision
    # (audited in plan_log).  None is bit-equal to the pre-mesh
    # single-device planes; a sharded run is tolerance-equal (fp32
    # reduction-order caveat, see core.round._shard_map_round).
    mesh: Optional[MeshSpec] = None

    def __post_init__(self):
        plane = _PLANE_ALIASES.get(self.plane, self.plane)
        object.__setattr__(self, "plane", plane)
        if plane not in PLANES + ("auto",):
            raise PlanError(
                f"unknown plane {self.plane!r}: want 'auto' or one of "
                f"{PLANES}", plane=self.plane)
        if self.chunk_rounds != "auto" and (
                not isinstance(self.chunk_rounds, int)
                or self.chunk_rounds < 1):
            raise PlanError(
                f"chunk_rounds must be an int >= 1 or the literal 'auto', "
                f"got {self.chunk_rounds!r}", plane=plane)
        if not isinstance(self.prefetch, int) or self.prefetch < 0:
            raise PlanError(
                f"prefetch must be an int >= 0, got {self.prefetch!r}",
                plane=plane)
        for name, v in (("cache.clients", self.cache.clients),
                        ("cache.bytes", self.cache.bytes),
                        ("cache.tiers", self.cache.tiers),
                        ("memory_budget_bytes", self.memory_budget_bytes),
                        ("local_batch", self.local_batch)):
            if v is not None and (not isinstance(v, int) or v < 1):
                raise PlanError(f"{name} must be a positive int, got {v!r}",
                                plane=plane)
        if not isinstance(self.cache.bucketed, bool):
            raise PlanError(
                f"cache.bucketed must be a bool, got "
                f"{self.cache.bucketed!r}", plane=plane)
        if self.cache.bucketed and plane not in ("auto", "streaming"):
            raise PlanError(
                f"cache.bucketed is a streaming-plane knob (tier-bucketed "
                f"dispatch over the shard cache) but the plan pins plane="
                f"{plane!r}", plane=plane, nearest="streaming")
        if not isinstance(self.eval.cadence, int) or self.eval.cadence < 1:
            raise PlanError(
                f"eval.cadence must be an int >= 1, got "
                f"{self.eval.cadence!r}", plane=plane)
        if (self.ckpt is not None and self.ckpt.every is not None
                and self.ckpt.every < 0):
            raise PlanError(
                f"ckpt.every must be >= 0, got {self.ckpt.every}",
                plane=plane)
        if self.scenario is not None \
                and not isinstance(self.scenario, ScenarioSpec):
            raise PlanError(
                f"scenario must be a repro.scenario.ScenarioSpec, got "
                f"{type(self.scenario).__name__}", plane=plane)
        if self.secure is not None \
                and not isinstance(self.secure, SecureAggSpec):
            raise PlanError(
                f"secure must be a repro.core.SecureAggSpec, got "
                f"{type(self.secure).__name__}", plane=plane)
        if self.mesh is not None and not isinstance(self.mesh, MeshSpec):
            raise PlanError(
                f"mesh must be a repro.launch.mesh.MeshSpec, got "
                f"{type(self.mesh).__name__}", plane=plane)


def as_plan(plan: Union[None, str, ExecutionPlan]) -> ExecutionPlan:
    """Normalize ``run(plan=...)`` input: ``None`` keeps the historical
    per-round behavior, a string names a plane (or ``"auto"``), an
    ``ExecutionPlan`` passes through (already validated)."""
    if plan is None:
        return ExecutionPlan(plane="per_round")
    if isinstance(plan, str):
        return ExecutionPlan(plane=plan)
    if isinstance(plan, ExecutionPlan):
        return plan
    if isinstance(plan, int):
        # run()'s second positional used to be log_every — point migrating
        # callers at the keyword instead of a bare type error
        raise PlanError(
            f"plan must be None, a plane name or an ExecutionPlan, got "
            f"{plan!r} — run()'s second positional argument is now `plan`; "
            f"if you meant the eval/log cadence, pass log_every={plan!r} "
            f"by keyword (or EvalSpec(cadence={plan!r}))")
    raise PlanError(
        f"plan must be None, a plane name or an ExecutionPlan, "
        f"got {type(plan).__name__}")


@dataclass
class PlanDecision:
    """The audited outcome of resolving a plan (``record()`` is the
    jsonl-able form logged to ``TrainSession.plan_log`` and, for auto runs,
    to history + the metrics log; no ``"round"`` key, so resume's
    ``prune_metrics`` never drops it)."""
    plane: str
    auto: bool
    reason: str
    packed_nbytes: Optional[int] = None
    budget_bytes: Optional[int] = None
    working_set_nbytes: Optional[int] = None
    chunk_rounds: Optional[int] = None        # the CONCRETE size run() uses
    dispatch_overhead_s: Optional[float] = None   # set when it was measured
    bucketed: bool = False
    scenario: bool = False
    secure: bool = False
    # mesh audit trail (set when the plan carries a MeshSpec): the built
    # mesh's shape/axes and the PER-DEVICE working-set bytes the auto rule
    # actually priced — so a plan_log/metrics-jsonl reader can see why a
    # corpus that overflows one device resolved to the device plane anyway
    mesh_shape: Optional[tuple] = None
    axis_names: Optional[tuple] = None
    per_device_nbytes: Optional[int] = None

    def record(self) -> dict:
        rec = {"event": "plan", "plane": self.plane, "auto": self.auto,
               "reason": self.reason}
        for k in ("packed_nbytes", "budget_bytes", "working_set_nbytes",
                  "chunk_rounds", "per_device_nbytes"):
            v = getattr(self, k)
            if v is not None:
                rec[k] = int(v)
        if self.dispatch_overhead_s is not None:
            rec["dispatch_overhead_s"] = round(
                float(self.dispatch_overhead_s), 9)
        if self.mesh_shape is not None:
            rec["mesh_shape"] = list(int(n) for n in self.mesh_shape)
            rec["axis_names"] = list(self.axis_names or ())
        if self.bucketed:
            rec["bucketed"] = True
        if self.scenario:
            rec["scenario"] = True
        if self.secure:
            rec["secure"] = True
        return rec


def device_memory_budget() -> Optional[int]:
    """Device memory limit in bytes (``memory_stats()['bytes_limit']``).

    ``None`` on the CPU, which reports no limit — the auto rule then treats
    memory as unbounded unless the plan carries an explicit
    ``memory_budget_bytes``.  On a TPU the limit is read, never assumed: a
    TPU that reports none raises rather than letting ``plan="auto"`` pick
    a plane without a budget."""
    import jax

    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return None
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        raise PlanError(
            f"{dev.device_kind} reports no memory_stats()['bytes_limit']; "
            f"pass ExecutionPlan(memory_budget_bytes=...) explicitly")
    return int(limit)


# chunk_rounds="auto": amortize the measured per-dispatch overhead (host
# Python + jit-cache lookup + runtime launch) down to ~25us/round, the point
# past which it disappears under even the smallest round's device work
_AUTO_CHUNK_TARGET_S = 25e-6
_AUTO_CHUNK_MIN = 8         # never chunk so small that compile count grows
_AUTO_CHUNK_MAX = 256       # bound staging memory + ragged-tail compiles


def measure_dispatch_overhead(n: int = 50) -> float:
    """Seconds of per-dispatch overhead for an already-compiled trivial
    jitted call — the fixed cost every chunk pays regardless of its size.
    Compiles outside the timed window, then times ``n`` chained dispatches
    (async: this measures the host-side dispatch path, the quantity chunking
    actually amortizes, not device compute)."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return x + 1.0

    x = jnp.zeros((8,), jnp.float32)
    jax.block_until_ready(probe(x))      # compile before the clock starts
    t0 = time.perf_counter()
    for _ in range(n):
        x = probe(x)
    jax.block_until_ready(x)
    return (time.perf_counter() - t0) / n


def auto_chunk_rounds(overhead_s: float, n_rounds: int) -> int:
    """Chunk size that amortizes ``overhead_s`` to ``_AUTO_CHUNK_TARGET_S``
    per round, clamped to [_AUTO_CHUNK_MIN, _AUTO_CHUNK_MAX] and to the run
    length (a chunk longer than the run just compiles a ragged shape)."""
    want = -(-float(overhead_s) // _AUTO_CHUNK_TARGET_S)   # ceil
    chunk = int(max(_AUTO_CHUNK_MIN, min(_AUTO_CHUNK_MAX, want)))
    return max(1, min(chunk, int(n_rounds)))


_CAPS = {"per_round": None, "scanned": None,
         "device": ("DeviceSampleable", DeviceSampleable),
         "streaming": ("KeyedReplayable", KeyedReplayable)}
_CAP_DETAIL = {
    "DeviceSampleable": "a traceable sample_device(key, t) drawn inside the "
                        "compiled scan",
    "KeyedReplayable": "a traceable sample_device(key, t) plus base_key(), "
                       "with the host sample(t) a stateless replay of the "
                       "(seed, t)-keyed device draw",
}


def nearest_viable_plane(sampler, dataset) -> str:
    """Most capable plane this sampler/dataset pair can actually run."""
    for plane in ("streaming", "device", "scanned", "per_round"):
        name_cap = _CAPS[plane]
        if name_cap is not None and not isinstance(sampler, name_cap[1]):
            continue
        if _dataset_supports(plane, dataset):
            return plane
    return "per_round"


def _dataset_supports(plane: str, dataset) -> bool:
    """Which planes a dataset can feed.  The two specialized dataset types
    pin their own plane; a host ``FederatedDataset`` (or a compatible
    custom dataset: keyed ``round_batches`` for the host-assembly planes,
    per-client ``data`` shards for the packable/streamable ones) feeds any
    plane."""
    if isinstance(dataset, DeviceFederatedDataset):
        return plane == "device"
    if isinstance(dataset, StreamingFederatedDataset):
        return plane == "streaming"
    if plane in ("per_round", "scanned"):
        return hasattr(dataset, "round_batches")
    # packing/streaming build from per-client shards + the draw-keying seed
    return hasattr(dataset, "data") and hasattr(dataset, "seed")


def check_plane(plane: str, sampler, dataset) -> None:
    """Raise a structured ``PlanError`` when ``plane`` cannot run with this
    sampler/dataset (missing capability Protocol or unsupported dataset)."""
    name_cap = _CAPS[plane]
    if name_cap is not None and not isinstance(sampler, name_cap[1]):
        name, _ = name_cap
        nearest = nearest_viable_plane(sampler, dataset)
        raise PlanError(
            f"plane {plane!r} needs sampler capability {name} "
            f"({_CAP_DETAIL[name]}) but {type(sampler).__name__} does not "
            f"provide it; nearest viable plane: {nearest!r}",
            plane=plane, missing=name, nearest=nearest)
    if not _dataset_supports(plane, dataset):
        nearest = nearest_viable_plane(sampler, dataset)
        raise PlanError(
            f"plane {plane!r} cannot use a {type(dataset).__name__} "
            f"(per_round/scanned need host round_batches; device/streaming "
            f"need packable per-client host data or an already-matching "
            f"dataset); nearest viable plane: {nearest!r}",
            plane=plane, nearest=nearest)


def resolve(plan: ExecutionPlan, trainer, n_rounds: int) -> PlanDecision:
    """Resolve ``plan`` to a concrete plane + chunk size for ``trainer``
    (the ROADMAP decision rule, now executable).  Explicit planes are
    capability-checked; ``"auto"`` compares the packed corpus and the chunk
    working set against the memory budget.  ``chunk_rounds="auto"`` is
    resolved here too, from the measured per-dispatch overhead (cached on
    the session — one measurement per workload, not per run).  A
    ``cache.bucketed`` plan must land on the streaming plane with
    ``placement="mesh"`` — anything else raises a structured ``PlanError``
    rather than silently training un-bucketed.  Pure resolution otherwise —
    builds at most the host-side streaming metadata, never uploads data."""
    decision = _resolve_plane(plan, trainer)
    if plan.chunk_rounds == "auto":
        overhead = trainer.session.dispatch_overhead()
        decision.chunk_rounds = auto_chunk_rounds(overhead, n_rounds)
        decision.dispatch_overhead_s = overhead
        decision.reason += (
            f"; chunk_rounds auto -> {decision.chunk_rounds} (measured "
            f"dispatch overhead {overhead * 1e6:.0f}us/chunk amortized to "
            f"<={_AUTO_CHUNK_TARGET_S * 1e6:.0f}us/round)")
    else:
        decision.chunk_rounds = int(plan.chunk_rounds)
    if plan.cache.bucketed:
        if decision.plane != "streaming":
            raise PlanError(
                f"cache.bucketed needs the streaming plane (the tier "
                f"bucketing is the shard cache's n_k layout) but the plan "
                f"resolved to {decision.plane!r} ({decision.reason})",
                plane=decision.plane, nearest="streaming")
        if trainer.rcfg.placement != "mesh":
            raise PlanError(
                f"cache.bucketed dispatches per-tier vmaps — "
                f"placement='mesh' only, got rcfg.placement="
                f"{trainer.rcfg.placement!r}", plane="streaming")
        decision.bucketed = True
        decision.reason += "; tier-bucketed dispatch"
    if plan.scenario is not None and not plan.scenario.null:
        # scenario masks are staged on host per round's COHORT, so the
        # fused planes (which draw cohorts inside the compiled scan) need
        # the host replay of the keyed draw to know who round t sampled.
        # The streaming plane already demands KeyedReplayable; the device
        # plane only demands DeviceSampleable, so gate it here.
        if decision.plane == "device" \
                and not isinstance(trainer.sampler, KeyedReplayable):
            raise PlanError(
                f"a scenario on the device plane needs the sampler "
                f"capability KeyedReplayable (the host replay of the keyed "
                f"cohort draw is what the scenario masks are staged "
                f"against) but {type(trainer.sampler).__name__} does not "
                f"provide it; nearest viable plane: 'scanned'",
                plane="device", missing="KeyedReplayable",
                nearest="scanned")
        decision.scenario = True
        parts = [type(m).__name__ for m in plan.scenario.models]
        if plan.scenario.availability is not None:
            parts.append(type(plan.scenario.availability).__name__)
        if plan.scenario.cohort is not None:
            parts.append("AdaptiveCohort")
        decision.reason += f"; scenario active ({', '.join(parts)})"
    if plan.secure is not None:
        if trainer.rcfg.placement != "mesh":
            raise PlanError(
                f"secure aggregation masks the [C, ...] cohort stack with a "
                f"[C, C, ...] pairwise grid — placement='mesh' only, got "
                f"rcfg.placement={trainer.rcfg.placement!r}",
                plane=decision.plane)
        decision.secure = True
        decision.reason += (
            f"; secure aggregation "
            f"({'masked' if plan.secure.masked else 'open ring'}, "
            f"frac_bits={plan.secure.frac_bits})")
    if plan.mesh is not None:
        # stamped centrally so explicit-plane plans get the mesh audit
        # fields too, not just auto resolutions
        n = plan.mesh.n_devices()
        decision.mesh_shape = (n,)
        decision.axis_names = (plan.mesh.axis,)
        if decision.per_device_nbytes is None:
            if decision.plane == "device" \
                    and decision.packed_nbytes is not None:
                decision.per_device_nbytes = -(-decision.packed_nbytes // n)
            elif decision.working_set_nbytes is not None:
                # streaming: each data shard owns a full-capacity cache
                # (per-device capacity semantics — see MeshShardedCache)
                decision.per_device_nbytes = decision.working_set_nbytes
        decision.reason += \
            f"; mesh-sharded over {n} device(s) on axis {plan.mesh.axis!r}"
    return decision


def _resolve_plane(plan: ExecutionPlan, trainer) -> PlanDecision:
    sampler, dataset = trainer.sampler, trainer.dataset
    if plan.plane != "auto":
        check_plane(plan.plane, sampler, dataset)
        return PlanDecision(plan.plane, False,
                            f"explicit plane {plan.plane!r}")
    if isinstance(dataset, StreamingFederatedDataset):
        check_plane("streaming", sampler, dataset)
        return PlanDecision(
            "streaming", True,
            "dataset is a host-resident StreamingFederatedDataset")
    if isinstance(dataset, DeviceFederatedDataset):
        check_plane("device", sampler, dataset)
        return PlanDecision(
            "device", True, "dataset is already device-resident")
    if not _dataset_supports("device", dataset):
        # a host-assembly-only dataset (keyed round_batches but no
        # per-client shards to pack or stream): the fused planes are out
        # before any budget math
        check_plane("scanned", sampler, dataset)
        return PlanDecision(
            "scanned", True,
            f"dataset {type(dataset).__name__} supports only host assembly "
            f"(no per-client data shards to pack or stream)")
    budget = (plan.memory_budget_bytes if plan.memory_budget_bytes is not None
              else device_memory_budget())
    sds = trainer.session.streaming_dataset(dataset)   # host metadata only
    packed = sds.packed_nbytes
    # under a mesh the budget is PER DEVICE and the packed corpus shards
    # its client axis n_shards ways — a corpus that overflows one device
    # may fit the mesh, flipping auto back to the device plane
    n_shards = 1 if plan.mesh is None else plan.mesh.n_devices()
    packed_per_dev = -(-packed // n_shards)
    if isinstance(sampler, DeviceSampleable) and (budget is None
                                                  or packed_per_dev <= budget):
        sharded = ("" if n_shards == 1 else
                   f", {packed_per_dev} B/device over {n_shards} shards")
        return PlanDecision(
            "device", True,
            f"packed corpus ({packed} B{sharded}) fits the device memory "
            f"budget ({'unbounded' if budget is None else f'{budget} B'})",
            packed_nbytes=packed, budget_bytes=budget,
            per_device_nbytes=packed_per_dev if n_shards > 1 else None)
    # streaming working set: the ACTUAL tiered cache footprint the declared
    # CacheSpec would allocate, not a uniform slot_nbytes multiple — under
    # n_k skew the tiered bytes are several-fold smaller, which can flip
    # the plane choice at mid budgets
    layout = sds.tier_layout(plan.cache.tiers)
    if plan.cache.clients is None and plan.cache.bytes is None:
        cap = min(trainer.rcfg.clients_per_round * plan.chunk_rounds,
                  sds.n_clients)
    else:
        # mirror ShardCache exactly (tighter declaration wins); None when
        # the declared byte budget is below one slot per occupied tier —
        # ShardCache would refuse it, so streaming is out
        cap = sds.n_clients
        if plan.cache.clients is not None:
            cap = min(cap, plan.cache.clients)
        if plan.cache.bytes is not None:
            by_bytes = layout.capacity_for_bytes(plan.cache.bytes)
            cap = None if by_bytes is None else min(cap, by_bytes)
    working_set = None if cap is None else layout.bytes_for_capacity(cap)
    if (cap is not None and isinstance(sampler, KeyedReplayable)
            and (budget is None or working_set <= budget)):
        # say what actually ruled the device plane out: the budget only
        # when there IS one and the corpus exceeds it, the missing
        # capability otherwise (never "exceeds the budget (None B)")
        if not isinstance(sampler, DeviceSampleable):
            blocked = (f"the device plane is out (sampler "
                       f"{type(sampler).__name__} lacks DeviceSampleable)")
        elif n_shards > 1:
            blocked = (f"packed corpus ({packed} B, {packed_per_dev} "
                       f"B/device over {n_shards} shards) exceeds the "
                       f"per-device budget ({budget} B)")
        else:
            blocked = (f"packed corpus ({packed} B) exceeds the budget "
                       f"({budget} B)")
        fits = ("the unbounded budget" if budget is None
                else f"the budget ({budget} B)")
        return PlanDecision(
            "streaming", True,
            f"{blocked} but one chunk's participant working set ({cap} "
            f"clients over {layout.n_tiers} size tier(s), {working_set} B "
            f"tiered) fits {fits}",
            packed_nbytes=packed, budget_bytes=budget,
            working_set_nbytes=working_set)
    if not isinstance(sampler, DeviceSampleable):
        why = (f"sampler {type(sampler).__name__} lacks DeviceSampleable "
               f"(no traceable sample_device), so the fused on-device "
               f"planes are out")
    elif not isinstance(sampler, KeyedReplayable):
        why = (f"corpus exceeds the budget and sampler "
               f"{type(sampler).__name__} lacks KeyedReplayable (host "
               f"sample does not replay the keyed draw), so streaming is "
               f"out")
    elif cap is None:
        why = (f"the declared cache budget ({plan.cache.bytes} B) is below "
               f"the minimum viable tiered cache ({layout.min_viable_bytes} "
               f"B: one slot in each of {layout.n_tiers} occupied size "
               f"tier(s)), so streaming is out")
    else:
        why = (f"even one chunk's participant working set ({working_set} B "
               f"tiered) exceeds the budget ({budget} B)")
    check_plane("scanned", sampler, dataset)   # structured error, never a
    return PlanDecision(                       # raw crash downstream
        "scanned", True, f"host prefetch-queue fallback: {why}",
        packed_nbytes=packed, budget_bytes=budget,
        working_set_nbytes=working_set)


class _IdKey:
    """Identity-keyed jit-cache key component.  Holds a strong reference, so
    the wrapped object's ``id`` can never be recycled while a cache entry
    keyed on it is alive (the hazard of keying on bare ``id(obj)``)."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _IdKey) and other.obj is self.obj

    def __repr__(self):
        return f"_IdKey({type(self.obj).__name__}@{id(self.obj):#x})"


@dataclass
class TrainSession:
    """Warm execution resources that outlive a single ``run()`` call.

    Owns the packed/streaming datasets (built once), the persistent
    ``ShardCache`` (resident shards survive across ``run()`` calls — a
    second run, an eval loop or a resumed run re-uploads nothing for
    already-cached clients) and the jit caches (keyed by config identity, so
    a fresh trainer sharing the session — e.g. rebuilt for a resume — reuses
    compiled executables).  ``plan_log`` is the in-memory audit trail of
    every plan resolution."""
    device_ds: Optional[DeviceFederatedDataset] = None
    stream_ds: Optional[StreamingFederatedDataset] = None
    shard_cache: Optional[ShardCache] = None
    jit_cache: dict = field(default_factory=dict)
    plan_log: list = field(default_factory=list)
    _device_src: Any = None
    _device_mesh: Any = None
    _stream_src: Any = None
    _cache_key: Any = None
    _mesh_cache: dict = field(default_factory=dict)
    _dispatch_overhead_s: Optional[float] = None

    def mesh_for(self, spec: MeshSpec):
        """The built jax ``Mesh`` for a ``MeshSpec``, cached per spec — a
        spec always names the same devices within a process, and caching
        keeps a Mesh identity stable across ``run()`` calls so jitted
        executables keyed on it stay warm."""
        mesh = self._mesh_cache.get(spec)
        if mesh is None:
            mesh = self._mesh_cache[spec] = spec.build()
        return mesh

    def dispatch_overhead(self) -> float:
        """Measured per-dispatch overhead (seconds), measured ONCE per
        session and reused by every ``chunk_rounds="auto"`` resolution —
        the probe costs a trivial compile, and the overhead is a property
        of the host/runtime, not of any one plan."""
        if self._dispatch_overhead_s is None:
            self._dispatch_overhead_s = measure_dispatch_overhead()
        return self._dispatch_overhead_s

    def jit_fn(self, key, build):
        fn = self.jit_cache.get(key)
        if fn is None:
            fn = self.jit_cache[key] = build()
        return fn

    def device_dataset(self, dataset, shard_clients: bool = True,
                       mesh: Optional[MeshSpec] = None
                       ) -> DeviceFederatedDataset:
        # keyed on (source identity, mesh spec): packing places buffers
        # under the ACTIVE mesh context, so a corpus packed for one mesh
        # must never be silently reused for another (or for no mesh)
        if (self.device_ds is None or self._device_src is not dataset
                or self._device_mesh != mesh):
            if isinstance(dataset, DeviceFederatedDataset):
                self.device_ds = dataset
            else:
                self.device_ds = DeviceFederatedDataset.from_federated(
                    dataset, shard_clients=shard_clients)
            self._device_src = dataset
            self._device_mesh = mesh
        return self.device_ds

    def streaming_dataset(self, dataset) -> StreamingFederatedDataset:
        if self.stream_ds is None or self._stream_src is not dataset:
            if isinstance(dataset, StreamingFederatedDataset):
                self.stream_ds = dataset
            else:
                self.stream_ds = StreamingFederatedDataset.from_federated(
                    dataset)
            self._stream_src = dataset
        return self.stream_ds

    def shard_cache_for(self, sds: StreamingFederatedDataset,
                        capacity_clients: Optional[int],
                        capacity_bytes: Optional[int],
                        tiers: Optional[int] = None,
                        mesh: Optional[MeshSpec] = None) -> ShardCache:
        """The persistent cache, rebuilt only when the dataset, the
        declared capacity/tiering or the mesh changes (same declaration =>
        warm reuse).  Keyed on ``_IdKey(sds)``, never bare ``id(sds)``: the
        key holds a strong reference, so a rebuilt dataset can never land
        on a recycled id and silently inherit another corpus's resident
        shards.  Under a multi-device ``mesh`` the cache is a
        ``MeshShardedCache``: one full-capacity ``ShardCache`` per data
        shard, clients assigned ``cid % n_shards``."""
        n_shards = 1 if mesh is None else mesh.n_devices()
        key = (_IdKey(sds), capacity_clients, capacity_bytes, tiers,
               mesh if n_shards > 1 else None)
        if self.shard_cache is None or self._cache_key != key:
            if n_shards > 1:
                self.shard_cache = MeshShardedCache(
                    sds, n_shards,
                    capacity_clients=capacity_clients,
                    capacity_bytes=capacity_bytes, tiers=tiers)
            else:
                self.shard_cache = ShardCache(
                    sds, capacity_clients=capacity_clients,
                    capacity_bytes=capacity_bytes, tiers=tiers)
            self._cache_key = key
        return self.shard_cache
