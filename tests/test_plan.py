"""ExecutionPlan certification: auto plane resolution at forced memory
budgets (audited into plan_log / history / jsonl), structured PlanError
diagnostics naming the missing sampler capability Protocol and the nearest
viable plane, TrainSession warm reuse across run() calls (zero re-uploads,
shared jit caches), plan validation, and the deprecated run_* shims."""
import numpy as np
import pytest

from _trajectory import (
    assert_same_trajectory,
    default_rcfg,
    flat_w,
    linreg_loss,
    linreg_params,
    make_clients,
    make_trainer,
    run_trajectory,
    strip_events,
)
from repro.core import (DeviceSampleable, DeviceUniformSampler,
                        KeyedReplayable, UniformSampler, fedavg, fedmom)
from repro.data import FederatedDataset, StreamingFederatedDataset
from repro.launch.plan import (CacheSpec, CkptSpec, ExecutionPlan, PlanError,
                               TrainSession, as_plan, resolve)
from repro.launch.train import FederatedTrainer


def _sds_of(clients):
    return StreamingFederatedDataset([dict(c) for c in clients], seed=1)


# ---------------------------------------------------------------------------
# capability Protocols (the hasattr replacement)
# ---------------------------------------------------------------------------
def test_capability_protocols_classify_samplers():
    clients = make_clients(seed=11)
    pop = FederatedDataset(clients, seed=1).population()

    class HostOnly:
        def sample(self, t=0):
            return np.array([0]), np.array([1.0])

    assert isinstance(DeviceUniformSampler(pop, 3), KeyedReplayable)
    assert isinstance(DeviceUniformSampler(pop, 3), DeviceSampleable)
    stateful = UniformSampler(pop, 3)
    assert isinstance(stateful, DeviceSampleable)     # traceable draw: yes
    assert not isinstance(stateful, KeyedReplayable)  # host replay: no
    assert not isinstance(HostOnly(), DeviceSampleable)


# ---------------------------------------------------------------------------
# auto resolution at forced memory budgets (the ROADMAP rule, executable)
# ---------------------------------------------------------------------------
def test_auto_picks_device_when_corpus_fits_budget():
    clients = make_clients(seed=13)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    hist = tr.run(6, plan=ExecutionPlan(plane="auto", chunk_rounds=3,
                                        memory_budget_bytes=1 << 40),
                  verbose=False)
    dec = tr.session.plan_log[-1]
    assert dec["plane"] == "device" and dec["auto"]
    assert dec["packed_nbytes"] <= dec["budget_bytes"]
    # ... and the decision is auditable from the history too
    events = [r for r in hist if r.get("event") == "plan"]
    assert len(events) == 1 and events[0]["plane"] == "device"


def test_auto_picks_streaming_at_mid_budget():
    """Budget below the packed corpus but above one chunk's working set —
    the working set priced at the ACTUAL tiered cache footprint."""
    clients = make_clients(seed=17, n=8)
    sds = _sds_of(clients)
    # exactly one chunk's tiered working set (M=3 distinct, chunk_rounds=1):
    # far below packed, and precisely what the cache will allocate
    budget = sds.tier_layout().bytes_for_capacity(3)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    tr.run(4, plan=ExecutionPlan(plane="auto", chunk_rounds=1,
                                 memory_budget_bytes=budget),
           verbose=False)
    dec = tr.session.plan_log[-1]
    assert dec["plane"] == "streaming"
    assert dec["working_set_nbytes"] <= budget < dec["packed_nbytes"]
    assert tr.stream_cache is not None
    assert tr.stream_cache.nbytes <= budget


def test_auto_falls_back_to_scanned_at_tiny_budget():
    clients = make_clients(seed=19)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    tr.run(4, plan=ExecutionPlan(plane="auto", chunk_rounds=2,
                                 memory_budget_bytes=1),
           verbose=False)
    dec = tr.session.plan_log[-1]
    assert dec["plane"] == "scanned"
    assert "working set" in dec["reason"]


def test_auto_without_device_sampler_resolves_scanned():
    clients = make_clients(seed=23)
    tr = make_trainer(fedavg(), default_rcfg(local_steps=2), clients)

    class HostOnly:
        lowered_clients = 3
        seed = 2

        def sample(self, t=0):
            rng = np.random.default_rng(1000 + t)
            idx = rng.choice(6, size=3, replace=False)
            pop = FederatedDataset(clients, seed=1).population()
            return idx, pop.weights[idx].astype(np.float32)
    tr.sampler = HostOnly()
    tr.run(4, plan=ExecutionPlan(plane="auto", chunk_rounds=2,
                                 memory_budget_bytes=1 << 40),
           verbose=False)
    dec = tr.session.plan_log[-1]
    assert dec["plane"] == "scanned"
    assert "DeviceSampleable" in dec["reason"]


def test_auto_with_host_assembly_only_dataset_resolves_scanned():
    """A custom dataset implementing only the keyed round_batches contract
    (no per-client shards to pack or stream) resolves to scanned instead of
    crashing while building streaming metadata."""
    clients = make_clients(seed=101)
    inner = FederatedDataset([dict(c) for c in clients], seed=1)

    class HostAssemblyOnly:
        def round_batches(self, ids, H, b, t=0):
            return inner.round_batches(ids, H, b, t=t)
    opt = fedmom()
    tr = FederatedTrainer(
        loss_fn=linreg_loss, server_opt=opt, rcfg=default_rcfg(),
        dataset=HostAssemblyOnly(),
        sampler=DeviceUniformSampler(inner.population(), 3, seed=2),
        state=opt.init(linreg_params()), local_batch=4)
    dec = resolve(as_plan("auto"), tr, 6)
    assert dec.plane == "scanned"
    assert "host assembly" in dec.reason


def test_partial_dataset_contracts_raise_structured_errors():
    """Custom datasets implementing only part of a contract get PlanErrors,
    never raw AttributeErrors from deep inside packing/streaming."""
    clients = make_clients(seed=103)
    inner = FederatedDataset([dict(c) for c in clients], seed=1)
    opt = fedmom()

    def mk(dataset):
        return FederatedTrainer(
            loss_fn=linreg_loss, server_opt=opt, rcfg=default_rcfg(),
            dataset=dataset,
            sampler=DeviceUniformSampler(inner.population(), 3, seed=2),
            state=opt.init(linreg_params()), local_batch=4)

    class ShardOnly:                      # packable, but no host assembly
        data = inner.data
        seed = 1
    # auto lands on scanned (budget too small) but the dataset cannot feed
    # it: the structured error must fire at resolution time
    with pytest.raises(PlanError, match="round_batches"):
        resolve(as_plan(ExecutionPlan(plane="auto", memory_budget_bytes=1)),
                mk(ShardOnly()), 4)

    class DataNoSeed:                     # shards without the draw keying
        data = inner.data
    with pytest.raises(PlanError) as ei:
        resolve(as_plan("streaming"), mk(DataNoSeed()), 4)
    assert ei.value.plane == "streaming"


def test_auto_working_set_priced_at_tiered_bytes():
    """The auto rule's working-set term is the ACTUAL tiered footprint, not
    slots * uniform slot_nbytes: under n_k skew a budget too small for the
    uniform working set still resolves to streaming (pre-tentpole this fell
    back to scanned)."""
    rng = np.random.default_rng(5)
    clients = []
    for n in (64, 3, 5, 2, 7, 4, 6, 3):          # one huge, many tiny
        x = rng.normal(size=(n, 5)).astype(np.float32)
        clients.append({"x": x, "y": x[:, 0].copy()})
    sds = _sds_of(clients)
    uniform_ws = 3 * sds.slot_nbytes             # 3 clients at n_max rows
    tiered_ws = sds.tier_layout().bytes_for_capacity(3)
    assert tiered_ws < uniform_ws
    tr = make_trainer(fedmom(), default_rcfg(), clients, local_batch=2)
    dec = resolve(as_plan(ExecutionPlan(plane="auto", chunk_rounds=1,
                                        memory_budget_bytes=tiered_ws)),
                  tr, 4)
    assert dec.plane == "streaming"
    assert dec.working_set_nbytes == tiered_ws <= dec.budget_bytes


def test_auto_skips_streaming_when_cache_bytes_below_viable():
    """A declared CacheSpec.bytes below one slot per occupied tier can never
    be honored — auto must fall to scanned and say why, instead of letting
    ShardCache blow up mid-run."""
    clients = make_clients(seed=107, n=6)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    dec = resolve(as_plan(ExecutionPlan(
        plane="auto", chunk_rounds=1, cache=CacheSpec(bytes=1),
        memory_budget_bytes=1 << 10)), tr, 4)
    assert dec.plane == "scanned"
    assert "minimum viable" in dec.reason
    # ... including when a (viable) clients cap rides along: the byte
    # declaration still wins, exactly as ShardCache enforces it
    dec2 = resolve(as_plan(ExecutionPlan(
        plane="auto", chunk_rounds=1, cache=CacheSpec(clients=3, bytes=1),
        memory_budget_bytes=1 << 10)), tr, 4)
    assert dec2.plane == "scanned"
    assert "minimum viable" in dec2.reason


def test_streaming_reason_with_unbounded_budget_names_capability(
        monkeypatch):
    """Regression: when the device plane is skipped for a CAPABILITY (not
    the budget) and the budget is unbounded, the streaming decision used to
    claim 'packed corpus (… B) exceeds the budget (None B)'.  The audited
    reason must state what actually happened."""
    from typing import Protocol, runtime_checkable

    import repro.launch.plan as plan_mod

    @runtime_checkable
    class _MissingCap(Protocol):
        def not_a_sampler_method(self): ...

    clients = make_clients(seed=109)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    # simulate a sampler that streams (KeyedReplayable) but cannot run the
    # fused device plane: the resolve-time DeviceSampleable gate fails
    monkeypatch.setattr(plan_mod, "DeviceSampleable", _MissingCap)
    dec = plan_mod.resolve(as_plan("auto"), tr, 4)
    assert dec.plane == "streaming"
    assert dec.budget_bytes is None
    assert "None" not in dec.reason                  # no "(None B)"
    assert "DeviceSampleable" in dec.reason          # the real blocker
    assert "unbounded" in dec.reason


def test_auto_honors_dataset_type():
    """A streaming/device dataset pins the plane regardless of budget."""
    clients = make_clients(seed=29)
    ds = FederatedDataset([dict(c) for c in clients], seed=1)
    opt = fedmom()
    tr = FederatedTrainer(
        loss_fn=linreg_loss, server_opt=opt, rcfg=default_rcfg(),
        dataset=_sds_of(clients),
        sampler=DeviceUniformSampler(ds.population(), 3, seed=2),
        state=opt.init(linreg_params()), local_batch=4)
    dec = resolve(as_plan("auto"), tr, 8)
    assert dec.plane == "streaming"
    assert "StreamingFederatedDataset" in dec.reason


# ---------------------------------------------------------------------------
# the acceptance matrix row: auto is bit-equal to the plane it resolves to
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("target,budget_of", [
    ("device", lambda sds: 1 << 40),
    ("streaming", lambda sds: sds.tier_layout().bytes_for_capacity(4)),
    ("scanned", lambda sds: 1),
])
def test_auto_bit_equal_to_resolved_plane(target, budget_of):
    clients = make_clients(seed=31, n=8)
    rcfg = default_rcfg()
    opt = fedmom()
    budget = budget_of(_sds_of(clients))
    explicit = run_trajectory(target, opt, rcfg, clients, 10, chunk_rounds=1,
                              cache_clients=4)
    auto = run_trajectory("auto", opt, rcfg, clients, 10, chunk_rounds=1,
                          cache_clients=4, memory_budget_bytes=budget)
    assert_same_trajectory(auto, explicit)


def test_auto_diurnal_and_hetero_matrix():
    """The auto row holds on the harder matrix cells too (time-varying M(t)
    and straggler H_k), against the per-round reference."""
    from _trajectory import diurnal_sampler_fn
    clients = make_clients(seed=37, n=8)
    rcfg = default_rcfg(clients_per_round=5, local_steps=3)
    sfn = diurnal_sampler_fn(m_min=2, m_max=5, period=7, seed=3)
    opt = fedmom()
    ref = run_trajectory("per-round", opt, rcfg, clients, 12, sampler_fn=sfn)
    got = run_trajectory("auto", opt, rcfg, clients, 12, sampler_fn=sfn,
                         chunk_rounds=5, memory_budget_bytes=1 << 40)
    assert_same_trajectory(got, ref)

    def hetero_fn(t):
        return np.random.default_rng(300 + t).integers(0, 4, size=3)
    rcfg2 = default_rcfg()
    ref2 = run_trajectory("per-round", opt, rcfg2, clients, 10,
                          hetero_fn=hetero_fn)
    got2 = run_trajectory("auto", opt, rcfg2, clients, 10,
                          hetero_fn=hetero_fn, chunk_rounds=4,
                          memory_budget_bytes=_sds_of(clients).tier_layout()
                          .bytes_for_capacity(8))
    assert_same_trajectory(got2, ref2)


# ---------------------------------------------------------------------------
# structured PlanError diagnostics
# ---------------------------------------------------------------------------
def test_plan_error_names_capability_and_nearest_plane():
    clients = make_clients(seed=41)
    tr = make_trainer(fedavg(), default_rcfg(local_steps=2), clients)
    ds = FederatedDataset([dict(c) for c in clients], seed=1)
    tr.sampler = UniformSampler(ds.population(), 3, seed=2)
    with pytest.raises(PlanError) as ei:
        tr.run(2, plan="streaming", verbose=False)
    err = ei.value
    assert err.plane == "streaming"
    assert err.missing == "KeyedReplayable"
    assert err.nearest == "device"
    assert "KeyedReplayable" in str(err) and "device" in str(err)
    assert isinstance(err, ValueError)     # old except-clauses keep working


def test_plan_error_on_incompatible_dataset():
    """per_round needs host round_batches; a streaming dataset cannot feed
    it — the error names the nearest viable plane instead."""
    clients = make_clients(seed=43)
    ds = FederatedDataset(clients, seed=1)
    opt = fedavg()
    tr = FederatedTrainer(
        loss_fn=linreg_loss, server_opt=opt, rcfg=default_rcfg(),
        dataset=_sds_of(clients),
        sampler=DeviceUniformSampler(ds.population(), 3, seed=2),
        state=opt.init(linreg_params()), local_batch=4)
    with pytest.raises(PlanError) as ei:
        tr.run(2, plan="per_round", verbose=False)
    assert ei.value.nearest == "streaming"


def test_plan_validation_rejects_bad_values():
    with pytest.raises(PlanError, match="chunk_rounds"):
        ExecutionPlan(plane="scanned", chunk_rounds=0)
    with pytest.raises(PlanError, match="plane"):
        ExecutionPlan(plane="warp-drive")
    with pytest.raises(PlanError, match="local_batch"):
        ExecutionPlan(local_batch=0)
    with pytest.raises(PlanError, match="cache.clients"):
        ExecutionPlan(cache=CacheSpec(clients=-1))
    with pytest.raises(PlanError, match="cache.tiers"):
        ExecutionPlan(cache=CacheSpec(tiers=0))
    with pytest.raises(PlanError, match="cache.tiers"):
        ExecutionPlan(cache=CacheSpec(tiers=2.5))
    with pytest.raises(PlanError, match="log_every"):
        as_plan(42)          # old positional run(n, log_every) migration
    with pytest.raises(PlanError, match="plan must be"):
        as_plan(object())
    # non-int knobs fail eagerly, not deep inside jit shape handling
    with pytest.raises(PlanError, match="local_batch"):
        ExecutionPlan(local_batch=2.5)
    with pytest.raises(PlanError, match="chunk_rounds"):
        ExecutionPlan(chunk_rounds=2.5)
    # aliases normalize
    assert ExecutionPlan(plane="per-round").plane == "per_round"
    assert as_plan("per-round").plane == "per_round"


def test_local_batch_is_a_field_and_plan_override_is_call_scoped():
    clients = make_clients(seed=47)
    tr = make_trainer(fedmom(), default_rcfg(), clients, local_batch=4)
    assert tr.local_batch == 4
    tr.run(2, plan=ExecutionPlan(plane="device", chunk_rounds=2,
                                 local_batch=2), verbose=False)
    # the run used b=2 (its jitted chunk is keyed on it) ...
    assert any(k[0] == "ondevice_chunk" and k[3] == 2
               for k in tr.session.jit_cache)
    # ... but a one-off plan never leaks into later runs
    assert tr.local_batch == 4
    with pytest.raises(PlanError, match="local_batch"):
        make_trainer(fedmom(), default_rcfg(), clients, local_batch=0)
    with pytest.deprecated_call():
        tr.set_local_batch(3)
    assert tr.local_batch == 3 and tr.local_batch_size() == 3


def test_plan_ckpt_spec_configures_checkpointing(tmp_path):
    """CkptSpec checkpoints the run it is declared for, call-scoped: the
    trainer's own (absent) checkpoint config is restored afterwards."""
    from repro.checkpoint import latest_round
    clients = make_clients(seed=53)
    tr = make_trainer(fedmom(), default_rcfg(local_steps=2), clients)
    ck = str(tmp_path / "plan-ck.npz")
    tr.run(6, plan=ExecutionPlan(plane="device", chunk_rounds=3,
                                 ckpt=CkptSpec(every=1, path=ck)),
           verbose=False)
    assert latest_round(ck) == 5
    assert tr.ckpt_path is None and tr.ckpt_every == 0
    tr.run(2, plan="device", verbose=False)          # no ckpt sink leaks
    assert latest_round(ck) == 5


def test_ckpt_spec_path_only_keeps_trainer_cadence(tmp_path):
    """CkptSpec(path=...) redirects the sink without zeroing a trainer's
    configured ckpt_every (unset fields merge, they don't overwrite)."""
    from repro.checkpoint import latest_round
    clients = make_clients(seed=89)
    old = str(tmp_path / "old.npz")
    alt = str(tmp_path / "alt.npz")
    tr = make_trainer(fedmom(), default_rcfg(local_steps=2), clients,
                      ckpt_path=old, ckpt_every=2)
    tr.run(6, plan=ExecutionPlan(plane="device", chunk_rounds=3,
                                 ckpt=CkptSpec(path=alt)),
           verbose=False)
    assert latest_round(alt) == 5                    # cadence preserved
    assert latest_round(old) == -1
    assert tr.ckpt_path == old and tr.ckpt_every == 2


def test_streaming_prefetch_disabled_stays_on_trajectory():
    """The serialized A/B arm (prefetch=0: upload strictly after the
    previous chunk's compute) trains the same trajectory."""
    clients = make_clients(seed=97, n=8)
    rcfg = default_rcfg()
    opt = fedmom()
    ref = run_trajectory("per-round", opt, rcfg, clients, 10)
    tr = make_trainer(opt, rcfg, clients)
    hist = tr.run(10, plan=ExecutionPlan(plane="streaming", chunk_rounds=4,
                                         cache=CacheSpec(clients=8),
                                         prefetch=0),
                  verbose=False)
    assert_same_trajectory((hist, tr.state), ref)


def test_fused_loop_retires_completed_chunk_on_failure(tmp_path):
    """If preparing a later chunk blows up after an earlier chunk's compute
    was dispatched, that chunk's metrics and due checkpoint are still
    retired before the error propagates — the jsonl and the checkpoint stay
    one trajectory prefix, and a resume continues instead of re-running the
    whole chunk."""
    import json

    from repro.checkpoint import latest_round
    clients = make_clients(seed=79)
    ck = str(tmp_path / "ck.npz")
    mp = str(tmp_path / "m.jsonl")

    def exploding_hetero(t):
        if t >= 3:
            raise RuntimeError("scheduler feed died")
        return np.full(3, 4)

    tr = make_trainer(fedmom(), default_rcfg(), clients,
                      hetero_fn=exploding_hetero, ckpt_path=ck,
                      ckpt_every=1, metrics_path=mp)
    with pytest.raises(RuntimeError, match="scheduler feed died"):
        tr.run(6, plan=ExecutionPlan(plane="device", chunk_rounds=3),
               verbose=False)
    # chunk 0 (rounds 0-2) completed on device: its checkpoint is durable
    # and its rounds are logged exactly once, nothing beyond them
    assert latest_round(ck) == 2
    with open(mp) as f:
        recs = [json.loads(line) for line in f]
    assert [r["round"] for r in recs if "event" not in r] == [0, 1, 2]
    assert [r["round"] for r in strip_events(tr.history)] == [0, 1, 2]


def test_auto_decision_logged_durably(tmp_path):
    """The jsonl audit record has no 'round' key, so resume's prune_metrics
    keeps it, and the per-round records around it stay intact."""
    import json
    clients = make_clients(seed=59)
    mp = str(tmp_path / "m.jsonl")
    tr = make_trainer(fedmom(), default_rcfg(local_steps=2), clients,
                      metrics_path=mp)
    tr.run(4, plan=ExecutionPlan(plane="auto", chunk_rounds=2,
                                 memory_budget_bytes=1 << 40),
           verbose=False)
    with open(mp) as f:
        recs = [json.loads(line) for line in f]
    events = [r for r in recs if r.get("event") == "plan"]
    assert len(events) == 1
    assert events[0]["plane"] == "device" and "reason" in events[0]
    assert "round" not in events[0]
    assert [r["round"] for r in recs if "event" not in r] == list(range(4))
    # explicit planes audit to plan_log only (history stays trajectory-pure)
    tr2 = make_trainer(fedmom(), default_rcfg(local_steps=2), clients)
    tr2.run(2, plan="scanned", verbose=False)
    assert strip_events(tr2.history) == tr2.history
    assert tr2.session.plan_log[-1]["plane"] == "scanned"


# ---------------------------------------------------------------------------
# TrainSession: warm caches across run() calls and across trainers
# ---------------------------------------------------------------------------
def test_warm_session_second_run_has_zero_reuploads():
    """Cross-call cache persistence (the ROADMAP candidate): a second run()
    over the same participant schedule re-uploads NOTHING for resident
    clients — the upload counter does not move."""
    clients = make_clients(seed=61, n=6)
    opt = fedmom()
    tr = make_trainer(opt, default_rcfg(), clients)
    plan = ExecutionPlan(plane="streaming", chunk_rounds=4,
                         cache=CacheSpec(clients=6))   # K slots: no evictions
    ref = tr.run(12, plan=plan, verbose=False)
    cache = tr.stream_cache
    cold_misses, cold_hits = cache.misses, cache.hits
    assert cold_misses > 0
    w_ref = flat_w(tr.state)
    tr.state = opt.init(linreg_params())
    tr.history = []
    hist = tr.run(12, plan=plan, verbose=False)
    assert tr.stream_cache is cache                  # same warm cache
    assert cache.misses == cold_misses               # zero re-uploads
    assert cache.hits > cold_hits                    # served from residency
    np.testing.assert_allclose(flat_w(tr.state), w_ref, atol=0)
    assert [r["round"] for r in strip_events(hist)] == list(range(12))


def test_session_shared_across_trainers_reuses_cache_and_jit():
    """An eval loop / resume rebuilds the trainer over the SAME dataset and
    sampler but passes session= — the shard cache stays warm and the jitted
    executables are reused, not rebuilt.  (A different dataset object
    rebuilds both: serving a stale cache for new data would be a bug.)"""
    clients = make_clients(seed=67, n=6)
    opt = fedmom()
    rcfg = default_rcfg()
    plan = ExecutionPlan(plane="streaming", chunk_rounds=4,
                         cache=CacheSpec(clients=6))
    tr1 = make_trainer(opt, rcfg, clients)
    tr1.run(8, plan=plan, verbose=False)
    cache = tr1.stream_cache
    misses = cache.misses
    n_jit = len(tr1.session.jit_cache)
    assert n_jit > 0
    tr2 = FederatedTrainer(
        loss_fn=tr1.loss_fn, server_opt=opt, rcfg=rcfg,
        dataset=tr1.dataset, sampler=tr1.sampler,
        state=opt.init(linreg_params()), local_batch=4,
        session=tr1.session)
    tr2.run(8, plan=plan, verbose=False)
    assert tr2.stream_cache is cache                 # warm across trainers
    assert cache.misses == misses                    # zero re-uploads
    assert len(tr2.session.jit_cache) == n_jit       # no recompilation
    ref = run_trajectory("per-round", opt, rcfg, clients, 8)
    assert_same_trajectory((strip_events(tr2.history), tr2.state), ref)


def test_new_dataset_object_rebuilds_session_resources():
    clients = make_clients(seed=67, n=6)
    opt = fedmom()
    rcfg = default_rcfg()
    plan = ExecutionPlan(plane="streaming", chunk_rounds=4,
                         cache=CacheSpec(clients=6))
    tr1 = make_trainer(opt, rcfg, clients)
    tr1.run(8, plan=plan, verbose=False)
    cache = tr1.stream_cache
    tr2 = make_trainer(opt, rcfg, clients, session=tr1.session)
    tr2.run(8, plan=plan, verbose=False)             # fresh dataset object
    assert tr2.stream_cache is not cache             # no stale shards


def test_cache_rebuilt_when_capacity_changes():
    clients = make_clients(seed=71, n=6)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    tr.run(4, plan=ExecutionPlan(plane="streaming", chunk_rounds=2,
                                 cache=CacheSpec(clients=6)), verbose=False)
    first = tr.stream_cache
    tr.run(4, plan=ExecutionPlan(plane="streaming", chunk_rounds=1,
                                 cache=CacheSpec(clients=3)), verbose=False)
    assert tr.stream_cache is not first
    assert tr.stream_cache.capacity == 3
    second = tr.stream_cache
    # ... and a tiering change alone rebuilds too (different slot layout)
    tr.run(4, plan=ExecutionPlan(plane="streaming", chunk_rounds=1,
                                 cache=CacheSpec(clients=3, tiers=1)),
           verbose=False)
    assert tr.stream_cache is not second
    assert tr.stream_cache.capacity == 3
    assert len(tr.stream_cache.tier_sizes) == 1


# ---------------------------------------------------------------------------
# deprecated shims (the CI legacy lane runs the full matrix through them;
# here: they warn, and they stay bit-equal to the plan API)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shim,plan", [
    ("run_scanned", ExecutionPlan(plane="scanned", chunk_rounds=4)),
    ("run_device", ExecutionPlan(plane="device", chunk_rounds=4)),
    ("run_streaming", ExecutionPlan(plane="streaming", chunk_rounds=4)),
])
def test_legacy_shims_warn_and_stay_bit_equal(shim, plan):
    clients = make_clients(seed=73)
    rcfg = default_rcfg()
    opt = fedmom()
    tr_new = make_trainer(opt, rcfg, clients)
    hist_new = tr_new.run(9, plan=plan, verbose=False)
    tr_old = make_trainer(opt, rcfg, clients)
    with pytest.deprecated_call():
        hist_old = getattr(tr_old, shim)(9, chunk_rounds=4, verbose=False)
    assert_same_trajectory((hist_old, tr_old.state),
                           (hist_new, tr_new.state))


# ---------------------------------------------------------------------------
# chunk_rounds="auto" + the bucketed knob
# ---------------------------------------------------------------------------

def test_auto_chunk_rounds_from_measured_overhead(monkeypatch):
    """chunk_rounds='auto' resolves from the session's measured dispatch
    overhead: amortized to the 25us/round target, clamped to [8, 256] and
    to the run length, and audited on the decision record."""
    from repro.launch import plan as plan_mod

    clients = make_clients(seed=31)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    monkeypatch.setattr(plan_mod, "measure_dispatch_overhead",
                        lambda n=50: 500e-6)     # 500us -> ceil(20) -> 20
    plan = ExecutionPlan(plane="streaming", chunk_rounds="auto")
    dec = resolve(plan, tr, 100)
    assert dec.chunk_rounds == 20
    assert dec.dispatch_overhead_s == 500e-6
    assert "chunk_rounds auto -> 20" in dec.reason
    rec = dec.record()
    assert rec["chunk_rounds"] == 20 and rec["dispatch_overhead_s"] > 0
    # measured once per session, reused across resolutions
    monkeypatch.setattr(plan_mod, "measure_dispatch_overhead",
                        lambda n=50: (_ for _ in ()).throw(AssertionError))
    assert resolve(plan, tr, 100).chunk_rounds == 20


def test_auto_chunk_rounds_clamps():
    from repro.launch.plan import auto_chunk_rounds

    assert auto_chunk_rounds(1e-6, 1000) == 8       # floor
    assert auto_chunk_rounds(1.0, 100_000) == 256   # ceiling
    assert auto_chunk_rounds(500e-6, 1000) == 20    # ceil(500/25)
    assert auto_chunk_rounds(500e-6, 12) == 12      # run-length clamp
    assert auto_chunk_rounds(1e-6, 3) == 3


def test_auto_chunk_rounds_trains_on_trajectory():
    clients = make_clients(seed=32)
    opt = fedmom()
    rcfg = default_rcfg()
    ref = run_trajectory("streaming", opt, rcfg, clients, 12)
    got = run_trajectory("streaming", opt, rcfg, clients, 12,
                         chunk_rounds="auto")
    assert_same_trajectory(got, ref)


def test_bucketed_validation():
    # non-bool rejected eagerly
    with pytest.raises(PlanError, match="cache.bucketed"):
        ExecutionPlan(cache=CacheSpec(bucketed=1))
    # pinned non-streaming plane rejected at construction
    with pytest.raises(PlanError, match="streaming"):
        ExecutionPlan(plane="device", cache=CacheSpec(bucketed=True))
    # placement='scan' rejected at resolve (bucketed dispatch is a vmap)
    clients = make_clients(seed=33)
    tr = make_trainer(fedmom(), default_rcfg(placement="scan"), clients)
    plan = ExecutionPlan(plane="streaming", cache=CacheSpec(bucketed=True))
    with pytest.raises(PlanError, match="placement"):
        resolve(plan, tr, 10)


def test_bucketed_decision_audited():
    clients = make_clients(seed=34)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    plan = ExecutionPlan(plane="streaming", cache=CacheSpec(bucketed=True))
    dec = resolve(plan, tr, 10)
    assert dec.bucketed and dec.record()["bucketed"] is True
    assert "tier-bucketed" in dec.reason
