"""Scenario engine + lazy shard providers.

Four contracts:

* **Keyed determinism** — every scenario draw is a pure function of
  ``(seed, tag, t, client_id)`` (vectorized splitmix64-style hashing, no
  sequential RNG), so rounds can be staged out of order and replayed.
* **Plane-agnostic trajectories** — a ``ScenarioSpec`` on the plan yields
  the SAME trajectory on per_round / scanned / device / streaming (and
  tolerance-equal on bucketed streaming, same as scenario-off), and
  ``ScenarioSpec()`` (null) is bit-equal to no scenario at all.
* **Resumability** — dropout runs resume bit-equal, including the
  sequential adaptive-cohort state (rebuilt by host warmup replay).
* **Provider transparency** — a ``ShardProvider``-backed corpus trains
  bit-equal to the same corpus materialized up front, scales to 100k+
  clients without materializing on host, and schema violations raise
  ``CorpusSchemaError`` naming the offending client.
"""
import numpy as np
import pytest

from _propcheck import given, settings, st
from _trajectory import (DRIVERS, assert_same_trajectory, flat_w,
                         linreg_loss, linreg_params, make_clients,
                         run_trajectory)
from repro.core import (DeviceUniformSampler, RoundConfig, UniformSampler,
                        fedmom)
from repro.data import (CorpusSchemaError, ShardProvider,
                        StreamingFederatedDataset)
from repro.launch.plan import CacheSpec, ExecutionPlan, PlanError
from repro.launch.train import FederatedTrainer, _eval_spans
from repro.scenario import (AdaptiveCohort, AvailabilityModel,
                            ConstantAvailability, DiurnalAvailability,
                            LatencyStragglers, LifecycleModel,
                            MinAvailability, PerClientDropout,
                            ScenarioSampler, ScenarioSpec, UniformDropout,
                            ZipfLinregProvider, keyed_uniforms,
                            zipf_linreg_provider)
from repro.scenario.spec import ScenarioRuntime

RCFG = RoundConfig(clients_per_round=4, local_steps=6, lr=0.05)
SPEC = ScenarioSpec(dropout=UniformDropout(rate=0.35),
                    stragglers=LatencyStragglers(deadline_s=5.0), seed=7)


# ---------------------------------------------------------------------------
# keyed draws
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 10_000))
def test_keyed_uniforms_deterministic_and_bounded(seed, t):
    cids = np.arange(64)
    u = keyed_uniforms(seed, "tag", t, cids)
    assert u.shape == (64,) and np.all((0.0 <= u) & (u < 1.0))
    assert np.array_equal(u, keyed_uniforms(seed, "tag", t, cids))
    # order independence: the draw for a client doesn't depend on the
    # cohort it is staged with (prefetch/bucketing reorder freely)
    assert np.array_equal(u[::2], keyed_uniforms(seed, "tag", t, cids[::2]))
    # separate streams per tag / per round
    assert not np.array_equal(u, keyed_uniforms(seed, "other", t, cids))
    assert not np.array_equal(u, keyed_uniforms(seed, "tag", t + 1, cids))


def test_lifecycle_models_cap_semantics():
    cids = np.arange(256)
    H = 10
    # rate=0 is the identity model; rate=1 drops everyone short of H
    assert np.all(UniformDropout(0.0).step_caps(0, 3, cids, H) == H)
    caps1 = UniformDropout(1.0).step_caps(0, 3, cids, H)
    assert np.all((0 <= caps1) & (caps1 < H))
    # a generous deadline lets everyone finish; an impossible one nobody
    lazy = LatencyStragglers(deadline_s=1e6)
    assert np.all(lazy.step_caps(0, 3, cids, H) == H)
    harsh = LatencyStragglers(deadline_s=1e-6)
    assert np.all(harsh.step_caps(0, 3, cids, H) == 0)
    # per-client rates are time-invariant (a flaky device is always flaky)
    pcd = PerClientDropout(scale=0.8)
    assert np.array_equal(pcd.client_rates(5, cids), pcd.client_rates(5, cids))
    assert np.all((0 <= pcd.client_rates(5, cids))
                  & (pcd.client_rates(5, cids) <= 0.8))
    for model in (UniformDropout(0.5), pcd, LatencyStragglers(5.0)):
        assert isinstance(model, LifecycleModel)
        caps = model.step_caps(0, 3, cids, H)
        assert caps.dtype == np.int32 and np.all((0 <= caps) & (caps <= H))


def test_model_validation():
    with pytest.raises(ValueError, match="rate"):
        UniformDropout(rate=1.5)
    with pytest.raises(ValueError, match="scale"):
        PerClientDropout(scale=-0.1)
    with pytest.raises(ValueError, match="deadline"):
        LatencyStragglers(deadline_s=0.0)
    with pytest.raises(TypeError, match="step_caps"):
        ScenarioSpec(dropout="not a model")
    with pytest.raises(TypeError, match="AvailabilityModel"):
        ScenarioSpec(availability=3)
    with pytest.raises(ValueError, match="goal"):
        AdaptiveCohort(goal=0)


def test_spec_null_and_stateful():
    assert ScenarioSpec().null
    assert not ScenarioSpec(dropout=UniformDropout(0.1)).null
    assert not ScenarioSpec(availability=ConstantAvailability(3)).null
    assert not ScenarioSpec().stateful
    assert ScenarioSpec(cohort=AdaptiveCohort(goal=2)).stateful
    assert SPEC.models == (SPEC.dropout, SPEC.stragglers)


# ---------------------------------------------------------------------------
# availability
# ---------------------------------------------------------------------------
def test_availability_models():
    d = DiurnalAvailability(m_min=2, m_max=8, period=10)
    assert isinstance(d, AvailabilityModel) and d.peak == 8
    for t in range(20):
        m = d.m_at(t)
        assert 2 <= m <= 8
        assert int(d.m_device(t)) == m
    comp = MinAvailability((d, ConstantAvailability(5)))
    assert comp.peak == 5
    assert all(comp.m_at(t) == min(d.m_at(t), 5) for t in range(20))
    with pytest.raises(ValueError, match="m_min"):
        DiurnalAvailability(m_min=0, m_max=4)


def test_scenario_sampler_replay_and_masking():
    from repro.data import FederatedDataset
    ds = FederatedDataset(make_clients(n=10, lo=4, hi=8), seed=1)
    av = DiurnalAvailability(m_min=2, m_max=6, period=7)
    sampler = ScenarioSampler(population=ds.population(), availability=av,
                              seed=3)
    assert sampler.lowered_clients == 6
    for t in range(14):
        idx, w = sampler.sample(t)          # host replay of the device draw
        di, dw = sampler.sample_device(sampler.base_key(), t)
        assert np.array_equal(np.asarray(idx), np.asarray(di))
        assert np.allclose(np.asarray(w), np.asarray(dw))
        m = av.m_at(t)
        assert np.all(np.asarray(w)[m:] == 0.0)
        assert np.all(np.asarray(w)[:m] > 0.0)
    with pytest.raises(ValueError, match="population has"):
        ScenarioSampler(population=ds.population(),
                        availability=ConstantAvailability(11))


# ---------------------------------------------------------------------------
# runtime composition
# ---------------------------------------------------------------------------
def test_runtime_masks_are_prefix_and_composed():
    rt = ScenarioRuntime(SPEC, local_steps=6)
    cids = np.arange(8)
    caps = rt.steps_for(3, cids)
    expect = np.minimum(SPEC.dropout.step_caps(7, 3, cids, 6),
                        SPEC.stragglers.step_caps(7, 3, cids, 6))
    assert np.array_equal(caps, expect)
    masks = rt.masks_for(3, cids)
    assert masks.shape == (8, 6)
    assert np.array_equal(masks.sum(axis=1).astype(np.int32), caps)
    # prefix form: once a client stops, it stays stopped
    assert np.all(np.diff(masks, axis=1) <= 0)


def test_runtime_availability_zeroes_tail_slots():
    spec = ScenarioSpec(availability=DiurnalAvailability(2, 6, period=7))
    rt = ScenarioRuntime(spec, local_steps=4)
    for t in range(10):
        caps = rt.steps_for(t, np.arange(6))
        m = spec.availability.m_at(t)
        assert np.all(caps[m:] == 0) and np.all(caps[:m] == 4)


def test_adaptive_cohort_monotone_and_warmup():
    spec = ScenarioSpec(dropout=UniformDropout(0.5),
                        cohort=AdaptiveCohort(goal=3, m_min=2), seed=5)
    sampler = DeviceUniformSampler(
        __import__("repro.data", fromlist=["FederatedDataset"])
        .FederatedDataset(make_clients(n=10, lo=4, hi=8), seed=1)
        .population(), 6, seed=2)
    a = ScenarioRuntime(spec, local_steps=6)
    seq = [a.steps_for(t, sampler.sample(t)[0]) for t in range(9)]
    # out-of-order staging is an error while the EMA is live
    with pytest.raises(RuntimeError, match="in order"):
        a.steps_for(4, np.arange(6))
    # warmup replay rebuilds the same EMA state as running from scratch
    b = ScenarioRuntime(spec, local_steps=6)
    b.warmup(6, sampler)
    for t in range(6, 9):
        assert np.array_equal(b.steps_for(t, sampler.sample(t)[0]), seq[t])
    assert a._rate_ema == b._rate_ema


# ---------------------------------------------------------------------------
# plane-agnostic trajectories
# ---------------------------------------------------------------------------
CLIENTS = make_clients(n=8, lo=8, hi=16)


def _ref(scenario=None, n_rounds=12, **kw):
    return run_trajectory("per-round", fedmom(eta=1.0, beta=0.9), RCFG,
                          CLIENTS, n_rounds, scenario=scenario, **kw)


def test_null_scenario_bit_equal_to_off():
    base = _ref()
    null = _ref(scenario=ScenarioSpec())
    assert [r["loss"] for r in base[0]] == [r["loss"] for r in null[0]]
    assert np.array_equal(flat_w(base[1]), flat_w(null[1]))
    # the completed metric only appears when a scenario is active
    assert all("completed" not in r for r in null[0])


@pytest.mark.parametrize("driver", DRIVERS[1:] + ("streaming-bucketed",))
def test_dropout_scenario_same_on_every_plane(driver):
    want = _ref(scenario=SPEC, chunk_rounds=5)
    got = run_trajectory(driver, fedmom(eta=1.0, beta=0.9), RCFG, CLIENTS,
                         12, scenario=SPEC, chunk_rounds=5)
    assert_same_trajectory(got, want)
    comp = [r["completed"] for r in got[0]]
    assert comp == [r["completed"] for r in want[0]]
    assert min(comp) < RCFG.clients_per_round     # attrition actually bites


def test_scenario_changes_the_trajectory():
    base = _ref()
    drop = _ref(scenario=SPEC)
    assert [r["loss"] for r in base[0]] != [r["loss"] for r in drop[0]]


@pytest.mark.parametrize("driver", ("per-round", "scanned", "streaming"))
def test_dropout_resume_bit_equal(driver, tmp_path):
    full = run_trajectory(driver, fedmom(eta=1.0, beta=0.9), RCFG, CLIENTS,
                          14, scenario=SPEC, chunk_rounds=5)
    stitched = run_trajectory(driver, fedmom(eta=1.0, beta=0.9), RCFG,
                              CLIENTS, 14, scenario=SPEC, chunk_rounds=5,
                              resume_at=8, tmp_path=tmp_path)
    assert_same_trajectory(stitched, full, atol=0)


def test_adaptive_cohort_resume_bit_equal(tmp_path):
    av = DiurnalAvailability(m_min=2, m_max=6, period=10)
    spec = ScenarioSpec(dropout=PerClientDropout(scale=0.8),
                        availability=av,
                        cohort=AdaptiveCohort(goal=3, m_min=2), seed=11)
    rcfg = RoundConfig(clients_per_round=6, local_steps=6, lr=0.05)

    def sampler_fn(pop):
        return ScenarioSampler(population=pop, availability=av, seed=2)

    kw = dict(scenario=spec, sampler_fn=sampler_fn, chunk_rounds=5)
    full = run_trajectory("scanned", fedmom(eta=1.0, beta=0.9), rcfg,
                          CLIENTS, 16, **kw)
    stitched = run_trajectory("scanned", fedmom(eta=1.0, beta=0.9), rcfg,
                              CLIENTS, 16, resume_at=9, tmp_path=tmp_path,
                              **kw)
    assert_same_trajectory(stitched, full, atol=0)


def test_device_plane_scenario_needs_keyed_sampler():
    from repro.data import FederatedDataset
    ds = FederatedDataset([dict(c) for c in CLIENTS], seed=1)
    tr = FederatedTrainer(
        loss_fn=linreg_loss, server_opt=fedmom(eta=1.0, beta=0.9),
        rcfg=RCFG, dataset=ds,
        sampler=UniformSampler(ds.population(), RCFG.clients_per_round,
                               seed=2),
        state=fedmom(eta=1.0, beta=0.9).init(linreg_params()))
    with pytest.raises(PlanError, match="KeyedReplayable"):
        tr.run(4, plan=ExecutionPlan(plane="device", scenario=SPEC),
               verbose=False)


# ---------------------------------------------------------------------------
# eval sub-chunk cadence
# ---------------------------------------------------------------------------
def test_eval_spans_boundaries():
    # no eval_fn: uniform chunking
    assert _eval_spans(0, 20, 8) == [(0, 8), (8, 16), (16, 20)]
    # cadence finer than the chunk: a span ends after every eval round
    spans = _eval_spans(0, 20, 8, 3)
    assert spans == [(0, 1), (1, 4), (4, 7), (7, 10), (10, 13), (13, 16),
                     (16, 19), (19, 20)]
    assert all(e - s <= 8 for s, e in spans)
    assert [e for s, e in spans] == sorted({t + 1 for t in range(20)
                                            if t % 3 == 0} | {20})
    # cadence coarser than the chunk: chunk_rounds still caps every span
    assert _eval_spans(0, 20, 8, 50) == [(0, 1), (1, 9), (9, 17), (17, 20)]
    # resume mid-schedule: spans re-align to the absolute eval rounds
    assert _eval_spans(5, 20, 8, 4) == [(5, 9), (9, 13), (13, 17), (17, 20)]
    assert _eval_spans(0, 0, 8, 3) == []


@pytest.mark.parametrize("driver", ("scanned", "device", "streaming"))
def test_eval_cadence_finer_than_chunk(driver):
    def ev(state):
        return {"eval_probe": float(np.asarray(flat_w(state)).sum())}

    hp, _ = _ref(n_rounds=17, eval_fn=ev, log_every=4)
    hc, _ = run_trajectory(driver, fedmom(eta=1.0, beta=0.9), RCFG, CLIENTS,
                           17, chunk_rounds=8, eval_fn=ev, log_every=4)
    per = {r["round"]: r["eval_probe"] for r in hp if "eval_probe" in r}
    chk = {r["round"]: r["eval_probe"] for r in hc if "eval_probe" in r}
    # every per-round eval round is evaluated under the chunked plane, at
    # the identical state (bit-equal planes => bit-equal probes)
    assert set(per) <= set(chk)
    assert all(per[t] == chk[t] for t in per)
    # the loss metric (round.py: sum(eff_w * losses) / wsum) feeds no
    # state, and XLA orders that fp32 reduction differently in the two
    # programs: the per-round plane's jitted round_step and the scan body
    # of the chunked planes (1 ulp at rounds 4, 8, 9 of 17, at any
    # chunk_rounds, 1 included, with or without the eval split); the state
    # itself is bit-equal, as the probes above show
    np.testing.assert_allclose([r["loss"] for r in hc],
                               [r["loss"] for r in hp], rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# lazy shard providers
# ---------------------------------------------------------------------------
def test_provider_protocol_and_zipf_counts():
    p = ZipfLinregProvider(100, dim=4, n_min=2, n_max=32, seed=0)
    assert isinstance(p, ShardProvider)
    assert p.n_clients == 100 and p.counts.shape == (100,)
    assert np.all((2 <= p.counts) & (p.counts <= 32))
    s = p.shard(17)
    assert s["x"].shape == (int(p.counts[17]), 4)
    assert s["y"].shape == (int(p.counts[17]),)
    # pure function of (seed, cid): refetch after eviction is bit-identical
    assert np.array_equal(s["x"], p.shard(17)["x"])
    assert not np.array_equal(p.shard(17)["x"][:1],
                              ZipfLinregProvider(100, dim=4, n_min=2,
                                                 n_max=32,
                                                 seed=1).shard(17)["x"][:1])


def test_provider_dataset_validation():
    p = zipf_linreg_provider(10, dim=3)
    with pytest.raises(ValueError, match="exactly one"):
        StreamingFederatedDataset(data=[{"x": np.zeros((2, 3))}], provider=p)
    with pytest.raises(ValueError, match="exactly one"):
        StreamingFederatedDataset()

    class BadCounts:
        n_clients = 10
        counts = np.array([3, 0, 3, 3, 3, 3, 3, 3, 3, 3])
        fields = p.fields

        def shard(self, cid):
            return p.shard(cid)

    with pytest.raises(CorpusSchemaError, match="client 1"):
        StreamingFederatedDataset.from_provider(BadCounts())

    class LyingProvider:
        """Declares counts that its shards don't honor."""
        n_clients = 10
        counts = p.counts + 1
        fields = p.fields

        def shard(self, cid):
            return p.shard(cid)

    ds = StreamingFederatedDataset.from_provider(LyingProvider())
    with pytest.raises(CorpusSchemaError, match="provider shard"):
        ds.shard(0)


@settings(max_examples=4, deadline=None)
@given(st.integers(6, 12), st.integers(0, 1000))
def test_provider_matches_materialized_bit_for_bit(n_clients, seed):
    provider = ZipfLinregProvider(n_clients, dim=5, n_min=4, n_max=16,
                                  seed=seed)
    materialized = [provider.shard(cid) for cid in range(n_clients)]

    def train(ds):
        rcfg = RoundConfig(clients_per_round=3, local_steps=4, lr=0.05)
        opt = fedmom(eta=1.0, beta=0.9)
        tr = FederatedTrainer(
            loss_fn=linreg_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
            sampler=DeviceUniformSampler(ds.population(), 3, seed=2),
            state=opt.init(linreg_params()), local_batch=4)
        plan = ExecutionPlan(plane="streaming", chunk_rounds=4,
                             cache=CacheSpec(clients=12))
        hist = [r for r in tr.run(8, plan=plan, verbose=False)
                if "event" not in r]
        return hist, tr.state

    got = train(StreamingFederatedDataset.from_provider(provider, seed=9))
    want = train(StreamingFederatedDataset(materialized, seed=9))
    assert [r["loss"] for r in got[0]] == [r["loss"] for r in want[0]]
    assert np.array_equal(flat_w(got[1]), flat_w(want[1]))


def test_provider_100k_clients_streams_without_materializing():
    provider = zipf_linreg_provider(100_000, dim=8, n_min=4, n_max=32,
                                    seed=0)
    ds = StreamingFederatedDataset.from_provider(provider, seed=9)
    assert ds.n_clients == 100_000 and ds.data is None
    rcfg = RoundConfig(clients_per_round=4, local_steps=4, lr=0.05)
    opt = fedmom(eta=100_000 / 4, beta=0.9)
    tr = FederatedTrainer(
        loss_fn=linreg_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=DeviceUniformSampler(ds.population(), 4, seed=2),
        state=opt.init({"w": np.zeros(8, np.float32),
                        "b": np.zeros((), np.float32)}),
        local_batch=4)
    plan = ExecutionPlan(plane="streaming", chunk_rounds=3,
                         cache=CacheSpec(clients=12),
                         scenario=SPEC)
    hist = [r for r in tr.run(6, plan=plan, verbose=False)
            if "event" not in r]
    assert len(hist) == 6 and all(np.isfinite(r["loss"]) for r in hist)
    cache = tr.stream_cache
    # the 100k-client corpus was never materialized: the cache (a few
    # dozen tiered slots) is a tiny fraction of the packed corpus, and only
    # the touched clients were ever synthesized
    row = (8 + 1) * 4
    assert cache.nbytes < 0.01 * int(provider.counts.sum()) * row
