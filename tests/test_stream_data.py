"""Data plane v2 certification: the streaming shard-cached plane trains the
same trajectory as every other tier — including the n_k-tiered slot layout
(5-way matrix on tests/_trajectory.py: per-round / scanned / device /
tiered-streaming / uniform-streaming) — resumed runs are bit-equal to
uninterrupted ones on all drivers, and the ShardCache tiering/LRU/packing
edge cases hold under property-based inputs (tests/_propcheck.py).  The
bugfix sweep (cache identity across dataset rebuilds, sub-slot byte
budgets, last-use LRU recency) has regression tests here that fail on the
pre-fix code."""
import gc
import weakref

import numpy as np
import pytest

from _propcheck import given, settings, st
from _trajectory import (
    STREAM_VARIANTS,
    assert_same_trajectory,
    default_rcfg,
    diurnal_sampler_fn,
    flat_w,
    make_clients,
    make_trainer,
    run_trajectory,
)
from repro.core import fedavg, fedmom, participants_in_span
from repro.core.sampling import DeviceUniformSampler
from repro.data import (FederatedDataset, ShardCache,
                        StreamingFederatedDataset, next_pow2)
from repro.launch.plan import CacheSpec, ExecutionPlan, PlanError


# ---------------------------------------------------------------------------
# five-way trajectory equivalence (the tentpole contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt_fn", [fedavg, fedmom])
def test_all_drivers_one_trajectory(opt_fn):
    """per-round == prefetch-queue == device-resident == tiered streaming
    == uniform streaming, over 13 rounds with a ragged last chunk."""
    clients = make_clients(seed=41)
    rcfg = default_rcfg()
    opt = opt_fn()
    ref = run_trajectory("per-round", opt, rcfg, clients, 13)
    for driver in ("scanned", "device", "streaming", "streaming-uniform"):
        got = run_trajectory(driver, opt, rcfg, clients, 13, chunk_rounds=5)
        assert_same_trajectory(got, ref)
    assert int(ref[1].t) == 13


def test_tiered_cache_smaller_than_uniform_same_trajectory():
    """The tentpole win: heavy n_k skew, identical trajectory, strictly
    smaller cache device footprint under tiered slots."""
    rng = np.random.default_rng(0)
    d = 5
    clients = []
    for n in [64, 3, 5, 2, 7, 4, 6, 3]:          # one huge, many tiny
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (x @ np.arange(1, d + 1) / d).astype(np.float32)
        clients.append({"x": x, "y": y})
    rcfg = default_rcfg()
    opt = fedmom()
    tr_t = make_trainer(opt, rcfg, clients, local_batch=2)
    hist_t = tr_t.run(10, plan=ExecutionPlan(plane="streaming",
                                             chunk_rounds=2,
                                             cache=CacheSpec(clients=8)),
                      verbose=False)
    tr_u = make_trainer(opt, rcfg, clients, local_batch=2)
    hist_u = tr_u.run(10, plan=ExecutionPlan(
        plane="streaming", chunk_rounds=2,
        cache=CacheSpec(clients=8, tiers=1)), verbose=False)
    ref = run_trajectory("per-round", opt, rcfg, clients, 10, local_batch=2)
    assert_same_trajectory((hist_t, tr_t.state), ref)
    assert_same_trajectory((hist_u, tr_u.state), ref)
    tiered, uniform = tr_t.stream_cache, tr_u.stream_cache
    assert len(tiered.tier_sizes) > 1 and len(uniform.tier_sizes) == 1
    assert tiered.nbytes < uniform.nbytes        # the footprint win
    assert tiered.hit_rate == uniform.hit_rate   # at equal hit-rate


def test_streaming_with_forced_evictions_stays_on_trajectory():
    """A cache guaranteeing exactly M clients + one-round chunks: every
    chunk may evict, and the trajectory still matches the per-round driver
    bit for bit."""
    clients = make_clients(seed=43, n=8)
    rcfg = default_rcfg()
    opt = fedmom()
    ref = run_trajectory("per-round", opt, rcfg, clients, 13)
    tr = make_trainer(opt, rcfg, clients)
    hist = tr.run(13, plan=ExecutionPlan(plane="streaming", chunk_rounds=1,
                                         cache=CacheSpec(clients=3)),
                  verbose=False)
    assert_same_trajectory((hist, tr.state), ref)
    cache = tr.stream_cache
    assert cache.capacity == 3
    assert all(s <= 3 for s in cache.tier_slots)
    assert cache.evictions > 0                  # streaming actually streamed
    assert cache.misses > cache.capacity
    assert 0.0 <= cache.hit_rate < 1.0


@pytest.mark.parametrize("tiers", [None, 1])
def test_streaming_corpus_exceeds_cache_capacity(tiers):
    """Acceptance: the packed corpus is bigger than the configured cache
    budget (in bytes), yet the plane trains the reference trajectory and
    the cache footprint honors the declared budget exactly."""
    clients = make_clients(seed=47, n=10)
    rcfg = default_rcfg()
    opt = fedmom()
    sds = StreamingFederatedDataset(
        [dict(c) for c in clients], seed=1)
    # cannot hold the corpus, but fits one round's 3-client working set in
    # BOTH layouts (the tiered guarantee prices every tier, so it needs a
    # little more headroom than budget // slot_nbytes rounding)
    budget = (2 * sds.packed_nbytes) // 3
    ref = run_trajectory("per-round", opt, rcfg, clients, 9)
    tr = make_trainer(opt, rcfg, clients)
    hist = tr.run(9, plan=ExecutionPlan(plane="streaming", chunk_rounds=1,
                                        cache=CacheSpec(bytes=budget,
                                                        tiers=tiers)),
                  verbose=False)
    assert_same_trajectory((hist, tr.state), ref)
    assert tr.stream_cache.nbytes <= budget
    assert tr.stream_cache.nbytes < sds.packed_nbytes
    assert len(tr.stream_cache.resident()) < sds.n_clients


@pytest.mark.parametrize("driver", STREAM_VARIANTS)
def test_streaming_diurnal_matches_per_round(driver):
    """Time-varying M(t): padded slots carry zero weight but still index
    data, so the cache must hold the full m_max participant set."""
    clients = make_clients(seed=53, n=8)
    rcfg = default_rcfg(clients_per_round=5, local_steps=3)
    opt = fedmom()
    sfn = diurnal_sampler_fn(m_min=2, m_max=5, period=7, seed=3)
    ref = run_trajectory("per-round", opt, rcfg, clients, 12, sampler_fn=sfn)
    got = run_trajectory(driver, opt, rcfg, clients, 12,
                         sampler_fn=sfn, chunk_rounds=1, cache_clients=6)
    assert_same_trajectory(got, ref)


@pytest.mark.parametrize("driver", STREAM_VARIANTS)
def test_streaming_hetero_steps_match_per_round(driver):
    clients = make_clients(seed=59)
    rcfg = default_rcfg()

    def hetero_fn(t):
        return np.random.default_rng(300 + t).integers(0, 5, size=3)

    opt = fedmom()
    ref = run_trajectory("per-round", opt, rcfg, clients, 10,
                         hetero_fn=hetero_fn)
    got = run_trajectory(driver, opt, rcfg, clients, 10,
                         hetero_fn=hetero_fn, chunk_rounds=4)
    assert_same_trajectory(got, ref)


# ---------------------------------------------------------------------------
# resume: a continued run == the uninterrupted run, per driver
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("driver",
                         ["per-round", "scanned", "device", "streaming",
                          "streaming-uniform"])
def test_resumed_run_equals_uninterrupted(driver, tmp_path):
    clients = make_clients(seed=61)
    rcfg = default_rcfg()
    opt = fedmom()
    ref = run_trajectory(driver, opt, rcfg, clients, 12, chunk_rounds=4)
    got = run_trajectory(driver, opt, rcfg, clients, 12, chunk_rounds=4,
                         resume_at=6, tmp_path=tmp_path)
    assert_same_trajectory(got, ref)
    assert int(got[1].t) == 12


def test_resume_rejects_stateful_sampler(tmp_path):
    """A sequential-RNG sampler would silently replay round-0 client sets
    after restore; resume must refuse it up front."""
    from repro.core import UniformSampler
    clients = make_clients(seed=69)
    rcfg = default_rcfg(local_steps=2)
    tr = make_trainer(fedavg(), rcfg, clients,
                      ckpt_path=str(tmp_path / "ck.npz"), ckpt_every=1)
    ds = FederatedDataset([dict(c) for c in clients], seed=1)
    tr.sampler = UniformSampler(ds.population(), 3, seed=2)
    with pytest.raises(ValueError, match="Device"):
        tr.run(2, verbose=False, resume=True)


def test_resume_rewinds_metrics_log(tmp_path):
    """Rounds logged after the last durable checkpoint (a crash window) are
    pruned on resume and re-logged once — no duplicate jsonl records."""
    import json

    from repro.checkpoint import append_metrics
    clients = make_clients(seed=77)
    rcfg = default_rcfg(local_steps=2)
    opt = fedmom()
    ck, mp = str(tmp_path / "ck.npz"), str(tmp_path / "m.jsonl")

    plan = ExecutionPlan(plane="device", chunk_rounds=3)

    def mk():
        return make_trainer(opt, rcfg, clients, ckpt_path=ck, ckpt_every=1,
                            metrics_path=mp)
    mk().run(6, plan=plan, verbose=False)                # durable round 5
    # simulate a crash that logged rounds 6-7 before their save landed
    append_metrics(mp, [{"round": 6, "loss": 999.0, "delta_norm": 0.0},
                        {"round": 7, "loss": 999.0, "delta_norm": 0.0}])
    tr = mk()
    tr.run(12, plan=plan, verbose=False, resume=True)
    with open(mp) as f:
        recs = [json.loads(line) for line in f]
    assert [r["round"] for r in recs] == list(range(12))  # each exactly once
    assert all(r["loss"] != 999.0 for r in recs)          # stale rows gone


def test_resume_without_ckpt_path_raises():
    clients = make_clients(seed=63)
    tr = make_trainer(fedavg(), default_rcfg(local_steps=2), clients)
    with pytest.raises(ValueError, match="ckpt_path"):
        tr.run(2, verbose=False, resume=True)


def test_resume_with_absent_checkpoint_starts_fresh(tmp_path):
    """First launch and resume-after-crash share one code path: no durable
    checkpoint means round 0, not an error."""
    clients = make_clients(seed=67)
    rcfg = default_rcfg(local_steps=2)
    opt = fedavg()
    ref = run_trajectory("per-round", opt, rcfg, clients, 5)
    tr = make_trainer(opt, rcfg, clients,
                      ckpt_path=str(tmp_path / "none.npz"), ckpt_every=1)
    hist = tr.run(5, verbose=False, resume=True)
    assert [r["round"] for r in hist] == list(range(5))
    np.testing.assert_allclose(flat_w(tr.state), flat_w(ref[1]), atol=1e-6)


# ---------------------------------------------------------------------------
# the streaming driver's contracts
# ---------------------------------------------------------------------------
def test_run_streaming_requires_device_sampler():
    clients = make_clients(seed=71)
    rcfg = default_rcfg(local_steps=2)
    tr = make_trainer(fedavg(), rcfg, clients)

    class HostOnly:
        def sample(self, t):
            raise NotImplementedError
    tr.sampler = HostOnly()
    with pytest.raises(PlanError, match="sample_device") as ei:
        tr.run(2, plan="streaming", verbose=False)
    assert ei.value.missing == "KeyedReplayable"


def test_run_streaming_rejects_stateful_sampler():
    """UniformSampler HAS sample_device but its host path is a sequential
    RNG, not a replay — staging the cache from it would silently feed the
    scan other clients' shards.  The streaming plane must refuse it, naming
    the missing KeyedReplayable capability and the nearest viable plane."""
    from repro.core import UniformSampler
    clients = make_clients(seed=75)
    rcfg = default_rcfg(local_steps=2)
    tr = make_trainer(fedavg(), rcfg, clients)
    ds = FederatedDataset([dict(c) for c in clients], seed=1)
    tr.sampler = UniformSampler(ds.population(), 3, seed=2)
    with pytest.raises(PlanError, match="replay") as ei:
        tr.run(2, plan="streaming", verbose=False)
    assert ei.value.missing == "KeyedReplayable"
    assert ei.value.nearest == "device"      # stateful sampler can still
    # run the fused device plane (keyed in-scan draws need no host replay)


def test_chunk_needing_more_clients_than_capacity_raises():
    clients = make_clients(seed=73, n=8, lo=30, hi=31)   # one size tier
    rcfg = default_rcfg()
    tr = make_trainer(fedavg(), rcfg, clients)
    with pytest.raises(ValueError, match="distinct clients"):
        # 4 rounds x M=3 from K=8 surfaces >2 distinct clients
        tr.run(4, plan=ExecutionPlan(plane="streaming", chunk_rounds=4,
                                     cache=CacheSpec(clients=2)),
               verbose=False)


def test_cache_stats_logged_in_chunk_metrics(tmp_path):
    """ShardCache hit/miss/eviction stats land durably on each chunk's last
    metrics record (history AND jsonl), not just on the live cache object —
    so perf_compare and resumed runs can read them after the fact."""
    import json
    clients = make_clients(seed=83, n=8)
    rcfg = default_rcfg()
    mp = str(tmp_path / "m.jsonl")
    tr = make_trainer(fedmom(), rcfg, clients, metrics_path=mp)
    tr.run(8, plan=ExecutionPlan(plane="streaming", chunk_rounds=2,
                                 cache=CacheSpec(clients=6)),
           verbose=False)
    cache = tr.stream_cache
    chunk_ends = [r for r in tr.history if "cache_misses" in r]
    assert [r["round"] for r in chunk_ends] == [1, 3, 5, 7]  # one per chunk
    assert sum(r["cache_hits"] for r in chunk_ends) == cache.hits
    assert sum(r["cache_misses"] for r in chunk_ends) == cache.misses
    assert sum(r["cache_evictions"] for r in chunk_ends) == cache.evictions
    assert chunk_ends[-1]["cache_hit_rate"] == pytest.approx(cache.hit_rate)
    with open(mp) as f:
        durable = [json.loads(line) for line in f]
    assert [r.get("cache_misses") for r in durable
            if "cache_misses" in r] == \
        [r["cache_misses"] for r in chunk_ends]
    # non-streaming planes carry no cache keys
    tr2 = make_trainer(fedmom(), rcfg, clients)
    tr2.run(4, plan=ExecutionPlan(plane="device", chunk_rounds=2),
            verbose=False)
    assert not any("cache_misses" in r for r in tr2.history)


def test_participants_in_span_replays_and_orders():
    clients = make_clients(seed=79, n=8)
    ds = FederatedDataset(clients, seed=1)
    s = DeviceUniformSampler(ds.population(), 3, seed=2)
    parts = participants_in_span(s, 0, 4)
    assert parts == list(dict.fromkeys(
        int(c) for t in range(4) for c in s.sample(t)[0]))
    assert len(parts) == len(set(parts))
    # dedup=False keeps the raw round-by-round sequence (repeats and round
    # order intact) — what ensure() needs for last-use LRU recency
    raw = participants_in_span(s, 0, 4, dedup=False)
    assert raw == [int(c) for t in range(4) for c in s.sample(t)[0]]
    assert list(dict.fromkeys(raw)) == parts
    # peeking ahead never perturbed the keyed draws
    np.testing.assert_array_equal(s.sample(0)[0], s.sample(0)[0])

    class Stateful:
        def sample(self, t):
            return np.array([0]), np.array([1.0])
    with pytest.raises(ValueError, match="Device"):
        participants_in_span(Stateful(), 0, 2)


def test_view_snapshot_survives_later_uploads():
    """The double-buffering invariant: a view taken before ensure() still
    reads the OLD shard contents (functional updates, no aliasing)."""
    clients = [{"x": np.full((4, 2), float(k), np.float32)}
               for k in range(6)]
    sds = StreamingFederatedDataset(clients, seed=0)
    cache = ShardCache(sds, capacity_clients=2)
    cache.ensure([0, 1])
    view0 = cache.view()
    before = np.asarray(view0.tier_arrays[0]["x"]).copy()
    cache.ensure([4, 5])                 # evicts both resident shards
    np.testing.assert_array_equal(
        np.asarray(view0.tier_arrays[0]["x"]), before)
    after = np.asarray(cache.view().tier_arrays[0]["x"])
    assert not np.array_equal(after, before)


def test_lru_evicts_least_recently_used_first():
    clients = [{"x": np.full((2, 1), float(k), np.float32)}
               for k in range(5)]
    sds = StreamingFederatedDataset(clients, seed=0)
    cache = ShardCache(sds, capacity_clients=3)
    cache.ensure([0, 1, 2])
    cache.ensure([1])                    # refresh 1: LRU order now 0, 2, 1
    cache.ensure([3])                    # evicts 0
    assert cache.resident() == {1, 2, 3}
    cache.ensure([4])                    # evicts 2
    assert cache.resident() == {1, 3, 4}
    assert cache.evictions == 2


def test_lru_recency_is_last_use_within_a_chunk():
    """Regression (pre-fix: recency refreshed in first-occurrence order of
    the deduped participant list): a multi-round chunk whose FINAL round
    reuses an early client must leave that client most-recent, so the next
    chunk's eviction targets the truly colder one."""
    clients = [{"x": np.full((2, 1), float(k), np.float32)}
               for k in range(4)]
    sds = StreamingFederatedDataset(clients, seed=0)
    cache = ShardCache(sds, capacity_clients=2)
    # one chunk, two rounds: round A uses [0, 1], round B reuses [0] —
    # the raw sequence the streaming driver now passes (dedup=False)
    cache.ensure([0, 1, 0])
    cache.ensure([2])                    # must evict 1 (0 was used LAST)
    assert cache.resident() == {0, 2}
    cache2 = ShardCache(sds, capacity_clients=2)
    cache2.ensure([1, 0, 1])
    cache2.ensure([3])                   # symmetric: evicts 0, keeps 1
    assert cache2.resident() == {1, 3}


def test_streaming_driver_feeds_raw_sequence_to_ensure(monkeypatch):
    """End-to-end guard on the recency bugfix: the chunk staging path must
    hand ensure() the RAW per-round participant sequence (repeats kept),
    not the deduped first-appearance list."""
    clients = make_clients(seed=91, n=8)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    seen = []
    orig = ShardCache.ensure

    def spy(self, client_ids):
        seen.append(list(client_ids))
        return orig(self, client_ids)
    monkeypatch.setattr(ShardCache, "ensure", spy)
    tr.run(6, plan=ExecutionPlan(plane="streaming", chunk_rounds=3,
                                 cache=CacheSpec(clients=8)),
           verbose=False)
    expected = [participants_in_span(tr.sampler, s, e, dedup=False)
                for s, e in ((0, 3), (3, 6))]
    assert seen == expected
    assert all(len(s) == 3 * tr.rcfg.clients_per_round for s in seen)


def test_cache_capacity_clamped_and_validated():
    clients = [{"x": np.zeros((3, 2), np.float32)} for _ in range(4)]
    sds = StreamingFederatedDataset(clients, seed=0)
    assert ShardCache(sds, capacity_clients=100).slots == 4   # clamp to K
    both = ShardCache(sds, capacity_clients=3,
                      capacity_bytes=2 * sds.slot_nbytes)
    assert both.slots == 2                                    # tighter wins
    with pytest.raises(ValueError, match="capacity"):
        ShardCache(sds)


def test_sub_slot_byte_budget_raises_with_minimum():
    """Regression (pre-fix: a byte budget below one slot silently rounded UP
    to a whole slot, exceeding the declaration): it must raise and name the
    minimum viable budget — one slot per occupied tier."""
    clients = [{"x": np.zeros((3, 2), np.float32)} for _ in range(4)]
    sds = StreamingFederatedDataset(clients, seed=0)
    lay = sds.tier_layout()
    with pytest.raises(ValueError, match="minimum viable") as ei:
        ShardCache(sds, capacity_bytes=1)
    assert str(lay.min_viable_bytes) in str(ei.value)
    # exactly the minimum is accepted, and the budget is honored
    edge = ShardCache(sds, capacity_bytes=lay.min_viable_bytes)
    assert edge.nbytes == lay.min_viable_bytes <= sds.slot_nbytes * 1
    # multi-tier: the minimum covers one slot in EVERY occupied tier
    skew = StreamingFederatedDataset(
        [{"x": np.zeros((n, 2), np.float32)} for n in (2, 3, 16, 64)],
        seed=0)
    mlay = skew.tier_layout()
    assert mlay.n_tiers > 1
    with pytest.raises(ValueError, match="minimum viable"):
        ShardCache(skew, capacity_bytes=mlay.min_viable_bytes - 1)
    assert ShardCache(skew,
                      capacity_bytes=mlay.min_viable_bytes).capacity == 1


def test_session_cache_keyed_on_object_not_raw_id():
    """Regression (pre-fix: the session cache key used raw ``id(sds)``, the
    exact id-recycling hazard ``_IdKey`` exists to prevent): the key must
    hold the dataset object itself, so a dead dataset's id can never be
    recycled into a stale-cache hit."""
    from repro.launch.plan import TrainSession, _IdKey
    clients = [{"x": np.full((3, 2), float(k), np.float32)}
               for k in range(4)]
    session = TrainSession()
    sds1 = StreamingFederatedDataset(clients, seed=0)
    c1 = session.shard_cache_for(sds1, 2, None)
    c1.ensure([0, 1])
    # same object + same declaration => warm reuse
    assert session.shard_cache_for(sds1, 2, None) is c1
    # the key component is an _IdKey holding a STRONG reference (pre-fix it
    # was the bare ``id()`` int): even with every OTHER reference severed,
    # the key alone must keep the dataset alive, so its id can never be
    # recycled while the key is still compared against
    assert isinstance(session._cache_key[0], _IdKey)
    ref = weakref.ref(sds1)
    session.stream_ds = None
    session._stream_src = None
    session.shard_cache = None           # sever the cache's own dataset ref
    del sds1, c1
    gc.collect()
    assert ref() is not None, \
        "cache key must keep the dataset alive (id-recycling guard)"
    # a different dataset object (rebuilt corpus) must get a FRESH cache
    sds2 = StreamingFederatedDataset([dict(c) for c in clients], seed=0)
    c2 = session.shard_cache_for(sds2, 2, None)
    assert c2.resident() == set()        # never inherits residency
    # a tiering change alone also rebuilds (different slot layout)
    c3 = session.shard_cache_for(sds2, 2, None, tiers=1)
    assert c3 is not c2


def test_streaming_dataset_validates_like_pack():
    with pytest.raises(ValueError, match="ragged"):
        StreamingFederatedDataset(
            [{"x": np.zeros((3, 2)), "y": np.zeros(4)}])
    with pytest.raises(ValueError, match="no samples"):
        StreamingFederatedDataset(
            [{"x": np.zeros((3, 2))}, {"x": np.zeros((0, 2))}])
    with pytest.raises(ValueError, match="fields"):
        StreamingFederatedDataset(
            [{"x": np.zeros((3, 2))}, {"y": np.zeros((3, 2))}])


# ---------------------------------------------------------------------------
# tier layout edges
# ---------------------------------------------------------------------------
def test_tier_layout_all_clients_one_tier_equals_uniform():
    """Same-size clients collapse to one tier whose footprint and slot
    geometry match the uniform (tiers=1) layout exactly."""
    clients = [{"x": np.zeros((24, 2), np.float32)} for _ in range(5)]
    sds = StreamingFederatedDataset(clients, seed=0)
    lay = sds.tier_layout()
    assert lay.sizes == (24,) and lay.tier_counts == (5,)
    tiered = ShardCache(sds, capacity_clients=3)
    uniform = ShardCache(sds, capacity_clients=3, tiers=1)
    assert tiered.nbytes == uniform.nbytes
    assert tiered.tier_slots == uniform.tier_slots == (3,)


def test_tier_layout_one_client_per_tier():
    clients = [{"x": np.zeros((n, 2), np.float32)} for n in (1, 2, 4, 8)]
    sds = StreamingFederatedDataset(clients, seed=0)
    lay = sds.tier_layout()
    assert lay.sizes == (1, 2, 4, 8)
    assert lay.tier_counts == (1, 1, 1, 1)
    cache = ShardCache(sds, capacity_clients=4)
    assert cache.tier_slots == (1, 1, 1, 1)
    cache.ensure([0, 1, 2, 3])
    assert cache.resident() == {0, 1, 2, 3}
    assert cache.nbytes == (1 + 2 + 4 + 8) * sds.row_nbytes


def test_tier_boundary_exact_power_of_two():
    """n_k == an exact power of two lands IN that tier, never the next one
    up — and next_pow2 itself is exact at the boundaries."""
    assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 31, 32, 33)] == \
        [1, 2, 4, 4, 8, 32, 32, 64]
    clients = [{"x": np.zeros((n, 2), np.float32)} for n in (32, 33, 64)]
    sds = StreamingFederatedDataset(clients, seed=0)
    lay = sds.tier_layout()
    assert lay.sizes == (32, 64)
    assert list(lay.tier_of) == [0, 1, 1]    # 32 stays in the 32 tier
    # the largest tier is capped at n_max, never padded past the corpus
    capped = StreamingFederatedDataset(
        [{"x": np.zeros((n, 2), np.float32)} for n in (3, 40)], seed=0)
    assert capped.tier_layout().sizes == (4, 40)


def test_tiers_knob_merges_smallest_upward():
    clients = [{"x": np.zeros((n, 2), np.float32)} for n in (1, 3, 9, 40)]
    sds = StreamingFederatedDataset(clients, seed=0)
    assert sds.tier_layout().sizes == (1, 4, 16, 40)
    lay2 = sds.tier_layout(tiers=2)
    assert lay2.sizes == (16, 40)
    assert list(lay2.tier_of) == [0, 0, 0, 1]    # small ones pad up
    lay1 = sds.tier_layout(tiers=1)
    assert lay1.sizes == (40,) and lay1.tier_counts == (4,)
    with pytest.raises(ValueError, match="tiers"):
        sds.tier_layout(tiers=0)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def test_tiered_gather_never_copies_a_corpus_per_client():
    """The per-client tier select keeps each tier corpus an unbatched
    operand.  A batched ``lax.switch`` broadcast every corpus to
    ``[C, slots_t, n_tier, ...]``, which a mesh-partitioned chunk could not
    fold away (14 GB of temporaries for LeNet's corpus on a v5e)."""
    import jax
    import jax.numpy as jnp

    clients = [{"x": np.zeros((n, 2), np.float32)}
               for n in (1, 1, 3, 3, 9, 9, 40, 40)]
    sds = StreamingFederatedDataset(clients, seed=0)
    cache = ShardCache(sds, capacity_clients=8)
    cache.ensure(range(8))
    view = cache.view()
    ids = jnp.asarray([0, 2, 4, 6])
    jaxpr = jax.make_jaxpr(lambda v: v.gather_round_batch(
        v.base_key(), jnp.int32(0), ids, 3, 1))(view)
    per_client = {(len(ids),) + a.shape
                  for arrs in view.tier_arrays for a in arrs.values()}
    assert all(a.shape[0] == 2 for arrs in view.tier_arrays
               for a in arrs.values())                 # two slots per tier
    shapes = {tuple(v.aval.shape) for e in _eqns(jaxpr.jaxpr)
              for v in e.outvars if hasattr(v.aval, "shape")}
    assert not shapes & per_client


# ---------------------------------------------------------------------------
# property-based packing/gather edge cases (seeded fallback when hypothesis
# is absent — see tests/_propcheck.py)
# ---------------------------------------------------------------------------
def _skewed_clients(rng, K, mixed_dtypes=False):
    """Heavily skewed n_k (1-sample clients next to 40-sample ones)."""
    out = []
    for k in range(K):
        n = int(rng.choice([1, 2, 3, 20, 40]))
        c = {"x": rng.normal(size=(n, 3)).astype(np.float32)}
        if mixed_dtypes:
            c["tokens"] = rng.integers(0, 50, size=(n, 4)).astype(np.int32)
        out.append(c)
    return out


def _assert_cache_gather_bit_equals_host(clients, cap, rounds, seed,
                                         m=2, H=3, b=2, tiers=None):
    """Drive a ShardCache through `rounds` keyed participant sets and check
    every gather against FederatedDataset.round_batches bit for bit."""
    import jax.numpy as jnp

    ds = FederatedDataset([dict(c) for c in clients], seed=seed)
    sds = StreamingFederatedDataset([dict(c) for c in clients], seed=seed)
    sampler = DeviceUniformSampler(ds.population(), m, seed=seed + 1)
    cache = ShardCache(sds, capacity_clients=cap, tiers=tiers)
    for t in range(rounds):
        ids, _ = sampler.sample(t)
        cache.ensure(ids)
        view = cache.view()
        got = view.gather_round_batch(view.base_key(), jnp.int32(t),
                                      jnp.asarray(ids), H, b)
        want = ds.round_batches(ids, H, b, t=t)
        for name in want:
            np.testing.assert_array_equal(want[name],
                                          np.asarray(got[name]))
    return cache


@settings(max_examples=6, deadline=None)
@given(st.integers(4, 9), st.integers(0, 1000))
def test_prop_skewed_counts_tiny_cache_forced_evictions(K, seed):
    """Skewed n_k + a cache guaranteeing exactly M clients: evictions are
    constant and the tiered gather never drifts from the host assembly
    (padding never leaks, the (tier, slot) indirection never mixes clients
    up)."""
    rng = np.random.default_rng(seed)
    clients = _skewed_clients(rng, K)
    cache = _assert_cache_gather_bit_equals_host(clients, cap=2, rounds=6,
                                                 seed=seed % 97)
    if K > 2:
        assert cache.misses > 2          # had to stream beyond capacity


@settings(max_examples=4, deadline=None)
@given(st.integers(4, 9), st.integers(0, 1000))
def test_prop_tiered_and_uniform_gathers_agree(K, seed):
    """tiers=None and tiers=1 read back identical bits for identical keyed
    draws — tiering only changes the footprint."""
    rng = np.random.default_rng(seed)
    clients = _skewed_clients(rng, K, mixed_dtypes=True)
    for tiers in (None, 1, 2):
        _assert_cache_gather_bit_equals_host(clients, cap=3, rounds=4,
                                             seed=seed % 91, tiers=tiers)


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 8), st.integers(0, 1000))
def test_prop_single_client_cache(K, seed):
    """capacity_clients=1 (the minimum): every round evicts, still exact."""
    rng = np.random.default_rng(seed)
    clients = _skewed_clients(rng, K)
    cache = _assert_cache_gather_bit_equals_host(clients, cap=1, rounds=5,
                                                 seed=seed % 89, m=1)
    assert cache.capacity == 1
    assert all(s <= 1 for s in cache.tier_slots)


@settings(max_examples=6, deadline=None)
@given(st.integers(3, 8), st.integers(0, 1000))
def test_prop_cache_exactly_at_capacity(K, seed):
    """distinct == capacity in one request must fill without raising; one
    more distinct client than the guarantee must raise."""
    rng = np.random.default_rng(seed)
    clients = _skewed_clients(rng, K)
    sds = StreamingFederatedDataset([dict(c) for c in clients], seed=0)
    cache = ShardCache(sds, capacity_clients=K)
    cache.ensure(list(range(K)))         # exactly at capacity: fine
    assert cache.resident() == set(range(K))
    assert cache.evictions == 0
    small = ShardCache(sds, capacity_clients=K - 1)
    with pytest.raises(ValueError, match="distinct clients"):
        small.ensure(list(range(K)))


@settings(max_examples=6, deadline=None)
@given(st.integers(3, 7), st.integers(0, 1000))
def test_prop_mixed_dtype_fields_roundtrip(K, seed):
    """int32 token fields next to float32 ones keep their dtypes and values
    through pad -> tiered upload -> (tier, slot) gather."""
    rng = np.random.default_rng(seed)
    clients = _skewed_clients(rng, K, mixed_dtypes=True)
    sds = StreamingFederatedDataset([dict(c) for c in clients], seed=0)
    cache = ShardCache(sds, capacity_clients=2)
    for arrs in cache.tier_arrays:
        assert arrs["tokens"].dtype == np.int32
        assert arrs["x"].dtype == np.float32
    _assert_cache_gather_bit_equals_host(clients, cap=2, rounds=4,
                                         seed=seed % 83)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 1000))
def test_prop_eviction_order_after_multi_round_chunks(seed):
    """Cross-chunk LRU property: after ensure() sees a raw multi-round
    sequence, the eviction victim is always the client whose LAST use is
    oldest — never one the final round just drew."""
    rng = np.random.default_rng(seed)
    K = 6
    clients = [{"x": np.zeros((2, 1), np.float32)} for _ in range(K)]
    sds = StreamingFederatedDataset(clients, seed=0)
    cache = ShardCache(sds, capacity_clients=3)
    last_use: dict = {}
    clock = 0
    for _ in range(8):
        chunk = [int(c) for c in rng.integers(0, K, size=4)]
        while len(set(chunk)) > 3:
            chunk = chunk[:-1]
        before = cache.resident()
        cache.ensure(chunk)
        for c in chunk:
            clock += 1
            last_use[c] = clock
        evicted = before - cache.resident()
        for v in evicted:
            # every survivor that was already resident must have a fresher
            # last use than the victim (the victim was the coldest)
            survivors = (before - evicted) - set(chunk)
            assert all(last_use.get(s, -1) >= last_use.get(v, -1)
                       for s in survivors)
