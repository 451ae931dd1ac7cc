"""Mesh-sharded round engine: sharded-vs-single-device certification.

``ExecutionPlan(mesh=MeshSpec(...))`` splits the round cohort over the
mesh's data axis and aggregates the weighted delta with a ``psum``.  The
contract certified here:

- ``mesh=None`` is BIT-equal to the pre-mesh planes (the refactor may not
  perturb the default path at all);
- a sharded run is trajectory-equal to the single-device run on every
  fused plane, within fp32 tolerance — the psum reassociates the cohort
  einsum, so the weighted-delta reduction order differs (observed drift
  ~5e-8 on the linreg fixture; the atol below is 1e-6);
- secure aggregation under a mesh stays BIT-equal: the uint32-ring sum is
  order-independent, and the secure path routes through the GSPMD
  fallback, never the fp32 psum;
- the auto rule re-prices the device plane at ceil(packed / n_devices)
  when the plan carries a mesh, and the flip is audited in ``plan_log``
  with ``mesh_shape`` / ``axis_names`` / ``per_device_nbytes``.

The sharded rows need >= 4 host devices; ``tests/conftest.py`` forces
four unless ``XLA_FLAGS`` already sets a count (the mesh-sharded CI lane
sets eight).  With fewer they skip.
"""
import jax
import numpy as np
import pytest

from _trajectory import (assert_bitwise_trajectory, assert_same_trajectory,
                         default_rcfg, flat_w, make_clients, make_trainer,
                         run_driver, run_trajectory)
from repro.core import fedavgm, fedmom
from repro.core.secure_agg import SecureAggSpec
from repro.data.stream import MeshShardedCache, StreamingFederatedDataset
from repro.kernels.client_step.ops import linreg_tier_step
from repro.launch.mesh import MeshSpec
from repro.launch.plan import ExecutionPlan, PlanError

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=4")

MESH4 = MeshSpec(devices=4)
# cohort size must divide the mesh for the shard_map plane; 4 clients per
# round over 4 devices puts exactly one client on each shard
N_CLIENTS, M = 8, 4


def _opt():
    return fedmom(eta=1.0, beta=0.9)


def _rcfg():
    return default_rcfg(clients_per_round=M)


# ---------------------------------------------------------------------------
# sharded == single-device on the fused planes (incl. streaming)
# ---------------------------------------------------------------------------
@needs_mesh
@pytest.mark.parametrize("driver", ["device", "streaming",
                                    "streaming-bucketed"])
def test_sharded_plane_matches_single_device(driver):
    clients = make_clients(n=N_CLIENTS)
    want = run_trajectory(driver, _opt(), _rcfg(), clients, 12,
                          chunk_rounds=4)
    got = run_trajectory(driver, _opt(), _rcfg(), clients, 12,
                         chunk_rounds=4, mesh=MESH4)
    assert_same_trajectory(got, want, atol=1e-6)


@needs_mesh
def test_sharded_uneven_cohort_falls_back():
    """C=3 does not divide a 4-way mesh: the round engine must take the
    GSPMD-constraint path, still matching the single-device trajectory."""
    clients = make_clients(n=N_CLIENTS)
    rcfg = default_rcfg(clients_per_round=3)
    want = run_trajectory("device", _opt(), rcfg, clients, 8, chunk_rounds=4)
    got = run_trajectory("device", _opt(), rcfg, clients, 8, chunk_rounds=4,
                         mesh=MESH4)
    assert_same_trajectory(got, want, atol=1e-6)


@needs_mesh
def test_sharded_resume_matches_straight_run(tmp_path):
    clients = make_clients(n=N_CLIENTS)
    straight = run_trajectory("streaming", _opt(), _rcfg(), clients, 10,
                              chunk_rounds=4, mesh=MESH4)
    resumed = run_trajectory("streaming", _opt(), _rcfg(), clients, 10,
                             chunk_rounds=4, mesh=MESH4, resume_at=5,
                             tmp_path=tmp_path)
    assert_same_trajectory(resumed, straight, atol=1e-6)


@needs_mesh
@pytest.mark.parametrize("kind", ["fedmom", "fedavgm"])
def test_sharded_plane_with_fused_kernels(kind):
    """The TPU compiler cannot partition a Pallas kernel, so under a mesh
    the fused server update runs whole on every device and still trains
    the one-device trajectory."""
    clients = make_clients(n=N_CLIENTS)
    make = {"fedmom": fedmom, "fedavgm": fedavgm}[kind]
    want = run_trajectory("device", make(eta=1.0, beta=0.9,
                                         use_fused_kernel=True),
                          _rcfg(), clients, 8, chunk_rounds=4)
    got = run_trajectory("device", make(eta=1.0, beta=0.9,
                                        use_fused_kernel=True),
                         _rcfg(), clients, 8, chunk_rounds=4, mesh=MESH4)
    assert_same_trajectory(got, want, atol=1e-6)


@needs_mesh
def test_client_step_hook_refused_under_mesh():
    """The fused client-step hook would gather every tier and run every
    client on every device: under a mesh the plan is refused, not run
    unsplit."""
    with pytest.raises(PlanError, match="does not split the cohort"):
        run_trajectory("streaming-bucketed", _opt(), _rcfg(),
                       make_clients(n=N_CLIENTS), 4, chunk_rounds=4,
                       mesh=MESH4,
                       client_step_fn=linreg_tier_step(use_kernel=True))


# ---------------------------------------------------------------------------
# mesh=None is the pre-mesh engine, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("driver", ["device", "streaming"])
def test_mesh_none_bitwise_equal_to_default(driver):
    """An explicit ``mesh=None`` never activates the sharding context, so
    the run is the SAME code path as a plan that predates the field —
    certified bitwise, no tolerance."""
    clients = make_clients(n=N_CLIENTS)
    want = run_trajectory(driver, _opt(), _rcfg(), clients, 10,
                          chunk_rounds=4)
    got = run_trajectory(driver, _opt(), _rcfg(), clients, 10,
                         chunk_rounds=4, mesh=None)
    assert_bitwise_trajectory(got, want)


# ---------------------------------------------------------------------------
# secure aggregation under a mesh: uint32 ring stays exact
# ---------------------------------------------------------------------------
@needs_mesh
def test_secure_under_mesh_bitwise_equal():
    masked = SecureAggSpec(masked=True, seed=5)
    clients = make_clients(n=N_CLIENTS)
    want = run_trajectory("device", _opt(), _rcfg(), clients, 8,
                          chunk_rounds=4, secure=masked)
    got = run_trajectory("device", _opt(), _rcfg(), clients, 8,
                         chunk_rounds=4, secure=masked, mesh=MESH4)
    assert_bitwise_trajectory(got, want)


# ---------------------------------------------------------------------------
# auto re-pricing + plan_log audit
# ---------------------------------------------------------------------------
@needs_mesh
def test_auto_flips_to_device_plane_under_mesh():
    """A budget between ceil(packed/4) and packed blocks the device plane
    on one device but admits it per-device under the 4-way mesh."""
    clients = make_clients(n=N_CLIENTS)
    sds = StreamingFederatedDataset([dict(c) for c in clients], seed=1)
    packed = sds.packed_nbytes
    budget = packed // 2                     # packed/4 <= budget < packed

    tr = make_trainer(_opt(), _rcfg(), clients)
    run_driver(tr, "auto", 4, chunk_rounds=4, memory_budget_bytes=budget)
    single = tr.session.plan_log[-1]
    assert single["plane"] != "device"
    assert "mesh_shape" not in single

    tr = make_trainer(_opt(), _rcfg(), clients)
    run_driver(tr, "auto", 4, chunk_rounds=4, memory_budget_bytes=budget,
               mesh=MESH4)
    sharded = tr.session.plan_log[-1]
    assert sharded["plane"] == "device"
    assert sharded["mesh_shape"] == [4]
    assert sharded["axis_names"] == ["data"]
    assert sharded["per_device_nbytes"] == -(-packed // 4)
    assert sharded["per_device_nbytes"] <= budget
    assert "mesh-sharded over 4 device(s)" in sharded["reason"]


@needs_mesh
def test_explicit_plane_plan_log_carries_mesh_fields():
    clients = make_clients(n=N_CLIENTS)
    tr = make_trainer(_opt(), _rcfg(), clients)
    run_driver(tr, "streaming", 4, chunk_rounds=4, mesh=MESH4)
    rec = tr.session.plan_log[-1]
    assert rec["plane"] == "streaming"
    assert rec["mesh_shape"] == [4]
    assert rec["axis_names"] == ["data"]


# ---------------------------------------------------------------------------
# MeshSpec validation (device-count independent)
# ---------------------------------------------------------------------------
def test_meshspec_validates_and_hashes():
    with pytest.raises(ValueError, match="positive int"):
        MeshSpec(devices=0)
    with pytest.raises(ValueError, match="axis"):
        MeshSpec(devices=2, axis="")
    assert hash(MeshSpec(devices=2)) == hash(MeshSpec(devices=2))
    assert MeshSpec(devices=2) != MeshSpec(devices=2, axis="pod")


def test_meshspec_build_rejects_oversized_mesh():
    too_many = jax.device_count() + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        MeshSpec(devices=too_many).build()


def test_plan_rejects_non_meshspec():
    with pytest.raises(PlanError, match="MeshSpec"):
        ExecutionPlan(mesh=4)


# ---------------------------------------------------------------------------
# MeshShardedCache unit behaviour (no mesh devices needed: host container)
# ---------------------------------------------------------------------------
def _uniform_clients(k=6, n_k=4, d=2):
    return [{"x": np.full((n_k, d), float(c), np.float32)} for c in range(k)]


def test_mesh_cache_routes_by_cid_mod_shards():
    sds = StreamingFederatedDataset(_uniform_clients(), seed=0)
    cache = MeshShardedCache(sds, 2, capacity_clients=2)
    cache.ensure([0, 1, 2, 3])
    assert cache.resident() == {0, 1, 2, 3}
    assert cache.shards[0].resident() == {0, 2}      # even cids -> shard 0
    assert cache.shards[1].resident() == {1, 3}
    cache.ensure([4, 5])                 # per-shard LRU evicts 0 and 1
    assert cache.resident() == {2, 3, 4, 5}
    assert cache.evictions == 2
    assert cache.hits == 0 and cache.misses == 6


def test_mesh_cache_view_slots_resolve_to_client_rows():
    """The composed view's client->slot table must point at each client's
    own corpus rows after the shard-order concat + offset shift."""
    sds = StreamingFederatedDataset(_uniform_clients(), seed=0)
    cache = MeshShardedCache(sds, 3, capacity_clients=2)
    cache.ensure([0, 1, 2, 3, 4, 5])
    view = cache.view()
    slots = np.asarray(view.client_slots)
    tiers = np.asarray(view.client_tiers)
    seen = set()
    for cid in range(6):
        rows = np.asarray(view.tier_arrays[int(tiers[cid])]["x"])[slots[cid]]
        np.testing.assert_array_equal(rows[:4], np.full((4, 2), float(cid)))
        seen.add((int(tiers[cid]), int(slots[cid])))
    assert len(seen) == 6                # no two clients share a slot


def test_mesh_cache_per_shard_capacity_semantics():
    """capacity_clients is a PER-DEVICE budget: 3 shards x 2 slots hold 6
    distinct clients even though one cache of 2 could not."""
    sds = StreamingFederatedDataset(_uniform_clients(), seed=0)
    cache = MeshShardedCache(sds, 3, capacity_clients=2)
    cache.ensure(range(6))
    assert cache.resident() == set(range(6))
    assert cache.capacity == 6 and cache.evictions == 0
    with pytest.raises(ValueError, match="n_shards"):
        MeshShardedCache(sds, 0, capacity_clients=2)
