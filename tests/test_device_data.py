"""Data plane v1 certification: corpus packing (padding, counts, dtypes),
bit-equality of the in-scan minibatch gather with the host keyed assembly,
trajectory equivalence of the device-resident tier (via the shared
tests/_trajectory.py harness), and the async checkpoint writer."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _trajectory import (
    assert_same_trajectory,
    default_rcfg,
    diurnal_sampler_fn,
    flat_w,
    make_clients,
    make_trainer,
    run_trajectory,
)
from repro.core import fedavg, fedmom
from repro.data import DeviceFederatedDataset, FederatedDataset
from repro.launch.train import FederatedTrainer


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------
def test_pack_shapes_counts_and_padding():
    clients = make_clients(seed=3)
    counts = np.array([len(c["x"]) for c in clients])
    dds = DeviceFederatedDataset.pack(clients, seed=1)
    K, n_max = len(clients), counts.max()
    assert dds.n_clients == K and dds.n_max == n_max
    assert dds.arrays["x"].shape == (K, n_max, 5)
    assert dds.arrays["y"].shape == (K, n_max)
    np.testing.assert_array_equal(np.asarray(dds.counts), counts)
    for k, c in enumerate(clients):
        got = np.asarray(dds.arrays["x"][k])
        np.testing.assert_array_equal(got[: counts[k]], c["x"])
        assert np.all(got[counts[k]:] == 0)          # zero padding above n_k
    assert dds.nbytes == sum(a.nbytes for a in dds.arrays.values())


def test_pack_boundary_client_at_n_max():
    """A client with n_k == n_max has no padding and round-trips exactly."""
    clients = make_clients(seed=5, n=4)
    counts = [len(c["x"]) for c in clients]
    k_max = int(np.argmax(counts))
    dds = DeviceFederatedDataset.pack(clients, seed=0)
    np.testing.assert_array_equal(
        np.asarray(dds.arrays["x"][k_max]), clients[k_max]["x"])


def test_pack_preserves_nonuniform_leaf_dtypes():
    """int32 token streams next to float32 images, per-field dtypes kept."""
    rng = np.random.default_rng(11)
    clients = [{"tokens": rng.integers(0, 90, size=(n, 8)).astype(np.int32),
                "x": rng.normal(size=(n, 4)).astype(np.float32)}
               for n in (7, 12, 9)]
    dds = DeviceFederatedDataset.pack(clients, seed=0)
    assert dds.arrays["tokens"].dtype == jnp.int32
    assert dds.arrays["x"].dtype == jnp.float32
    assert dds.arrays["tokens"].shape == (3, 12, 8)


def test_pack_rejects_ragged_fields():
    with pytest.raises(ValueError, match="ragged"):
        DeviceFederatedDataset.pack(
            [{"x": np.zeros((3, 2)), "y": np.zeros(4)}])
    with pytest.raises(ValueError, match="no samples"):
        DeviceFederatedDataset.pack(
            [{"x": np.zeros((3, 2))}, {"x": np.zeros((0, 2))}])


# ---------------------------------------------------------------------------
# host/device gather equivalence (the bit-replay contract)
# ---------------------------------------------------------------------------
def _image_clients(rng, sizes):
    return [{"x": rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
             "y": rng.integers(0, 62, size=n).astype(np.int32)}
            for n in sizes]


def _token_clients(rng, sizes):
    return [{"tokens": rng.integers(0, 90, size=(n, 80)).astype(np.int32),
             "labels": rng.integers(0, 90, size=(n, 80)).astype(np.int32)}
            for n in sizes]


def _gather_case(case):
    """(clients, cohort size, mesh or None) for one bit-equality case."""
    rng = np.random.default_rng(7)
    if case == "vector":
        return make_clients(seed=7), 3, None
    if case == "image":
        return _image_clients(rng, (23, 31, 17, 26, 35, 20)), 3, None
    if case == "tokens":
        return _token_clients(rng, (23, 31, 17, 26, 35, 20)), 3, None
    if case == "small_client":          # n_0 = 5 < H*b = 12, every round
        return _image_clients(rng, (5, 31, 17, 26)), 4, None
    from repro.launch.mesh import MeshSpec
    return make_clients(seed=7, n=8), 4, MeshSpec(devices=4).build()


@pytest.mark.parametrize("case", ["vector", "image", "tokens",
                                  "small_client", "mesh4"])
def test_gather_round_batch_bit_equals_host_assembly(case):
    from repro.core import DeviceUniformSampler
    from repro.sharding import FED_MESH_RULES, axis_rules
    clients, C, mesh = _gather_case(case)
    ds = FederatedDataset([dict(c) for c in clients], seed=1)
    if mesh is None:
        dds = DeviceFederatedDataset.from_federated(ds)
    else:
        with axis_rules(mesh, FED_MESH_RULES):
            dds = DeviceFederatedDataset.from_federated(ds)
        for leaf in dds.arrays.values():     # the client axis is split
            shards = leaf.addressable_shards
            assert len(shards) == 4
            assert all(sh.data.shape[0] * 4 == leaf.shape[0]
                       for sh in shards)
    sampler = DeviceUniformSampler(ds.population(), C, seed=2)
    gather = jax.jit(
        lambda d, key, t, ids: d.gather_round_batch(key, t, ids, 4, 3))
    for t in range(25):
        idx, _ = sampler.sample(t)
        host = ds.round_batches(idx, 4, 3, t=t)
        dev = gather(dds, dds.base_key(), jnp.int32(t), jnp.asarray(idx))
        assert set(dev) == set(host)
        for name in host:
            assert dev[name].dtype == host[name].dtype
            np.testing.assert_array_equal(host[name],
                                          np.asarray(dev[name]))


_GATHER = re.compile(r'"?stablehlo\.gather"?.*slice_sizes = array<i64: '
                     r'([0-9, ]+)>.*: \(tensor<([0-9x]+)x[a-z]')


@pytest.mark.parametrize("make", [_image_clients, _token_clients],
                         ids=["image_f32", "tokens_i32"])
def test_gather_reads_only_drawn_rows(make):
    """The lowered round gather slices single (client, row) samples out of
    the corpus: no gather on a corpus leaf takes more than one row, so a
    round reads C*H*b rows, never a client's whole padded slab."""
    clients = make(np.random.default_rng(3), (7, 19, 12, 16, 9))
    dds = DeviceFederatedDataset.pack(clients, seed=0)
    K, n_max = dds.n_clients, dds.n_max
    text = jax.jit(
        lambda d, key, t, ids: d.gather_round_batch(key, t, ids, 4, 3)
    ).lower(dds, dds.base_key(), jnp.int32(0), jnp.arange(3)).as_text()
    corpus = []
    for m in map(_GATHER.search, text.splitlines()):
        if m is None:
            continue
        sizes = [int(v) for v in m.group(1).split(",")]
        dims = [int(v) for v in m.group(2).split("x")]
        if dims[:2] == [K, n_max]:
            corpus.append(sizes)
    assert len(corpus) == len(dds.arrays)         # one gather per leaf
    assert all(s[:2] == [1, 1] for s in corpus), corpus


def test_gather_with_replacement_small_client():
    """n_k < H*b: every drawn row is a real sample (padding never leaks)."""
    rng = np.random.default_rng(13)
    clients = [{"x": rng.normal(size=(3, 2)).astype(np.float32)},
               {"x": rng.normal(size=(30, 2)).astype(np.float32)}]
    dds = DeviceFederatedDataset.pack(clients, seed=4)
    H, b = 4, 2                                   # need 8 > n_0 = 3
    batch = dds.gather_round_batch(dds.base_key(), 0, jnp.asarray([0, 1]),
                                   H, b)
    rows = np.asarray(batch["x"][0]).reshape(-1, 2)
    real = clients[0]["x"]
    for r in rows:
        assert any(np.array_equal(r, s) for s in real)
    # and the host assembly replays the same draw bit for bit
    ds = FederatedDataset(clients, seed=4)
    host = ds.round_batches([0, 1], H, b, t=0)
    np.testing.assert_array_equal(host["x"], np.asarray(batch["x"]))


def test_round_batches_keyed_draws_are_call_order_independent():
    """The reproducibility fix: round t's batches depend only on
    (seed, t, client_id), not on how many draws happened before (the
    prefetch queue and checkpoint resume both rely on this)."""
    clients = make_clients(seed=17)
    a = FederatedDataset([dict(c) for c in clients], seed=9)
    b = FederatedDataset([dict(c) for c in clients], seed=9)
    ids = [0, 2, 4]
    out_a = [a.round_batches(ids, 3, 4, t=t) for t in (0, 1, 2)]
    out_b = [b.round_batches(ids, 3, 4, t=t) for t in (2, 1, 0)][::-1]
    for x, y in zip(out_a, out_b):
        for name in x:
            np.testing.assert_array_equal(x[name], y[name])
    # different rounds draw differently
    assert not np.array_equal(out_a[0]["x"], out_a[1]["x"])


# ---------------------------------------------------------------------------
# trajectory equivalence (shared harness; the 4-way matrix incl. the
# streaming plane lives in tests/test_stream_data.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt_fn", [fedavg, fedmom])
def test_run_device_matches_run_and_run_scanned(opt_fn):
    """21 rounds (ragged last chunk), FedAvg and FedMom: v1 == v2 == v3."""
    clients = make_clients(seed=21)
    rcfg = default_rcfg()
    opt = opt_fn()
    ref = run_trajectory("per-round", opt, rcfg, clients, 21)
    scanned = run_trajectory("scanned", opt, rcfg, clients, 21,
                             chunk_rounds=8)
    device = run_trajectory("device", opt, rcfg, clients, 21,
                            chunk_rounds=8)
    assert_same_trajectory(device, ref)
    assert_same_trajectory(device, scanned)
    assert len(device[0]) == 21
    assert int(device[1].t) == 21


def test_run_device_scan_placement_matches():
    clients = make_clients(seed=31)
    rcfg = default_rcfg(local_steps=3, placement="scan")
    opt = fedmom()
    ref = run_trajectory("per-round", opt, rcfg, clients, 10)
    got = run_trajectory("device", opt, rcfg, clients, 10, chunk_rounds=4)
    np.testing.assert_allclose(flat_w(got[1]), flat_w(ref[1]), atol=1e-6)


def test_diurnal_sampler_wired_through_all_drivers():
    """Time-varying M(t) via padded-C + zero-weight tail: run, run_scanned
    and run_device stay on one trajectory (the ROADMAP wiring item)."""
    clients = make_clients(seed=23, n=8)
    rcfg = default_rcfg(clients_per_round=5, local_steps=3)
    opt = fedmom()
    sfn = diurnal_sampler_fn(m_min=2, m_max=5, period=7, seed=3)
    ref = run_trajectory("per-round", opt, rcfg, clients, 15, sampler_fn=sfn)
    scanned = run_trajectory("scanned", opt, rcfg, clients, 15,
                             sampler_fn=sfn, chunk_rounds=6)
    device = run_trajectory("device", opt, rcfg, clients, 15,
                            sampler_fn=sfn, chunk_rounds=6)
    assert_same_trajectory(scanned, ref)
    assert_same_trajectory(device, ref)


def test_hetero_steps_match_across_drivers():
    clients = make_clients(seed=27)
    rcfg = default_rcfg()

    def hetero_fn(t):
        return np.random.default_rng(200 + t).integers(0, 5, size=3)

    opt = fedmom()
    ref = run_trajectory("per-round", opt, rcfg, clients, 12,
                         hetero_fn=hetero_fn)
    got = run_trajectory("device", opt, rcfg, clients, 12,
                         hetero_fn=hetero_fn, chunk_rounds=5)
    assert_same_trajectory(got, ref)


def test_client_extent_mismatch_raises():
    clients = make_clients(seed=33, n=8)
    rcfg = default_rcfg(local_steps=2)
    opt = fedavg()
    tr = make_trainer(opt, rcfg, clients,
                      sampler_fn=diurnal_sampler_fn(m_min=2, m_max=5,
                                                    period=1000, seed=3))
    with pytest.raises(ValueError, match="clients_per_round"):
        tr.run(4, plan="device", verbose=False)
    with pytest.raises(ValueError, match="clients_per_round"):
        tr.run(4, plan="scanned", verbose=False)


def test_run_device_requires_device_sampler():
    """The device plane needs the DeviceSampleable capability; the PlanError
    names it and points at the nearest viable plane."""
    from repro.launch.plan import PlanError
    clients = make_clients(seed=35)
    rcfg = default_rcfg(local_steps=2)
    opt = fedavg()
    tr = make_trainer(opt, rcfg, clients)

    class HostOnly:
        def sample(self, t):
            raise NotImplementedError
    tr.sampler = HostOnly()
    with pytest.raises(PlanError, match="sample_device") as ei:
        tr.run(2, plan="device", verbose=False)
    assert ei.value.missing == "DeviceSampleable"
    assert ei.value.nearest == "scanned"


# ---------------------------------------------------------------------------
# checkpointing (async writer) + metrics
# ---------------------------------------------------------------------------
def test_run_device_checkpoints_and_metrics(tmp_path):
    from repro.checkpoint import latest_round, restore_state
    clients = make_clients(seed=19)
    rcfg = default_rcfg(local_steps=2)
    opt = fedavg(eta=1.0)
    ck = os.path.join(tmp_path, "state.npz")
    mp = os.path.join(tmp_path, "metrics.jsonl")
    from repro.launch.plan import ExecutionPlan
    tr = make_trainer(opt, rcfg, clients, ckpt_path=ck, ckpt_every=1,
                      metrics_path=mp)
    tr.run(10, plan=ExecutionPlan(plane="device", chunk_rounds=4),
           verbose=False)
    assert latest_round(ck) == 9
    restored, meta = restore_state(ck, tr.state)
    np.testing.assert_allclose(flat_w(restored), flat_w(tr.state))
    with open(mp) as f:
        assert len(f.readlines()) == 10


def test_async_writer_flushes_all_submits(tmp_path):
    from repro.checkpoint import AsyncCheckpointWriter, restore_state
    opt = fedavg()
    path = os.path.join(tmp_path, "w.npz")
    writer = AsyncCheckpointWriter()
    last = None
    for i in range(5):
        last = opt.init({"w": jnp.full((4,), float(i))})
        writer.submit(path, last, {"round": i})
    writer.close()                      # joins + flushes: last write wins
    restored, meta = restore_state(path, last)
    assert meta["round"] == 4
    np.testing.assert_allclose(flat_w(restored), flat_w(last))


def test_async_writer_survives_donation(tmp_path):
    """The submitted snapshot must stay valid after the caller's buffer is
    donated to the next chunk (the exact run_* usage pattern)."""
    from repro.checkpoint import AsyncCheckpointWriter, restore_state
    opt = fedavg()
    state = opt.init({"w": jnp.arange(4, dtype=jnp.float32)})

    def bump(s):
        return s._replace(w=jax.tree.map(lambda x: x + 1.0, s.w))
    donating = jax.jit(bump, donate_argnums=(0,))
    path = os.path.join(tmp_path, "w.npz")
    writer = AsyncCheckpointWriter()
    expect = np.asarray(state.w["w"]).copy()
    writer.submit(path, state, {"round": 0})
    state = donating(state)             # donates the submitted buffers
    writer.close()
    restored, _ = restore_state(path, state)
    np.testing.assert_array_equal(np.asarray(restored.w["w"]), expect)


def test_scanned_driver_still_checkpoints_with_async_writer(tmp_path):
    from repro.checkpoint import latest_round
    clients = make_clients(seed=37)
    rcfg = default_rcfg(local_steps=2)
    opt = fedavg(eta=1.0)
    ck = os.path.join(tmp_path, "state.npz")
    from repro.launch.plan import ExecutionPlan
    tr = make_trainer(opt, rcfg, clients, ckpt_path=ck, ckpt_every=3)
    tr.run(9, plan=ExecutionPlan(plane="scanned", chunk_rounds=4),
           verbose=False)
    assert latest_round(ck) == 8
