"""n_k-bucketed streaming compute: trajectory equivalence + tier edges.

The bucketed plane (``CacheSpec(bucketed=True)``) regroups each round's
cohort by cache size tier and runs one sized launch per occupied tier
instead of the C-wide padded switch-gather.  The contract it must keep:

* the TRAJECTORY is untouched — same keyed draws, same model, across
  hetero H_k, diurnal M(t), resume, and both server optimizers
  (tolerance-equal across tiers: fp32 reduction order moves with the
  cohort concat order; BIT-equal with a single occupied tier);
* tier-boundary shapes are exact: power-of-two n_k landing on a tier
  edge, a cohort living in one tier, H_k=0 fully-masked rounds;
* the fused ``kernels/client_step`` hook is a drop-in (tolerance 1e-5:
  hand-fused gradients vs AD).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import fedavg, fedmom
from repro.kernels.client_step.ops import linreg_tier_step
from _trajectory import (
    assert_same_trajectory,
    default_rcfg,
    diurnal_sampler_fn,
    flat_w,
    make_clients,
    run_trajectory,
)


def _mk_opt(name):
    return fedmom(eta=1.0, beta=0.9) if name == "fedmom" else fedavg(eta=1.0)


@pytest.mark.parametrize("opt_name", ["fedavg", "fedmom"])
def test_bucketed_matches_streaming(opt_name):
    opt = _mk_opt(opt_name)
    rcfg = default_rcfg()
    clients = make_clients(n=8, lo=4, hi=40)
    ref = run_trajectory("streaming", opt, rcfg, clients, 12)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 12)
    assert_same_trajectory(got, ref)


def test_bucketed_hetero_steps():
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    clients = make_clients(n=8, lo=4, hi=40)

    def hetero_fn(t):
        return np.random.default_rng(300 + t).integers(
            0, rcfg.local_steps + 1, size=rcfg.clients_per_round)

    ref = run_trajectory("streaming", opt, rcfg, clients, 10,
                         hetero_fn=hetero_fn)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 10,
                         hetero_fn=hetero_fn)
    assert_same_trajectory(got, ref)


def test_bucketed_hetero_all_masked_round():
    """H_k=0 across the whole cohort: the bucketed launch must produce a
    zero delta exactly like the padded plane (frozen params, losses
    excluded from the metric)."""
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    clients = make_clients(n=6, lo=4, hi=30)

    def hetero_fn(t):
        if t % 3 == 0:                    # every third round fully masked
            return np.zeros(rcfg.clients_per_round, np.int32)
        return np.random.default_rng(17 + t).integers(
            1, rcfg.local_steps + 1, size=rcfg.clients_per_round)

    ref = run_trajectory("streaming", opt, rcfg, clients, 9,
                         hetero_fn=hetero_fn)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 9,
                         hetero_fn=hetero_fn)
    assert_same_trajectory(got, ref)


def test_bucketed_diurnal():
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg(clients_per_round=5)
    clients = make_clients(n=8, lo=4, hi=40)
    sf = diurnal_sampler_fn()
    ref = run_trajectory("streaming", opt, rcfg, clients, 14, sampler_fn=sf)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 14,
                         sampler_fn=sf)
    assert_same_trajectory(got, ref)


def test_bucketed_single_tier_bit_equal():
    """tiers=1 collapses bucketing to one n_max launch == the uniform
    padded plane, so the trajectories must be BIT-equal, not just close."""
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    clients = make_clients(n=8, lo=4, hi=40)
    ref = run_trajectory("streaming-uniform", opt, rcfg, clients, 12)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 12,
                         cache_tiers=1)
    assert np.array_equal(flat_w(got[1]), flat_w(ref[1]))
    assert [r["loss"] for r in got[0]] == [r["loss"] for r in ref[0]]


def test_bucketed_resume_bit_equal(tmp_path):
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    clients = make_clients(n=8, lo=4, hi=40)
    ref = run_trajectory("streaming-bucketed", opt, rcfg, clients, 12)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 12,
                         resume_at=7, tmp_path=tmp_path)
    assert np.array_equal(flat_w(got[1]), flat_w(ref[1]))
    assert [r["round"] for r in got[0]] == [r["round"] for r in ref[0]]


def test_bucketed_pow2_boundary_nk():
    """n_k exactly on power-of-two tier edges (8, 16, 32): the boundary
    client must land in the tier that holds it without padding loss."""
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    rng = np.random.default_rng(11)
    d = 5
    clients = []
    for n in (8, 8, 16, 16, 32, 32, 9, 17):   # edges + just-over-edge
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (x @ np.arange(1, d + 1) / d).astype(np.float32)
        clients.append({"x": x, "y": y})
    ref = run_trajectory("streaming", opt, rcfg, clients, 12)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 12)
    assert_same_trajectory(got, ref)


def test_bucketed_single_occupied_tier_cohort():
    """All clients share one natural size tier: exactly one sized launch
    per round, and the trajectory is bit-equal to the padded plane (one
    occupied tier => identical reduction order)."""
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    clients = make_clients(n=6, lo=17, hi=31)     # all in the 32-row tier
    ref = run_trajectory("streaming", opt, rcfg, clients, 10)
    got = run_trajectory("streaming-bucketed", opt, rcfg, clients, 10)
    assert np.array_equal(flat_w(got[1]), flat_w(ref[1]))
    assert [r["loss"] for r in got[0]] == [r["loss"] for r in ref[0]]


@pytest.mark.parametrize("hetero", [False, True])
def test_bucketed_fused_kernel_hook(hetero):
    """The fused gather+local-SGD hook (interpret-mode Pallas) is a
    drop-in for the sized per-tier launches: same trajectory to 1e-5
    (hand-fused gradients vs AD)."""
    opt = _mk_opt("fedmom")
    rcfg = default_rcfg()
    clients = make_clients(n=8, lo=4, hi=40)

    def hetero_fn(t):
        return np.random.default_rng(50 + t).integers(
            0, rcfg.local_steps + 1, size=rcfg.clients_per_round)

    hf = hetero_fn if hetero else None
    ref = run_trajectory("streaming", opt, rcfg, clients, 8, hetero_fn=hf)
    got = run_trajectory(
        "streaming-bucketed", opt, rcfg, clients, 8, hetero_fn=hf,
        client_step_fn=linreg_tier_step(use_kernel=True))
    assert_same_trajectory(got, ref, atol=1e-5)
