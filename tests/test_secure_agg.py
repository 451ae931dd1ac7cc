"""Secure aggregation: uint32-ring pairwise masks cancel BIT-exactly (no
atol anywhere in the cancellation tests), individual messages are blinded,
dropout recovery reconstructs the survivors' sum, and the masked execution
planes are certified bit-equal to the open ring across the whole plane
matrix (incl. bucketed streaming, resume, and scenario dropouts).  DP rows
certify seeded-noise equivalence across planes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _trajectory import (STREAM_VARIANTS, assert_bitwise_trajectory,
                         assert_same_trajectory, default_rcfg, flat_w,
                         make_clients, run_trajectory, strip_events)
from repro.core import dp_fedavg, dp_fedmom, fedmom
from repro.core.secure_agg import (EmptyCohortError, SecureAggSpec,
                                   aggregate_masked, decode, encode,
                                   mask_client_updates, mask_cohort,
                                   round_mask_key, unmask_sum)

SPEC = SecureAggSpec(masked=True, seed=0)


def _updates(n=4, d=6, seed=0):
    rng = np.random.default_rng(seed)
    return [{"w": jnp.asarray(rng.normal(size=d), jnp.float32)}
            for _ in range(n)], jnp.asarray(rng.uniform(0.1, 0.3, size=n),
                                            jnp.float32)


def _ring_reference(ups, weights, spec=SPEC):
    """The open-ring sum: encode each weighted update, ring-add, decode —
    what the masked aggregate must equal bit for bit."""
    q = [encode(jax.tree.map(lambda x, wi=wi: wi * x, u), spec)
         for u, wi in zip(ups, weights)]
    total = jax.tree.map(lambda *ls: sum(ls[1:], ls[0]), *q)
    return decode(total, spec)


# ---------------------------------------------------------------------------
# exact cancellation (the old fp32 masks needed atol=1e-4 here; the ring
# masks cancel bit-exactly, so these are == assertions)
# ---------------------------------------------------------------------------
def test_masks_cancel_in_aggregate_exactly():
    ups, weights = _updates()
    key = jax.random.PRNGKey(0)
    masked = mask_client_updates(key, ups, weights, SPEC)
    agg = aggregate_masked(masked, spec=SPEC, key=key)
    expect = _ring_reference(ups, weights)
    np.testing.assert_array_equal(np.asarray(agg["w"]),
                                  np.asarray(expect["w"]))


def test_masked_equals_open_plane_bitwise():
    """masked=True vs masked=False: same encode/aggregate/decode, masks
    cancel — the aggregates are the same bits."""
    ups, weights = _updates()
    key = jax.random.PRNGKey(7)
    open_spec = dataclasses.replace(SPEC, masked=False)
    m = aggregate_masked(mask_client_updates(key, ups, weights, SPEC),
                         spec=SPEC, key=key)
    o = aggregate_masked(
        mask_client_updates(key, ups, weights, open_spec), spec=open_spec)
    np.testing.assert_array_equal(np.asarray(m["w"]), np.asarray(o["w"]))


def test_different_keys_different_masks_same_sum_exactly():
    ups, weights = _updates()
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    a = aggregate_masked(mask_client_updates(k1, ups, weights, SPEC),
                         spec=SPEC, key=k1)
    b = aggregate_masked(mask_client_updates(k2, ups, weights, SPEC),
                         spec=SPEC, key=k2)
    assert not np.array_equal(
        np.asarray(mask_client_updates(k1, ups, weights, SPEC)[0]["w"]),
        np.asarray(mask_client_updates(k2, ups, weights, SPEC)[0]["w"]))
    np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))


def test_individual_updates_are_blinded():
    ups, weights = _updates()
    masked = mask_client_updates(jax.random.PRNGKey(0), ups, weights, SPEC)
    for i in range(len(ups)):
        plain = np.asarray(weights[i] * ups[i]["w"])
        msg = np.asarray(decode(masked[i], SPEC)["w"])
        assert not np.allclose(msg, plain, atol=1e-3)


def test_encode_decode_roundtrip_exact_on_grid():
    """Values on the fixed-point grid survive encode/decode exactly,
    including negatives (two's-complement ring wrap)."""
    x = jnp.asarray([-3.5, -1.0 / 1024, 0.0, 0.25, 100.125], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(decode(encode(x, SPEC), SPEC)), np.asarray(x))


def test_spec_validation():
    with pytest.raises(ValueError):
        SecureAggSpec(frac_bits=0)
    with pytest.raises(ValueError):
        SecureAggSpec(frac_bits=31)
    with pytest.raises(ValueError):
        SecureAggSpec(masked="yes")


# ---------------------------------------------------------------------------
# degenerate cohorts (the old aggregate_masked IndexError'd on [])
# ---------------------------------------------------------------------------
def test_empty_cohort_raises_structured_error():
    with pytest.raises(EmptyCohortError) as ei:
        aggregate_masked([], spec=SPEC, round=12)
    assert ei.value.round == 12
    assert "round 12" in str(ei.value)


def test_empty_cohort_with_like_returns_zeros():
    ups, _ = _updates()
    z = aggregate_masked([], spec=SPEC, like=ups[0])
    np.testing.assert_array_equal(np.asarray(z["w"]),
                                  np.zeros_like(np.asarray(ups[0]["w"])))


def test_single_client_cohort():
    """One client: no pairs, the aggregate is that client's own weighted
    update on the fixed-point grid."""
    ups, weights = _updates(n=1)
    key = jax.random.PRNGKey(3)
    masked = mask_client_updates(key, ups, weights, SPEC)
    agg = aggregate_masked(masked, spec=SPEC, key=key)
    expect = _ring_reference(ups, weights)
    np.testing.assert_array_equal(np.asarray(agg["w"]),
                                  np.asarray(expect["w"]))


# ---------------------------------------------------------------------------
# dropout recovery
# ---------------------------------------------------------------------------
def test_dropout_recovery_matches_survivor_sum():
    ups, weights = _updates(n=5)
    key = jax.random.PRNGKey(9)
    y = jax.tree.map(
        lambda *xs: jnp.stack(
            [weights[i] * x for i, x in enumerate(xs)]), *ups)
    masked = mask_cohort(key, y, SPEC)
    survivors = jnp.asarray([1, 0, 1, 1, 0])
    got = unmask_sum(key, masked, survivors, SPEC)
    expect = _ring_reference(
        [u for i, u in enumerate(ups) if int(survivors[i])],
        weights[np.asarray(survivors).astype(bool)])
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(expect["w"]))


def test_dropout_recovery_requires_key():
    ups, weights = _updates(n=3)
    key = jax.random.PRNGKey(4)
    masked = mask_client_updates(key, ups, weights, SPEC)
    with pytest.raises(ValueError, match="per-round mask key"):
        aggregate_masked(masked, spec=SPEC, survivors=jnp.asarray([1, 1, 0]))


def test_round_keys_differ_by_round():
    k0 = round_mask_key(SPEC, 0)
    k1 = round_mask_key(SPEC, 1)
    assert not np.array_equal(np.asarray(jax.random.key_data(k0)),
                              np.asarray(jax.random.key_data(k1)))


# ---------------------------------------------------------------------------
# plane certification: masked bit-equal to open across the whole matrix
# ---------------------------------------------------------------------------
MASKED = SecureAggSpec(masked=True, seed=5)
OPEN = SecureAggSpec(masked=False, seed=5)
ALL_PLANES = ("per-round", "scanned", "device") + STREAM_VARIANTS


def _opt():
    return fedmom(eta=1.0, beta=0.9)


@pytest.mark.parametrize("driver", ALL_PLANES)
def test_masked_plane_bit_equal_to_open(driver):
    clients = make_clients()
    rcfg = default_rcfg()
    got = run_trajectory(driver, _opt(), rcfg, clients, 10,
                         chunk_rounds=4, secure=MASKED)
    want = run_trajectory(driver, _opt(), rcfg, clients, 10,
                          chunk_rounds=4, secure=OPEN)
    assert_bitwise_trajectory(got, want)


def test_masked_planes_bit_equal_cross_plane():
    """All planes under masking train the same PARAMS bit for bit — incl.
    bucketed streaming, where the ring accumulation removes the fp32
    reduction-order caveat of the open-fp32 bucketed path.  The loss
    METRIC stream is tolerance-only across planes (bucketed accumulates
    the loss per tier, a different fp32 reduction order; the ring
    guarantee covers the aggregate, not the diagnostics)."""
    clients = make_clients()
    rcfg = default_rcfg()
    ref = run_trajectory("per-round", _opt(), rcfg, clients, 10,
                         chunk_rounds=4, secure=MASKED)
    for driver in ("scanned", "device", "streaming", "streaming-bucketed"):
        got = run_trajectory(driver, _opt(), rcfg, clients, 10,
                             chunk_rounds=4, secure=MASKED)
        np.testing.assert_array_equal(flat_w(got[1]), flat_w(ref[1]))
        assert_same_trajectory(got, ref)


def test_masked_resume_bit_equal(tmp_path):
    clients = make_clients()
    rcfg = default_rcfg()
    straight = run_trajectory("streaming-bucketed", _opt(), rcfg, clients,
                              10, chunk_rounds=4, secure=MASKED)
    resumed = run_trajectory("streaming-bucketed", _opt(), rcfg, clients,
                             10, chunk_rounds=4, secure=MASKED,
                             resume_at=5, tmp_path=tmp_path)
    assert_bitwise_trajectory(resumed, straight)


def test_masked_scenario_dropout_recovery_bit_equal():
    """Scenario dropouts compose with masking: non-reporting clients'
    pairwise terms are recovered, and masked == open still holds bitwise
    on every plane that runs the scenario."""
    from repro.scenario import ScenarioSpec
    from repro.scenario.lifecycle import UniformDropout

    scen = ScenarioSpec(dropout=UniformDropout(rate=0.4), seed=11)
    clients = make_clients()
    rcfg = default_rcfg()
    for driver in ("per-round", "streaming", "streaming-bucketed"):
        got = run_trajectory(driver, _opt(), rcfg, clients, 10,
                             chunk_rounds=4, secure=MASKED, scenario=scen)
        want = run_trajectory(driver, _opt(), rcfg, clients, 10,
                              chunk_rounds=4, secure=OPEN, scenario=scen)
        assert_bitwise_trajectory(got, want)


def test_masked_close_to_plain_fp32():
    """Secure-vs-plain differs only by fixed-point quantization: tolerance
    equality, NOT bit equality (the plain path reduces in fp32)."""
    clients = make_clients()
    rcfg = default_rcfg()
    got = run_trajectory("per-round", _opt(), rcfg, clients, 10,
                         secure=MASKED)
    want = run_trajectory("per-round", _opt(), rcfg, clients, 10)
    assert_same_trajectory(got, want, atol=1e-4)


# ---------------------------------------------------------------------------
# DP rows: seeded-noise equivalence across planes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mk_opt", [
    lambda: dp_fedavg(clip=0.5, noise_multiplier=0.3, dp_seed=9),
    lambda: dp_fedmom(clip=0.5, noise_multiplier=0.3, dp_seed=9,
                      eta=1.0, beta=0.9),
], ids=["dp_fedavg", "dp_fedmom"])
def test_dp_seeded_noise_equivalent_across_planes(mk_opt):
    clients = make_clients()
    rcfg = default_rcfg()
    ref = run_trajectory("per-round", mk_opt(), rcfg, clients, 8)
    for driver in ("scanned", "device", "streaming"):
        got = run_trajectory(driver, mk_opt(), rcfg, clients, 8,
                             chunk_rounds=4)
        assert_same_trajectory(got, ref)


def test_dp_noise_is_really_applied_and_seeded():
    clients = make_clients()
    rcfg = default_rcfg()
    _, a = run_trajectory("per-round", dp_fedavg(
        clip=0.5, noise_multiplier=0.3, dp_seed=9), rcfg, clients, 8)
    _, a2 = run_trajectory("per-round", dp_fedavg(
        clip=0.5, noise_multiplier=0.3, dp_seed=9), rcfg, clients, 8)
    _, b = run_trajectory("per-round", dp_fedavg(
        clip=0.5, noise_multiplier=0.3, dp_seed=10), rcfg, clients, 8)
    np.testing.assert_array_equal(flat_w(a), flat_w(a2))
    assert not np.array_equal(flat_w(a), flat_w(b))


def test_dp_composes_with_secure_masking():
    """The full privacy stack — masked transport + central clip/noise —
    stays plane-independent bit for bit (noise is a pure (seed, t)
    function; the masked aggregate is ring-exact)."""
    def mk():
        return dp_fedmom(clip=0.5, noise_multiplier=0.3, dp_seed=9,
                         eta=1.0, beta=0.9)

    clients = make_clients()
    rcfg = default_rcfg()
    ref = run_trajectory("per-round", mk(), rcfg, clients, 8, secure=MASKED)
    for driver in ("scanned", "streaming-bucketed"):
        got = run_trajectory(driver, mk(), rcfg, clients, 8,
                             chunk_rounds=4, secure=MASKED)
        np.testing.assert_array_equal(flat_w(got[1]), flat_w(ref[1]))
        hg, hr = strip_events(got[0]), strip_events(ref[0])
        assert [r["round"] for r in hg] == [r["round"] for r in hr]
        np.testing.assert_array_equal([r["delta_norm"] for r in hg],
                                      [r["delta_norm"] for r in hr])
        # the loss metric feeds no state, and XLA orders its fp32
        # reduction differently in the per-round plane's jitted round_step
        # than in the chunked planes' scan body (1-ulp drift, see
        # test_scenario.test_eval_cadence_finer_than_chunk); state and
        # delta norms above stay bit-equal
        np.testing.assert_allclose([r["loss"] for r in hg],
                                   [r["loss"] for r in hr], rtol=1e-6,
                                   atol=0)
