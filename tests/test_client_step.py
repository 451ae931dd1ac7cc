"""Fused client-step kernel (gather + H local SGD) vs its oracles.

Three-link chain, so the Pallas kernel is anchored to the engine's
reference semantics:

  kernel (interpret on CPU)  ==  ref.client_step  ==  core.client.local_update

``ref.client_step`` consumes the streaming layout (tier corpus + cache
slots + pre-drawn row indices); ``local_update`` consumes host-gathered
[H, b, ...] batches.  Equality across the middle link proves the fused
path computes exactly what the engine's per-client vmap would.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import local_update
from repro.kernels.client_step import ops as cs_ops
from repro.kernels.client_step import ref as cs_ref


def _linreg_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean(jnp.square(pred - batch["y"])), {}


def _corpus(S=3, N=12, D=5, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(S, N, D)).astype(np.float32)
    ys = rng.normal(size=(S, N)).astype(np.float32)
    return jnp.asarray(xs), jnp.asarray(ys)


def _draw(rng, C, H, b, N):
    slots = jnp.asarray(rng.permutation(C).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, N, size=(C, H * b)).astype(np.int32))
    return slots, idx


@pytest.mark.parametrize("C,H,b,D,N", [(1, 1, 2, 3, 4), (3, 4, 2, 5, 12),
                                       (4, 2, 3, 8, 16), (2, 5, 4, 17, 9)])
def test_kernel_matches_ref_sweep(C, H, b, D, N):
    rng = np.random.default_rng(1)
    xs, ys = _corpus(S=C, N=N, D=D, seed=2)
    slots, idx = _draw(rng, C, H, b, N)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    bb = jnp.float32(rng.normal())
    got = cs_ops.client_step(xs, ys, slots, idx, w, bb, 0.05, H, b,
                             use_kernel=True)
    want = cs_ref.client_step(xs, ys, slots, idx, w, bb, 0.05, H, b)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-5, rtol=1e-5)


def test_kernel_matches_ref_with_masks():
    rng = np.random.default_rng(3)
    C, H, b, D, N = 3, 4, 2, 6, 10
    xs, ys = _corpus(S=C, N=N, D=D, seed=4)
    slots, idx = _draw(rng, C, H, b, N)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    bb = jnp.float32(0.2)
    # one straggler (H_k=2), one fully masked (H_k=0), one full H
    mask = jnp.asarray([[1, 1, 0, 0], [0, 0, 0, 0], [1, 1, 1, 1]],
                       jnp.float32)
    got = cs_ops.client_step(xs, ys, slots, idx, w, bb, 0.05, H, b,
                             step_mask=mask, use_kernel=True)
    want = cs_ref.client_step(xs, ys, slots, idx, w, bb, 0.05, H, b,
                              step_mask=mask)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-5, rtol=1e-5)
    # the fully-masked client returns the start params untouched
    np.testing.assert_array_equal(np.asarray(got[0][1]), np.asarray(w))
    np.testing.assert_allclose(np.asarray(got[1][1]), float(bb), atol=1e-6)


@pytest.mark.parametrize("step_mask", [None, [1.0, 1.0, 0.0], [0.0] * 3])
def test_ref_matches_local_update(step_mask):
    """The streaming-layout oracle == the engine's local_update on the
    equivalent host-gathered [H, b, ...] batches."""
    rng = np.random.default_rng(5)
    C, H, b, D, N = 4, 3, 2, 5, 11
    xs, ys = _corpus(S=C, N=N, D=D, seed=6)
    slots, idx = _draw(rng, C, H, b, N)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    bb = jnp.float32(-0.4)
    mask = None if step_mask is None else jnp.asarray(
        np.tile(np.asarray(step_mask, np.float32), (C, 1)))
    wf, bf, losses = cs_ref.client_step(xs, ys, slots, idx, w, bb, 0.07,
                                        H, b, step_mask=mask)
    for c in range(C):
        batches = {
            "x": xs[slots[c]][idx[c]].reshape(H, b, D),
            "y": ys[slots[c]][idx[c]].reshape(H, b),
        }
        params, loss = local_update(
            _linreg_loss, {"w": w, "b": bb}, batches, jnp.float32(0.07),
            step_mask=None if mask is None else mask[c])
        np.testing.assert_allclose(np.asarray(wf[c]),
                                   np.asarray(params["w"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(bf[c]),
                                   np.asarray(params["b"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(losses[c]),
                                   np.asarray(loss), atol=1e-6)


def test_padding_is_exact():
    """D and N off the 128-lane / 8-sublane grid: the wrapper's zero
    padding must not move any output (zero feature columns contribute zero
    gradient; idx < n_k never reaches a padded row)."""
    rng = np.random.default_rng(7)
    C, H, b, D, N = 2, 2, 3, 130, 9
    xs, ys = _corpus(S=C, N=N, D=D, seed=8)
    slots, idx = _draw(rng, C, H, b, N)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    bb = jnp.float32(0.0)
    got = cs_ops.client_step(xs, ys, slots, idx, w, bb, 0.03, H, b,
                             use_kernel=True)
    want = cs_ref.client_step(xs, ys, slots, idx, w, bb, 0.03, H, b)
    assert got[0].shape == (C, D)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-5, rtol=1e-5)


def test_use_kernel_false_routes_to_ref():
    rng = np.random.default_rng(9)
    C, H, b, D, N = 2, 2, 2, 4, 8
    xs, ys = _corpus(S=C, N=N, D=D, seed=10)
    slots, idx = _draw(rng, C, H, b, N)
    w = jnp.zeros(D)
    got = cs_ops.client_step(xs, ys, slots, idx, w, jnp.float32(0.0),
                             0.1, H, b, use_kernel=False)
    want = cs_ref.client_step(xs, ys, slots, idx, w, jnp.float32(0.0),
                              0.1, H, b)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_linreg_tier_step_rejects_wrong_family():
    fn = cs_ops.linreg_tier_step(use_kernel=False)

    class FakeView:
        tier_arrays = ({"a": jnp.zeros((1, 2, 3))},)
        client_slots = jnp.zeros(1, jnp.int32)
        counts = jnp.ones(1, jnp.int32)

    with pytest.raises(ValueError, match="linear-regression family"):
        fn(FakeView(), 0, jax.random.PRNGKey(0), 0,
           jnp.zeros(1, jnp.int32), {"w": jnp.zeros(3), "b": jnp.zeros(())},
           0.1, None, 2, 2)
