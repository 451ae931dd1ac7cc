"""The char-LSTM's own VJP gives autodiff's numbers.

``small.lstm_loss`` runs its recurrence under a ``jax.custom_vjp`` whose
reverse scan carries only (dh, dc) and forms the weight gradients in one
contraction afterwards.  Each case compares it, at ``highest`` matmul
precision, against autodiff of the plain ``lax.scan`` cell kept in
``_lstm_reference``: the loss, and each gradient leaf (or each leaf of the
update a client or a round makes) to 1e-5 relative.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _lstm_reference as reference
from repro.core import DeviceUniformSampler, RoundConfig, fedavg
from repro.core.client import local_update
from repro.data import synthetic_shakespeare
from repro.data.federated import lm_clients_to_dataset
from repro.launch.plan import ExecutionPlan
from repro.launch.train import FederatedTrainer
from repro.models import small

RTOL = 1e-5


def _params(seed=0, clients=None):
    p = small.lstm_init(jax.random.PRNGKey(seed))
    # a nonzero bias, so the gates start away from their symmetric point
    p["b"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                     p["b"].shape)
    if clients is None:
        return p
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), clients)
    return jax.vmap(lambda k: jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.fold_in(k, a.size), a.shape), p))(keys)


def _batch(shape, seed=3):
    kt, kl = jax.random.split(jax.random.PRNGKey(seed))
    return {"tokens": jax.random.randint(kt, shape, 0, small.SHAKESPEARE_VOCAB),
            "labels": jax.random.randint(kl, shape, 0, small.SHAKESPEARE_VOCAB)}


def _assert_leaves_close(got, want, what):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        err = float(jnp.linalg.norm(g - w))
        assert err <= RTOL * float(jnp.linalg.norm(w)), (what, path, err)


def _value_and_grad(loss_fn, B, S):
    params, batch = _params(), _batch((B, S))
    return jax.value_and_grad(lambda p: loss_fn(p, batch)[0])(params)


def _local_update(loss_fn, step_mask):
    """Three local SGD steps of each of 4 clients under vmap: the update
    and the mean loss."""
    C, H = 4, 3
    params, batches = _params(clients=C), _batch((C, H, 5, 11))
    masks = None if step_mask is None else jnp.tile(step_mask, (C, 1))
    new, loss = jax.vmap(
        lambda p, b, m: local_update(loss_fn, p, b, 0.5, step_mask=m),
        in_axes=(0, 0, None if masks is None else 0))(params, batches, masks)
    return loss, jax.tree.map(jnp.subtract, new, params)


def _device_round(loss_fn):
    """One FedAvg round on the device plane over 3 of 5 synthetic
    Shakespeare roles, 2 local steps of 4 sequences: the loss and the
    server model's change."""
    streams, _ = synthetic_shakespeare(n_clients=5, seed=0, mean=400.0,
                                       std=150.0)
    ds = lm_clients_to_dataset([s["text"] for s in streams], 80, seed=1)
    rcfg = RoundConfig(clients_per_round=3, local_steps=2, lr=0.5,
                       compute_dtype="float32")
    opt = fedavg(eta=1.0)
    # the embedding at ten times its initial scale: wx's change then
    # stands well clear of the float32 rounding of w0 + delta
    w0 = _params()
    w0["embed"] = 10.0 * w0["embed"]
    tr = FederatedTrainer(
        loss_fn=loss_fn, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=DeviceUniformSampler(ds.population(), 3, seed=2),
        state=opt.init(w0), local_batch=4)
    hist = tr.run(1, plan=ExecutionPlan(plane="device", chunk_rounds=1),
                  verbose=False)
    loss = [r["loss"] for r in hist if "event" not in r]
    return jnp.asarray(loss), jax.tree.map(jnp.subtract, tr.state.w, w0)


CASES = {
    "B10_S80": lambda f: _value_and_grad(f, 10, 80),
    "B1_S1": lambda f: _value_and_grad(f, 1, 1),
    "B3_S7": lambda f: _value_and_grad(f, 3, 7),
    "vmap_local_update_H3": lambda f: _local_update(f, None),
    "step_mask_freezes_a_step": lambda f: _local_update(
        f, jnp.array([1.0, 0.0, 1.0])),
    "device_round": _device_round,
}


@pytest.mark.parametrize("case", list(CASES))
def test_lstm_vjp_matches_autodiff_of_the_scan_cell(case):
    with jax.default_matmul_precision("highest"):
        loss, grads = CASES[case](small.lstm_loss)
        want_loss, want = CASES[case](reference.lstm_loss)
    np.testing.assert_allclose(loss, want_loss, rtol=RTOL)
    _assert_leaves_close(grads, want, case)
    # the comparison is not vacuous: every case moves wh
    assert float(jnp.linalg.norm(want["wh"])) > 0 or case == "B1_S1"


def test_lstm_keeps_float32_state_under_bfloat16_weights():
    """With bfloat16 weights (a round's default compute dtype) h and c stay
    float32, as in the plain scan cell: the same loss, and gradients in the
    weights' dtype."""
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _params())
    batch = _batch((3, 7))
    (loss, _), grads = jax.value_and_grad(small.lstm_loss, has_aux=True)(
        params, batch)
    want_loss, want = jax.value_and_grad(
        lambda p: reference.lstm_loss(p, batch)[0])(params)
    assert loss.dtype == jnp.float32
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for k in params:
        assert grads[k].dtype == jnp.bfloat16, k
        np.testing.assert_allclose(grads[k].astype(jnp.float32),
                                   want[k].astype(jnp.float32),
                                   rtol=0.05, atol=0.01 * float(
                                       jnp.abs(want[k]).max()))
