"""Round-engine semantics: placement equivalence, weighting, local solvers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RoundConfig, round_step, server_opt as so
from repro.core.client import local_update
from repro.optim import local as lo


def tree_allclose(a, b, atol=1e-5):
    return all(np.allclose(x, y, atol=atol)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def linreg_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean(jnp.square(pred - batch["y"]))
    return loss, {}


def _setup(seed=0, C=4, H=3, b=5, d=6):
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.normal(size=(d,)), jnp.float32),
              "b": jnp.zeros(())}
    batches = {
        "x": jnp.asarray(rng.normal(size=(C, H, b, d)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(C, H, b)), jnp.float32),
    }
    weights = jnp.asarray(rng.uniform(0.05, 0.3, size=C), jnp.float32)
    return params, batches, weights


@pytest.mark.parametrize("opt_name", ["fedavg", "fedmom"])
@pytest.mark.parametrize("local_opt", ["sgd", "momentum", "adam"])
def test_mesh_scan_equivalence(opt_name, local_opt):
    """The two client placements implement identical algorithm semantics."""
    params, batches, weights = _setup()
    opt = so.get(opt_name)
    out = {}
    for placement in ("mesh", "scan"):
        rcfg = RoundConfig(clients_per_round=4, local_steps=3, lr=0.1,
                           placement=placement, local_opt=local_opt,
                           compute_dtype="float32")
        state, metrics = round_step(linreg_loss, opt, opt.init(params),
                                    batches, weights, rcfg)
        out[placement] = (state, metrics)
    assert tree_allclose(out["mesh"][0].w, out["scan"][0].w)
    assert np.allclose(out["mesh"][1]["loss"], out["scan"][1]["loss"],
                       atol=1e-5)


def test_round_matches_manual_computation():
    """The whole round against a hand-rolled reference (vmap-free)."""
    params, batches, weights = _setup(seed=1)
    H, lr, eta = 3, 0.1, 2.0
    rcfg = RoundConfig(clients_per_round=4, local_steps=H, lr=lr,
                       placement="mesh", compute_dtype="float32")
    opt = so.fedavg(eta=eta)
    state, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                          weights, rcfg)

    # manual
    delta = jax.tree.map(jnp.zeros_like, params)
    for c in range(4):
        p = params
        for h in range(H):
            g = jax.grad(lambda q: linreg_loss(
                q, jax.tree.map(lambda x: x[c, h], batches))[0])(p)
            p = jax.tree.map(lambda a, gi: a - lr * gi, p, g)
        delta = jax.tree.map(lambda dl, w0, wk: dl + weights[c] * (w0 - wk),
                             delta, params, p)
    expect = jax.tree.map(lambda w0, dl: w0 - eta * dl, params, delta)
    assert tree_allclose(state.w, expect, atol=1e-4)


def test_weight_scaling_linearity():
    """delta is linear in the client weights (biased-gradient structure)."""
    params, batches, weights = _setup(seed=2)
    rcfg = RoundConfig(clients_per_round=4, local_steps=3, lr=0.05,
                       placement="mesh", compute_dtype="float32")
    opt = so.fedavg(eta=1.0)
    s1, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                       weights, rcfg)
    s2, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                       2.0 * weights, rcfg)
    d1 = jax.tree.map(lambda w0, w: w0 - w, params, s1.w)
    d2 = jax.tree.map(lambda w0, w: w0 - w, params, s2.w)
    assert tree_allclose(jax.tree.map(lambda x: 2.0 * x, d1), d2, atol=1e-5)


def test_local_update_momentum_differs_from_sgd():
    params, batches, _ = _setup(seed=3)
    b0 = jax.tree.map(lambda x: x[0], batches)
    p_sgd, _ = local_update(linreg_loss, params, b0, jnp.float32(0.1),
                            lo.sgd())
    p_mom, _ = local_update(linreg_loss, params, b0, jnp.float32(0.1),
                            lo.momentum(0.9))
    assert not tree_allclose(p_sgd, p_mom, atol=1e-6)


def test_eq2_model_averaging_equals_eq3_round_partial_hetero():
    """Regression pin: eq. (2) model averaging == the eq. (3) biased-gradient
    round implemented by ``round_step``, under BOTH partial participation
    (sum of n_k/n < 1: the inactive mass stays on w_t) and heterogeneous
    per-client work H_k (step masks).  FedAvg with eta=1 IS model averaging,
    so the w' the engine produces must equal averaging the explicitly
    computed local models."""
    from repro.core.round import model_averaging_reference
    params, batches, _ = _setup(seed=7)
    C, H = 4, 3
    weights = jnp.asarray([0.15, 0.25, 0.05, 0.2], jnp.float32)  # sum < 1
    h_k = np.array([3, 1, 0, 2])            # one client does zero work
    mask = (np.arange(H)[None, :] < h_k[:, None]).astype(np.float32)
    rcfg = RoundConfig(clients_per_round=C, local_steps=H, lr=0.1,
                       placement="mesh", compute_dtype="float32")
    opt = so.fedavg(eta=1.0)
    state, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                          weights, rcfg, step_mask=jnp.asarray(mask))

    # explicit local models: client c runs its first H_k steps; a client
    # with H_k = 0 stays at w_t (the eq. (2) convention for inactive ones)
    locals_ = []
    for c in range(C):
        if h_k[c] == 0:
            locals_.append(params)
            continue
        bc = jax.tree.map(lambda x: x[c, :h_k[c]], batches)
        wk, _ = local_update(linreg_loss, params, bc, jnp.float32(0.1))
        locals_.append(wk)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *locals_)
    eq2 = model_averaging_reference(params, stacked, weights)
    assert tree_allclose(state.w, eq2, atol=1e-5)


def test_dynamic_lr_overrides_static():
    """gamma_t passed per round (Corollary 3.3 schedules) must override
    the static RoundConfig.lr."""
    import jax.numpy as jnp
    from repro.core import RoundConfig, round_step, fedavg
    params, batches, weights = _setup(seed=5)
    rcfg = RoundConfig(clients_per_round=4, local_steps=3, lr=0.1,
                       placement="mesh", compute_dtype="float32")
    opt = fedavg(eta=1.0)
    s_static, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                             weights, rcfg)
    rcfg2 = RoundConfig(clients_per_round=4, local_steps=3, lr=0.777,
                        placement="mesh", compute_dtype="float32")
    s_dyn, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                          weights, rcfg2, lr=jnp.float32(0.1))
    assert tree_allclose(s_static.w, s_dyn.w)


@pytest.mark.parametrize("placement", ["mesh", "scan"])
def test_bf16_delta_is_rounded_fp32_reduction(placement):
    """delta_dtype='bfloat16' must round the FP32 reduction, not reduce in
    bf16: casting the n_k/n weights (or per-client diffs) before the einsum
    leaks weight mass under skewed n_k.  Recover delta through fedavg
    (w' = w - eta*delta, eta=1) and pin it to the fp32 round's delta cast
    once at the end."""
    params, batches, _ = _setup(seed=7)
    # heavily skewed weights — where premature bf16 rounding actually bites
    weights = jnp.asarray([0.9, 0.0731, 0.0211, 0.0058], jnp.float32)
    opt = so.fedavg(eta=1.0)
    deltas = {}
    for ddt in ("float32", "bfloat16"):
        rcfg = RoundConfig(clients_per_round=4, local_steps=3, lr=0.1,
                           placement=placement, compute_dtype="float32",
                           delta_dtype=ddt)
        state, _ = round_step(linreg_loss, opt, opt.init(params), batches,
                              weights, rcfg)
        deltas[ddt] = jax.tree.map(lambda w0, w1: w0 - w1, params, state.w)
    want = jax.tree.map(lambda d: d.astype(jnp.bfloat16), deltas["float32"])
    for g, r in zip(jax.tree.leaves(deltas["bfloat16"]),
                    jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32))


def test_scan_placement_accepts_param_axes():
    """Regression for the scan-placement sharding fix: param_axes must
    thread through the scan body (broadcast model + fp32 accumulator
    constraints) and leave the math identical to the unsharded run."""
    from repro.launch.mesh import make_mesh
    from repro.sharding import FED_MESH_RULES, axis_rules

    params, batches, weights = _setup(seed=8)
    axes = {"w": ("embed",), "b": ()}
    rcfg = RoundConfig(clients_per_round=4, local_steps=3, lr=0.1,
                       placement="scan", compute_dtype="float32")
    opt = so.fedmom(eta=1.0, beta=0.9)
    ref, ref_m = round_step(linreg_loss, opt, opt.init(params), batches,
                            weights, rcfg)
    n = jax.device_count()
    mesh = make_mesh((n, 1), ("data", "model"))
    with mesh, axis_rules(mesh, FED_MESH_RULES):
        got, got_m = round_step(linreg_loss, opt, opt.init(params), batches,
                                weights, rcfg, param_axes=axes)
    assert tree_allclose(ref.w, got.w, atol=1e-6)
    assert np.allclose(ref_m["loss"], got_m["loss"], atol=1e-6)
