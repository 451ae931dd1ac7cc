"""Per-kernel shape/dtype sweeps, interpret=True vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedmom_update import kernel as fm_k
from repro.kernels.fedmom_update import ref as fm_ref
from repro.kernels.flash_attention import ops as fl_ops
from repro.kernels.rwkv6_scan import ops as rw_ops
from repro.kernels.rwkv6_scan import ref as rw_ref
from repro.models import layers as L


# ---------------------------------------------------------------------------
# fedmom_update
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(7,), (128,), (513, 9), (32, 32, 3),
                                   (1, 1), (256 * 128,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("eta,beta", [(1.0, 0.9), (3.5, 0.0), (62.5, 0.99)])
def test_fedmom_kernel_sweep(shape, dtype, eta, beta):
    ks = jax.random.split(jax.random.PRNGKey(hash((shape, eta)) % 2**31), 3)
    w = {"p": jax.random.normal(ks[0], shape).astype(dtype)}
    v = {"p": jax.random.normal(ks[1], shape).astype(dtype)}
    d = {"p": (0.01 * jax.random.normal(ks[2], shape)).astype(dtype)}
    w1, v1 = fm_k.fused_update_tree(w, v, d, eta=eta, beta=beta,
                                    interpret=True)
    w2, v2 = fm_ref.fedmom_update(w, v, d, eta, beta)
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(w1["p"], np.float32),
                               np.asarray(w2["p"], np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(v1["p"], np.float32),
                               np.asarray(v2["p"], np.float32), atol=atol)


def _mixed_tree(seed=0):
    """One pytree hitting every padding/reshape edge at once: ragged sizes
    (not multiples of the 256x128 tile), a bf16 leaf, and a scalar leaf."""
    rng = np.random.default_rng(seed)
    w = {"ragged": jnp.asarray(rng.normal(size=(513, 9)), jnp.float32),
         "big": jnp.asarray(rng.normal(size=(256 * 128 + 1,)), jnp.float32),
         "bf16": jnp.asarray(rng.normal(size=(37, 5)), jnp.bfloat16),
         "scalar": jnp.asarray(rng.normal(), jnp.float32)}
    v = jax.tree.map(lambda x: x + jnp.ones((), x.dtype), w)
    d = jax.tree.map(lambda x: (0.05 * x.astype(jnp.float32)).astype(x.dtype),
                     w)
    return w, v, d


def _assert_tree_close(a, b, atol):
    for ka in a:
        np.testing.assert_allclose(np.asarray(a[ka], np.float32),
                                   np.asarray(b[ka], np.float32), atol=atol)


@pytest.mark.parametrize("fuse_tree", [True, False])
def test_fedmom_kernel_mixed_tree_edges(fuse_tree):
    """Ragged + bf16 + scalar leaves in one tree, packed single-launch vs
    per-leaf launches vs the unfused v'=w-eta*d; w'=v'+beta*(v'-v) oracle."""
    w, v, d = _mixed_tree(1)
    w1, v1 = fm_k.fused_update_tree(w, v, d, eta=1.5, beta=0.9,
                                    fuse_tree=fuse_tree, interpret=True)
    w2, v2 = fm_ref.fedmom_update(w, v, d, 1.5, 0.9)
    # output dtypes must follow the input leaves, not the f32 stream
    assert all(w1[k].dtype == w[k].dtype for k in w)
    _assert_tree_close(w1, w2, atol=5e-2)    # bf16 leaf bounds the tol
    _assert_tree_close(v1, v2, atol=5e-2)
    for k in ("ragged", "big", "scalar"):    # fp32 leaves are tight
        np.testing.assert_allclose(np.asarray(w1[k]), np.asarray(w2[k]),
                                   atol=1e-5)


def test_fedmom_packed_equals_per_leaf_exactly():
    """Leaf boundaries are invisible to an elementwise update: the packed
    single-launch stream must agree with per-leaf launches bitwise."""
    w, v, d = _mixed_tree(2)
    w1, v1 = fm_k.fused_update_tree(w, v, d, eta=2.0, beta=0.7,
                                    fuse_tree=True, interpret=True)
    w2, v2 = fm_k.fused_update_tree(w, v, d, eta=2.0, beta=0.7,
                                    fuse_tree=False, interpret=True)
    for k in w:
        np.testing.assert_array_equal(np.asarray(w1[k]), np.asarray(w2[k]))
        np.testing.assert_array_equal(np.asarray(v1[k]), np.asarray(v2[k]))


@pytest.mark.parametrize("shape", [(7,), (513, 9), (1, 1), (256 * 128,)])
@pytest.mark.parametrize("eta,beta", [(1.0, 0.9), (0.3, 0.0)])
def test_fedavgm_kernel_sweep(shape, eta, beta):
    ks = jax.random.split(jax.random.PRNGKey(hash((shape, eta)) % 2**31), 3)
    w = {"p": jax.random.normal(ks[0], shape)}
    m = {"p": jax.random.normal(ks[1], shape)}
    d = {"p": 0.01 * jax.random.normal(ks[2], shape)}
    w1, m1 = fm_k.fused_update_tree(w, m, d, eta=eta, beta=beta,
                                    kind="fedavgm", interpret=True)
    w2, m2 = fm_ref.fedavgm_update(w, m, d, eta, beta)
    np.testing.assert_allclose(np.asarray(w1["p"]), np.asarray(w2["p"]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1["p"]), np.asarray(m2["p"]),
                               atol=1e-5)


def test_fedavgm_server_opt_fused_matches_unfused():
    from repro.core import server_opt as so
    rng = np.random.default_rng(3)
    w0 = {"a": jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
          "b": jnp.asarray(rng.normal(size=(130,)), jnp.float32)}
    delta = jax.tree.map(lambda x: 0.05 * x, w0)
    s1 = so.fedavgm(eta=0.7, beta=0.9).init(w0)
    s2 = so.fedavgm(eta=0.7, beta=0.9, use_fused_kernel=True).init(w0)
    for _ in range(3):
        s1 = so.fedavgm(eta=0.7, beta=0.9).update(s1, delta)
        s2 = so.fedavgm(eta=0.7, beta=0.9,
                        use_fused_kernel=True).update(s2, delta)
    for k in w0:
        np.testing.assert_allclose(np.asarray(s1.w[k]), np.asarray(s2.w[k]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(s1.extra["m"][k]),
                                   np.asarray(s2.extra["m"][k]), atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,T,Hq,Hkv,d", [
    (128, 128, 4, 4, 64),
    (256, 256, 4, 2, 64),     # GQA
    (128, 128, 2, 1, 128),    # MQA, TPU-aligned head dim
    (512, 512, 2, 2, 64),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, T, Hq, Hkv, d, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + T + Hq), 3)
    q = jax.random.normal(ks[0], (2, S, Hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (2, T, Hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, T, Hkv, d)).astype(dtype)
    out = fl_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64)
    ref = fl_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 use_kernel=False)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_attention_matches_model_attention():
    """The kernel agrees with the XLA chunked attention used in the model."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    out = fl_ops.flash_attention(q, k, v, causal=True, block_q=64,
                                 block_k=64)
    ref = L.attention(q, k, v, causal=True, q_chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# rwkv6 chunked scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,H,Dk,Dv,chunk", [
    (64, 2, 64, 64, 32),
    (128, 4, 64, 64, 32),
    (96, 1, 32, 32, 32),      # chunk does not divide -> internal fallback? no: 96%32=0
    (256, 2, 64, 128, 64),    # Dk != Dv
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_kernel_sweep(S, H, Dk, Dv, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S * H), 5)
    B = 2
    r = jax.random.normal(ks[0], (B, S, H, Dk)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, Dk)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, Dv)).astype(dtype)
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, Dk))).astype(jnp.float32)
    u = (0.1 * jax.random.normal(ks[4], (H, Dk))).astype(jnp.float32)
    out = rw_ops.rwkv6(r, k, v, lw, u, chunk=chunk)
    ref = rw_ops.rwkv6(r, k, v, lw, u, use_kernel=False)
    atol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=1e-2)


def test_rwkv6_extreme_decay_no_overflow():
    """Very fast decays (log w << 0) must stay finite — the exp(L_i - L_j)
    factorization guarantee."""
    B, S, H, D = 1, 64, 1, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    r = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    lw = jnp.full((B, S, H, D), -50.0)   # near-instant forgetting
    u = jnp.zeros((H, D))
    out = rw_ops.rwkv6(r, k, v, lw, u)
    assert bool(jnp.isfinite(out).all())
    ref = rw_ops.rwkv6(r, k, v, lw, u, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


def test_rwkv6_chunk_invariance():
    """The chunked algorithm is exact: results must not depend on chunk."""
    B, S, H, D = 2, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, D)))
    u = 0.1 * jax.random.normal(ks[4], (H, D))
    o16 = rw_ops.rwkv6(r, k, v, lw, u, chunk=16)
    o64 = rw_ops.rwkv6(r, k, v, lw, u, chunk=64)
    np.testing.assert_allclose(np.asarray(o16), np.asarray(o64), atol=2e-3,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# rglru scan kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,R,chunk", [(64, 128, 32), (100, 128, 128),
                                       (256, 256, 64)])
def test_rglru_scan_kernel_sweep(S, R, chunk):
    from repro.kernels.rglru_scan import ops as rg_ops
    ks = jax.random.split(jax.random.PRNGKey(S + R), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, S, R)) + 2.0)
    b = jax.random.normal(ks[1], (2, S, R)) * 0.5
    out = rg_ops.rglru_scan(a, b, chunk=chunk)
    ref = rg_ops.rglru_scan(a, b, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_rglru_scan_kernel_matches_model_layer():
    """The kernel agrees with the model's associative-scan path on the
    full RG-LRU layer math (gates + recurrence)."""
    from repro.kernels.rglru_scan import ops as rg_ops
    R, B, S = 128, 2, 64
    kg = jax.random.split(jax.random.PRNGKey(3), 4)
    p = {
        "w_a": jax.random.normal(kg[0], (R, R)) * 0.1,
        "w_i": jax.random.normal(kg[1], (R, R)) * 0.1,
        "lam": jax.random.normal(kg[2], (R,)),
    }
    u = jax.random.normal(kg[3], (B, S, R))
    y_model, _ = L.rglru_scan(p, u)
    log_a, x_in = L._rglru_gates(p, u)
    y_kernel = rg_ops.rglru_scan(jnp.exp(log_a), x_in)
    np.testing.assert_allclose(np.asarray(y_model, np.float32),
                               np.asarray(y_kernel), atol=1e-4, rtol=1e-4)
