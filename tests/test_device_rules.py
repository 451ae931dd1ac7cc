"""Device-facing rules that hold without a chip.

* A Pallas kernel is compiled on a TPU and interpreted only on the CPU; its
  target comes from concrete operands or the trainer's ``kernel_platform``
  scope, a kernel that cannot tell raises, and so do operands that
  contradict the scope.
* Peak device numbers are looked up by ``device_kind``; an unknown kind
  raises.
* The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
  one fixed, git-ignored path in the checkout.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _trajectory import default_rcfg, flat_w, make_clients, run_trajectory
from repro.core import fedmom
from repro.kernels import fedmom_ops
from repro.kernels._device import kernel_platform, resolve_interpret
from repro.kernels.fedmom_update import ref as fm_ref
from repro.launch import compile_cache, hlo_analysis, hw
from repro.launch.plan import device_memory_budget


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(33, 7)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}


def _fused(w, v, d, **kw):
    return fedmom_ops.fused_update_tree(w, v, d, eta=1.5, beta=0.9, **kw)


def test_concrete_cpu_operands_interpret():
    assert resolve_interpret(_tree()) is True
    got = _fused(_tree(0), _tree(1), _tree(2))
    want = fm_ref.fedmom_update(_tree(0), _tree(1), _tree(2), 1.5, 0.9)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


def test_inside_jit_without_a_target_raises():
    with pytest.raises(ValueError, match="cannot see its target"):
        jax.jit(_fused)(_tree(0), _tree(1), _tree(2))


def test_kernel_platform_scope_names_the_target():
    want = _fused(_tree(0), _tree(1), _tree(2))
    with kernel_platform("cpu"):
        got = jax.jit(_fused)(_tree(0), _tree(1), _tree(2))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("where", ["eager", "nested"])
def test_operands_contradicting_the_scope_raise(where):
    """CPU-committed operands under a TPU scope: the kernel neither guesses
    nor lets the scope override where the data lives."""
    if where == "eager":
        with kernel_platform("tpu"), pytest.raises(ValueError,
                                                   match="contradicts"):
            _fused(_tree(0), _tree(1), _tree(2))
    else:
        with kernel_platform("cpu"):
            with kernel_platform("tpu"), pytest.raises(ValueError,
                                                       match="contradicts"):
                _fused(_tree(0), _tree(1), _tree(2))
            assert resolve_interpret(_tree()) is True


def test_kernel_platform_rejects_other_platforms():
    with pytest.raises(ValueError, match="'gpu'"):
        with kernel_platform("gpu"):
            pass


@pytest.mark.parametrize("driver", ["scanned", "device"])
def test_trainer_threads_the_kernel_mode(driver):
    """FederatedTrainer.run names the platform for the jitted rounds, so the
    fused server update resolves its mode inside jit and trains the unfused
    trajectory bit for bit (interpret mode runs the same fp32 ops)."""
    clients, rcfg = make_clients(), default_rcfg()
    want = run_trajectory(driver, fedmom(eta=1.0, beta=0.9), rcfg, clients,
                          6, chunk_rounds=3)
    got = run_trajectory(driver, fedmom(eta=1.0, beta=0.9,
                                        use_fused_kernel=True),
                         rcfg, clients, 6, chunk_rounds=3)
    np.testing.assert_array_equal(flat_w(got[1]), flat_w(want[1]))


def test_peaks_are_keyed_by_device_kind():
    v5e = hw.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (
        197e12, 819e9, 16 * 10**9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no declared peaks"):
        hw.peaks("cpu")
    terms = hlo_analysis.roofline_terms(197e12, 819e9, 0.0, "TPU v5 lite")
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(1.0)


def test_memory_budget_is_none_on_cpu():
    assert device_memory_budget() is None


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.cache_dir() == str(repo / ".jax_cache")
    ignored = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
