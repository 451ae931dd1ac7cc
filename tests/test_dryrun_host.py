"""Host-mesh (real devices) integration of the distributed round: the same
code path the 512-chip dry-run lowers, executed for real on the available
CPU device(s) — catches semantic (not just lowering) sharding bugs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import RoundConfig, round_step, fedmom
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.sharding import FED_MESH_RULES, axis_rules, tree_shardings

pytestmark = pytest.mark.slow   # transformer lowering: minutes, not seconds


def test_round_under_mesh_context_matches_plain():
    """Running the round inside a (trivial) mesh with sharding constraints
    active must give identical numbers to the constraint-free path."""
    cfg = get_config("qwen3-1.7b").reduced().replace(dtype="float32")
    params, axes = T.init(cfg, jax.random.PRNGKey(0))
    C, H, B, S = 2, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    batches = {
        "tokens": jax.random.randint(ks[0], (C, H, B, S), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (C, H, B, S), 0, cfg.vocab),
    }
    weights = jnp.asarray([0.4, 0.1])
    opt = fedmom(eta=1.0, beta=0.9)
    rcfg = RoundConfig(C, H, 0.05, "mesh", compute_dtype="float32")

    def loss_fn(p, b):
        return T.loss_fn(p, cfg, b)

    s_plain, m_plain = round_step(loss_fn, opt, opt.init(params), batches,
                                  weights, rcfg, param_axes=axes)

    n = min(jax.device_count(), C)          # the cohort must tile 'data'
    mesh = make_mesh((1, n, 1), ("pod", "data", "model"))
    rules = dict(FED_MESH_RULES, batch=None)
    with mesh, axis_rules(mesh, rules):
        s_mesh, m_mesh = round_step(loss_fn, opt, opt.init(params), batches,
                                    weights, rcfg, param_axes=axes)
    assert np.allclose(float(m_plain["loss"]), float(m_mesh["loss"]),
                       atol=1e-4)
    for a, b in zip(jax.tree.leaves(s_plain.w), jax.tree.leaves(s_mesh.w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
