"""Logical-axis sharding rules: mapping, axis dedup, divisibility fallback."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding import (
    FED_MESH_RULES,
    FSDP_RULES,
    axis_rules,
    client_axis_size,
    current_mesh,
    logical_spec,
    shard,
    spmd_client_axes,
)
from repro.sharding.rules import put_logical


@pytest.fixture(scope="module")
def mesh():
    n = jax.device_count()
    return make_mesh((n, 1), ("data", "model"))


@pytest.fixture(scope="module")
def mesh16():
    # abstract 16x16 mesh shape via the real single device repeated is not
    # possible; use a fake mesh over available devices but with the axis
    # names used by the rules (sizes 1).
    n = jax.device_count()
    return make_mesh((1, n, 1), ("pod", "data", "model"))


def test_logical_spec_basic(mesh):
    spec = logical_spec(("embed", "mlp"), FED_MESH_RULES, mesh)
    assert spec == P(None, "model")


def test_logical_spec_filters_missing_pod(mesh):
    spec = logical_spec(("clients", None), FED_MESH_RULES, mesh)
    assert spec == P("data", None)      # 'pod' dropped on single-pod mesh


def test_logical_spec_axis_used_once(mesh16):
    # both dims map to 'model': the second occurrence must be dropped
    spec = logical_spec(("mlp", "vocab"), FED_MESH_RULES, mesh16)
    assert spec == P("model", None)


def test_divisibility_fallback():
    """On a production-sized (abstract) mesh, non-divisible dims must drop
    mesh axes — kv_heads=1 over model=16 degrades to replication (MQA),
    40 heads over 16 likewise, while divisible dims keep their sharding."""
    from jax.sharding import AbstractMesh
    try:   # jax >= 0.5: (axis_sizes, axis_names)
        amesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    except TypeError:   # jax 0.4.x: tuple of (name, size) pairs
        amesh = AbstractMesh((("pod", 2), ("data", 16), ("model", 16)))
    spec = logical_spec(("kv_heads", "head_dim"), FED_MESH_RULES, amesh,
                        shape=(1, 128))
    assert spec == P(None, None)
    spec = logical_spec(("embed", "heads", "head_dim"), FED_MESH_RULES,
                        amesh, shape=(5120, 40, 128))
    assert spec == P(None, None, None)      # 40 % 16 != 0 -> replicated
    spec = logical_spec(("embed", "heads", "head_dim"), FED_MESH_RULES,
                        amesh, shape=(8192, 64, 128))
    assert spec == P(None, "model", None)   # 64 % 16 == 0 -> sharded
    # clients over ('pod','data') with only 2 clients: keeps pod, drops data
    spec = logical_spec(("clients", None), FED_MESH_RULES, amesh,
                        shape=(2, 7))
    assert spec == P("pod", None)


def test_fsdp_rules_shard_embed():
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    spec = logical_spec(("embed", "mlp"), FSDP_RULES, mesh)
    assert spec[0] in ("data", ("data",))


def test_shard_noop_outside_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    y = shard(x, "batch", "embed")       # no ambient mesh: no-op
    assert (x == y).all()


def test_shard_rank_mismatch_raises(mesh):
    import jax.numpy as jnp
    with axis_rules(mesh, FED_MESH_RULES):
        with pytest.raises(ValueError):
            shard(jnp.ones((2, 2)), "batch")


# ---------------------------------------------------------------------------
# rules naming ('pod','data') against meshes that lack 'pod', and the
# no-active-mesh no-ops the round engine's gates rely on
# ---------------------------------------------------------------------------
def test_clients_rule_filters_to_live_axes(mesh, mesh16):
    """FED_MESH_RULES maps 'clients' to ('pod','data'); the filtered entry
    must only ever name axes the live mesh actually has."""
    with axis_rules(mesh, FED_MESH_RULES):
        assert spmd_client_axes() == "data"    # 'pod' dropped -> bare str
        assert client_axis_size() == mesh.shape["data"]
    with axis_rules(mesh16, FED_MESH_RULES):
        assert spmd_client_axes() == ("pod", "data")
        assert client_axis_size() == (mesh16.shape["pod"]
                                      * mesh16.shape["data"])


def test_clients_rule_mapped_to_no_live_axis(mesh):
    """Rules that map 'clients' to an axis the mesh lacks degrade to the
    unsharded behaviour (entry None, size 1) — never a KeyError."""
    rules = dict(FED_MESH_RULES, clients=("pod",))
    with axis_rules(mesh, rules):
        assert spmd_client_axes() is None
        assert client_axis_size() == 1
        # and shard() on such an axis replicates instead of raising
        import jax.numpy as jnp
        y = shard(jnp.ones((4, 2)), "clients", None)
        assert (y == 1).all()


def test_no_active_mesh_noops():
    """Outside axis_rules: no ambient mesh, size-1 client axis, and both
    shard() and put_logical() pass values through untouched."""
    import jax.numpy as jnp
    assert current_mesh() is None
    assert spmd_client_axes() is None
    assert client_axis_size() == 1
    x = jnp.arange(6.0).reshape(2, 3)
    assert (shard(x, "clients", "embed") == x).all()
    import numpy as np
    y = put_logical(np.ones((2, 3), np.float32), "clients", None)
    assert isinstance(y, jax.Array) and (y == 1).all()


def test_client_axis_size_restored_after_context(mesh):
    with axis_rules(mesh, FED_MESH_RULES):
        assert client_axis_size() >= 1
        with axis_rules(None, None):       # nested deactivation
            assert client_axis_size() == 1
        assert client_axis_size() == mesh.shape["data"]
    assert client_axis_size() == 1
