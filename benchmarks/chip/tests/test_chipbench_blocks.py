"""The reference in blocks of clients: blocks of 1, 2 and the whole cohort
follow the same rounds; the block the paper's configurations get at their
cohort of 100 on one v5e is the whole cohort, and a model at published
widths gets one client; the planted faults still fail the limits with one
client to a block; and, compiled for a described v5e, a one-client block
of a 491M-parameter stand-in fits the chip where a cohort of 8 side by side
does not."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, registry
from chipbench.population import Seeds
from chipbench_tiny import tiny_cell

LENET = "lenet-femnist.device.c100"
LSTM = "charlstm-shakespeare.device.c100"
MESH = "mesh"
V5E_BYTES = int(15.75 * 2**30)     # a v5e chip's bytes_limit
SEED = 2**31 + 23
# LFM2-8B-A1B cut to one chip's share (PERF.md, section 7)
STANDIN_PARAMS = 491_000_000


def _cell(name):
    if name != MESH:
        return tiny_cell(name)[0]
    cell, _ = tiny_cell(LENET)           # the LeNet cell on four devices
    cell.chips = 4
    cell.traffic["plan"]["mesh_devices"] = 4
    cell.traffic["cohort"]["clients_per_round"] = 16
    return cell


@functools.lru_cache(maxsize=None)
def _data(name):
    cell = _cell(name)
    seeds = Seeds.from_seed(SEED)
    clients, counts = cell.model.make_data(cell.config, seeds.data)
    return cell, clients, counts, seeds


def _per_client(cell, clients):
    shapes = jax.eval_shape(lambda: cell.model.init(
        jax.random.PRNGKey(0), cell.config["model_config"]))
    shape = cell.traffic["cohort"]
    return reference.client_bytes(
        sum(x.size for x in jax.tree.leaves(shapes)),
        sum(a[0].nbytes for a in clients[0].values()),
        shape["local_steps"], shape["local_batch"])


def _memory_for(block, cell, clients):
    """Device bytes whose ``BLOCK_SHARE`` holds ``block`` clients and not
    one more."""
    return int((block + 0.5) * _per_client(cell, clients)
               / reference.BLOCK_SHARE)


@functools.lru_cache(maxsize=None)
def _follow(name, block, fault=None):
    cell, clients, counts, seeds = _data(name)
    memory = None if block is None else _memory_for(block, cell, clients)
    return reference.follow(cell, clients, counts, seeds,
                            rounds=cell.traffic["plan"]["chunk_rounds"],
                            fault=fault, shards=cell.chips,
                            memory_bytes=memory)


@pytest.mark.parametrize("block", [1, 2, "cohort"])
@pytest.mark.parametrize("name", [LENET, LSTM, MESH])
def test_blocks_follow_the_same_rounds(name, block):
    whole = _follow(name, None)
    C = _data(name)[0].traffic["cohort"]["clients_per_round"]
    assert whole["block"] == C            # the CPU reports no memory
    got = _follow(name, C if block == "cohort" else block)
    assert got["block"] == (C if block == "cohort" else block)
    for key in ("loss", "dnorm"):
        np.testing.assert_allclose(got[key], whole[key], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got["w"]), jax.tree.leaves(whole["w"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("name", [LENET, LSTM])
def test_accepted_cells_take_the_whole_cohort(name):
    """At the committed cohort (C = 100, H = 10, b = 10) on one v5e."""
    cell = harness.Cell.load(name, registry.benchmark())
    _, clients, _, _ = _data(name)          # the same sample shapes
    C = cell.traffic["cohort"]["clients_per_round"]
    assert C == 100
    assert reference.block_size(C, _per_client(cell, clients),
                                V5E_BYTES) == C


@pytest.mark.parametrize("cohort", [8, 100])
def test_published_widths_take_one_client(cohort):
    # a sequence of 2,048 token ids and as many labels per sample
    per = reference.client_bytes(STANDIN_PARAMS, 2 * 2048 * 4, 10, 10)
    assert reference.block_size(cohort, per, 16 * 2**30) == 1


def test_block_is_a_divisor_of_the_cohort():
    per = 1000
    assert reference.block_size(100, per, None) == 100
    assert reference.block_size(100, per, 2 * 30 * per) == 25
    assert reference.block_size(7, per, 2 * 6 * per) == 1
    assert reference.block_size(12, per, 1) == 1


@pytest.mark.parametrize("fault,name", [("frozen", LENET),
                                        ("half_batch", LENET),
                                        ("frozen", LSTM),
                                        ("half_batch", LSTM),
                                        ("no_exchange", MESH)])
def test_faults_fail_the_limits_with_one_client_a_block(fault, name):
    cell = _data(name)[0]
    lim = cell.limits["limits"]
    got = reference.gaps(_follow(name, 1, fault), _follow(name, None),
                         cell.limits["loss_rounds"])
    assert _follow(name, 1, fault)["block"] == 1
    assert any(got[k] > lim[k] for k in lim), got


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _standin_block(one_chip, B):
    """The reference's block step over a stand-in of 117 dense 2,048 x
    2,048 layers (490.7M float32 parameters), compiled for one v5e."""
    L, D, H, b = 117, 2048, 10, 10

    def loss(p, batch):
        h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None),
                            batch["x"], p["w"])
        return jnp.mean((h - batch["y"]) ** 2)

    sgd = reference.LocalRule(
        init=lambda p: (),
        step=lambda p, g, s, lr: (jax.tree.map(lambda a, d: a - lr * d,
                                               p, g), s))
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    step = jax.jit(functools.partial(reference._block, loss, sgd, False))
    return step.lower({"w": sds((L, D, D))},
                      {"x": sds((B, H, b, D)), "y": sds((B, H, b, D))},
                      sds((B,)), sds((B,), jnp.int32), sds(()),
                      sds((B,))).compile()


def test_standin_block_of_one_fits_a_chip(one_chip):
    m = _standin_block(one_chip, 1).memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_BYTES


def test_standin_cohort_of_eight_side_by_side_does_not(one_chip):
    try:
        m = _standin_block(one_chip, 8).memory_analysis()
    except Exception as e:         # the compiler refuses what cannot fit
        assert "RESOURCE_EXHAUSTED" in str(e), e
        return
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) > V5E_BYTES
