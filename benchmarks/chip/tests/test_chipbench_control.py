"""The control -- the reference in bfloat16, the precision below the
configuration's float32, put in the program's place -- fails a cell's
limits (at a size a CPU test run holds)."""
import jax.numpy as jnp
import pytest

from chipbench import reference
from chipbench.population import Seeds
from chipbench_tiny import tiny_cell


@pytest.mark.parametrize("name", ["lenet-femnist.device.c100",
                                  "charlstm-shakespeare.device.c100"])
def test_control_fails_the_limits(name):
    cell, _ = tiny_cell(name)
    seeds = Seeds.from_seed(7)
    clients, counts = cell.model.make_data(cell.config, seeds.data)
    rounds = cell.traffic["plan"]["chunk_rounds"]
    ref = reference.follow(cell, clients, counts, seeds, rounds=rounds)
    control = reference.follow(cell, clients, counts, seeds, rounds=rounds,
                               dtype=jnp.bfloat16)
    gaps = reference.gaps(control, ref, cell.limits["loss_rounds"])
    lim = cell.limits["limits"]
    assert any(gaps[k] > lim[k] for k in lim), gaps
