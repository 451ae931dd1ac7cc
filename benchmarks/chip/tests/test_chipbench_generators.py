"""The benchmark's vectorised data generators: sizes are the program's
``lognormal_sizes`` draw for the configuration's size seed, never the run
seed; client counts and shapes are the configuration's."""
import copy

import numpy as np
import pytest

from chipbench import registry
from repro.data.partition import lognormal_sizes

CONFIGS = ["lenet-femnist", "charlstm-shakespeare"]


def _program_sizes(config):
    pop = config["population"]
    return lognormal_sizes(pop["num_clients"], pop["size_mean"],
                           pop["size_std"], seed=pop["size_seed"])


@pytest.mark.parametrize("name", CONFIGS)
def test_sizes_are_the_programs_draw(name):
    config = registry.config(name)
    model = registry.model(config["model"])
    sizes = model.client_sizes(config)
    assert len(sizes) == config["population"]["num_clients"]
    want = _program_sizes(config)
    if config["model"] == "charlstm":      # characters -> sequences
        want = np.maximum((want - 1) // config["model_config"]["seq_len"], 1)
    np.testing.assert_array_equal(sizes, want)


def test_stated_extremes():
    lenet = registry.config("lenet-femnist")
    assert registry.model("lenet").client_sizes(lenet).max() == 861
    lstm = registry.config("charlstm-shakespeare")
    assert registry.model("charlstm").client_sizes(lstm).max() == 2172


@pytest.mark.parametrize("name", CONFIGS)
def test_shapes_follow_the_config_not_the_seed(name):
    config = copy.deepcopy(registry.config(name))
    config["population"]["num_clients"] = 12
    config["population"]["size_mean"] = 60.0
    model = registry.model(config["model"])
    mc = config["model_config"]
    runs = [model.make_data(config, seed) for seed in (1, 2**31 + 5)]
    (c1, n1), (c2, n2) = runs
    np.testing.assert_array_equal(n1, n2)
    np.testing.assert_array_equal(n1, model.client_sizes(config))
    assert len(c1) == 12
    for k, (a, b) in enumerate(zip(c1, c2)):
        assert a.keys() == b.keys()
        for field in a:
            assert a[field].shape == b[field].shape
            assert a[field].shape[0] == n1[k]
        if config["model"] == "lenet":
            assert a["x"].shape[1:] == (mc["input_hw"], mc["input_hw"], 1)
            assert a["x"].dtype == np.float32
            assert 0 <= a["y"].min() and a["y"].max() < mc["classes"]
        else:
            assert a["tokens"].shape[1:] == (mc["seq_len"],)
            np.testing.assert_array_equal(a["tokens"][:, 1:],
                                          a["labels"][:, :-1])
            assert a["tokens"].max() < mc["vocab"]
    assert not np.array_equal(c1[0][next(iter(c1[0]))],
                              c2[0][next(iter(c2[0]))])
