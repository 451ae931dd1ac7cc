"""A cell cut to a size a CPU test run holds: the committed cell's model,
precision, optimiser and limits, with a 16-client population and a cohort
of 4 clients x 2 local steps x batch 4 in 4-round chunks."""
import copy

from chipbench import harness, registry


def tiny_cell(name: str, bench=None):
    bench = bench or registry.benchmark()
    cell = harness.Cell.load(name, bench)
    cell.config = copy.deepcopy(cell.config)
    pop = cell.config["population"]
    pop["num_clients"] = 16
    if cell.config["model"] == "lenet":
        pop["size_mean"], pop["size_std"] = 30.0, 10.0
    else:
        pop["size_mean"], pop["size_std"] = 800.0, 400.0
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["plan"]["chunk_rounds"] = 4
    cell.traffic["cohort"].update(clients_per_round=4, local_steps=2,
                                  local_batch=4)
    return cell, bench
