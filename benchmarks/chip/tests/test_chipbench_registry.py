"""The harness finds every cell's configuration, traffic, limits, model
and metric readers by name, and BENCHMARK.json keeps the contract's
shape."""
import re

import pytest

from chipbench import harness, parts, registry

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_pieces_found_by_name(cell):
    c = harness.Cell.load(cell, BENCH)
    entry = next(e for e in BENCH["configs"]
                 if e["name"] == registry.cell(cell, BENCH)["config"])
    assert entry["file"] == f"benchmarks/chip/configs/{entry['name']}.json"
    assert c.config["name"] == entry["name"]
    assert sorted(c.config["reduced"]) == sorted(entry["reduced"])
    for fn in ("program_loss", "init", "loss", "client_sizes", "make_data",
               "forward_flops"):
        assert callable(getattr(c.model, fn)), fn
    assert c.limits["loss_rounds"] >= 1 and c.limits["limits"]
    assert c.traffic["plan"]["chunk_rounds"] >= c.limits["loss_rounds"]
    assert c.chips in (1, 4)
    for role, spec in parts.specs(c.config, c.traffic).items():
        mod, _ = parts.load(role, spec)
        assert callable(mod.program)
        if role in parts.REFERENCE_ROLES:
            assert callable(mod.reference)


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    BENCH["end_to_end"] + BENCH["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(registry.metric(metric).read)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        registry.cell("no-such-cell", BENCH)
    with pytest.raises(FileNotFoundError):
        registry.metric("no_such_metric")


def test_unknown_part_or_setting_raises():
    c = harness.Cell.load(BENCH["workloads"][0]["name"], BENCH)
    with pytest.raises(FileNotFoundError):
        parts.load("server_opt", {"name": "fedadam"})
    with pytest.raises(FileNotFoundError):
        parts.load("local_opt", {"name": "momentum"})
    spec = dict(c.config["server_opt"], nesterov=True)
    with pytest.raises(ValueError, match="nesterov"):
        parts.load("server_opt", spec)
    with pytest.raises(KeyError):
        parts.specs(c.config, {"cohort": {}, "plan": {}})
