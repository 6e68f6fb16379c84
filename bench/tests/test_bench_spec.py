"""BENCHMARK.json against its contract, and every file it names."""
import json
import re

import pytest

from _bench import BENCH, ROOT, cells, run

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in
                                           SPEC["workloads"]]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells()


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    mod = run._module(BENCH / "metrics" / f"{metric}.py")
    assert callable(mod.read)


@pytest.mark.parametrize("cell", cells())
def test_cell_files_load_and_match_the_program(cell):
    import program
    c = run.load_cell(cell)
    assert c.work["workers"] >= 1 and c.work["limits"]
    assert set(c.work["limits"]) == set(run._module(BENCH / "check.py").NUMBERS)
    assert c.traffic["per_worker"] * c.work["workers"] == c.traffic["batch"]
    program.model_config(c.config["model"])          # sizes are the program's
    ref = run.reference_module(c)
    assert callable(ref.init) and callable(ref.loss)
    conf = {x["name"]: x for x in SPEC["configs"]}[c.spec["config"]]
    assert sorted(conf["reduced"]) == sorted(c.config["reduced"])


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_config_is_used_by_a_cell():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
