"""BENCHMARK.json and the files it names: each configuration, traffic mix
and metric is found by name from a file of its own."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import json
import os
import re

import pytest

import deploy
import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = ("n_classes", "dim")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    t = BENCH["run_seconds"]
    assert 1 <= t <= 51
    runs = 2 + 14 * 24
    assert runs * (t + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_uniqueness():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names)), kind
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_found_by_name(conf):
    assert conf["file"] == f"bench/configs/{conf['name']}.json"
    c = spec.load_config(conf["name"])
    assert c["reduced"] == conf["reduced"]
    assert not set(conf["reduced"]) & set(WIDTHS)
    assert deploy.sizes(c, False)["mesh"][1] in (1, 4)
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    spec.load_traffic(cell["traffic"])
    e2e = {m["name"] for m in spec.metrics_for(BENCH, cell["name"], "end_to_end")}
    layer = spec.metrics_for(BENCH, cell["name"], "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], cell["name"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert callable(spec.load_reader(metric["name"]))
    assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                       f"{metric['name']}.py"))


def test_layer_names_are_consistent():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers == {"scheduler and admission", "device",
                      "OTA bundle and RX fan-out", "search kernel"}


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.load_traffic("no-such-mix")
