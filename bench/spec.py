"""Find a cell's pieces by name: its entry in BENCHMARK.json, the
configuration's file, the traffic mix's file, the mix's arrival process and
each metric's reader. Nothing here knows a particular cell, so a later
cell, mix, arrival process or metric is added by adding files and entries."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        conf = json.load(f)
    if conf.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {conf.get('name')!r}")
    return conf


def load_traffic(name: str) -> dict:
    """The traffic mix's parameters, ``bench/traffic/<name>.json``."""
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it under ``workloads``, or that list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def _load_module(subdir: str, name: str):
    path = os.path.join(BENCH_DIR, subdir, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{subdir}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The per-layer metric's reader: ``read(ctx)`` from
    ``bench/metrics/<name>.py``."""
    return _load_module("metrics", name).read


def load_process(name: str):
    """The arrival process of a traffic mix: ``Process`` from
    ``bench/traffic/<name>.py``."""
    return _load_module("traffic", name).Process
