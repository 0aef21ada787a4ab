"""Finds a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: a configuration (its file is
named in ``configs``), a traffic mix (``chipbench/traffic/<name>.json``)
and the metrics reported in it (``chipbench/metrics/<name>.py``, one
reader each). Nothing here knows any cell, mix or metric by name.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root, workload, here=HERE):
    """The cell's dict: its BENCHMARK.json entry plus ``config_file``
    (the configuration as run), ``traffic_file`` (the mix's parameters)
    and ``end_to_end`` / ``per_layer`` (the metric entries it reports).
    ``root`` holds BENCHMARK.json; ``here`` the benchmark's files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("chipbench: no workload %r in BENCHMARK.json "
                         "(has: %s)" % (workload, ", ".join(cells)))
    cell = dict(cells[workload])
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    cell["config_file"] = load_json(os.path.join(root, config["file"]))
    cell["traffic_file"] = load_json(
        os.path.join(here, "traffic", cell["traffic"] + ".json"))
    for group in ("end_to_end", "per_layer"):
        cell[group] = [m for m in bench[group]
                       if workload in m.get("workloads", [workload])]
    return cell


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name, here=HERE):
    """The reader of one metric: ``chipbench/metrics/<name>.py``."""
    return load_module(os.path.join(here, "metrics", name + ".py"),
                       "chipbench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))


def load_driver(kind, here=HERE):
    """The driver of one kind of traffic: ``drivers/<kind>.py``."""
    return load_module(os.path.join(here, "drivers", kind + ".py"),
                       "chipbench_driver_" + kind)


def read_metrics(cell, group, run, here=HERE):
    """``{name: {"value", "unit"}}`` for the cell's metrics of a group.
    A reader that finds nothing to read returns None and its metric is
    left out of the line."""
    out = {}
    for m in cell[group]:
        value = load_metric(m["name"], here).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
