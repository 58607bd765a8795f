"""Find a cell's pieces by name: ``BENCHMARK.json`` at the root of the
checkout names the cells and metrics; each configuration, traffic mix
and metric reader sits in a file of its own, found here by its name:

    configs/<config>.json      the deployment, as run
    traffic/<traffic>.json     the mix (see traffic.py)
    e2e/<metric>.py            reader of an end-to-end metric
    layers/<metric>.py         reader of a per-layer metric

A reader is a module with ``read(win) -> float | None`` (``win`` is
``run.Window``).  None means the run had nothing to read; the metric is
then left out of the result line.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def config(bench, cell_):
    for c in bench["configs"]:
        if c["name"] == cell_["config"]:
            with open(os.path.join(ROOT, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"workload {cell_['name']!r} names no known config "
                   f"{cell_['config']!r}")


def traffic(cell_):
    with open(os.path.join(HERE, "traffic", cell_["traffic"] + ".json")) as f:
        return json.load(f)


def metrics(bench, cell_, trace):
    """The metric entries this cell reports: its end-to-end metrics in an
    untraced run, its per-layer metrics in a traced one."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if cell_["name"] in m.get("workloads", [cell_["name"]])]


def reader(metric, trace):
    """The ``read`` function of one metric, from its own file."""
    kind = "layers" if trace else "e2e"
    path = os.path.join(HERE, kind, metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{metric['name']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
