"""``BENCHMARK.json`` and the data files it names.

Nothing here, and nothing in any ``chipbench/*.py``, knows a cell by name: a
cell is an entry of ``workloads`` plus the files that entry names.
"""
from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json "
                     f"(there are: {', '.join(e['name'] for e in entries)})")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def metrics_for(manifest: dict, workload: str, section: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports: an
    entry without a ``workloads`` key is every cell's (per-layer: every cell
    that reports the end-to-end metric it moves)."""
    e2e = [m for m in manifest["end_to_end"] if workload in m.get("workloads", [workload])]
    if section == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in mine)]


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files."""

    def __init__(self, manifest: dict, name: str):
        self.manifest = manifest
        self.entry = find(manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = find(manifest["configs"], self.entry["config"], "configuration")
        with open(os.path.join(ROOT, conf["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.end_to_end = metrics_for(manifest, name, "end_to_end")
        self.per_layer = metrics_for(manifest, name, "per_layer")

    def limit(self, name: str):
        """A limit of ``correct``: the traffic file's, unless the configuration
        states its own (an error that grows with depth has no one limit)."""
        return {**self.traffic.get("correct", {}), **self.config.get("correct", {})}[name]

    def driver(self):
        return importlib.import_module(f"chipbench.drivers.{self.traffic['driver']}")

    def generator(self):
        return importlib.import_module(f"chipbench.generators.{self.traffic['generator']}")


def reader(section: str, name: str):
    """The reader of one metric, found by the metric's name in the directory of
    its section, ``end_to_end`` or ``layer_metrics`` (``.`` and ``-`` in a
    name are ``_`` in the file's)."""
    return importlib.import_module(
        f"chipbench.{section}." + name.replace(".", "_").replace("-", "_"))
