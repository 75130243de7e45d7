"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration's file
names its ``family`` (``families/<family>.py`` builds the program's
circuit, ``reference/<family>.py`` is its plain reference); the traffic
file ``traffic/<traffic>.json`` names its ``kind``, the driver
``drivers/<kind>.py``; a per-layer metric is read by
``metrics/<metric name>.py``. Adding any of them adds files only.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class Registry:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.bench_dir = os.path.join(root, "benchmark")

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench_dir, "traffic",
                               f"{name}.json")) as f:
            return json.load(f)

    @staticmethod
    def driver(kind: str):
        return importlib.import_module(f"benchmark.drivers.{kind}")

    @staticmethod
    def family(name: str):
        return importlib.import_module(f"benchmark.families.{name}")

    @staticmethod
    def reference(name: str):
        return importlib.import_module(f"benchmark.reference.{name}")

    def metrics(self, section: str, workload: str) -> list:
        """The metrics of ``section`` (``end_to_end`` or ``per_layer``)
        that ``workload`` reports: those that list it or list no cell."""
        return [m for m in self.spec[section]
                if workload in m.get("workloads", (workload,))]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.metrics.{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
