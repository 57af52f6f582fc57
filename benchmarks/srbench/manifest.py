"""``BENCHMARK.json`` and the data files it names.

The harness is driven by data: a cell, a configuration or a per-layer
metric is found by its NAME in the manifest —

 - cell ``<name>``            -> ``<bench dir>/workloads/<name>.json``
 - configuration ``<name>``   -> the manifest entry's ``file``
 - per-layer metric ``<name>``-> ``<bench dir>/layer_metrics/<name>.py``

so a later PR adds one by adding files and manifest entries only.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    """The parsed ``BENCHMARK.json`` plus where its data files live.

    ``root`` is the checkout (configuration ``file`` paths are relative to
    it); ``bench_dir`` holds ``workloads/`` and ``layer_metrics/``."""

    def __init__(self, path: str, bench_dir: str):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        self.bench_dir = os.path.abspath(bench_dir)
        self.doc = load_json(self.path)

    # -- lookups -------------------------------------------------------------

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise KeyError(f"no workload {name!r} in {self.path} (has: {known})")

    def config_entry(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in {self.path}")

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.root, self.config_entry(name)["file"]))

    def workload_path(self, cell: str) -> str:
        return os.path.join(self.bench_dir, "workloads", f"{cell}.json")

    def workload(self, cell: str) -> dict:
        return load_json(self.workload_path(cell))

    def metrics_for(self, kind: str, cell: str) -> list:
        """Entries of ``end_to_end`` / ``per_layer`` that apply to
        ``cell`` (an entry without ``workloads`` applies to every cell)."""
        return [
            m for m in self.doc[kind]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def reader_path(self, metric: str) -> str:
        return os.path.join(self.bench_dir, "layer_metrics", f"{metric}.py")

    def reader_module(self, metric: str):
        """A per-layer metric's own file, imported by path."""
        path = self.reader_path(metric)
        spec = importlib.util.spec_from_file_location(
            f"layer_metric_{metric}", path
        )
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # -- consistency ---------------------------------------------------------

    def problems(self) -> list:
        """Everything that would stop a run or break the contract's
        naming rules; empty when the manifest and its files agree."""
        out = []
        doc = self.doc
        e2e = {m["name"] for m in doc["end_to_end"]}
        configs = {c["name"] for c in doc["configs"]}
        cells = {w["name"] for w in doc["workloads"]}
        names = (
            [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
            + list(configs) + list(cells)
            + [w["traffic"] for w in doc["workloads"]]
            + [k for c in doc["configs"] for k in c["reduced"]]
        )
        out += [f"bad name {n!r}" for n in names if not NAME_RE.match(n)]
        metric_names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
        out += [
            f"duplicate metric {n!r}" for n in set(metric_names)
            if metric_names.count(n) > 1
        ]
        if "setup_s" not in e2e:
            out.append("no setup_s among the end-to-end metrics")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                out.append(f"bad unit {m['unit']!r} on {m['name']}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"bad 'better' on {m['name']}")
            if m["source"] not in SOURCES:
                out.append(f"bad source on {m['name']}")
            for w in m.get("workloads", []):
                if w not in cells:
                    out.append(f"{m['name']} lists unknown cell {w!r}")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"end-to-end {m['name']} has source {m['source']}")
        for m in doc["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{m['name']} moves unknown metric {m['moves']!r}")
            if not os.path.isfile(self.reader_path(m["name"])):
                out.append(f"no reader file for per-layer metric {m['name']}")
        for c in doc["configs"]:
            if not any(w["config"] == c["name"] for w in doc["workloads"]):
                out.append(f"configuration {c['name']} is used by no cell")
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                out.append(f"configuration file {c['file']} is missing")
        for w in doc["workloads"]:
            if w["config"] not in configs:
                out.append(f"cell {w['name']} names unknown config {w['config']}")
            if w["chips"] not in (1, 4):
                out.append(f"cell {w['name']} asks for {w['chips']} chips")
            if len(w["why"]) > 200:
                out.append(f"cell {w['name']}: why is over 200 characters")
            if not os.path.isfile(self.workload_path(w["name"])):
                out.append(f"no workload file for cell {w['name']}")
                continue
            wl = self.workload(w["name"])
            for key in ("config", "traffic", "chips"):
                if wl.get(key) != w[key]:
                    out.append(
                        f"cell {w['name']}: {key} differs between the "
                        "manifest and the workload file"
                    )
        return out
