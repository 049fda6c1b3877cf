"""`BENCHMARK.json` and the data files it names. Everything that belongs to
one configuration, one traffic mix, one cell's limits or one per-layer metric
is a file of its own, found by the name in the manifest:

    configuration   the `file` its entry gives      (sizes, family, source)
    traffic mix     perf/traffic/<traffic>.json     (the job's parameters)
    limits          perf/limits/<cell>.json         (what `correct` allows)
    metric          perf/metrics/<name>.json        (reader and arguments)
    reader          perf/readers/<reader>.py        (code, `read(ctx, ...)`)
    adapter         perf/adapters/<family>.py       (program side)
    reference       perf/reference/<family>.py      (plain reference)

So a later PR adds a cell, a configuration or a metric by adding files and
entries, and edits none that is here.
"""
from __future__ import annotations

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _json(root, "BENCHMARK.json")
        self.cells = {w["name"]: w for w in self.data["workloads"]}
        self.configs = {c["name"]: c for c in self.data["configs"]}

    def cell(self, name: str) -> dict:
        """A cell with its configuration, traffic and limits loaded."""
        try:
            w = self.cells[name]
        except KeyError:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"there are: {sorted(self.cells)}") from None
        return {
            "name": name, "chips": w["chips"],
            "config": _json(self.root, self.configs[w["config"]]["file"]),
            "traffic": _json(self.root, "perf", "traffic",
                             w["traffic"] + ".json"),
            "limits": _json(self.root, "perf", "limits", name + ".json"),
        }

    def metrics(self, kind: str, cell: str) -> list:
        """The `end_to_end` or `per_layer` metrics that `cell` reports."""
        return [m for m in self.data[kind]
                if cell in m.get("workloads", self.cells)]

    def reader(self, metric: str):
        """(read function, its arguments) of a per-layer metric."""
        spec = _json(self.root, "perf", "metrics", metric + ".json")
        module = importlib.import_module("perf.readers." + spec["reader"])
        return module.read, spec.get("args", {})

    def problems(self) -> list:
        """Everything about the manifest and its files that the contract
        would refuse; empty when it is sound."""
        d, out = self.data, []
        e2e = {m["name"]: m for m in d["end_to_end"]}
        for kind in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [x["name"] for x in d[kind]]
            out += [f"{kind}: name {n!r} not allowed" for n in names
                    if not NAME.match(n)]
            out += [f"{kind}: name {n!r} twice" for n in set(names)
                    if names.count(n) > 1]
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                out.append(f"{m['name']}: unit {m['unit']!r} not allowed")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"{m['name']}: source {m['source']!r}")
            out += [f"{m['name']}: no workload {w!r}"
                    for w in m.get("workloads", []) if w not in self.cells]
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"{m['name']}: end-to-end source {m['source']!r}")
            if not 0 < m["bound"] <= 0.1:
                out.append(f"{m['name']}: bound {m['bound']}")
        if "setup_s" not in e2e:
            out.append("no setup_s")
        for c in d["configs"]:
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                out.append(f"{c['name']}: no file {c['file']}")
            if not any(w["config"] == c["name"] for w in d["workloads"]):
                out.append(f"{c['name']}: used by no cell")
        for w in d["workloads"]:
            if w["config"] not in self.configs:
                out.append(f"{w['name']}: no configuration {w['config']!r}")
            for sub in ("traffic/" + w["traffic"], "limits/" + w["name"]):
                if not os.path.isfile(os.path.join(self.root, "perf",
                                                   sub + ".json")):
                    out.append(f"{w['name']}: no perf/{sub}.json")
            if len(self.metrics("end_to_end", w["name"])) < 2:
                out.append(f"{w['name']}: needs setup_s and one more")
            if not self.metrics("per_layer", w["name"]):
                out.append(f"{w['name']}: no per-layer metric")
        for m in d["per_layer"]:
            moved = e2e.get(m["moves"])
            if moved is None:
                out.append(f"{m['name']}: moves unknown {m['moves']!r}")
                continue
            for w in m.get("workloads", self.cells):
                if w not in moved.get("workloads", self.cells):
                    out.append(f"{m['name']}: cell {w} does not report "
                               f"{m['moves']}")
            try:
                self.reader(m["name"])
            except (OSError, ImportError, KeyError, AttributeError) as e:
                out.append(f"{m['name']}: no reader ({e})")
        return out
