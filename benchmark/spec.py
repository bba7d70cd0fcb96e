"""Where the benchmark's data lives, and the rules its files keep.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives it. A later PR adds files and entries and edits nothing here:

    benchmark/configs/<configuration>.json
    benchmark/traffic/<mix>.json
    benchmark/layer_metrics/<metric>.json
    benchmark/readers/<kind>.py

A cell is ``<configuration>.<mix>``. Which cells report a metric stands in
one place, its entry in ``BENCHMARK.json``: the cells under ``workloads``, or
every cell where the entry has no such key. No traffic, configuration or
metric file lists metrics or cells, so a later PR gives a cell that is there
one more per-layer metric with a new metric file and a new entry, and no
edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """A benchmark file breaks a rule of the contract."""


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


class Spec:
    """``BENCHMARK.json`` and the files it names, under one checkout."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, "benchmark")
        self.harness = _read_json(os.path.join(self.dir, "harness.json"))
        self.peaks = _read_json(os.path.join(self.dir, "peaks.json"))

    # -- lookups by name ----------------------------------------------------

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise SpecError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _read_json(os.path.join(self.dir, "traffic", name + ".json"))

    def layer_metric(self, name: str) -> Dict[str, Any]:
        return _read_json(
            os.path.join(self.dir, "layer_metrics", name + ".json"))

    def reader(self, kind: str):
        """The module ``benchmark/readers/<kind>.py``; a new kind is a new
        file."""
        if not NAME_RE.match(kind):
            raise SpecError(f"bad reader kind {kind!r}")
        path = os.path.join(self.dir, "readers", kind + ".py")
        if not os.path.exists(path):
            raise SpecError(f"no reader {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_reader_{kind}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def peak(self, device_kind: str) -> Dict[str, Any]:
        if device_kind not in self.peaks:
            raise SpecError(f"device kind {device_kind!r} is not in "
                            f"benchmark/peaks.json: no default peak exists")
        return self.peaks[device_kind]

    def metric_entry(self, name: str) -> Dict[str, Any]:
        for m in self.bench["end_to_end"] + self.bench["per_layer"]:
            if m["name"] == name:
                return m
        raise SpecError(f"metric {name!r} is not in BENCHMARK.json")

    def cell(self, workload: str):
        """``(workload entry, configuration file, traffic file)``."""
        w = self.workload(workload)
        return w, self.config(w["config"]), self.traffic(w["traffic"])

    # -- the rules ----------------------------------------------------------

    def problems(self) -> List[str]:
        """Every breach of the rules the files keep among themselves; empty
        when the benchmark is whole. The tests call this, and so does every
        run before it boots anything."""
        out: List[str] = []
        b = self.bench
        e2e = {m["name"]: m for m in b["end_to_end"]}
        per = {m["name"]: m for m in b["per_layer"]}
        for m in list(e2e.values()) + list(per.values()):
            if not NAME_RE.match(m["name"]):
                out.append(f"metric name {m['name']!r}")
            if not UNIT_RE.match(m["unit"]):
                out.append(f"unit {m['unit']!r} of {m['name']}")
            if m["source"] not in SOURCES:
                out.append(f"source {m['source']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"better {m['better']!r} of {m['name']}")
        if "setup_s" not in e2e:
            out.append("no setup_s among end_to_end")
        cfg_names = {c["name"] for c in b["configs"]}
        seen = set()
        for w in b["workloads"]:
            if w["name"] != f"{w['config']}.{w['traffic']}":
                out.append(f"workload {w['name']} is not <config>.<traffic>")
            for k in ("name", "config", "traffic"):
                if not NAME_RE.match(w[k]):
                    out.append(f"workload {k} {w[k]!r}")
            if w["config"] not in cfg_names:
                out.append(f"workload {w['name']}: unknown config")
            if (w["config"], w["traffic"]) in seen:
                out.append(f"workload pair {w['name']} twice")
            seen.add((w["config"], w["traffic"]))
            try:
                _, cfg, _ = self.cell(w["name"])
            except SpecError as e:
                out.append(str(e))
                continue
            if cfg.get("chips") != w["chips"]:
                out.append(f"{w['name']}: chips {w['chips']} but its "
                           f"configuration says {cfg.get('chips')}")
            mine = set(self.cell_end_to_end(w["name"]))
            if "setup_s" not in mine or len(mine) < 2:
                out.append(f"{w['name']}: needs setup_s and one more "
                           f"end-to-end metric")
            layer_metrics = self.cell_layer_metrics(w["name"])
            if not layer_metrics:
                out.append(f"{w['name']}: reports no per-layer metric")
            for name in layer_metrics:
                try:
                    mf = self.layer_metric(name)
                    self.reader(mf["reader"]["kind"])
                except SpecError as e:
                    out.append(str(e))
                    continue
                entry = per[name]
                for k in ("layer", "unit", "moves", "source", "better"):
                    if mf.get(k) != entry[k]:
                        out.append(f"{name}: {k} differs between its file "
                                   f"and BENCHMARK.json")
                if entry["moves"] not in mine:
                    out.append(f"{name} moves {entry['moves']}, which "
                               f"{w['name']} does not report")
        cells = {w["name"] for w in b["workloads"]}
        for m in list(e2e.values()) + list(per.values()):
            for wl in m.get("workloads", ()):
                if wl not in cells:
                    out.append(f"{m['name']} lists {wl}, which is no "
                               f"workload")
        return out

    def cell_end_to_end(self, workload: str) -> List[str]:
        """The end-to-end metrics a cell reports, in ``BENCHMARK.json``'s
        order."""
        return [m["name"] for m in self.bench["end_to_end"]
                if _reported_in(m, workload)]

    def cell_layer_metrics(self, workload: str) -> List[str]:
        """The per-layer metrics a cell reports, likewise."""
        return [m["name"] for m in self.bench["per_layer"]
                if _reported_in(m, workload)]


def _reported_in(entry: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]
