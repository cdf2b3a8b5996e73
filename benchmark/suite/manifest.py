"""Finds everything by name. ``BENCHMARK.json`` names cells, configurations
and metrics; each has a file of its own under this directory, so that a later
PR adds a cell, a configuration, a traffic mix or a per-layer metric by adding
files and manifest entries and edits none that is here:

    configs/<config>.json          sizes as run, source, reduced, assumed
    traffic/<traffic>.json         parameters of the one general generator
    cells/<workload>.json          job kind, its parameters, correctness limits
    rehearsal.json                 a tiny stand-in cell for each job kind
    layer_metrics/<metric>.py      read(run) -> number, or None: left out
    jobs/<job>.py                  the only code a new KIND of cell needs
"""

from __future__ import annotations

import importlib.util
import json
import os

SUITE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(SUITE))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(rows, name: str, what: str) -> dict:
    for row in rows:
        if row["name"] == name:
            return row
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(have: {', '.join(r['name'] for r in rows)})")


def load_module(path: str, name: str):
    """Import a file by path; metric names carry dots, so no package import."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"benchmark: {path} is missing")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of the manifest with the files its names lead to."""

    def __init__(self, workload: str, root: str = ROOT, suite: str = SUITE,
                 rehearsal: bool = False):
        self.root, self.suite = root, suite
        self.manifest = load_manifest(root)
        self.name, self.rehearsal = workload, rehearsal
        if rehearsal:
            # a tiny stand-in for the whole cell, by job kind: ``workload``
            # is a job kind, or a cell of the manifest whose kind is walked
            tiny = _load_json(os.path.join(suite, "rehearsal.json"))
            kind = workload if workload in tiny else self._spec()["job"]
            self.chips, self.spec = 1, dict(tiny[kind], job=kind)
            config, traffic = tiny[kind]["config"], tiny[kind]["traffic"]
            self.config_path = os.path.join(suite, "configs", config + ".json")
        else:
            entry = _by_name(self.manifest["workloads"], workload, "workload")
            self.chips, self.spec = int(entry["chips"]), self._spec()
            traffic = entry["traffic"]
            self.config_path = os.path.join(root, _by_name(
                self.manifest["configs"], entry["config"], "config")["file"])
        self.config = _load_json(self.config_path)
        self.traffic = _load_json(
            os.path.join(suite, "traffic", traffic + ".json"))

    def _spec(self) -> dict:
        _by_name(self.manifest["workloads"], self.name, "workload")
        return _load_json(os.path.join(self.suite, "cells",
                                       self.name + ".json"))

    def reports(self, metric: dict) -> bool:
        """Whether this cell reports ``metric`` (an ``end_to_end`` or
        ``per_layer`` entry). Without a ``workloads`` key an end-to-end
        metric is every cell's, and a per-layer one belongs to every cell
        that reports the end-to-end metric it moves."""
        if "workloads" in metric:
            return self.name in metric["workloads"]
        if "moves" in metric:
            moved = _by_name(self.manifest["end_to_end"], metric["moves"],
                             "end-to-end metric")
            return self.reports(moved)
        return True

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self.reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.manifest["per_layer"] if self.reports(m)]

    def job(self):
        kind = self.spec["job"]
        return load_module(os.path.join(self.suite, "jobs", kind + ".py"),
                           "suite_job_" + kind)

    def reader(self, metric_name: str):
        return load_module(
            os.path.join(self.suite, "layer_metrics", metric_name + ".py"),
            "suite_metric_" + metric_name.replace(".", "_").replace("-", "_"))


def load_peaks(device_kind: str, suite: str = SUITE) -> dict:
    table = _load_json(os.path.join(suite, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return table[device_kind]
