"""The manifest, the files its names lead to, and that each kind of thing is
added by new files plus a manifest entry, with no edit to a file that is
there."""

import json
import os
import re
import shutil

import pytest

import manifest
from conftest import ROOT, SUITE

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def test_top_level_keys_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark/suite"]
    assert bench["command"] == ["python3", "benchmark/suite/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_sources(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and 0 < len(m["layer"]) <= 200
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_has_its_files_and_reports_enough(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(bench["workloads"])
    used = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = manifest.Cell(w["name"])
        used.add(w["config"])
        assert cell.spec["job"] and cell.spec["limits"]
        assert os.path.exists(os.path.join(
            SUITE, "jobs", cell.spec["job"] + ".py"))
        e2e = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer()
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]).read)
        # the rehearsal walks the cell's job kind and judges the same numbers
        tiny = manifest.Cell(w["name"], rehearsal=True)
        assert tiny.config["n_embd"] < 128
        assert set(tiny.spec["limits"]) == set(cell.spec["limits"])
    assert used == {c["name"] for c in bench["configs"]}


def test_configs_keep_published_widths(bench):
    want = {"gpt2-medium": (1024, 16, 50257, 1024, None),
            "cerebras-gpt-1.3b": (2048, 16, 50257, 2048, 8192)}
    files = set()
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/suite/configs/")
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert (cfg["n_embd"], cfg["n_head"], cfg["vocab_size"],
                cfg["n_positions"], cfg["n_inner"]) == want[c["name"]]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|n_embd|n_inner|n_head)$", key)
    assert len(files) == len(bench["configs"])


def test_unlisted_device_is_an_error():
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        manifest.load_peaks("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        manifest.load_peaks("_source")


def test_a_later_pr_adds_by_files_and_entries_only(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric, each
    added as new files plus manifest entries in a copy of the tree."""
    root = tmp_path / "checkout"
    suite = root / "benchmark" / "suite"
    shutil.copytree(SUITE, suite, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    bench = manifest.load_manifest()
    before = {p: p.read_bytes() for p in suite.rglob("*") if p.is_file()}

    cfg = json.load(open(os.path.join(SUITE, "configs", "gpt2-medium.json")))
    cfg["n_layer"] = 12
    (suite / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = json.load(open(os.path.join(SUITE, "traffic",
                                      "rehearsal-chat.json")))
    mix["rate_per_s"] = 9.0
    (suite / "traffic" / "chat_saturated.json").write_text(json.dumps(mix))
    tiny = json.load(open(os.path.join(SUITE, "rehearsal.json")))
    spec = {"job": "serve_open_loop",
            "job_params": tiny["serve_open_loop"]["job_params"],
            "limits": tiny["serve_open_loop"]["limits"]}
    (suite / "cells" / "new_serve_saturated.json").write_text(json.dumps(spec))
    (suite / "layer_metrics" / "slot_occupancy_pct.serve.py").write_text(
        "def read(view):\n"
        "    s = view.get('serving_stats') or {}\n"
        "    return 100.0 * s['slot_occupancy'] if 'slot_occupancy' in s "
        "else None\n")
    bench["configs"].append({
        "name": "new-model", "source": cfg["source"],
        "file": "benchmark/suite/configs/new-model.json",
        "reduced": ["n_layer"], "why": "a test"})
    bench["workloads"].append({
        "name": "new_serve_saturated", "config": "new-model",
        "traffic": "chat_saturated", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({
        "name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.02, "source": "host_clock",
        "workloads": ["new_serve_saturated"]})
    bench["per_layer"].append({
        "name": "slot_occupancy_pct.serve", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "admission + scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["new_serve_saturated"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = manifest.Cell("new_serve_saturated", root=str(root),
                         suite=str(suite))
    assert cell.config["n_layer"] == 12
    assert cell.traffic["rate_per_s"] == 9.0
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s",
                                                      "serve_tokens_per_s"]
    mine = [m["name"] for m in cell.per_layer()]
    assert mine == ["slot_occupancy_pct.serve"]
    read = cell.reader("slot_occupancy_pct.serve").read
    assert read({"serving_stats": {"slot_occupancy": 0.5}}) == 50.0
    assert read({}) is None
    assert cell.job().run
    # and an existing cell is untouched: it neither gains the new metrics...
    old = manifest.Cell("gpt2m_train_t1024", root=str(root), suite=str(suite))
    assert "serve_tokens_per_s" not in [m["name"] for m in old.end_to_end()]
    assert "slot_occupancy_pct.serve" not in [m["name"]
                                              for m in old.per_layer()]
    # ...nor was any file that was there edited
    assert all(p.read_bytes() == data for p, data in before.items())


def test_rehearsal_stands_in_by_job_kind():
    for kind in ("train", "serve_open_loop"):
        cell = manifest.Cell(kind, rehearsal=True)
        assert cell.spec["job"] == kind and cell.config["n_embd"] < 128
    assert manifest.Cell("gpt2m_train_t1024",
                         rehearsal=True).spec["job"] == "train"
    with pytest.raises(SystemExit):
        manifest.Cell("no_such_cell", rehearsal=True)
