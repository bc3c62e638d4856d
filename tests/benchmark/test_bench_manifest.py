"""BENCHMARK.json against the files it names and the contract's limits."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    bench = manifest["paths"][0] + "/"
    assert any(w.startswith(bench) for w in manifest["command"])


def test_every_cell_has_its_files(manifest):
    bench = os.path.join(ROOT, manifest["paths"][0])
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    seen = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cfg = configs[w["config"]]
        with open(os.path.join(ROOT, cfg["file"])) as fh:
            body = json.load(fh)
        assert body["reduced"] == cfg["reduced"]
        assert body["layout"]["chips"] == w["chips"]
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as fh:
            kind = json.load(fh)["kind"]
        assert os.path.isfile(os.path.join(bench, "traffic", kind + ".py"))
    assert used == set(configs), "a configuration no cell uses"
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_every_layer_metric_has_its_reader(manifest):
    bench = os.path.join(ROOT, manifest["paths"][0])
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert os.path.isfile(
            os.path.join(bench, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells


def test_end_to_end_metrics_keep_to_the_contract(manifest):
    names = [m["name"] for m in manifest["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    bench = os.path.join(ROOT, manifest["paths"][0])
    for m in manifest["end_to_end"]:
        assert os.path.isfile(
            os.path.join(bench, "end_to_end", m["name"] + ".py"))
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_names_and_units_keep_to_the_allowed_characters(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in manifest[group]]
    names += [w["config"] for w in manifest["workloads"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in manifest["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16


def test_files_under_paths_are_named_from_name_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
