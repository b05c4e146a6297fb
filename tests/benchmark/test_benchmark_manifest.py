"""BENCHMARK.json against the contract, and against the files it names."""

import os
import re

import pytest

from _tiny import ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return load(ROOT, "BENCHMARK.json")


def _find(manifest, *parts):
    for base in manifest["paths"]:
        path = os.path.join(ROOT, base, *parts)
        if os.path.exists(path):
            return path
    return None


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= 1


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_unique_and_well_formed(manifest, section):
    names = [e["name"] for e in manifest[section]]
    assert len(names) == len(set(names))
    for entry in manifest[section]:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry and section != "end_to_end" \
                    and not (section == "per_layer" and key == "source"):
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_configs_and_cells_resolve(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for cell in manifest["workloads"]:
        assert cell["config"] in configs
        assert cell["chips"] in (1, 4)
        used.add(cell["config"])
        traffic = _find(manifest, "traffic", cell["traffic"] + ".json")
        assert traffic, cell["traffic"]
        driver = load(traffic)["driver"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", driver + ".py"))
        limits = _find(manifest, "correctness", cell["name"] + ".json")
        assert limits and load(limits)["limits"], cell["name"]
    assert used == set(configs)
    for entry in configs.values():
        assert entry["file"].startswith(tuple(manifest["paths"]))
        cfg = load(ROOT, entry["file"])
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        for key in entry["reduced"]:
            assert not key.endswith(("_dim", "_rank")), key
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "references", cfg["reference"] + ".py"))


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    assert "workloads" not in end["setup_s"]
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
        assert _find(manifest, "end_to_end", m["name"] + ".py"), m["name"]
    reports = {c: {n for n, m in end.items()
                   if c in m.get("workloads", cells)} for c in cells}
    for cell, names in reports.items():
        assert len(names) >= 2, cell   # setup_s and at least one other
    layered = set()
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["moves"] in end
        listed = set(m.get("workloads", cells))
        assert listed <= cells
        for cell in listed:
            assert m["moves"] in reports[cell], (m["name"], cell)
            layered.add(cell)
        assert _find(manifest, "layer_metrics", m["name"] + ".py"), m["name"]
    assert layered == cells


def test_tiny_manifest_covers_the_real_one(manifest):
    """The rehearsal manifest runs the same drivers and metric readers, and
    those of the cells kept for later (serving, the mesh)."""
    tiny = load(ROOT, "tests", "benchmark", "tiny", "manifest.json")
    assert {m["name"] for m in tiny["per_layer"]} >= \
        {m["name"] for m in manifest["per_layer"]}
    assert {m["name"] for m in tiny["end_to_end"]} >= \
        {m["name"] for m in manifest["end_to_end"]}


def test_peaks_have_their_source():
    peaks = load(ROOT, "benchmark", "peaks.json")
    assert "TPU v5 lite" in peaks and peaks["_source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
