"""BENCHMARK.json against the contract, and against the files it names."""

import copy
import json
import os
import re

import pytest

import _tiny
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


def _families(manifest):
    """{(reference, adapter)} of a manifest's configurations."""
    out = set()
    for entry in manifest["configs"]:
        cfg = load(ROOT, entry["file"])
        out.add((cfg["reference"], cfg["adapter"]))
    return out


def test_tiny_manifest_covers_the_real_one(manifest):
    """The rehearsal manifest (the base file with its fragments merged in)
    runs the same drivers and metric readers, and those of the cells kept
    for later (serving, the mesh); and every family of ``BENCHMARK.json``
    has a tiny cell, so that it can be rehearsed on the CPU."""
    tiny = _tiny.manifest()
    assert {m["name"] for m in tiny["per_layer"]} >= \
        {m["name"] for m in manifest["per_layer"]}
    assert {m["name"] for m in tiny["end_to_end"]} >= \
        {m["name"] for m in manifest["end_to_end"]}
    used = {w["config"] for w in tiny["workloads"]}
    assert used == {c["name"] for c in tiny["configs"]}
    assert _families(tiny) >= _families(manifest)


def test_every_family_brings_its_files(manifest):
    """A configuration's ``reference`` and ``adapter`` are names of files:
    the plain reference, the adapter and the count, each found by that name
    alone (no table anywhere lists the families)."""
    for reference, adapter in _families(manifest) | _families(
            _tiny.manifest()):
        for kind, name in (("references", reference), ("adapters", adapter),
                           ("counts", reference)):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", kind, name + ".py")), (kind, name)


BASE = {"configs": [{"name": "c"}], "workloads": [{"name": "w"}],
        "end_to_end": [{"name": "e", "workloads": ["w"]},
                       {"name": "setup_s"}],
        "per_layer": [{"name": "p", "workloads": ["w"]}]}


def _base():
    return copy.deepcopy(BASE)


def test_a_fragment_appends_entries_and_cells():
    merged = _tiny.merge_fragment(_base(), {
        "what": "a new family's tiny cell",
        "configs": [{"name": "c2"}], "workloads": [{"name": "w2"}],
        "per_layer": [{"name": "p2", "workloads": ["w2"]}],
        "append_workloads": {"e": ["w2"], "p": ["w2", "w"]}})
    assert [c["name"] for c in merged["configs"]] == ["c", "c2"]
    assert [w["name"] for w in merged["workloads"]] == ["w", "w2"]
    assert [m["name"] for m in merged["per_layer"]] == ["p", "p2"]
    assert merged["end_to_end"][0]["workloads"] == ["w", "w2"]
    assert merged["per_layer"][0]["workloads"] == ["w", "w2"]   # no doubles


@pytest.mark.parametrize("fragment", [
    {"per_layer": [{"name": "p"}]},                   # a name that is there
    {"workloads": [{"name": "w2"}, {"name": "w2"}]},  # twice in one fragment
    {"append_workloads": {"nobody": ["w"]}},          # no such metric
    {"append_workloads": {"setup_s": ["w"]}},         # a metric of every cell
    {"paths": ["elsewhere"]},                         # not a fragment's to set
])
def test_a_fragment_that_would_edit_is_refused(fragment):
    with pytest.raises(ValueError):
        _tiny.merge_fragment(_base(), fragment)


def test_fragments_merge_in_name_order(tmp_path):
    """``manifest.d/*.json`` in name order over the base file; the merged
    file is what ``run.py --manifest`` is given."""
    tiny = tmp_path / "tiny"
    (tiny / "manifest.d").mkdir(parents=True)
    (tiny / "manifest.json").write_text(json.dumps(_base()))
    (tiny / "manifest.d" / "20_cell.json").write_text(json.dumps(
        {"workloads": [{"name": "w2"}], "append_workloads": {"p2": ["w2"]}}))
    (tiny / "manifest.d" / "10_metric.json").write_text(json.dumps(
        {"per_layer": [{"name": "p2", "workloads": ["w"]}]}))
    merged = _tiny.manifest(str(tiny))
    assert merged["per_layer"][1] == {"name": "p2", "workloads": ["w", "w2"]}
    assert load(_tiny.manifest_path(tmp_path, str(tiny))) == merged
    # the tree's own: the base file names none of the feed cycle's seven,
    # the first fragment brings them
    base = {m["name"] for m in load(_tiny.TINY, "manifest.json")["per_layer"]}
    merged = {m["name"] for m in _tiny.manifest()["per_layer"]}
    assert "feeder_ship_ms_per_krow" in merged - base


def test_peaks_have_their_source():
    peaks = load(ROOT, "benchmark", "peaks.json")
    assert "TPU v5 lite" in peaks and peaks["_source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
