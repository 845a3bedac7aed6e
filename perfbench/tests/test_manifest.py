"""`BENCHMARK.json` obeys the contract's rules of names, and every name in it
finds its file."""

import importlib
import os
import re

import pytest

from perfbench.lib.manifest import BENCH_DIR, ROOT, Cell, load_json, load_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["perfbench"]
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_name_is_a_name(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names += [c["name"], *c["reduced"]]
        assert c["file"].startswith("perfbench/") and len(c["reduced"]) <= 16
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        names.append(m["layer"])
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), f"not a name: {n!r}"
    for group in ("configs", "workloads"):
        got = [x["name"] for x in manifest[group]]
        assert len(got) == len(set(got))
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for entry in manifest["configs"] + manifest["workloads"]:
        for key in ("why", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                    and "\t" not in entry[key]


def test_arrows_and_cells(manifest):
    ends = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in ends and len(ends) >= 2
    cells = {w["name"] for w in manifest["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in manifest["workloads"]} \
        == {c["name"] for c in manifest["configs"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in ends
        assert set(m.get("workloads", cells)) <= cells
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert any("mfu" in m["name"] for m in manifest["per_layer"])


def test_every_name_finds_its_file(manifest):
    for w in manifest["workloads"]:
        cell = Cell(manifest, w["name"])
        assert cell.module("drivers", cell.traffic["driver"]).run
        assert cell.module("programs", cell.config["program"]).build
        assert cell.module("flops", cell.config["flops"]).train_flops_per_sample
        limits = load_json(os.path.join(BENCH_DIR, "limits", f"{w['name']}.json"))
        assert limits["limits"]["rows_unmatched"] == 0
        for m in cell.metrics("end_to_end"):
            assert importlib.import_module(f"perfbench.end_metrics.{m['name']}").read
        for m in cell.metrics("per_layer"):
            assert importlib.import_module(f"perfbench.layer_metrics.{m['name']}").read


def test_config_files_keep_published_widths(manifest):
    for c in manifest["configs"]:
        cfg = load_json(os.path.join(ROOT, c["file"]))
        assert cfg["_source"] == c["source"]
        assert (cfg["hidden_size"], cfg["num_hidden_layers"],
                cfg["num_attention_heads"], cfg["intermediate_size"]) \
            == (768, 12, 12, 3072)
        assert sorted(cfg.get("reduced_notes", {})) == sorted(c["reduced"])


def test_files_under_paths_are_named_from_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, _, files in os.walk(BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert ok.match(rel), rel
