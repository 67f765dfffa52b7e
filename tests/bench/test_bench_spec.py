"""BENCHMARK.json against the rules for its format, and against the
files it names."""
import json
import re
import pytest

import harness

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_check_budget_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_every_name_and_unit():
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for w in m.get("workloads", []):
            assert w in WORKLOADS


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry_matches_its_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["source"].startswith("https://") and _line(conf["why"])
    assert conf["file"].startswith("benchmarks/chip/configs/")
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert sorted(conf["reduced"]) == sorted(data["reduced"])
    assert len(conf["reduced"]) <= 16
    widths = re.compile(r"(^hidden_size$|^intermediate_size$|latent|"
                        r"state_size|proj|head_dim|_dim$|_rank$|"
                        r"expansion|per_tok)")
    for key in conf["reduced"]:
        assert NAME.match(key) and not widths.search(key), key
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_entry_matches_its_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    data = harness.load_cell(cell["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert data[key] == cell[key]
    harness.load_config(cell["config"])
    mix = harness.load_mix(cell["traffic"])
    harness.load_module("traffic", mix["generator"])
    got = harness.cell_metrics(SPEC, cell["name"])
    e2e = {m["name"] for m in got["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert got["per_layer"]
    for m in got["end_to_end"] + got["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for m in got["per_layer"]:
        assert m["moves"] in e2e


def test_pairs_are_unique_and_four_chips_bounded():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(WORKLOADS) // 2)


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25


def test_per_layer_entries():
    layers = {}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", WORKLOADS)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for spellings in layers.values():
        assert len(spellings) == 1


def test_files_under_paths_are_named_from_name_characters():
    for p in SPEC["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            rel = f.relative_to(ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
