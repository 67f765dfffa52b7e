"""The harness finds configurations, cells, traffic and metrics by the
names in BENCHMARK.json, so a later change adds files and entries and
edits none: shown here by adding one of each to a copy."""
import json
import shutil

import pytest

import harness


@pytest.fixture
def copy(tmp_path, monkeypatch):
    dst = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.SPEC_FILE, tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(harness, "HERE", dst)
    return tmp_path


def test_each_piece_found_by_its_name():
    spec = json.loads(harness.SPEC_FILE.read_text())
    for conf in spec["configs"]:
        assert harness.load_config(conf["name"])["name"] == conf["name"]
    for cell in spec["workloads"]:
        assert harness.load_cell(cell["name"])["config"] == cell["config"]
        harness.load_mix(cell["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()


def test_a_missing_piece_is_an_error():
    with pytest.raises(SystemExit):
        harness.load_cell("no-such.cell")
    with pytest.raises(SystemExit):
        harness.load_module("metrics", "no_such_metric")


def test_one_more_of_each_without_editing(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmarks").rglob("*")
              if p.is_file()}
    chip = copy / "benchmarks" / "chip"
    conf = json.loads((chip / "configs" / "qwen2.5-0.5b.json").read_text())
    conf.update(name="other-model", num_hidden_layers=2)
    (chip / "configs" / "other-model.json").write_text(json.dumps(conf))
    mix = {"generator": "open_poisson",
           "prompt_tokens": {"median": 64, "sigma": 0.5, "min": 8,
                             "max": 128},
           "output_tokens": {"median": 16, "sigma": 0.5, "min": 4,
                             "max": 64}}
    (chip / "traffic" / "burst.json").write_text(json.dumps(mix))
    cell = json.loads((chip / "workloads" / "rollout.qwen2.5-0.5b.json")
                      .read_text())
    cell.update(name="burst.other-model", config="other-model",
                traffic="burst", clients={"rate_per_s": 2.0})
    (chip / "workloads" / "burst.other-model.json").write_text(
        json.dumps(cell))
    (chip / "metrics" / "requests_sent.burst.py").write_text(
        "def read(run):\n    return float(len(run.sent))\n")
    spec = json.loads((copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "burst.other-model",
                              "config": "other-model", "traffic": "burst",
                              "chips": 1, "why": "x"})
    spec["end_to_end"].append({"name": "ttft_p90_ms", "unit": "ms",
                               "better": "lower", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["burst.other-model"]})
    spec["per_layer"].append({"name": "requests_sent.burst", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "ttft_p90_ms",
                              "workloads": ["burst.other-model"]})

    assert harness.load_cell("burst.other-model")["traffic"] == "burst"
    assert harness.load_config("other-model")["num_hidden_layers"] == 2
    assert harness.load_mix("burst")["generator"] == "open_poisson"
    got = harness.cell_metrics(spec, "burst.other-model")
    assert {m["name"] for m in got["end_to_end"]} == {"ttft_p90_ms",
                                                       "setup_s"}
    assert [m["name"] for m in got["per_layer"]] == ["requests_sent.burst"]
    read = harness.load_module("metrics", "requests_sent.burst").read
    assert read(harness.RunRecord(cell={}, config={}, seed=0)) == 0.0
    after = {p: p.read_bytes() for p in before}
    assert after == before      # nothing that was there changed
