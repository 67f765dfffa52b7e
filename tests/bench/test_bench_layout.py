"""The harness finds configurations, cells, traffic and metrics by the
names in BENCHMARK.json, so a later change adds files and entries and
edits none: shown here by adding one of each to a copy."""
import json
import shutil
import time
from pathlib import Path

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


WINDOWED = dict(
    name="tiny-windowed", source="https://example.org/tiny-windowed",
    architectures=["TinyWindowedLM"], hidden_act="silu",
    attention_bias=False, hidden_size=128, intermediate_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=48,
    num_hidden_layers=4, sliding_window=16,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    vocab_size=512, rope_theta=1e4, rms_norm_eps=1e-6,
    tie_word_embeddings=False, matmul_precision="highest")


def test_a_second_architecture_as_new_files_only(copy):
    """No bias, ``head_dim`` 48 against 128 / 4 = 32, and windows of 16 on
    three layers of four: the class's module, its configuration, a mix
    and a cell are new files, and the run through ``run.execute`` comes
    out correct at the rollout cell's limit, with its attention counted
    inside each layer's window."""
    import run
    import work

    before = {p: p.read_bytes() for p in (copy / "benchmarks").rglob("*")
              if p.is_file()}
    chip = copy / "benchmarks" / "chip"
    shutil.copy(Path(__file__).parent / "fixtures" / "TinyWindowedLM.py",
                chip / "arch" / "TinyWindowedLM.py")
    (chip / "configs" / "tiny-windowed.json").write_text(
        json.dumps(WINDOWED))
    # Prompts and outputs of up to 48 and 60 tokens: most rows lie past
    # the window.
    (chip / "traffic" / "tiny-rollout.json").write_text(json.dumps(
        {"generator": "closed_groups", "completions_per_prompt": 4,
         "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
         "output_tokens": {"median": 24, "sigma": 0.5, "min": 8,
                           "max": 60}}))
    limit = harness.load_cell("rollout.qwen2.5-0.5b")["check"][
        "max_logprob_gap"]
    (chip / "workloads" / "rollout.tiny-windowed.json").write_text(
        json.dumps({"name": "rollout.tiny-windowed",
                    "config": "tiny-windowed", "traffic": "tiny-rollout",
                    "chips": 1, "why": "x",
                    "clients": {"groups_in_flight": 2},
                    "engine": {"max_batch": 8, "max_seq_len": 128,
                               "pool_tokens": 1024, "prefill_chunk": 4,
                               "dispatch_budget": 64, "temperature": 1.0},
                    "check": {"sample_requests": 8,
                              "max_logprob_gap": limit}}))
    entry = {"name": "rollout.tiny-windowed", "config": "tiny-windowed",
             "traffic": "tiny-rollout", "chips": 1, "why": "x"}
    cell = harness.load_cell(entry["name"])
    conf = harness.load_config(entry["config"])
    res = run.execute(entry, cell, conf, harness.load_mix(entry["traffic"]),
                      2 ** 33 + 11, 2.0, False,
                      {"end_to_end": [{"name": "gen_tokens_per_s",
                                       "unit": "tokens/s"}],
                       "per_layer": []}, time.monotonic())
    rec, checks = res.pop("_run"), res.pop("_checks")
    assert res["correct"], checks
    assert checks[0]["value"] <= limit / 10
    assert res["attempted"] >= 8 and res["failed"] == 0

    # Each round's slots give its sums; the windowed count is the full
    # layer's plus three layers clipped to 16 positions.
    for r in rec.rounds:
        assert sum(n for _, n in r.slots) == r.rows
        assert sum(n * live - n * (n - 1) // 2
                   for live, n in r.slots) == r.ctx_rows
        assert sum(live for live, _ in r.slots) == r.kv_rows
    slots = [s for r in rec.rounds for s in r.slots]
    ctx = sum(r.ctx_rows for r in rec.rounds)
    clipped = sum(min(c, 16) for live, n in slots
                  for c in range(live - n + 1, live + 1))
    assert clipped < ctx
    assert work.attention_flops(conf, ctx, slots) == \
        4 * 4 * 48 * (ctx + 3 * clipped)
    after = {p: p.read_bytes() for p in before}
    assert after == before      # nothing that was there changed
