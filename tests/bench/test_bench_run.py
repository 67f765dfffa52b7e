"""The harness end to end on the CPU at a tiny size, without its look
for a chip: a sound program comes out correct, and each fault the
serving cells can have, planted in the timed path, comes out not
correct.  And ``run.py`` itself refuses a host without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

import harness
import run

TINY = dict(name="tiny", source="https://example.org/tiny",
            architectures=["Qwen2ForCausalLM"], hidden_size=128,
            intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=512,
            rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=True,
            hidden_act="silu", matmul_precision="highest")
MIX = {"generator": "closed_groups", "completions_per_prompt": 4,
       "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
       "output_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 60}}
# The limit is the rollout cell's own: a fault has to fail it.
LIMIT = harness.load_cell("rollout.qwen2.5-0.5b")["check"]["max_logprob_gap"]
CELL = {"name": "tiny.rollout", "clients": {"groups_in_flight": 2},
        "engine": {"max_batch": 8, "max_seq_len": 128, "pool_tokens": 1024,
                   "prefill_chunk": 4, "dispatch_budget": 64,
                   "temperature": 1.0},
        "check": {"sample_requests": 8, "max_logprob_gap": LIMIT}}
E2E = [{"name": "gen_tokens_per_s", "unit": "tokens/s"},
       {"name": "setup_s", "unit": "s"}]


def _run(seed=2 ** 33 + 3):
    res = run.execute({"chips": 1}, CELL, TINY, MIX, seed, 2.0, False,
                      {"end_to_end": E2E, "per_layer": []},
                      time.monotonic())
    res.pop("_run")
    return res, res.pop("_checks")


def test_sound_program_is_correct():
    res, checks = _run()
    assert res["correct"], checks
    assert checks[0]["value"] <= LIMIT / 10
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert res["metrics"]["gen_tokens_per_s"]["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_token_altered_where_produced(monkeypatch):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine._record

    def altered(self, req, tok, lp, finished):
        if len(req.tokens) == 4:      # each request's fifth token
            tok = (tok + TINY["vocab_size"] // 2) % TINY["vocab_size"]
        return orig(self, req, tok, lp, finished)

    monkeypatch.setattr(ServeEngine, "_record", altered)
    res, checks = _run()
    assert not res["correct"] and checks[0]["value"] > LIMIT


def test_step_that_leaves_the_cache_unchanged(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "paged_kv_write",
                        lambda k, v, *a, **kw: (k, v))
    res, checks = _run()
    assert not res["correct"] and checks[0]["value"] > LIMIT


def test_half_the_batch_left_out(monkeypatch):
    from repro.kernels import ops

    orig = ops.paged_attention_varlen

    def half(q, *a, **kw):
        out = orig(q, *a, **kw)
        keep = (np.arange(out.shape[0]) < out.shape[0] // 2)
        return out * keep[:, None, None, None].astype(out.dtype)

    monkeypatch.setattr(ops, "paged_attention_varlen", half)
    res, checks = _run()
    assert not res["correct"] and checks[0]["value"] > LIMIT


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "rollout.qwen2.5-0.5b", "--seed", str(2 ** 33), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out):
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except json.JSONDecodeError:
        return True


def test_run_refuses_a_host_without_a_tpu():
    p = _cli(harness.ROOT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_refuses_a_tree_of_only_the_benchmark(tmp_path):
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)
