"""Each configuration's architecture is found by its published class, and
Qwen2's code, moved into ``arch/Qwen2ForCausalLM.py``, gives what the
harness gave before the move, bit for bit.

``fixtures/qwen2_golden.json`` was recorded from the harness before its
Qwen2 code left ``weights.py``, ``reference.py``, ``program.py`` and
``work.py``: the SHA-256 of every weight leaf that ``weights.make`` gives
the tiny configuration (``conf``, tied and untied) at a seed past 32 bits,
the reference's float32 logits (and the control's) of one sequence of 300
ids at six rows, as hex, and the integer work counts of both published
configurations at fixed sums.
"""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import reference
import weights
import work

GOLD = json.loads((Path(__file__).parent / "fixtures" / "qwen2_golden.json")
                  .read_text())
CONF = dict(GOLD["conf"], architectures=["Qwen2ForCausalLM"])


def _conf(key):
    return dict(CONF, tie_word_embeddings=key.split(".")[0] == "tied")


@pytest.mark.parametrize("key", ["tied", "untied"])
def test_weights_are_bit_equal(key):
    w = weights.make(_conf(key), GOLD["seed"])
    got = {jax.tree_util.keystr(p): {
        "shape": list(x.shape), "dtype": str(x.dtype),
        "sha256": hashlib.sha256(np.asarray(x).tobytes()).hexdigest()}
        for p, x in jax.tree.flatten_with_path(w)[0]}
    assert got == GOLD["weights"][key]


@pytest.mark.parametrize("key", ["tied", "untied", "tied.control",
                                 "untied.control"])
def test_reference_logits_are_bit_equal(key):
    conf = _conf(key)
    w = weights.make(conf, GOLD["seed"])
    got = reference.logits_at(w, conf, np.asarray(GOLD["ids"], np.int32),
                              np.asarray(GOLD["rows"], np.int32),
                              bf16x3=key.endswith(".control"))
    want = np.frombuffer(bytes.fromhex(GOLD["logits"][key]), np.float32)
    got = np.asarray(got, np.float32)
    assert got.shape == (len(GOLD["rows"]), conf["vocab_size"])
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("name", sorted(GOLD["counts"]))
def test_work_counts_are_equal(name):
    conf = harness.load_config(name)
    sums = GOLD["sums"]
    got = {"matmul_params": work.matmul_params(conf),
           "attention_flops": work.attention_flops(conf, sums["ctx_rows"]),
           "forward_flops": work.forward_flops(conf, sums["rows"],
                                               sums["ctx_rows"]),
           "paged_attention": work.paged_attention(conf, **sums)}
    assert got == GOLD["counts"][name]
    assert work.count(conf, "paged_attention", slots=None,
                      **sums) == got["paged_attention"]


def test_a_class_without_its_module_is_refused():
    with pytest.raises(SystemExit, match=r"arch/NoSuchForCausalLM\.py"):
        harness.arch(dict(CONF, architectures=["NoSuchForCausalLM"]))
    no_class = {k: v for k, v in CONF.items() if k != "architectures"}
    with pytest.raises(SystemExit, match="architectures"):
        weights.make(no_class, 1)
    with pytest.raises(SystemExit, match="no_such_count"):
        work.count(CONF, "no_such_count", rows=1, ctx_rows=1, kv_rows=1,
                   slots=None)


def test_every_configuration_names_a_class_with_its_module():
    for entry in harness.load_json(harness.SPEC_FILE)["configs"]:
        conf = harness.load_config(entry["name"])
        mod = harness.arch(conf)
        for name in ("shapes", "scale", "layer_statics", "layer",
                     "model_config", "params", "dims", "matmul_params",
                     "attention_flops", "paged_attention"):
            assert callable(getattr(mod, name)), (entry["name"], name)
        assert harness.arch(conf) is mod      # run once, compiled once
