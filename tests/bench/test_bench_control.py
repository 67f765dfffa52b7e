"""The control of each serving cell's check comes out not correct: the
reference put in the program's place at the step below the float32 at
``highest`` that the configurations state (three bfloat16 passes, the
TPU's ``high``), judged by the comparison ``run.py`` applies, at each
cell's published widths and depth, with the vocabulary cut to what a
test run holds."""
import numpy as np
import pytest

import harness
import reference
import run
import weights

VOCAB = 16384
CELLS = [c["name"] for c in harness.load_json(harness.SPEC_FILE)["workloads"]]


def _weights(conf, seed):
    """The configuration's weights, each layer made as ``weights.make``
    makes a one-layer model, so that a test run holds the 14B's layers
    without the peak of making them all in one call on the CPU."""
    one = dict(conf, num_hidden_layers=1)
    w = weights.make(one, seed)
    w["layers"] = {k: [v[0]] for k, v in w["layers"].items()}
    for i in range(1, conf["num_hidden_layers"]):
        more = weights.make(dict(one, vocab_size=8), seed * 64 + i)["layers"]
        for k, v in more.items():
            w["layers"][k].append(v[0])
        del more
    return w


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 2, 3])
def test_three_pass_control_fails_every_serving_limit(seed):
    r = np.random.default_rng(seed)
    prompt = r.integers(3, VOCAB, 200)
    served = r.integers(3, VOCAB, 200)
    for name in CELLS:
        cell = harness.load_cell(name)
        conf = dict(harness.load_config(cell["config"]), vocab_size=VOCAB)
        w = _weights(conf, seed)
        ref = reference.served_logprobs(w, conf, prompt, served)
        low = reference.served_logprobs(w, conf, prompt, served, bf16x3=True)
        del w
        gap = float(np.max(np.abs(low - ref)))
        checks, correct = run.judge(cell, gap, 0)
        assert not correct, (name, checks)
