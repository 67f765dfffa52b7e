"""``work.py`` against arithmetic done by hand at both configurations,
and the weights it counts."""
import numpy as np
import pytest

import harness
import weights
import work

Q05 = harness.load_config("qwen2.5-0.5b")
Q14 = harness.load_config("qwen2.5-14b")


def test_matmul_params_by_hand():
    # 0.5B: q 896x896, k and v 896x128 each, o 896x896, mlp 3 x 896x4864,
    # ten layers, then the tied 896 x 151,936 readout.
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert layer == 14_909_440
    assert work.matmul_params(Q05) == 10 * layer + 896 * 151_936
    # 14B: q 5120x5120, k and v 5120x1024, o 5120x5120, mlp 3 x 5120x13824.
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 13824
    assert layer == 275_251_200
    assert work.matmul_params(Q14) == 4 * layer + 5120 * 152_064


def test_forward_flops_by_hand():
    # One decode row at context 100 on the 0.5B: 2 per weight, plus
    # 4 * 14 heads * 64 * 100 per layer.
    want = 2 * work.matmul_params(Q05) + 10 * 4 * 14 * 64 * 100
    assert work.forward_flops(Q05, rows=1, ctx_rows=100) == want


@pytest.mark.parametrize("conf,heads,kv,dh", [(Q05, 14, 2, 64),
                                              (Q14, 40, 8, 128)])
def test_paged_attention_by_hand(conf, heads, kv, dh):
    # 3 slots decoding at contexts 10, 20, 30: 3 rows, 60 row-context
    # pairs, 60 K and V rows read, per layer.
    got = work.paged_attention(conf, rows=3, ctx_rows=60, kv_rows=60)
    layers = conf["num_hidden_layers"]
    assert got["flops"] == layers * 4 * heads * dh * 60
    assert got["bytes"] == layers * (2 * kv * dh * 4 * 60
                                     + 2 * heads * dh * 4 * 3)


def test_roofline_names_its_bound():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    decode = work.roofline_seconds(1e9, 1e9, peaks)
    assert decode["bound"] == "bytes"
    assert decode["seconds"] == pytest.approx(1e9 / 819e9)
    assert work.roofline_seconds(1e15, 1e6, peaks)["bound"] == "compute"


def test_weights_match_the_configured_sizes():
    def count(tree):
        n = 0
        for v in tree.values():
            if isinstance(v, dict):
                n += count(v)
            else:
                p = 1
                for d in v:
                    p *= d
                n += p
        return n

    # 285.2M params for the 0.5B at 10 layers, 2,658M for the 14B at 4.
    assert count(weights.shapes(Q05)) == pytest.approx(285.2e6, rel=1e-3)
    assert count(weights.shapes(Q14)) == pytest.approx(2658e6, rel=1e-3)


@pytest.mark.parametrize("tied", [True, False])
def test_weights_readout_of_eos_is_zero(tied):
    conf = dict(Q05, num_hidden_layers=1, vocab_size=64,
                tie_word_embeddings=tied)
    w = weights.make(conf, 2 ** 33 + 5)
    head = w["embed"] if tied else w["lm_head"].T
    assert not np.any(np.asarray(head[weights.EOS_ID]))
    assert np.all(np.abs(np.asarray(head[weights.EOS_ID + 1])) > 0)
