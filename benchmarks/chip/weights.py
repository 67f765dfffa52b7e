"""The benchmark's own weights, made on the device from the seed.

Layout is the published Qwen2 one (``Qwen2ForCausalLM``), stacked over
layers, with one storage convention: the token embedding is kept as
``embed = E / sqrt(hidden_size)``, where ``E`` is the published
embedding.  The reference multiplies it back.  Nothing here imports the
program; ``program.py`` hands the same buffers to it.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    dh = d // h
    f = conf["intermediate_size"]
    v = conf["vocab_size"]
    n = conf["num_hidden_layers"]
    out = {
        "embed": (v, d),
        "final_norm": (d,),
        "layers": {
            "ln1": (n, d), "ln2": (n, d),
            "wq": (n, d, h * dh), "bq": (n, h * dh),
            "wk": (n, d, kv * dh), "bk": (n, kv * dh),
            "wv": (n, d, kv * dh), "bv": (n, kv * dh),
            "wo": (n, h * dh, d),
            "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
        },
    }
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = (d, v)
    return out


# Query and key projections QK_GAIN times wider than unit fan-in make
# scores of standard deviation QK_GAIN**2, so each head attends to a few
# positions and its output is of the same order as the embedding: the
# cache and attention then decide the logits, as they do in a trained
# model, and a fault in either shows in the served tokens.
QK_GAIN = 2.0
# The embedding is small beside what the layers add, and the tied
# readout's gain makes logits of standard deviation LOGIT_STD: with an
# embedding of the layers' own size, the tied readout would return the
# input token at every position and greedy decoding would repeat it,
# whatever attention computed.
EMBED_STD = 0.05
LOGIT_STD = 2.0
# The id the program stops a sequence at (its tokenizer's pad, bos, eos
# are 0, 1, 2).  Its readout row is zero, so its logit is 0 where the
# others spread with LOGIT_STD: sampled about once in 1.1 million
# tokens rather than once in 152 thousand.  An early stop takes a
# completion's remaining tokens out of the window, which would make the
# work differ from seed to seed.
EOS_ID = 2


def _std(name: str, shape, conf) -> float:
    """Scale of each leaf: unit-variance activations through every
    layer, logits of order one, biases and norm gains away from their
    trivial values so that each is exercised."""
    d = conf["hidden_size"]
    if name == "embed":
        return EMBED_STD * d ** -0.5    # E itself is N(0, EMBED_STD**2)
    if name in ("bq", "bk", "bv"):
        return 0.1
    gain = {"wq": QK_GAIN, "wk": QK_GAIN, "lm_head": LOGIT_STD}.get(name, 1.0)
    return gain * shape[-2] ** -0.5  # fan-in of a [.., in, out] matrix


def make(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights in one jitted call on the default device, float32."""
    tree = shapes(conf)
    flat, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(p[-1].key) for p, _ in flat]
    leaf_shapes = [s for _, s in flat]
    d = conf["hidden_size"]
    tied = conf["tie_word_embeddings"]

    def gen(key):
        keys = jax.random.split(key, len(leaf_shapes))
        out = []
        for k, name, shape in zip(keys, names, leaf_shapes):
            z = jax.random.normal(k, shape, jnp.float32)
            if name in ("ln1", "ln2", "final_norm"):
                w = 1.0 + 0.1 * z
                if name == "final_norm" and tied:
                    # Rows of E have norm EMBED_STD * sqrt(hidden_size).
                    w = w * (LOGIT_STD / (EMBED_STD * math.sqrt(d)))
            else:
                w = z * _std(name, shape, conf)
            if name == "lm_head":
                w = w.at[:, EOS_ID].set(0.0)
            elif name == "embed" and tied:
                w = w.at[EOS_ID].set(0.0)
            out.append(w)
        return out

    leaves = jax.jit(gen)(key_from_seed(seed))
    return jax.tree.unflatten(treedef, leaves)


def nbytes(tree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)))
