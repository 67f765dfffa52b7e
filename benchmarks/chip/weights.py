"""The benchmark's own weights, made on the device from the seed.

The parameter tree and each layer leaf's scale are the architecture's
(``shapes`` and ``scale`` of ``arch/<class>.py``), stacked over layers;
the embedding, the final norm and the readout are made here for every
architecture, with one storage convention: the token embedding is kept
as ``embed = E / sqrt(hidden_size)``, where ``E`` is the published
embedding.  The reference multiplies it back.  Nothing here imports the
program; ``program.py`` hands the same buffers to it.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import harness


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """Every leaf's shape, from the architecture's module."""
    return harness.arch(conf).shapes(conf)


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


def _std(arch, name: str, shape, conf) -> Optional[float]:
    """Scale of each leaf, or None for a norm's gain: unit-variance
    activations through every layer, logits of order one, biases and
    norm gains away from their trivial values so that each is
    exercised.  A layer's leaves are the architecture's."""
    if name == "embed":    # E itself is N(0, EMBED_STD**2)
        return EMBED_STD * conf["hidden_size"] ** -0.5
    if name == "lm_head":  # fan-in of the [d, vocab] readout
        return LOGIT_STD * shape[-2] ** -0.5
    if name == "final_norm":
        return None
    return arch.scale(name, shape, conf)


def make(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights in one jitted call on the default device, float32."""
    arch = harness.arch(conf)
    flat, treedef = jax.tree.flatten_with_path(
        arch.shapes(conf), is_leaf=lambda x: isinstance(x, tuple))
    names = [str(p[-1].key) for p, _ in flat]
    leaf_shapes = [s for _, s in flat]
    stds = [_std(arch, n, s, conf) for n, s in zip(names, leaf_shapes)]
    d = conf["hidden_size"]
    tied = conf["tie_word_embeddings"]

    def gen(key):
        keys = jax.random.split(key, len(leaf_shapes))
        out = []
        for k, name, shape, std in zip(keys, names, leaf_shapes, stds):
            z = jax.random.normal(k, shape, jnp.float32)
            if std is None:
                w = 1.0 + 0.1 * z
                if name == "final_norm" and tied:
                    # Rows of E have norm EMBED_STD * sqrt(hidden_size).
                    w = w * (LOGIT_STD / (EMBED_STD * math.sqrt(d)))
            else:
                w = z * std
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
