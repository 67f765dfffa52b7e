"""Plain forward pass of a causal language model, in jax.numpy.

No kernel, cache or batching: one sequence, every position, layer by
layer, so that a long sequence of the widest model fits beside its
weights.  The layer is the architecture's (``layer`` and
``layer_statics`` of ``arch/<class>.py``), written with ``mm``, ``rms``
and ``rope`` from here; the embedding, the readout and the loop are
shared.  Every product is float32 at ``highest`` precision.  The
control (``bf16x3=True``) takes each product in three bfloat16 passes
(``hi*hi + hi*lo + lo*hi`` with float32 sums), which is what the TPU's
``high`` precision does, written out so that it runs the same on any
backend.  Imports nothing of the program, and reads only the benchmark's
weights (``weights.py``; the stored embedding is the published one over
``sqrt(hidden_size)``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import harness

# Sequences are padded to a multiple of this, so a handful of programs
# serve every length; causal attention keeps the padding out of the
# positions that are read.
BUCKET = 256


def _split(a):
    """``a`` as bfloat16 high and low halves.  ``reduce_precision`` rounds
    where a cast may be kept at float32 by the compiler (XLA's excess
    precision on TPU), which would leave the low half zero."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def mm(spec, a, b, bf16x3):
    """``einsum(spec, a, b)``: float32 at ``highest``, or three bfloat16
    passes with float32 sums."""
    if not bf16x3:
        return jnp.einsum(spec, a, b, precision="highest")
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = functools.partial(jnp.einsum, spec,
                            preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """Rotate-half rotary embedding at positions 0..S-1; x [S, N, dh]."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x32 = x.astype(jnp.float32)
    half = dh // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "bf16x3"))
def _head(x, rows, final, head, *, eps, tied, bf16x3):
    """Logits at ``rows`` of the last hidden states."""
    d = x.shape[-1]
    h = rms(x[rows], final, eps)
    if tied:   # E^T = sqrt(d) * embed^T
        return mm("sd,vd->sv", h, head, bf16x3) * math.sqrt(d)
    return mm("sd,dv->sv", h, head, bf16x3)


@jax.jit
def _embed(table, ids):
    return table[ids] * math.sqrt(table.shape[-1])


def logits_at(w: Dict[str, Any], conf: Dict[str, Any], ids: np.ndarray,
              rows: np.ndarray, bf16x3: bool = False) -> jax.Array:
    """Logits ``[len(rows), vocab]`` of the sequence ``ids`` at ``rows``."""
    s = len(ids)
    pad = -(-s // BUCKET) * BUCKET
    ids_p = np.zeros((pad,), np.int32)
    ids_p[:s] = ids
    n_rows = -(-len(rows) // BUCKET) * BUCKET
    rows_p = np.full((n_rows,), rows[-1], np.int32)
    rows_p[:len(rows)] = rows
    arch = harness.arch(conf)
    x = _embed(w["embed"], jnp.asarray(ids_p))
    for i in range(conf["num_hidden_layers"]):
        x = arch.layer(x, {k: v[i] for k, v in w["layers"].items()},
                       bf16x3=bf16x3, **arch.layer_statics(conf, i))
    tied = conf["tie_word_embeddings"]
    out = _head(x, jnp.asarray(rows_p), w["final_norm"],
                w["embed"] if tied else w["lm_head"],
                eps=float(conf["rms_norm_eps"]), tied=tied, bf16x3=bf16x3)
    return out[:len(rows)]


@jax.jit
def _logprobs(logits, toks):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, toks[:, None], -1)[:, 0]


def served_logprobs(w, conf, prompt: np.ndarray, served: Sequence[int],
                    bf16x3: bool = False) -> np.ndarray:
    """Log-probability of each served token at the position that
    produced it, given the prompt and the tokens served before it."""
    served = np.asarray(served, np.int32)
    ids = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    rows = np.arange(len(prompt) - 1, len(ids), dtype=np.int32)
    logits = logits_at(w, conf, ids, rows, bf16x3=bf16x3)
    return np.asarray(_logprobs(logits, jnp.asarray(served)))
