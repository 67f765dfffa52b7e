"""Qwen2 (``Qwen2ForCausalLM``): what the benchmark needs of it.

Every layer is the same block: RMS norm, grouped-query attention over the
whole causal context with a bias on q, k and v and one rotary embedding,
then a SwiGLU MLP.  ``head_dim`` is ``hidden_size / num_attention_heads``.

The weights' leaves and scales, the reference's layer, the bridge to the
program's ``ModelConfig`` and parameter tree, and the work counts.  The
reference half imports nothing of the program; only ``model_config``
does.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

import reference
import weights
from work import F32


# -- weights ----------------------------------------------------------------


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


def scale(name: str, shape, conf: Dict[str, Any]) -> Optional[float]:
    """Biases away from zero so that each is exercised; projections at
    unit fan-in, q and k ``weights.QK_GAIN`` wider."""
    if name in ("ln1", "ln2"):
        return None
    if name in ("bq", "bk", "bv"):
        return 0.1
    gain = {"wq": weights.QK_GAIN, "wk": weights.QK_GAIN}.get(name, 1.0)
    return gain * shape[-2] ** -0.5  # fan-in of a [.., in, out] matrix


# -- reference --------------------------------------------------------------


def layer_statics(conf: Dict[str, Any], i: int) -> Dict[str, Any]:
    return dict(heads=conf["num_attention_heads"],
                kv=conf["num_key_value_heads"],
                eps=float(conf["rms_norm_eps"]),
                theta=float(conf["rope_theta"]))


@functools.partial(jax.jit, static_argnames=("heads", "kv", "eps",
                                             "theta", "bf16x3"))
def layer(x, lw, *, heads, kv, eps, theta, bf16x3):
    s, d = x.shape
    dh = d // heads
    mm = functools.partial(reference.mm, bf16x3=bf16x3)
    h = reference.rms(x, lw["ln1"], eps)
    q = (mm("sd,de->se", h, lw["wq"]) + lw["bq"]).reshape(s, heads, dh)
    k = (mm("sd,de->se", h, lw["wk"]) + lw["bk"]).reshape(s, kv, dh)
    v = (mm("sd,de->se", h, lw["wv"]) + lw["bv"]).reshape(s, kv, dh)
    q, k = reference.rope(q, theta), reference.rope(k, theta)
    group = heads // kv
    k = jnp.repeat(k, group, axis=1)          # head j reads kv j // group
    v = jnp.repeat(v, group, axis=1)
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = mm("hqk,khd->qhd", probs, v).reshape(s, heads * dh)
    x = x + mm("se,ed->sd", o, lw["wo"])
    h = reference.rms(x, lw["ln2"], eps)
    gate = jax.nn.silu(mm("sd,df->sf", h, lw["w_gate"]))
    x = x + mm("sf,fd->sd", gate * mm("sd,df->sf", h, lw["w_up"]),
               lw["w_down"])
    return x


# -- the program ------------------------------------------------------------


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig``, with every published number the
    program has a setting for."""
    from repro.configs.base import ModelConfig

    if conf["hidden_act"] != "silu":
        raise SystemExit(f"{conf['name']}: hidden_act "
                         f"{conf['hidden_act']!r} is not served here")
    return ModelConfig(
        name=conf["name"], arch_type="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=True, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), activation="swiglu",
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        value_head=False, source=conf["source"])


def params(conf: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree over the same device buffers.

    The program multiplies token embeddings by ``sqrt(hidden_size)``
    (``repro.models.transformer._embed_inputs``), which Qwen2 does not.
    The stored ``embed`` is ``E / sqrt(hidden_size)``, so its lookup
    gives ``E`` exactly as published; under a tied readout the final
    norm's gain carries the same factor, so the logits are the published
    ones too."""
    lw = w["layers"]
    final = w["final_norm"]
    if conf["tie_word_embeddings"]:
        final = final * jnp.float32(math.sqrt(conf["hidden_size"]))
    p = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": final},
        "layers": {
            "norm1": {"scale": lw["ln1"]},
            "norm2": {"scale": lw["ln2"]},
            "attn": {
                "wq": {"w": lw["wq"], "b": lw["bq"]},
                "wk": {"w": lw["wk"], "b": lw["bk"]},
                "wv": {"w": lw["wv"], "b": lw["bv"]},
                "wo": {"w": lw["wo"]},
            },
            "mlp": {
                "gate": {"w": lw["w_gate"]},
                "up": {"w": lw["w_up"]},
                "down": {"w": lw["w_down"]},
            },
        },
    }
    if not conf["tie_word_embeddings"]:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


# -- work counts ------------------------------------------------------------


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"d": d, "h": h, "kv": conf["num_key_value_heads"],
            "dh": d // h, "f": conf["intermediate_size"],
            "v": conf["vocab_size"], "layers": conf["num_hidden_layers"]}


def matmul_params(conf: Dict[str, Any]) -> int:
    """Weights that each token multiplies: every projection of every
    layer and the readout (biases and norms are not matmuls)."""
    x = dims(conf)
    per_layer = (x["d"] * x["dh"] * (x["h"] + 2 * x["kv"])   # q, k, v
                 + x["h"] * x["dh"] * x["d"]                  # o
                 + 3 * x["d"] * x["f"])                       # gate, up, down
    return x["layers"] * per_layer + x["d"] * x["v"]


def attention_flops(conf: Dict[str, Any], ctx_rows: int, slots=None) -> int:
    """Scores and weighted sum over all layers: 2 * 2 * heads * head_dim
    per (query row, context row) pair; every layer sees the whole
    context, so the sum ``ctx_rows`` is enough."""
    x = dims(conf)
    return 4 * x["h"] * x["dh"] * ctx_rows * x["layers"]


def paged_attention(conf: Dict[str, Any], rows: int, ctx_rows: int,
                    kv_rows: int, slots=None) -> Dict[str, int]:
    """One paged-attention call per layer, summed over the layers: query
    ``rows`` attending ``ctx_rows`` (row, context) pairs, reading each
    slot's ``kv_rows`` live K and V rows once, writing one output row per
    query row."""
    x = dims(conf)
    flops = attention_flops(conf, ctx_rows)
    kv_bytes = 2 * x["kv"] * x["dh"] * F32 * kv_rows
    qo_bytes = 2 * x["h"] * x["dh"] * F32 * rows
    return {"flops": flops,
            "bytes": (kv_bytes + qo_bytes) * x["layers"]}
