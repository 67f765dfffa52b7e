"""A second architecture, ``TinyWindowedLM``, as a later configuration
would bring it: ``arch/<class>.py`` beside the harness, and nothing else
of the harness changed.  ``test_bench_layout.py`` copies it into a copy
of the harness; it is never part of the harness itself.

The block is Qwen2's without the bias on q, k and v, with ``head_dim``
given outright (not ``hidden_size / num_attention_heads``), and with a
window per layer: ``layer_types`` names each layer ``sliding_attention``
(each query attends its last ``sliding_window`` positions, itself among
them) or ``full_attention``, as the published configurations of such
models do.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

import reference
import weights
from work import F32


def windows(conf: Dict[str, Any]) -> List[Optional[int]]:
    """Each layer's window; None where it attends the whole context."""
    types = conf["layer_types"]
    if len(types) != conf["num_hidden_layers"]:
        raise SystemExit(f"{conf['name']}: {len(types)} layer_types for "
                         f"{conf['num_hidden_layers']} layers")
    return [conf["sliding_window"] if t == "sliding_attention" else None
            for t in types]


# -- weights ----------------------------------------------------------------


def shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    dh = conf["head_dim"]
    f = conf["intermediate_size"]
    n = conf["num_hidden_layers"]
    out = {
        "embed": (conf["vocab_size"], d),
        "final_norm": (d,),
        "layers": {
            "ln1": (n, d), "ln2": (n, d),
            "wq": (n, d, h * dh), "wk": (n, d, kv * dh),
            "wv": (n, d, kv * dh), "wo": (n, h * dh, d),
            "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
        },
    }
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = (d, conf["vocab_size"])
    return out


def scale(name: str, shape, conf: Dict[str, Any]) -> Optional[float]:
    if name in ("ln1", "ln2"):
        return None
    gain = {"wq": weights.QK_GAIN, "wk": weights.QK_GAIN}.get(name, 1.0)
    return gain * shape[-2] ** -0.5


# -- reference --------------------------------------------------------------


def layer_statics(conf: Dict[str, Any], i: int) -> Dict[str, Any]:
    return dict(heads=conf["num_attention_heads"],
                kv=conf["num_key_value_heads"], dh=conf["head_dim"],
                eps=float(conf["rms_norm_eps"]),
                theta=float(conf["rope_theta"]), window=windows(conf)[i])


@functools.partial(jax.jit, static_argnames=("heads", "kv", "dh", "eps",
                                             "theta", "window", "bf16x3"))
def layer(x, lw, *, heads, kv, dh, eps, theta, window, bf16x3):
    s = x.shape[0]
    mm = functools.partial(reference.mm, bf16x3=bf16x3)
    h = reference.rms(x, lw["ln1"], eps)
    q = mm("sd,de->se", h, lw["wq"]).reshape(s, heads, dh)
    k = mm("sd,de->se", h, lw["wk"]).reshape(s, kv, dh)
    v = mm("sd,de->se", h, lw["wv"]).reshape(s, kv, dh)
    q, k = reference.rope(q, theta), reference.rope(k, theta)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(dh)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    seen = ki <= qi
    if window is not None:
        seen = seen & (qi - ki < window)
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    o = mm("hqk,khd->qhd", probs, v).reshape(s, heads * dh)
    x = x + mm("se,ed->sd", o, lw["wo"])
    h = reference.rms(x, lw["ln2"], eps)
    gate = jax.nn.silu(mm("sd,df->sf", h, lw["w_gate"]))
    return x + mm("sf,fd->sd", gate * mm("sd,df->sf", h, lw["w_up"]),
                  lw["w_down"])


# -- the program ------------------------------------------------------------


def model_config(conf: Dict[str, Any]):
    """The program states a window pattern as one ``sliding_window`` and
    a full layer every ``global_every``: the one that gives each layer
    the configuration's window, or an error."""
    from repro.configs.base import ModelConfig

    if conf["hidden_act"] != "silu" or conf["attention_bias"]:
        raise SystemExit(f"{conf['name']}: not served here")
    want = windows(conf)
    full = [i for i, w in enumerate(want) if w is None]
    cfg = ModelConfig(
        name=conf["name"], arch_type="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=False, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), activation="swiglu",
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        sliding_window=conf["sliding_window"],
        global_every=full[0] + 1 if full else len(want) + 1,
        value_head=False, source=conf["source"])
    got = [cfg.window_for_layer(i) for i in range(cfg.n_layers)]
    if got != want:
        raise SystemExit(f"{conf['name']}: the program's window pattern "
                         f"{got} is not the configuration's {want}")
    return cfg


def params(conf: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """As Qwen2's bridge, without biases: the program's embedding scale
    is folded into the stored embedding and the tied readout's gain."""
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
            "attn": {name: {"w": lw[name]}
                     for name in ("wq", "wk", "wv", "wo")},
            "mlp": {"gate": {"w": lw["w_gate"]}, "up": {"w": lw["w_up"]},
                    "down": {"w": lw["w_down"]}},
        },
    }
    if not conf["tie_word_embeddings"]:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


# -- work counts ------------------------------------------------------------


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    return {"d": conf["hidden_size"], "h": conf["num_attention_heads"],
            "kv": conf["num_key_value_heads"], "dh": conf["head_dim"],
            "f": conf["intermediate_size"], "v": conf["vocab_size"],
            "layers": conf["num_hidden_layers"]}


def matmul_params(conf: Dict[str, Any]) -> int:
    x = dims(conf)
    per_layer = (x["d"] * x["dh"] * (x["h"] + 2 * x["kv"])
                 + x["h"] * x["dh"] * x["d"] + 3 * x["d"] * x["f"])
    return x["layers"] * per_layer + x["d"] * x["v"]


def _clipped(a: int, b: int, w: int) -> int:
    """``sum(min(c, w) for c in range(a, b + 1))``."""
    if b <= w:
        return (a + b) * (b - a + 1) // 2
    if a >= w:
        return w * (b - a + 1)
    return (a + w) * (w - a + 1) // 2 + w * (b - w)


def _per_layer(conf, slots, ctx_rows, kv_rows):
    """(row, context) pairs and K/V rows read, summed over the layers,
    each windowed layer clipped to its window."""
    if slots is None:
        raise SystemExit(f"{conf['name']}: windowed counts need the "
                         "rounds' slots")
    slots = list(slots)
    pairs = reads = 0
    for w in windows(conf):
        if w is None:
            pairs += ctx_rows
            reads += kv_rows
        else:
            pairs += sum(_clipped(live - n + 1, live, w)
                         for live, n in slots)
            reads += sum(min(live, w + n - 1) for live, n in slots)
    return pairs, reads


def attention_flops(conf: Dict[str, Any], ctx_rows: int, slots=None) -> int:
    x = dims(conf)
    pairs, _ = _per_layer(conf, slots, ctx_rows, 0)
    return 4 * x["h"] * x["dh"] * pairs


def paged_attention(conf: Dict[str, Any], rows: int, ctx_rows: int,
                    kv_rows: int, slots=None) -> Dict[str, int]:
    x = dims(conf)
    pairs, reads = _per_layer(conf, slots, ctx_rows, kv_rows)
    return {"flops": 4 * x["h"] * x["dh"] * pairs,
            "bytes": 2 * x["kv"] * x["dh"] * F32 * reads
            + 2 * x["h"] * x["dh"] * F32 * rows * x["layers"]}
