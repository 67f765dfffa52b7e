"""Operations and bytes that the algorithm needs, from shapes alone.

Counted at ``head_dim``, never at the 128 lanes the pool pads a row to,
and over live context only: a layout that moves more bytes, or a grid
that visits dead pages, reads below 100% and cannot push a share over.
"""
from __future__ import annotations

from typing import Any, Dict

F32 = 4


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


def attention_flops(conf: Dict[str, Any], ctx_rows: int) -> int:
    """Scores and weighted sum over all layers: 2 * 2 * heads * head_dim
    per (query row, context row) pair; ``ctx_rows`` sums each row's
    context."""
    x = dims(conf)
    return 4 * x["h"] * x["dh"] * ctx_rows * x["layers"]


def forward_flops(conf: Dict[str, Any], rows: int, ctx_rows: int) -> int:
    """Model operations to run ``rows`` tokens forward (2 per weight per
    token), attention included."""
    return 2 * matmul_params(conf) * rows + attention_flops(conf, ctx_rows)


def paged_attention(conf: Dict[str, Any], rows: int, ctx_rows: int,
                    kv_rows: int) -> Dict[str, int]:
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


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_b),
            "bound": "compute" if t_c >= t_b else "bytes"}
