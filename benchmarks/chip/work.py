"""Operations and bytes that the algorithm needs, from shapes alone.

Counted at ``head_dim``, never at the 128 lanes the pool pads a row to,
and over live context only: a layout that moves more bytes, or a grid
that visits dead pages, reads below 100% and cannot push a share over.
The counts are the architecture's (``arch/<class>.py``); this module
finds them by the configuration.  ``slots`` is every ``(live length,
rows computed)`` pair of the window's rounds (``harness.Round.slots``),
which a count clipped to a window per layer reads; one whose layers all
see the whole context reads the sums alone.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

import harness

F32 = 4
Slots = Optional[Iterable[Tuple[int, int]]]


def count(conf: Dict[str, Any], name: str, **work) -> Dict[str, int]:
    """The architecture's count ``name`` (``flops`` and ``bytes``) for
    the window's ``rows``, ``ctx_rows``, ``kv_rows`` and ``slots``."""
    fn = getattr(harness.arch(conf), name, None)
    if fn is None:
        raise SystemExit(f"{conf['architectures'][0]} has no count "
                         f"{name!r}")
    return fn(conf, **work)


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    return harness.arch(conf).dims(conf)


def matmul_params(conf: Dict[str, Any]) -> int:
    """Weights that each token multiplies: for experts, only those a
    token is routed to (biases and norms are not matmuls)."""
    return harness.arch(conf).matmul_params(conf)


def attention_flops(conf: Dict[str, Any], ctx_rows: int,
                    slots: Slots = None) -> int:
    """Scores and weighted sum over all layers, for the (query row,
    context row) pairs that each layer attends."""
    return harness.arch(conf).attention_flops(conf, ctx_rows, slots)


def forward_flops(conf: Dict[str, Any], rows: int, ctx_rows: int,
                  slots: Slots = None) -> int:
    """Model operations to run ``rows`` tokens forward (2 per weight per
    token), attention included."""
    return (2 * matmul_params(conf) * rows
            + attention_flops(conf, ctx_rows, slots))


def paged_attention(conf: Dict[str, Any], rows: int, ctx_rows: int,
                    kv_rows: int, slots: Slots = None) -> Dict[str, int]:
    """One paged-attention call per layer, summed over the layers."""
    return count(conf, "paged_attention", rows=rows, ctx_rows=ctx_rows,
                 kv_rows=kv_rows, slots=slots)


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_b),
            "bound": "compute" if t_c >= t_b else "bytes"}
