"""The bridge to the system under test: its model configuration, its
parameter tree over the benchmark's weights, and its serve engine.

This is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import math
import sys
from typing import Any, Dict

from harness import ROOT

SRC = ROOT / "src"


def _import_program():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program is not here: {SRC / 'repro'} "
                         "missing")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def enable_compile_cache() -> None:
    """The program's persistent compile cache, inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names another."""
    _import_program()
    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, with every
    published number the program has a setting for."""
    _import_program()
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
    import jax.numpy as jnp

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


def page_size() -> int:
    """The engine's default page size, which cells leave as it is."""
    _import_program()
    import inspect

    from repro.serve import ServeEngine

    return int(inspect.signature(ServeEngine).parameters["block_size"]
               .default)


def engine(conf: Dict[str, Any], w: Dict[str, Any], cell: Dict[str, Any],
           seed: int, **kw):
    """The serve engine as a user deploying it would set it up: batch,
    sequence length, pool in tokens, the per-round token budget and the
    sampling temperature.
    Page size, decode chunk and prefix cache stay at the program's
    defaults."""
    _import_program()
    from repro.models.registry import build
    from repro.serve import ServeEngine

    e = cell["engine"]
    bs = page_size()
    return ServeEngine(
        build(model_config(conf)), params(conf, w),
        num_blocks=-(-e["pool_tokens"] // bs), max_batch=e["max_batch"],
        max_seq_len=e["max_seq_len"], prefill_chunk=e["prefill_chunk"],
        dispatch_budget=e["dispatch_budget"], temperature=e["temperature"],
        seed=seed, **kw)
