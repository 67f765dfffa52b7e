"""Architecture registry: the 10 assigned configs + the paper's own model.

``get_config(name)`` returns the FULL published config;
``reduced_config(name)`` returns the CPU-smoke variant of the same family
(<= 2 layers, d_model <= 512, <= 4 experts) used by tests and the
runnable examples.  The launchers' ``--arch`` goes through
``launch_config``: ``qwen2.5-0.5b`` is the published model and
``qwen2.5-0.5b-reduced`` its CPU-smoke reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs.base import (
    INPUT_SHAPES,
    InputShape,
    ModelConfig,
    MoEConfig,
    SSMConfig,
)
from repro.configs.qwen2_5_14b import CONFIG as _qwen14b
from repro.configs.paligemma_3b import CONFIG as _paligemma
from repro.configs.gemma3_12b import CONFIG as _gemma3
from repro.configs.hymba_1_5b import CONFIG as _hymba
from repro.configs.granite_20b import CONFIG as _granite
from repro.configs.codeqwen1_5_7b import CONFIG as _codeqwen
from repro.configs.whisper_large_v3 import CONFIG as _whisper
from repro.configs.kimi_k2_1t_a32b import CONFIG as _kimi
from repro.configs.llama4_scout_17b_a16e import CONFIG as _llama4
from repro.configs.rwkv6_1_6b import CONFIG as _rwkv6
from repro.configs.qwen2_5_0_5b import CONFIG as _qwen05b

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in [
        _qwen14b, _paligemma, _gemma3, _hymba, _granite, _codeqwen,
        _whisper, _kimi, _llama4, _rwkv6,
    ]
}
# The paper's own model (not in the assigned pool, used by examples).
EXTRA_ARCHS: Dict[str, ModelConfig] = {_qwen05b.name: _qwen05b}


def list_archs() -> List[str]:
    return list(ARCHS)


def get_config(name: str) -> ModelConfig:
    if name in ARCHS:
        return ARCHS[name]
    if name in EXTRA_ARCHS:
        return EXTRA_ARCHS[name]
    raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")


def reduced_config(name: str, vocab: int = 512) -> ModelConfig:
    """Family-preserving reduction: 2 layers, d_model<=256, <=4 experts.

    Keeps every structural feature live (GQA grouping, QKV bias, windows,
    MoE top-k + shared experts, SSM state size, prefix-LM, enc-dec) so the
    smoke test exercises the same code paths as the full config.
    """
    cfg = get_config(name)
    group = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    if cfg.attn_free:
        heads, kv = 2, 2
        d_model = 128  # rwkv requires d_model % 64 == 0
    else:
        heads = min(group, 8) if group > 1 else 2
        kv = max(1, heads // min(group, heads))
        d_model = 256
    changes = dict(
        n_layers=2,
        d_model=d_model,
        n_heads=heads,
        n_kv_heads=kv,
        d_head=64,
        d_ff=256,
        vocab_size=vocab,
    )
    if cfg.moe is not None:
        changes["moe"] = MoEConfig(
            n_experts=4,
            top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=128,
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            capacity_factor=2.0,
        )
    if cfg.ssm is not None:
        changes["ssm"] = SSMConfig(
            state_dim=cfg.ssm.state_dim, conv_width=cfg.ssm.conv_width,
            expand=cfg.ssm.expand,
        )
    if cfg.sliding_window is not None:
        changes["sliding_window"] = 16
        changes["global_every"] = 2
    if cfg.vision_prefix_len > 0:
        changes["vision_prefix_len"] = 8
    if cfg.encoder_layers > 0:
        changes["encoder_layers"] = 2
        changes["encoder_seq_len"] = 16
    return cfg.replace(name=f"{cfg.name}-reduced", **changes)


REDUCED_SUFFIX = "-reduced"


def launch_config(name: str, vocab: int) -> ModelConfig:
    """The config a launcher's ``--arch name`` builds.

    ``<arch>-reduced`` is :func:`reduced_config` with the tokenizer's
    ``vocab``; any other name is the published config, whose vocabulary
    must hold the tokenizer's ``vocab`` ids (they are its first ids).
    """
    if name.endswith(REDUCED_SUFFIX):
        return reduced_config(name[:-len(REDUCED_SUFFIX)], vocab=vocab)
    cfg = get_config(name)
    if cfg.vocab_size < vocab:
        raise ValueError(
            f"{name}: vocabulary {cfg.vocab_size} cannot hold the "
            f"tokenizer's {vocab} ids")
    return cfg


__all__ = [
    "ARCHS",
    "EXTRA_ARCHS",
    "INPUT_SHAPES",
    "InputShape",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "list_archs",
    "get_config",
    "launch_config",
    "reduced_config",
]
