"""The bridge to the system under test: its model configuration, its
parameter tree over the benchmark's weights, and its serve engine.

This module and the bridge half of each ``arch/<class>.py``
(``model_config``, ``params``) are the only code of the benchmark that
reaches the program; the architecture's part is called from here.
"""
from __future__ import annotations

import sys
from typing import Any, Dict

import harness
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
    """The program's ``ModelConfig`` for a configuration file, from its
    architecture's module."""
    _import_program()
    return harness.arch(conf).model_config(conf)


def params(conf: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree over the benchmark's weights (the
    same device buffers), from the architecture's module."""
    return harness.arch(conf).params(conf, w)


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
