"""Greedy tokens of the engine are identical whether its paged kernels
run as Pallas (in interpret mode here) or as the jnp oracles.

This pins the kernels' TPU layouts end to end: the grouped-query varlen
block, decode as its ``row_len == 1`` shape, the one-call row write of a
whole ``[B, T]`` chunk and the lane-padded pool, across ragged chunked
prefill, sliding windows, and the NB-sharded pool on 8 host devices.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data.tokenizer import get_tokenizer
from repro.models.registry import build
from repro.serve import ServeEngine

TOK = get_tokenizer()
PROMPTS = [np.asarray(TOK.encode(p), np.int32)
           for p in ("12+345=?#", "998-76=?#", "7*8=?#", "(3+4)*5-6=?#")]
BUDGETS = [6, 9, 4, 7]
CONFIGS = {
    "ragged": ModelConfig(
        name="layout-ragged", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=TOK.vocab_size),
    # Window 4 < every prompt: pages fall out of the window mid-prompt.
    "windowed": ModelConfig(
        name="layout-windowed", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=TOK.vocab_size,
        sliding_window=4, global_every=2),
}


def _serve(cfg, mode, mesh=None):
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    eng = ServeEngine(
        bundle, params, num_blocks=64, block_size=4, max_batch=3,
        max_seq_len=48, decode_chunk=2, prefill_chunk=3, dispatch_budget=5,
        temperature=1e-4, seed=0, kernel_mode=mode, mesh=mesh)
    for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS)):
        eng.submit(p, b, request_id=f"r{i}")
    return {t.request_id: np.asarray(t.tokens)
            for t in eng.run(max_steps=400)}


def _assert_same(got, want):
    assert set(got) == set(want) == {f"r{i}" for i in range(len(PROMPTS))}
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])


@pytest.mark.parametrize("shape", sorted(CONFIGS))
def test_pallas_layouts_token_exact(shape):
    cfg = CONFIGS[shape]
    _assert_same(_serve(cfg, "pallas_interpret"), _serve(cfg, "reference"))


def sharded_case() -> None:
    """Pallas over an 8-shard pool == the single-device oracle path."""
    from repro.launch.mesh import make_debug_mesh

    cfg = CONFIGS["ragged"]
    _assert_same(_serve(cfg, "pallas_interpret", make_debug_mesh(data=8)),
                 _serve(cfg, "reference"))


def test_pallas_layouts_token_exact_sharded():
    """The NB-sharded pool on 8 forced host devices.  The device count
    is fixed when JAX starts, so the case runs in a fresh process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    tests = Path(__file__).resolve().parent
    env["PYTHONPATH"] = os.pathsep.join(
        [str(tests.parent / "src"), str(tests),
         env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, "-c",
         "import test_pallas_token_exact as t; t.sharded_case()"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
