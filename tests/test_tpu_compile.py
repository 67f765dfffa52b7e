"""The main-path kernels compile for a TPU v5e at qwen2.5-0.5b's widths.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: nothing runs, but whatever the chip's compiler
would refuse (a block shape off the (8, 128) tiling, an unaligned DMA
slice, too much fast memory) is refused here too.  This is the only file
that describes the chip; the topology is described inside a fixture, so
every pytest worker collects the same tests and only the worker given
this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.fused_logprob_pallas import logprobs_pallas
from repro.kernels.paged_attention_pallas import (
    paged_attention,
    paged_attention_varlen,
)
from repro.kernels.paged_kv_write_pallas import paged_kv_write
from repro.kernels.vtrace_pallas import vtrace_pallas

CFG = get_config("qwen2.5-0.5b")
L, H, KV, D = CFG.n_layers, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
LANES = 128                 # the pool's lane-padded row (init_paged_cache)
BS, NB, B, M = 8, 256, 8, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described chip's programs are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("t", [1, 16])
def test_paged_attention_varlen_compiles(one_chip, t):
    """The kernel reads the whole pool at a traced layer."""
    i32 = jnp.int32
    pool = (L, KV, NB, BS, LANES)
    _compile(
        lambda q, k, v, tb, rs, rl, ly: paged_attention_varlen(
            q, k, v, tb, rs, rl, layer=ly),
        _on(one_chip, (B, t, H, D)), _on(one_chip, pool),
        _on(one_chip, pool), _on(one_chip, (B, M), i32),
        _on(one_chip, (B,), i32), _on(one_chip, (B,), i32),
        _on(one_chip, (), i32))


def test_paged_attention_decode_compiles(one_chip):
    i32 = jnp.int32
    pool = (L, KV, NB, BS, LANES)
    _compile(
        lambda q, k, v, tb, cl, ly: paged_attention(
            q, k, v, tb, cl, layer=ly),
        _on(one_chip, (B, H, D)), _on(one_chip, pool),
        _on(one_chip, pool), _on(one_chip, (B, M), i32),
        _on(one_chip, (B,), i32), _on(one_chip, (), i32))


def test_paged_kv_write_compiles(one_chip):
    i32 = jnp.int32
    pool = (L, KV, NB, BS, LANES)
    _compile(
        lambda kp, vp, kr, vr, pi, off, act: paged_kv_write(
            kp, vp, kr, vr, pi, off, act, layer=L - 1),
        _on(one_chip, pool), _on(one_chip, pool),
        _on(one_chip, (B, KV, D)), _on(one_chip, (B, KV, D)),
        _on(one_chip, (B,), i32), _on(one_chip, (B,), i32),
        _on(one_chip, (B,), i32))


def test_logprobs_compiles_at_full_vocab(one_chip):
    n = 4 * 8               # a learner batch's completion tokens
    _compile(lambda x, t: logprobs_pallas(x, t),
             _on(one_chip, (n, CFG.vocab_size)),
             _on(one_chip, (n,), jnp.int32))


def test_vtrace_compiles(one_chip):
    b, t = 32, 128
    rows = _on(one_chip, (b, t))
    _compile(lambda lr, v, bv, r, d: vtrace_pallas(lr, v, bv, r, d),
             rows, rows, _on(one_chip, (b,)), rows, rows)


def test_sharded_varlen_dispatch_compiles(topo):
    """The NB-sharded pool's varlen dispatch on a 4-chip mesh: the
    kernel inside ``shard_map`` and a psum recombining the batch."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())
    pool = NamedSharding(mesh, P(None, None, "data", None, None))
    i32 = jnp.int32
    text = _compile(
        lambda q, k, v, tb, rs, rl, ly, ss: ops.paged_attention_varlen(
            q, k, v, tb, rs, rl, layer=ly, mode="pallas", mesh=mesh,
            slot_shard=ss),
        _on(rep, (B, 16, H, D)), _on(pool, (L, KV, NB, BS, LANES)),
        _on(pool, (L, KV, NB, BS, LANES)), _on(rep, (B, M), i32),
        _on(rep, (B,), i32), _on(rep, (B,), i32), _on(rep, (), i32),
        _on(rep, (B,), i32))
    assert "all-reduce" in text
