"""Mesh-sharded serve path: shard_map kernel parity vs the single-device
oracles, in-place pool updates (buffer donation) under shard_map, the
placement-aware scheduler, and full-engine token-exactness — greedy,
speculative and preemption-churned — against the single-device engine.

Needs a multi-device host: CI runs this suite under
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``
(the sharded-serve job); on a 1-device host everything here skips, so
tier-1 collection is unaffected.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data.tokenizer import get_tokenizer
from repro.distributed.sharding import paged_pool_sharding, replicated
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.launch.mesh import make_debug_mesh, parse_mesh_spec
from repro.models.registry import build
from repro.models.transformer import write_prefill_batch_to_pages
from repro.runtime import PolicyStore
from repro.serve import ServeEngine, ShardedBlockAllocator, make_allocator

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="sharded-serve suite needs >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

TOK = get_tokenizer()
CFG = ModelConfig(
    name="sharded-test", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=TOK.vocab_size,
)
BUNDLE = build(CFG)
PARAMS = BUNDLE.init(jax.random.PRNGKey(0))
PROMPTS = [np.asarray(TOK.encode(p), np.int32)
           for p in ("1+2=?#", "3*4=?#", "10-7=?#", "6/2=?#")]
BUDGETS = [5, 9, 13, 7]


def _mesh(data=2):
    return make_debug_mesh(data=data)


# --- shard_map kernel parity vs the single-device oracles -------------------


POOL_LAYERS = 3


def _ragged_sharded_case(seed, *, shards, per_shard, bs, b, kv, d, h,
                         t=1):
    """A ragged batch whose per-slot pages cross page (and shard-table)
    boundaries: each slot lives on one shard, owns a random *permuted*
    set of that shard's pages, and has its own context length (0 = an
    inactive slot — included on purpose).  The pools are whole
    ``[L, KV, NB, BS, D]`` pools of ``POOL_LAYERS`` distinct layers."""
    rng = np.random.default_rng(seed)
    nb = shards * per_shard
    m = per_shard                                    # table width
    shape = (POOL_LAYERS, kv, nb, bs, d)
    k_pages = rng.normal(size=shape).astype(np.float32)
    v_pages = rng.normal(size=shape).astype(np.float32)
    local_tables = np.stack(
        [rng.permutation(per_shard)[:m] for _ in range(b)]).astype(np.int32)
    slot_shard = (rng.permutation(b) % shards).astype(np.int32)
    lens = rng.integers(0, m * bs + 1, size=(b,)).astype(np.int32)
    lens[0] = 0                                       # pinned inactive slot
    lens[1] = per_shard * bs                          # full table, crosses
    # Global ids: shard-local id + shard offset (the single-device view).
    global_tables = local_tables + slot_shard[:, None] * per_shard
    q = rng.normal(size=(b, t, h, d)).astype(np.float32)
    return (k_pages, v_pages, local_tables, global_tables, slot_shard,
            lens, q)


@pytest.mark.parametrize("mode", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("window", [None, 5])
def test_sharded_paged_attention_parity(mode, window):
    mesh = _mesh(2)
    (k_pages, v_pages, local_t, global_t, ss, lens, q) = \
        _ragged_sharded_case(0, shards=2, per_shard=4, bs=4, b=5, kv=2,
                             d=8, h=4)
    q1 = q[:, 0]
    for layer in range(POOL_LAYERS):
        want = kops.paged_attention(
            q1, k_pages, v_pages, global_t, lens, layer=layer,
            window=window, mode=mode)
        got = kops.paged_attention(
            q1, k_pages, v_pages, local_t, lens, layer=layer,
            window=window, mode=mode, mesh=mesh,
            slot_shard=jnp.asarray(ss))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("mode", ["reference", "pallas_interpret"])
def test_sharded_paged_attention_multi_parity(mode):
    mesh = _mesh(2)
    (k_pages, v_pages, local_t, global_t, ss, lens, q) = \
        _ragged_sharded_case(1, shards=2, per_shard=4, bs=4, b=4, kv=2,
                             d=8, h=4, t=3)
    lens = np.maximum(lens, 0)
    lens[lens > 0] = np.maximum(lens[lens > 0], 3)   # room for the chunk
    for layer in range(POOL_LAYERS):
        want = kops.paged_attention_multi(
            q, k_pages, v_pages, global_t, lens, layer=layer, mode=mode)
        got = kops.paged_attention_multi(
            q, k_pages, v_pages, local_t, lens, layer=layer, mode=mode,
            mesh=mesh, slot_shard=jnp.asarray(ss))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("mode", ["reference", "pallas_interpret"])
def test_sharded_paged_kv_write_parity(mode):
    """Row writes land on the right page of the right shard — including
    a masked (inactive) slot that must write nothing anywhere."""
    mesh = _mesh(2)
    rng = np.random.default_rng(2)
    L, kv, per_shard, bs, d, b = 2, 2, 4, 4, 8, 5
    nb = 2 * per_shard
    pool = rng.normal(size=(L, kv, nb, bs, d)).astype(np.float32)
    k_rows = rng.normal(size=(b, kv, d)).astype(np.float32)
    v_rows = rng.normal(size=(b, kv, d)).astype(np.float32)
    local_idx = rng.integers(0, per_shard, size=(b,)).astype(np.int32)
    ss = (np.arange(b) % 2).astype(np.int32)
    offset = rng.integers(0, bs, size=(b,)).astype(np.int32)
    active = np.array([True, True, False, True, True])
    global_idx = local_idx + ss * per_shard
    want_k, want_v = kops.paged_kv_write(
        pool[0:1] * 0 + pool, pool.copy(), k_rows, v_rows, global_idx,
        offset, active, layer=1, mode=mode)
    got_k, got_v = kops.paged_kv_write(
        jnp.asarray(pool), jnp.asarray(pool), k_rows, v_rows, local_idx,
        offset, active, layer=1, mode=mode,
        mesh=mesh, slot_shard=jnp.asarray(ss))
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               atol=1e-6)


def test_sharded_kv_write_donation_in_place():
    """The aliased in-place pool update survives sharding: donated
    NB-sharded pools are updated buffer-in-place on every shard (the
    acceptance bar for shard_map not re-materializing the pool)."""
    mesh = _mesh(2)
    L, kv, nb, bs, d, b = 2, 2, 8, 4, 8, 3
    sharding = paged_pool_sharding(mesh)
    k_pool = jax.device_put(jnp.zeros((L, kv, nb, bs, d)), sharding)
    v_pool = jax.device_put(jnp.zeros((L, kv, nb, bs, d)), sharding)
    k_ptrs = [s.data.unsafe_buffer_pointer()
              for s in k_pool.addressable_shards]

    fn = jax.jit(
        lambda kp, vp, kr, vr, pi, off, act, ss: kops.paged_kv_write(
            kp, vp, kr, vr, pi, off, act, layer=0,
            mesh=mesh, slot_shard=ss),
        donate_argnums=(0, 1))
    k2, v2 = fn(k_pool, v_pool,
                jnp.ones((b, kv, d)), jnp.ones((b, kv, d)),
                jnp.arange(b, dtype=jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.ones((b,), bool),
                jnp.asarray([0, 1, 0], jnp.int32))
    assert [s.data.unsafe_buffer_pointer()
            for s in k2.addressable_shards] == k_ptrs
    assert k2.sharding.is_equivalent_to(sharding, k2.ndim)


def test_sharded_prefill_batch_write_parity():
    """write_prefill_batch_to_pages places each request's rows on its
    home shard only, matching the single-device writer on the global
    view."""
    mesh = _mesh(2)
    rng = np.random.default_rng(3)
    L, kv, per_shard, bs, d, n, p = 2, 2, 4, 4, 8, 3, 10
    nb = 2 * per_shard
    cache_k = rng.normal(size=(L, n, p, kv, d)).astype(np.float32)
    cache_v = rng.normal(size=(L, n, p, kv, d)).astype(np.float32)
    m = -(-p // bs)
    local_blocks = np.stack(
        [rng.permutation(per_shard)[:m] for _ in range(n)]).astype(np.int32)
    home = np.asarray([0, 1, 1], np.int32)
    plens = np.asarray([10, 7, 4], np.int32)
    global_blocks = local_blocks + home[:, None] * per_shard
    zero = {"k_pages": jnp.zeros((L, kv, nb, bs, d)),
            "v_pages": jnp.zeros((L, kv, nb, bs, d))}
    want = write_prefill_batch_to_pages(
        cache_k, cache_v, zero, jnp.asarray(global_blocks),
        jnp.asarray(plens))
    got = write_prefill_batch_to_pages(
        cache_k, cache_v,
        jax.device_put(zero, paged_pool_sharding(mesh)),
        jnp.asarray(local_blocks), jnp.asarray(plens),
        jnp.asarray(home), mesh=mesh)
    np.testing.assert_allclose(np.asarray(got["k_pages"]),
                               np.asarray(want["k_pages"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["v_pages"]),
                               np.asarray(want["v_pages"]), atol=1e-6)


# --- allocator + placement ---------------------------------------------------


def test_sharded_allocator_per_shard_free_lists():
    a = ShardedBlockAllocator(num_blocks=16, block_size=4, num_shards=4)
    assert a.num_free == 16 and a.shard_num_blocks == 4
    got = a.allocate(3, shard=2)
    assert all(0 <= b < 4 for b in got)       # shard-local ids
    assert a.free_by_shard() == [4, 4, 1, 4]
    assert not a.can_allocate(2, shard=2) and a.can_allocate(2, shard=0)
    a.release(got, shard=2)
    assert a.free_by_shard() == [4, 4, 4, 4]
    with pytest.raises(ValueError):
        ShardedBlockAllocator(num_blocks=10, block_size=4, num_shards=4)
    assert make_allocator(8, 4, 1).num_shards == 1


def test_scheduler_balances_live_slots_per_shard():
    """Placement spreads admissions across shards instead of piling
    onto shard 0; pages come off each request's home-shard free list."""
    mesh = _mesh(2)
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=32, block_size=4,
                      max_batch=4, max_seq_len=64, temperature=1e-4,
                      seed=0, mesh=mesh)
    for r, n in zip(PROMPTS, BUDGETS):
        eng.submit(r, n)
    eng.step()
    shards = sorted(r.shard for r in eng.scheduler.running)
    assert shards == [0, 0, 1, 1]
    from repro.metrics.runtime_metrics import collect_serve_stats

    stats = collect_serve_stats(eng)
    assert stats["num_shards"] == 2
    assert stats["live_slots_by_shard"] == [2, 2]
    assert sum(stats["pool_free_by_shard"]) == stats["pool_blocks_free"]


# --- full-engine token-exactness vs the single-device engine ----------------


def _run_engine(mesh, *, num_blocks=32, decode_chunk=2, max_batch=3,
                **kw):
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=num_blocks, block_size=4,
                      max_batch=max_batch, max_seq_len=64,
                      temperature=1e-4, seed=0,
                      decode_chunk=decode_chunk, mesh=mesh, **kw)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=600)}
    return [trajs[r.request_id].tokens for r in reqs], eng


@pytest.mark.parametrize("data", [2, 4])
def test_sharded_engine_token_exact_greedy(data):
    """ISSUE acceptance bar: with a data-sharded mesh on forced
    multi-device CPU, greedy serve output is token-exact vs the
    single-device engine at mixed lengths."""
    if len(jax.devices()) < data:
        pytest.skip(f"needs {data} devices")
    single, _ = _run_engine(None)
    sharded, eng = _run_engine(_mesh(data))
    for s, h in zip(single, sharded):
        np.testing.assert_array_equal(s, h)
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_sharded_engine_speculative_token_exact():
    """Speculation over sharded pools (draft pool shards like the
    verifier pool): token-exact with both the sharded and single-device
    non-speculative engines."""
    single, _ = _run_engine(None)
    spec, eng = _run_engine(_mesh(2), speculate_k=3,
                            draft=("params", PARAMS))
    for s, h in zip(single, spec):
        np.testing.assert_array_equal(s, h)
    stats = eng.stats.as_dict()
    assert stats["drafted_tokens"] > 0
    assert stats["acceptance_rate"] > 0.5     # same-params draft


def test_sharded_engine_preemption_token_exact():
    """A pool under pressure preempts on the starved request's own
    shard; recompute re-prefill over the sharded pool must not change
    a single emitted token."""
    single, _ = _run_engine(None, num_blocks=12, decode_chunk=1)
    sharded, eng = _run_engine(_mesh(2), num_blocks=12, decode_chunk=1)
    assert eng.stats.preemptions > 0
    for s, h in zip(single, sharded):
        np.testing.assert_array_equal(s, h)
    assert eng.allocator.num_free == 12


def test_sharded_engine_inflight_swap_provenance():
    """In-flight weight swap over a mesh: the PolicyStore publishes
    replicated params and per-token version provenance stays intact."""
    mesh = _mesh(2)
    store = PolicyStore(PARAMS, capacity=4, sharding=replicated(mesh))
    eng = ServeEngine(BUNDLE, store=store, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1.0,
                      seed=3, mesh=mesh)
    eng.submit(PROMPTS[0], 12)
    for _ in range(5):
        assert not eng.step()
    store.publish(jax.tree.map(lambda x: x + 0.01, PARAMS))
    traj = eng.run(max_steps=200)[0]
    assert eng.stats.swaps == 1
    v = traj.versions
    assert v[0] == 0 and v[-1] == 1
    dv = np.diff(v)
    assert (dv >= 0).all() and dv.sum() == 1


# --- launcher plumbing -------------------------------------------------------


def test_parse_mesh_spec():
    assert parse_mesh_spec("data=4") == {"data": 4, "model": 1}
    assert parse_mesh_spec("data=2,model=2") == {"data": 2, "model": 2}
    with pytest.raises(ValueError):
        parse_mesh_spec("rows=3")
    with pytest.raises(ValueError):
        parse_mesh_spec("data=x")
    with pytest.raises(ValueError):
        parse_mesh_spec("data=0")


def test_launcher_serves_sharded(capsys):
    """--mesh data=2 end to end through the CLI (versioned runtime)."""
    from repro.launch.serve import main

    rc = main(["--arch", "qwen2.5-0.5b-reduced",
               "--engine", "continuous", "--mesh", "data=2",
               "--requests", "4", "--mixed-lengths", "2,4",
               "--max-batch", "2", "--runtime", "versioned"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "sharded over 2 shards" in out
    assert "serving over mesh" in out
