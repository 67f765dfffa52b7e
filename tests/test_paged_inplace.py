"""In-place paged KV pool: kernel parity, aliased-vs-carried decode
bit-exactness (incl. preemption/retire churn), prefill tile writes, and
the donation/buffer-reuse contract the flat-in-num_blocks cost rests on."""
import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data.tokenizer import get_tokenizer
from repro.kernels import ops, ref
from repro.kernels.paged_kv_write_pallas import paged_kv_write
from repro.models import transformer as tf_mod
from repro.models.registry import build
from repro.rollout.sampler import generate
from repro.serve import ServeEngine

KEY = jax.random.PRNGKey(0)
TOK = get_tokenizer()
CFG = ModelConfig(
    name="inplace-test", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=TOK.vocab_size,
)
BUNDLE = build(CFG)
PARAMS = BUNDLE.init(jax.random.PRNGKey(0))

PROMPTS = [np.asarray(TOK.encode(p), np.int32)
           for p in ("1+2=?#", "3*4=?#", "10-7=?#")]
BUDGETS = [5, 9, 13]


# --- paged_kv_write kernel vs oracle ----------------------------------------


@pytest.mark.parametrize(
    "layers,kv,nb,bs,d,b",
    [(2, 2, 8, 4, 16, 4), (3, 1, 12, 8, 32, 3), (1, 4, 6, 4, 8, 5)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kv_write_kernel_parity(layers, kv, nb, bs, d, b, dtype):
    """Pallas (interpret) vs DUS oracle on random rows, ragged offsets,
    and inactive slots, at every layer index."""
    rng = np.random.default_rng(layers * nb + b)
    ks = jax.random.split(jax.random.fold_in(KEY, nb * d), 4)
    kp = jax.random.normal(ks[0], (layers, kv, nb, bs, d)).astype(dtype)
    vp = jax.random.normal(ks[1], (layers, kv, nb, bs, d)).astype(dtype)
    k_rows = jax.random.normal(ks[2], (b, kv, d))
    v_rows = jax.random.normal(ks[3], (b, kv, d))
    page_idx = jnp.asarray(
        rng.choice(nb, size=b, replace=False), jnp.int32)
    offset = jnp.asarray(rng.integers(0, bs, size=b), jnp.int32)
    active = jnp.asarray(rng.integers(0, 2, size=b).astype(bool))
    for layer in range(layers):
        got_k, got_v = paged_kv_write(
            kp, vp, k_rows, v_rows, page_idx, offset, active,
            layer=layer, interpret=True)
        want_k, want_v = ref.ref_paged_kv_write(
            kp, vp, k_rows, v_rows, page_idx, offset, active, layer=layer)
        np.testing.assert_array_equal(np.asarray(got_k, np.float32),
                                      np.asarray(want_k, np.float32))
        np.testing.assert_array_equal(np.asarray(got_v, np.float32),
                                      np.asarray(want_v, np.float32))


def test_paged_kv_write_drop_semantics():
    """Inactive slots must leave the pool untouched — even when their
    page_idx is garbage (the engine never reads it)."""
    kp = jnp.zeros((1, 2, 4, 4, 8))
    vp = jnp.zeros((1, 2, 4, 4, 8))
    rows = jnp.ones((2, 2, 8))
    page_idx = jnp.asarray([1, 9999], jnp.int32)   # slot 1 inactive
    offset = jnp.asarray([2, 0], jnp.int32)
    active = jnp.asarray([True, False])
    for impl in (
        lambda: ref.ref_paged_kv_write(
            kp, vp, rows, rows, page_idx, offset, active, layer=0),
        lambda: paged_kv_write(
            kp, vp, rows, rows, page_idx, offset,
            active, layer=0, interpret=True),
    ):
        nk, nv = impl()
        nk = np.array(nk)
        assert nk[0, :, 1, 2, :].min() == 1.0     # active slot landed
        nk[0, :, 1, 2, :] = 0.0
        np.testing.assert_array_equal(nk, 0.0)    # nothing else moved
        np.testing.assert_array_equal(
            np.asarray(nv)[0, :, 1, 2, :], 1.0)


def test_paged_kv_write_ops_dispatch_modes_agree():
    ks = jax.random.split(KEY, 4)
    kp = jax.random.normal(ks[0], (2, 2, 6, 4, 16))
    vp = jax.random.normal(ks[1], (2, 2, 6, 4, 16))
    k_rows = jax.random.normal(ks[2], (3, 2, 16))
    v_rows = jax.random.normal(ks[3], (3, 2, 16))
    page_idx = jnp.asarray([0, 3, 5], jnp.int32)
    offset = jnp.asarray([1, 0, 3], jnp.int32)
    active = jnp.asarray([True, True, False])
    a = ops.paged_kv_write(kp, vp, k_rows, v_rows, page_idx, offset,
                           active, layer=1, mode="reference")
    b = ops.paged_kv_write(kp, vp, k_rows, v_rows, page_idx, offset,
                           active, layer=1, mode="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


# --- aliased decode path vs the carried-pool oracle -------------------------


def _random_paged_state(rng, batch, num_blocks, max_blocks, block_size):
    """Disjoint per-slot block tables + ragged positions."""
    perm = rng.permutation(num_blocks)
    tables = np.zeros((batch, max_blocks), np.int32)
    pos = np.zeros((batch,), np.int32)
    for i in range(batch):
        tables[i] = perm[i * max_blocks:(i + 1) * max_blocks]
        pos[i] = int(rng.integers(0, max_blocks * block_size - 8))
    return jnp.asarray(tables), jnp.asarray(pos)


def test_decode_step_paged_matches_carried():
    """The hoisted/aliased decode step matches the legacy scan-carried
    step over a multi-step rollout — logits and every pool row — with a
    mid-run slot deactivation (retire churn).

    Tolerance is ulp-level, not bitwise: the carried path's layer body
    compiles inside a lax.scan (fused), the hoisted path dispatches the
    same ops standalone, and XLA's fusion changes rounding in the last
    bit.  Greedy *token* equality under churn is asserted bit-for-bit by
    the engine-level test below.
    """
    rng = np.random.default_rng(7)
    batch, num_blocks, max_blocks, block_size = 3, 12, 4, 4
    pages_a = tf_mod.init_paged_cache(CFG, num_blocks, block_size)
    pages_c = jax.tree.map(jnp.copy, pages_a)
    tables, pos = _random_paged_state(
        rng, batch, num_blocks, max_blocks, block_size)
    token = jnp.asarray(rng.integers(0, CFG.vocab_size, batch), jnp.int32)
    active = jnp.asarray([True, True, True])
    for step in range(6):
        if step == 3:
            active = jnp.asarray([True, False, True])   # slot 1 retires
        out_a, pages_a = tf_mod.decode_step_paged(
            PARAMS, CFG, token, pages_a, tables, pos, active)
        out_c, pages_c = tf_mod.decode_step_paged_carried(
            PARAMS, CFG, token, pages_c, tables, pos, active)
        np.testing.assert_allclose(np.asarray(out_a.logits),
                                   np.asarray(out_c.logits),
                                   rtol=2e-6, atol=2e-6)
        for leaf in ("k_pages", "v_pages"):
            np.testing.assert_allclose(np.asarray(pages_a[leaf]),
                                       np.asarray(pages_c[leaf]),
                                       rtol=2e-6, atol=2e-6)
        token = jnp.argmax(out_a.logits, axis=-1).astype(jnp.int32)
        pos = pos + active.astype(jnp.int32)


@pytest.mark.parametrize("decode_chunk", [1, 4])
def test_engine_aliased_matches_carried_under_preemption(
        monkeypatch, decode_chunk):
    """Full-engine bit-exactness: a pool too small for all requests
    forces preemption + recompute churn; the aliased path must emit
    token-for-token what the carried path emits (greedy, fixed seed),
    across multi-chunk decode."""
    def _run(impl):
        monkeypatch.setattr(tf_mod, "decode_step_paged", impl)
        eng = ServeEngine(
            BUNDLE, PARAMS, num_blocks=7, block_size=4, max_batch=3,
            max_seq_len=64, temperature=1e-4, seed=0,
            decode_chunk=decode_chunk)
        reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
        trajs = {t.request_id: t for t in eng.run(max_steps=400)}
        if decode_chunk == 1:
            # Multi-chunk lookahead reserves pages up front, so the
            # scheduler serializes instead of preempting there; the
            # chunk=1 case is the one that churns through preemption.
            assert eng.stats.preemptions > 0
        assert eng.allocator.num_free == 7
        return [trajs[r.request_id].tokens for r in reqs]

    aliased = _run(tf_mod.decode_step_paged)
    carried = _run(tf_mod.decode_step_paged_carried)
    for a, c in zip(aliased, carried):
        np.testing.assert_array_equal(a, c)


# --- the attention read: the whole pool, one trace --------------------------


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("step", ["decode", "multi", "varlen"])
def test_attention_reads_whole_pool_with_one_trace(step):
    """Every paged-attention ``pallas_call`` of a paged step receives K
    and V as the whole ``[L, KV, NB, BS, lanes]`` pool, nothing in the
    step has the shape of one layer's slice of it (which a
    ``pallas_call`` operand would make XLA copy out every round), and
    all layers share one cached trace of the kernel: the layer is an
    argument, not a static value, so it is lowered once per shape."""
    cfg = dataclasses.replace(CFG, n_layers=3)
    b, t, nb, bs, m = 3, 2, 12, 4, 4
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    pages = jax.eval_shape(lambda: tf_mod.init_paged_cache(cfg, nb, bs))
    pool = pages["k_pages"].shape
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    kw = {"kernel_mode": "pallas_interpret"}
    if step == "decode":
        fn = lambda p, pg, tb: tf_mod.decode_step_paged(
            p, cfg, jnp.zeros((b,), jnp.int32), pg, tb,
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool), **kw)
    elif step == "multi":
        fn = lambda p, pg, tb: tf_mod.decode_step_paged_multi(
            p, cfg, jnp.zeros((b, t), jnp.int32), pg, tb,
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool),
            jnp.full((b,), m * bs, jnp.int32), **kw)
    else:
        fn = lambda p, pg, tb: tf_mod.decode_step_paged_varlen(
            p, cfg, jnp.zeros((b, t), jnp.int32), pg, tb,
            jnp.zeros((b,), jnp.int32), jnp.full((b,), t, jnp.int32),
            jnp.full((b,), m * bs, jnp.int32), **kw)
    jaxpr = jax.make_jaxpr(fn)(params, pages, i32(b, m)).jaxpr

    eqns = list(_eqns(jaxpr))
    assert not [e for e in eqns for v in e.outvars
                if getattr(v.aval, "shape", None) == pool[1:]]
    kernels = [e.params["jaxpr"] for e in eqns
               if e.params.get("name") == "paged_attention_varlen"]
    assert len(kernels) == cfg.n_layers
    assert all(k is kernels[0] for k in kernels)
    calls = [e for e in _eqns(kernels[0].jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert [v.aval.shape for v in calls[0].invars[-2:]] == [pool, pool]


# --- prefill tile writes ----------------------------------------------------


def test_write_prefill_to_pages_matches_row_scatter():
    """The DUS-per-tile prefill write equals the row-scatter semantics:
    rows < prompt_len land at blocks[row // BS], everything else —
    including whatever lives in the pad slots' page 0 — is untouched."""
    layers, kv, nb, bs, d = 2, 2, 10, 4, 8
    p, plen = 12, 9
    ks = jax.random.split(KEY, 4)
    pages = {
        "k_pages": jax.random.normal(ks[0], (layers, kv, nb, bs, d)),
        "v_pages": jax.random.normal(ks[1], (layers, kv, nb, bs, d)),
    }
    cache_k = jax.random.normal(ks[2], (layers, 1, p, kv, d))
    cache_v = jax.random.normal(ks[3], (layers, 1, p, kv, d))
    blocks = jnp.asarray([7, 2, 5, 0, 0], jnp.int32)   # pads -> page 0
    got = tf_mod.write_prefill_to_pages(
        cache_k, cache_v, pages, blocks, jnp.int32(plen))
    want_k = np.asarray(pages["k_pages"]).copy()
    want_v = np.asarray(pages["v_pages"]).copy()
    rows_k = np.asarray(cache_k)[:, 0].transpose(0, 2, 1, 3)
    rows_v = np.asarray(cache_v)[:, 0].transpose(0, 2, 1, 3)
    for r in range(plen):
        want_k[:, :, int(blocks[r // bs]), r % bs, :] = rows_k[:, :, r, :]
        want_v[:, :, int(blocks[r // bs]), r % bs, :] = rows_v[:, :, r, :]
    np.testing.assert_array_equal(np.asarray(got["k_pages"]), want_k)
    np.testing.assert_array_equal(np.asarray(got["v_pages"]), want_v)


# --- donation / buffer reuse ------------------------------------------------


def test_engine_decode_donates_and_reuses_pool_buffer():
    """The decode dispatch must consume the pool it was handed
    (donate_argnums) and, on this single-device host, write the result
    into the *same* buffer — the no-copy property the flat-in-num_blocks
    per-step cost rests on."""
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1e-4,
                      seed=0)
    eng.submit(PROMPTS[0], 8)
    eng.step()                       # prefill + first chunk: all compiled
    before = eng.pages["k_pages"]
    ptr_before = before.unsafe_buffer_pointer()
    eng.step()
    assert before.is_deleted(), "pool was not donated into the dispatch"
    assert eng.pages["k_pages"].unsafe_buffer_pointer() == ptr_before, (
        "pool buffer was copied, not updated in place")


def test_released_pages_overwritten_not_stale():
    """Copy-free release means retired requests' rows stay in the pool
    until reused; a later request that inherits those pages must produce
    exactly the dense-path tokens (a stale-row read would corrupt its
    attention)."""
    def _greedy_reference(row, n):
        g = jax.jit(lambda p, t, k: generate(
            BUNDLE, p, t, k, max_new_tokens=n, temperature=1e-4))(
            PARAMS, jnp.asarray(row)[None], jax.random.PRNGKey(7))
        return np.asarray(g.completion[0])

    # Pool of exactly one request's working set: every admission reuses
    # the predecessor's just-released pages.
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=8, block_size=4,
                      max_batch=1, max_seq_len=32, temperature=1e-4,
                      seed=0)
    for prompt, budget in zip(PROMPTS, BUDGETS):
        want = _greedy_reference(prompt, budget)
        req = eng.submit(prompt, budget)
        traj = {t.request_id: t for t in eng.run(max_steps=200)}
        np.testing.assert_array_equal(traj[req.request_id].tokens, want)
        assert eng.allocator.num_free == 8     # all pages back in pool
