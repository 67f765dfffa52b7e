"""Speculative decode over the paged cache: multi-token verify kernel
parity (interpret mode vs the jnp oracle), the accept/rollback rule,
engine token-exactness vs non-speculative greedy decode at every
acceptance rate, rollback under preemption churn, PolicyStore draft
pinning, and batched prefill admissions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _layered_pool import layer_cases, layered
from repro.configs.base import ModelConfig
from repro.data.tokenizer import PAD, get_tokenizer
from repro.kernels import ref
from repro.kernels.paged_attention_pallas import (
    paged_attention,
    paged_attention_multi,
)
from repro.models.registry import build
from repro.rollout.sampler import generate, score_tokens, speculative_accept
from repro.runtime import PolicyStore
from repro.runtime.policy_store import StaleVersionError
from repro.serve import ServeEngine

TOK = get_tokenizer()
CFG = ModelConfig(
    name="spec-test", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=TOK.vocab_size,
)
BUNDLE = build(CFG)
PARAMS = BUNDLE.init(jax.random.PRNGKey(0))
# A draft from a different init proposes junk -> acceptance ~0 (the
# adversarial end of the acceptance spectrum).
ADVERSARIAL_PARAMS = BUNDLE.init(jax.random.PRNGKey(99))

PROMPTS = [np.asarray(TOK.encode(p), np.int32)
           for p in ("1+2=?#", "3*4=?#", "10-7=?#")]
BUDGETS = [5, 9, 13]
KEY = jax.random.PRNGKey(0)


def _greedy_reference(params, row, n):
    g = jax.jit(lambda p, t, k: generate(
        BUNDLE, p, t, k, max_new_tokens=n, temperature=1e-4))(
        params, jnp.asarray(row)[None], jax.random.PRNGKey(7))
    return np.asarray(g.completion[0])


GREEDY_WANT = [_greedy_reference(PARAMS, r, n)
               for r, n in zip(PROMPTS, BUDGETS)]


# --- multi-token verify kernel: interpret-mode parity vs the oracle ---------


def _ragged_tables(rng, b, num_blocks, max_blocks, bs, t):
    tables = np.zeros((b, max_blocks), np.int32)
    lens = np.zeros((b,), np.int32)
    perm = rng.permutation(num_blocks)
    pi = 0
    for i in range(b):
        n = int(rng.integers(t, max_blocks * bs))
        lens[i] = n
        pages = -(-n // bs)
        tables[i, :pages] = perm[pi:pi + pages]
        pi += pages
    return jnp.asarray(tables), jnp.asarray(lens)


@pytest.mark.parametrize("b,t,h,kv,d,bs,window,layers,layer", layer_cases([
    (2, 4, 4, 2, 16, 4, None),
    (3, 2, 2, 2, 8, 4, None),
    (2, 3, 4, 4, 16, 8, None),
    (2, 4, 4, 2, 16, 4, 6),
    (1, 5, 8, 2, 32, 4, None),
], (2, 4, 4, 2, 16, 4, 6)))
def test_paged_attention_multi_parity_sweep(b, t, h, kv, d, bs, window,
                                            layers, layer):
    """Pallas multi-query kernel (interpret) vs jnp oracle on shuffled,
    ragged block tables; the kernel reads layer ``layer`` of the whole
    pool in place, the oracle that layer alone."""
    rng = np.random.default_rng(b * 17 + t)
    ks = jax.random.split(jax.random.fold_in(KEY, b * t * d), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    kp = jax.random.normal(ks[1], (kv, 24, bs, d))
    vp = jax.random.normal(ks[2], (kv, 24, bs, d))
    tables, lens = _ragged_tables(rng, b, 24, 4, bs, t)
    out = paged_attention_multi(q, layered(kp, layers, layer),
                                layered(vp, layers, layer), tables, lens,
                                layer=layer, window=window, interpret=True)
    want = ref.ref_paged_attention_multi(q, kp, vp, tables, lens,
                                         window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_multi_t1_reduces_to_single():
    """T=1 is exactly the plain decode kernel (oracle and Pallas)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16))
    kp = jax.random.normal(ks[1], (2, 8, 4, 16))
    vp = jax.random.normal(ks[2], (2, 8, 4, 16))
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    lens = jnp.asarray([7, 3], jnp.int32)
    kp, vp = kp[None], vp[None]
    multi = paged_attention_multi(q, kp, vp, tables, lens, layer=0,
                                  interpret=True)
    single = paged_attention(q[:, 0], kp, vp, tables, lens, layer=0,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(multi[:, 0]), np.asarray(single),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_multi_inactive_slot_zero():
    """context_len 0 (an empty serve slot) must yield exactly zero."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 3, 4, 16))
    kp = jax.random.normal(ks[1], (2, 8, 4, 16))
    vp = jax.random.normal(ks[2], (2, 8, 4, 16))
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.asarray([0, 6], jnp.int32)
    for fn in (
        lambda: paged_attention_multi(q, kp[None], vp[None], tables, lens,
                                      layer=0, interpret=True),
        lambda: ref.ref_paged_attention_multi(q, kp, vp, tables, lens),
    ):
        out = np.asarray(fn())
        np.testing.assert_array_equal(out[0], 0.0)
        assert np.abs(out[1]).max() > 0


def test_decode_step_paged_multi_matches_sequential_steps():
    """The fused T-token verify step == T sequential single-token paged
    decode steps, in logits and in the pool it leaves behind."""
    B, T, NB, BS, M = 2, 4, 16, 4, 6
    rng = np.random.default_rng(0)
    tables = np.zeros((B, M), np.int32)
    tables[0, :4] = [3, 7, 1, 9]
    tables[1, :4] = [2, 5, 8, 11]
    pos = jnp.asarray([5, 2], jnp.int32)
    active = jnp.asarray([True, True])
    cap = jnp.asarray([4 * BS, 4 * BS], jnp.int32)
    toks = rng.integers(0, CFG.vocab_size, (B, T)).astype(np.int32)

    seq_logits, p, pages = [], pos, BUNDLE.init_paged_cache(NB, BS)
    for t in range(T):
        out, pages = BUNDLE.decode_step_paged(
            PARAMS, jnp.asarray(toks[:, t]), pages, jnp.asarray(tables),
            p, active)
        seq_logits.append(out.logits)
        p = p + 1
    out_m, pages_m = BUNDLE.decode_step_paged_multi(
        PARAMS, jnp.asarray(toks), BUNDLE.init_paged_cache(NB, BS),
        jnp.asarray(tables), pos, active, cap)
    np.testing.assert_allclose(
        np.asarray(out_m.logits), np.asarray(jnp.stack(seq_logits, 1)),
        rtol=2e-5, atol=2e-5)
    for k in ("k_pages", "v_pages"):
        np.testing.assert_allclose(np.asarray(pages_m[k]),
                                   np.asarray(pages[k]),
                                   rtol=1e-6, atol=1e-6)


def test_decode_step_paged_multi_write_cap_drops_overflow():
    """Positions past a slot's allocated rows must not write anywhere —
    especially not into the table's in-range pad pages (page 0)."""
    B, T, NB, BS, M = 1, 4, 8, 4, 2
    tables = jnp.asarray([[3, 0]], jnp.int32)   # pad slot points at page 0
    pos = jnp.asarray([2], jnp.int32)
    active = jnp.asarray([True])
    cap = jnp.asarray([BS], jnp.int32)          # only page 3's 4 rows owned
    toks = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    pages = BUNDLE.init_paged_cache(NB, BS)
    _, pages = BUNDLE.decode_step_paged_multi(
        PARAMS, toks, pages, tables, pos, active, cap)
    # rows 2..3 land in page 3; rows 4..5 (>= cap) must be dropped
    assert np.abs(np.asarray(pages["k_pages"][:, :, 3])).max() > 0
    np.testing.assert_array_equal(np.asarray(pages["k_pages"][:, :, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(pages["v_pages"][:, :, 0]), 0.0)


# --- the accept rule --------------------------------------------------------


def _peaked(tokens, vocab, hi=8.0):
    """Logits strongly peaked on `tokens` ([B, K])."""
    return jnp.where(
        tokens[..., None] == jnp.arange(vocab), hi, 0.0)


def test_speculative_accept_greedy_accept_all():
    v = 11
    drafts = jnp.asarray([[3, 5, 7, 2]], jnp.int32)
    logits = _peaked(drafts, v)
    toks, lps, n_acc, n_emit = speculative_accept(
        logits, drafts, logits, KEY, temperature=1e-4)
    assert int(n_acc[0]) == 4 and int(n_emit[0]) == 4
    np.testing.assert_array_equal(np.asarray(toks[0]), [3, 5, 7, 2])
    assert np.asarray(lps[0]).max() <= 0.0


def test_speculative_accept_greedy_reject_first():
    """Adversarial draft: everything rejected, the correction is the
    verifier argmax, and the tail is PAD with log-prob exactly 0."""
    v = 11
    drafts = jnp.asarray([[3, 5, 7, 2]], jnp.int32)
    verifier = _peaked(jnp.asarray([[4, 6, 8, 1]], jnp.int32), v)
    toks, lps, n_acc, n_emit = speculative_accept(
        verifier, drafts, _peaked(drafts, v), KEY, temperature=1e-4)
    assert int(n_acc[0]) == 0 and int(n_emit[0]) == 1
    assert int(toks[0, 0]) == 4                 # verifier argmax
    np.testing.assert_array_equal(np.asarray(toks[0, 1:]), PAD)
    np.testing.assert_array_equal(np.asarray(lps[0, 1:]), 0.0)


def test_speculative_accept_greedy_partial_prefix():
    v = 11
    drafts = jnp.asarray([[3, 5, 7, 2]], jnp.int32)
    verifier = _peaked(jnp.asarray([[3, 5, 9, 2]], jnp.int32), v)
    toks, lps, n_acc, n_emit = speculative_accept(
        verifier, drafts, _peaked(drafts, v), KEY, temperature=1e-4)
    assert int(n_acc[0]) == 2 and int(n_emit[0]) == 3
    np.testing.assert_array_equal(np.asarray(toks[0, :3]), [3, 5, 9])
    np.testing.assert_array_equal(np.asarray(toks[0, 3:]), PAD)


def test_speculative_accept_identical_distributions_accept_all():
    """q == p accepts everything at ANY temperature (the ratio is 1)."""
    logits = jax.random.normal(KEY, (2, 3, 17))
    drafts = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _, _, n_acc, _ = speculative_accept(
        logits, drafts, logits, jax.random.PRNGKey(5), temperature=1.0)
    np.testing.assert_array_equal(np.asarray(n_acc), 3)


def test_speculative_accept_onehot_draft_marginal_is_verifier():
    """A deterministic (one-hot) proposal still emits tokens distributed
    exactly as the verifier: empirically the first-position marginal
    matches softmax(p) to sampling error."""
    v = 5
    verifier = jnp.tile(
        jnp.asarray([[0.5, 1.5, -0.3, 0.2, -1.0]]), (1, 1, 1))
    p = np.asarray(jax.nn.softmax(verifier[0, 0]))
    drafts = jnp.asarray([[1]], jnp.int32)       # always propose token 1
    onehot = jnp.where(drafts[..., None] == jnp.arange(v), 0.0, -1e9)
    counts = np.zeros(v)
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    emit = jax.jit(jax.vmap(lambda k: speculative_accept(
        verifier, drafts, onehot, k, temperature=1.0)[0][0, 0]))(keys)
    for t in np.asarray(emit):
        counts[int(t)] += 1
    np.testing.assert_allclose(counts / n, p, atol=0.03)


# --- engine: token-exactness across the acceptance spectrum -----------------


@pytest.mark.parametrize("label,draft,k", [
    ("accept_all", ("params", PARAMS), 4),
    ("adversarial", ("params", ADVERSARIAL_PARAMS), 4),
    ("k1", ("params", PARAMS), 1),
    ("callable", lambda req, k: np.zeros(k, np.int32), 3),
])
def test_spec_engine_token_exact_vs_nonspec_greedy(label, draft, k):
    """Speculative greedy output == non-speculative greedy output at any
    acceptance rate (the tentpole correctness bar)."""
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=32, block_size=4, max_batch=2,
        max_seq_len=64, temperature=1e-4, seed=0,
        speculate_k=k, draft=draft)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    for rq, w in zip(reqs, GREEDY_WANT):
        t = trajs[rq.request_id]
        np.testing.assert_array_equal(t.tokens, w)
        assert t.mask.tolist() == [1.0] * len(w)
    stats = eng.stats.as_dict()
    if label == "accept_all":
        assert stats["acceptance_rate"] == 1.0
    if label == "adversarial":
        assert stats["acceptance_rate"] == 0.0
    assert eng.allocator.num_free == eng.allocator.num_blocks


@pytest.mark.parametrize("label,draft", [
    ("accept_all", ("params", PARAMS)),
    ("adversarial", ("params", ADVERSARIAL_PARAMS)),
])
def test_spec_engine_adaptive_k_token_exact_and_adapts(label, draft):
    """Draft-aware scheduling: adaptive k stays token-exact (the round
    length never touches correctness) and the chosen-k histogram moves
    the way the acceptance EMA says it should — pinned at the max for
    an accept-all draft, collapsing toward 1 for an adversarial one."""
    from repro.metrics.runtime_metrics import collect_serve_stats

    k_max = 4
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=32, block_size=4, max_batch=2,
        max_seq_len=64, temperature=1e-4, seed=0,
        speculate_k=k_max, draft=draft, speculate_adaptive=True)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    for rq, w in zip(reqs, GREEDY_WANT):
        np.testing.assert_array_equal(trajs[rq.request_id].tokens, w)
    stats = collect_serve_stats(eng)
    assert stats["speculate_adaptive"] is True
    hist = {int(k): v for k, v in stats["chosen_k_histogram"].items()}
    assert sum(hist.values()) == eng.stats.spec_rounds > 0
    if label == "accept_all":
        # Acceptance EMA stays 1.0 -> every round drafts the full k.
        assert set(hist) == {k_max}
    else:
        # Rejections drag the EMA down; later rounds must shrink k.
        assert min(hist) < k_max


def test_adaptive_k_ema_resets_on_admission():
    """A slot's acceptance EMA belongs to its occupant: once a request
    retires and a new one is admitted into the slot, the EMA restarts
    optimistic (k back at the max) instead of inheriting the previous
    occupant's rejections."""
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=32, block_size=4, max_batch=1,
        max_seq_len=64, temperature=1e-4, seed=0,
        speculate_k=4, draft=("params", ADVERSARIAL_PARAMS),
        speculate_adaptive=True)
    eng.submit(PROMPTS[0], BUDGETS[0])
    eng.run(max_steps=100)
    assert eng._accept_ema[0] < 1.0          # adversarial draft rejected
    before = eng._chosen_k_hist.snapshot().get(4, 0)
    eng.submit(PROMPTS[1], 4)
    eng.step()   # admission round: chunked prefill tiles, no spec yet
    eng.step()   # EMA was reset at admission -> this round drafts k=4
    assert eng._chosen_k_hist.snapshot().get(4, 0) == before + 1


def test_spec_engine_rollback_under_preemption_churn():
    """A pool too small for every request forces preemption mid-spec;
    re-prefill + pos-rewind rollback must not change a single token."""
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=6, block_size=4, max_batch=3,
        max_seq_len=64, temperature=1e-4, seed=0,
        speculate_k=3, draft=("params", PARAMS))
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    assert eng.stats.preemptions > 0
    for rq, w in zip(reqs, GREEDY_WANT):
        np.testing.assert_array_equal(trajs[rq.request_id].tokens, w)
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_spec_engine_log_beta_matches_rescoring():
    """Per-token log_beta recorded by speculative serving == the
    verifier's teacher-forced rescoring (β stays the latest policy)."""
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=32, block_size=4, max_batch=2,
        max_seq_len=64, temperature=1.0, seed=5,
        speculate_k=3, draft=("params", PARAMS))
    eng.submit(PROMPTS[0], 8)
    t = eng.run(max_steps=100)[0]
    full = np.concatenate([t.prompt, t.tokens])
    logp, _, _ = score_tokens(BUNDLE, PARAMS, jnp.asarray(full)[None],
                              prompt_len=len(t.prompt))
    np.testing.assert_allclose(np.asarray(logp[0]), t.log_beta, atol=2e-4)


def test_spec_engine_selfspec_pins_and_swaps():
    """Self-speculation pins its draft version (survives ring eviction)
    and re-pins latest+offset after every verifier swap; serve stats
    expose acceptance rate + the draft-version lag histogram."""
    from repro.metrics.runtime_metrics import collect_serve_stats

    store = PolicyStore(PARAMS, capacity=2)
    store.publish(jax.tree.map(lambda x: x + 0.01, PARAMS))      # v1
    eng = ServeEngine(
        BUNDLE, store=store, num_blocks=32, block_size=4, max_batch=2,
        max_seq_len=64, temperature=1.0, seed=3,
        speculate_k=2, draft=("version", -1))
    assert (eng.version, eng.draft.version) == (1, 0)
    assert store.pinned_versions() == [0]
    eng.submit(PROMPTS[0], 12)
    for _ in range(3):
        eng.step()
    # Two publishes evict v0 from the capacity-2 ring; the pin keeps the
    # draft readable until the next swap re-pins v2 and releases v0.
    store.publish(jax.tree.map(lambda x: x + 0.01, store.latest()[0]))
    store.publish(jax.tree.map(lambda x: x + 0.01, store.latest()[0]))
    trajs = eng.run(max_steps=200)
    assert (eng.version, eng.draft.version) == (3, 2)
    assert store.pinned_versions() == [2]
    v = trajs[0].versions
    assert (np.diff(v) >= 0).all()
    stats = collect_serve_stats(eng)
    assert stats["drafted_tokens"] > 0
    assert 0.0 <= stats["acceptance_rate"] <= 1.0
    assert stats["draft_version"] == 2
    assert sum(stats["draft_version_lag_histogram"].values()) > 0


def test_policy_store_pin_release_refcount():
    store = PolicyStore(PARAMS, capacity=2)
    store.publish(jax.tree.map(lambda x: x + 1.0, PARAMS))       # v1
    store.pin(0)
    store.pin(0)                                                 # refcount 2
    store.publish(jax.tree.map(lambda x: x + 2.0, PARAMS))       # v2: v0 out
    assert store.retained_versions() == [1, 2]
    p0 = store.get(0)                                            # via pin
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(p0)[0]),
        np.asarray(jax.tree.leaves(PARAMS)[0]))
    store.release(0)
    p0 = store.get(0)                                            # still held
    store.release(0)
    with pytest.raises(StaleVersionError):
        store.get(0)
    with pytest.raises(KeyError):
        store.release(0)


def test_policy_store_resolve_lagged():
    store = PolicyStore(PARAMS, capacity=2)
    for _ in range(3):
        store.publish(PARAMS)                                    # v1..v3
    assert store.resolve_lagged(0) == 3
    assert store.resolve_lagged(-1) == 2
    assert store.resolve_lagged(-3) == 2     # v0 evicted -> oldest resident
    store.pin(2)
    store.publish(PARAMS)                                        # v4: ring 3,4
    assert store.resolve_lagged(-2) == 2     # pinned version is resident
    with pytest.raises(ValueError):
        store.resolve_lagged(1)


def test_policy_store_pin_lagged_atomic():
    """pin_lagged resolves AND pins in one lock hold (the engine's
    draft handoff path); the pinned version survives eviction."""
    store = PolicyStore(PARAMS, capacity=2)
    store.publish(PARAMS)                                        # v1
    params, version = store.pin_lagged(-1)
    assert version == 0 and store.pinned_versions() == [0]
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(params)[0]),
        np.asarray(jax.tree.leaves(PARAMS)[0]))
    store.publish(PARAMS)                                        # evicts v0
    _, again = store.pin_lagged(-10)     # clamps to pinned v0, refcount 2
    assert again == 0
    store.release(0)
    store.release(0)
    with pytest.raises(ValueError):
        store.pin_lagged(1)


def test_spec_engine_requires_multi_capable_arch():
    cfg = CFG.replace(name="rwkv-ish", attn_free=True)
    bundle = build(cfg)
    assert bundle.decode_step_paged_multi is None
    with pytest.raises(ValueError):
        ServeEngine(bundle, PARAMS, speculate_k=2)


# --- batched prefill --------------------------------------------------------


def test_batched_prefill_token_exact_and_one_dispatch():
    """A burst of same-padded-length admissions prefills in ONE dispatch
    and emits exactly the tokens the per-request path emits."""
    prompts = PROMPTS + [np.asarray(TOK.encode("9-5=?#"), np.int32)]
    outs, dispatches = {}, {}
    for bp in (True, False):
        with pytest.warns(DeprecationWarning):
            eng = ServeEngine(
                BUNDLE, PARAMS, num_blocks=32, block_size=8, max_batch=4,
                max_seq_len=64, temperature=1e-4, seed=0,
                batch_prefill=bp, chunked_prefill=False)
        reqs = [eng.submit(r, 6) for r in prompts]
        trajs = {t.request_id: t for t in eng.run(max_steps=200)}
        outs[bp] = [trajs[rq.request_id].tokens for rq in reqs]
        dispatches[bp] = eng.stats.prefill_dispatches
        assert eng.stats.prefills == 4
    assert dispatches[True] == 1 and dispatches[False] == 4
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_batched_prefill_mixed_lengths_grouped_separately():
    """Different padded lengths cannot share a dispatch; each length
    class gets its own, and tokens still match the dense reference."""
    short = PROMPTS[0]                      # 6 ids -> pads to 8
    long = np.concatenate([PROMPTS[1]] * 2)  # 12 ids -> pads to 16
    with pytest.warns(DeprecationWarning):
        eng = ServeEngine(
            BUNDLE, PARAMS, num_blocks=32, block_size=8, max_batch=4,
            max_seq_len=64, temperature=1e-4, seed=0,
            chunked_prefill=False)
    r1 = eng.submit(short, 5)
    r2 = eng.submit(long, 5)
    trajs = {t.request_id: t for t in eng.run(max_steps=200)}
    assert eng.stats.prefill_dispatches == 2
    np.testing.assert_array_equal(
        trajs[r1.request_id].tokens, _greedy_reference(PARAMS, short, 5))
    np.testing.assert_array_equal(
        trajs[r2.request_id].tokens, _greedy_reference(PARAMS, long, 5))


def test_batched_prefill_records_first_token_latency():
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=32, block_size=8, max_batch=2,
        max_seq_len=64, temperature=1e-4, seed=0)
    reqs = [eng.submit(r, 4) for r in PROMPTS]
    eng.run(max_steps=200)
    for rq in reqs:
        assert rq.first_token_time is not None
        assert rq.first_token_time >= rq.submit_time


# --- speculation composes with the rest of the engine -----------------------


def test_spec_engine_with_batched_prefill_and_mixed_lengths():
    """Speculation + batched prefill + mixed budgets, all at once."""
    with pytest.warns(DeprecationWarning):
        eng = ServeEngine(
            BUNDLE, PARAMS, num_blocks=32, block_size=8, max_batch=3,
            max_seq_len=64, temperature=1e-4, seed=0,
            speculate_k=4, draft=("params", PARAMS),
            chunked_prefill=False)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    for rq, w in zip(reqs, GREEDY_WANT):
        np.testing.assert_array_equal(trajs[rq.request_id].tokens, w)
    assert eng.stats.prefill_dispatches < eng.stats.prefills
