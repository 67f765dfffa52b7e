"""Serve subsystem: block allocator, continuous-batching scheduler,
paged-KV engine parity vs the dense generate loop, preemption
correctness, in-flight weight swap provenance, and tokenwise TV
admission over served trajectories."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data.tokenizer import EOS, get_tokenizer
from repro.models.registry import build
from repro.rollout.sampler import generate, score_tokens
from repro.runtime import (
    PolicyStore,
    TokenwiseTVGate,
    TrajectoryQueue,
    TVGatedAdmission,
    make_regime,
)
from repro.serve import (
    BlockAllocator,
    ContinuousBatchingScheduler,
    OutOfBlocks,
    Request,
    ServeEngine,
)

TOK = get_tokenizer()
CFG = ModelConfig(
    name="serve-test", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=TOK.vocab_size,
)
BUNDLE = build(CFG)
PARAMS = BUNDLE.init(jax.random.PRNGKey(0))

PROMPTS = [np.asarray(TOK.encode(p), np.int32)
           for p in ("1+2=?#", "3*4=?#", "10-7=?#")]
BUDGETS = [5, 9, 13]


def _greedy_reference(params, row, n):
    g = jax.jit(lambda p, t, k: generate(
        BUNDLE, p, t, k, max_new_tokens=n, temperature=1e-4))(
        params, jnp.asarray(row)[None], jax.random.PRNGKey(7))
    return np.asarray(g.completion[0])


# --- allocator --------------------------------------------------------------


def test_allocator_free_list_and_reuse():
    a = BlockAllocator(num_blocks=4, block_size=8)
    assert a.num_free == 4
    b1 = a.allocate(3)
    assert a.num_free == 1 and len(set(b1)) == 3
    with pytest.raises(OutOfBlocks):
        a.allocate(2)
    a.release(b1[:2])               # copy-free release
    assert a.num_free == 3
    b2 = a.allocate(3)
    assert set(b2) & set(b1[:2])    # released pages are reused
    assert a.blocks_for(1) == 1 and a.blocks_for(8) == 1
    assert a.blocks_for(9) == 2


def test_allocator_padded_table_in_range():
    a = BlockAllocator(num_blocks=8, block_size=4)
    row = a.padded_table([5, 2], width=4)
    np.testing.assert_array_equal(row, [5, 2, 0, 0])
    with pytest.raises(ValueError):
        a.padded_table([1, 2, 3], width=2)


# --- scheduler --------------------------------------------------------------


def _sched(num_blocks=8, block_size=4, max_batch=2, max_blocks=8):
    return ContinuousBatchingScheduler(
        BlockAllocator(num_blocks, block_size),
        max_batch=max_batch, max_blocks_per_request=max_blocks)


def test_scheduler_admits_fifo_into_slots():
    s = _sched()
    reqs = [Request(prompt=np.zeros((6,), np.int32), max_new_tokens=4)
            for _ in range(3)]
    for r in reqs:
        s.submit(r)
    admitted, preempted = s.schedule()
    assert admitted == reqs[:2] and not preempted   # 2 slots
    assert [r.slot for r in admitted] == [0, 1]
    assert all(len(r.blocks) >= 2 for r in admitted)  # 7 rows -> 2 pages
    s.retire(reqs[0], "eos")
    admitted, _ = s.schedule()
    assert admitted == [reqs[2]] and reqs[2].slot == 0  # slot reused


def test_scheduler_rejects_impossible_request():
    s = _sched(num_blocks=2, block_size=4, max_blocks=2)
    with pytest.raises(ValueError):
        s.submit(Request(prompt=np.zeros((6,), np.int32),
                         max_new_tokens=8))   # 14 rows > 8-row pool


def test_scheduler_preempts_latest_admitted_on_pressure():
    s = _sched(num_blocks=4, block_size=4, max_batch=2)
    r1 = Request(prompt=np.zeros((4,), np.int32), max_new_tokens=9)
    r2 = Request(prompt=np.zeros((4,), np.int32), max_new_tokens=9)
    s.submit(r1), s.submit(r2)
    admitted, _ = s.schedule()
    assert admitted == [r1, r2]     # 2 pages each (5 rows)
    # r1 grows past its pages (9th row): pool dry -> r2 (latest) evicted
    r1.tokens.extend([5, 5, 5, 5, 5])
    admitted, preempted = s.schedule()
    assert preempted == [r2] and not admitted    # r1's extension won
    assert r2.state.value == "waiting" and r2.blocks == []
    assert r2.num_preemptions == 1
    assert s.waiting[0] is r2       # requeued at the front


# --- engine correctness -----------------------------------------------------


@pytest.mark.parametrize("decode_chunk", [1, 4])
def test_engine_matches_dense_generate_greedy(decode_chunk):
    """Continuous batching over the paged cache is token-exact vs the
    phase-locked dense loop under greedy decoding, at mixed lengths."""
    want = [_greedy_reference(PARAMS, r, n)
            for r, n in zip(PROMPTS, BUDGETS)]
    eng = ServeEngine(
        BUNDLE, PARAMS, num_blocks=32, block_size=4, max_batch=2,
        max_seq_len=64, temperature=1e-4, seed=0,
        decode_chunk=decode_chunk)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    for rq, w in zip(reqs, want):
        t = trajs[rq.request_id]
        np.testing.assert_array_equal(t.tokens, w)
        assert t.mask.tolist() == [1.0] * len(w)
        assert t.finish_reason in ("eos", "length")
    # every page returned to the pool, copy-free
    assert eng.allocator.num_free == eng.allocator.num_blocks
    assert eng.stats.finished == 3
    from repro.metrics.runtime_metrics import collect_serve_stats

    stats = collect_serve_stats(eng)
    assert stats["tokens_out"] == sum(BUDGETS)
    assert stats["pool_utilization"] == 0.0       # all freed
    assert stats["waiting"] == 0 and stats["running"] == 0
    assert 0.0 < stats["mean_occupancy"] <= 2.0   # max_batch slots


def test_engine_log_beta_matches_rescoring():
    """Recorded behavior log-probs == teacher-forced rescoring under the
    same params (the β == π_serve invariant, per request)."""
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1.0,
                      seed=5)
    eng.submit(PROMPTS[0], 8)
    t = eng.run(max_steps=100)[0]
    full = np.concatenate([t.prompt, t.tokens])
    logp, _, _ = score_tokens(BUNDLE, PARAMS, jnp.asarray(full)[None],
                              prompt_len=len(t.prompt))
    np.testing.assert_allclose(np.asarray(logp[0]), t.log_beta, atol=2e-4)


def test_engine_preemption_preserves_tokens():
    """A pool too small for all requests forces preemption; recompute
    re-prefill must not change any emitted token (greedy)."""
    want = [_greedy_reference(PARAMS, r, n)
            for r, n in zip(PROMPTS, BUDGETS)]
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=7, block_size=4,
                      max_batch=3, max_seq_len=64, temperature=1e-4,
                      seed=0)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    assert eng.stats.preemptions > 0
    for rq, w in zip(reqs, want):
        np.testing.assert_array_equal(trajs[rq.request_id].tokens, w)
    assert eng.allocator.num_free == 7


def test_engine_requires_paged_capable_arch():
    cfg = CFG.replace(name="rwkv-ish", attn_free=True)
    bundle = build(cfg)
    assert bundle.decode_step_paged is None
    with pytest.raises(ValueError, match="attn-free"):
        ServeEngine(bundle, PARAMS)


# --- sliding-window (gemma3-style) archs on the paged path ------------------


WIN_CFG = CFG.replace(name="serve-window-test", sliding_window=4,
                      global_every=2)   # layer 0 local(4), layer 1 global
WIN_BUNDLE = build(WIN_CFG)
WIN_PARAMS = WIN_BUNDLE.init(jax.random.PRNGKey(1))


def test_sliding_window_arch_is_paged_capable():
    """The per-layer window gate is lifted: gemma3-style local:global
    patterns run the paged path (prefix-LM/VLM and SSM state stay
    gated)."""
    assert WIN_BUNDLE.decode_step_paged is not None
    assert WIN_BUNDLE.decode_step_paged_multi is not None
    vlm = CFG.replace(name="vlm-ish", vision_prefix_len=16, prefix_lm=True)
    assert build(vlm).decode_step_paged is None


def test_engine_windowed_matches_dense_generate_greedy():
    """Paged serve over a sliding-window arch is token-exact vs the
    dense generate loop, with contexts well past the window so the
    local layers' masks actually bite."""
    budgets = [10, 14, 12]
    want = []
    for row, n in zip(PROMPTS, budgets):
        g = jax.jit(lambda p, t, k, n=n: generate(
            WIN_BUNDLE, p, t, k, max_new_tokens=n, temperature=1e-4))(
            WIN_PARAMS, jnp.asarray(row)[None], jax.random.PRNGKey(7))
        comp = np.asarray(g.completion[0])
        if (comp == EOS).any():       # engine retires at EOS; cut the pad
            comp = comp[: int(np.argmax(comp == EOS)) + 1]
        want.append(comp)
    eng = ServeEngine(
        WIN_BUNDLE, WIN_PARAMS, num_blocks=32, block_size=4, max_batch=2,
        max_seq_len=64, temperature=1e-4, seed=0, decode_chunk=2)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, budgets)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    for rq, w in zip(reqs, want):
        np.testing.assert_array_equal(trajs[rq.request_id].tokens, w)


def test_engine_windowed_speculative_token_exact():
    """Multi-token verify carries the same per-layer windows: the spec
    engine on a windowed arch is token-exact with its own non-spec
    greedy output."""
    def _run(k):
        eng = ServeEngine(
            WIN_BUNDLE, WIN_PARAMS, num_blocks=32, block_size=4,
            max_batch=2, max_seq_len=64, temperature=1e-4, seed=0,
            speculate_k=k,
            draft=("params", WIN_PARAMS) if k else None)
        reqs = [eng.submit(r, 12) for r in PROMPTS]
        trajs = {t.request_id: t for t in eng.run(max_steps=400)}
        return [trajs[r.request_id].tokens for r in reqs]

    plain = _run(0)
    spec = _run(3)
    for p, s in zip(plain, spec):
        np.testing.assert_array_equal(p, s)


# --- in-flight weight swap (acceptance: per-token version provenance) -------


# The swap tests follow one sampled request across a publish: an EOS
# sampled before it would end the request early, whatever the sample
# stream.  Their readout gives EOS a logit no sample reaches (the bias
# form of the zero stop-id readout row the chip benchmark's weights
# use), so every request runs to its budget.
SWAP_PARAMS = {**PARAMS, "lm_head": {
    **PARAMS["lm_head"],
    "b": jnp.zeros((CFG.vocab_size,), jnp.float32).at[EOS].set(-1e9)}}


def _swap_trajectory(seed=3, swap_after=5, total=12):
    """Fixed-seed run with one learner publish mid-generation."""
    store = PolicyStore(SWAP_PARAMS, capacity=4)
    eng = ServeEngine(BUNDLE, store=store, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1.0,
                      seed=seed)
    eng.submit(PROMPTS[0], total)
    for _ in range(swap_after):
        assert not eng.step()
    p2 = jax.tree.map(lambda x: x + 0.01, SWAP_PARAMS)
    store.publish(p2)
    trajs = eng.run(max_steps=200)
    return trajs[0], p2, eng


def test_inflight_swap_versions_change_at_boundary():
    traj, _, eng = _swap_trajectory()
    v = traj.versions
    assert v[0] == 0 and v[-1] == 1          # straddles the publish
    dv = np.diff(v)
    assert (dv >= 0).all() and dv.sum() == 1  # one clean step boundary
    assert eng.stats.swaps == 1
    assert traj.behavior_version == 0         # oldest-version convention


def test_inflight_swap_tokenwise_gate_differs_from_whole_trajectory():
    """Eq. 8 per version segment weights the stale segment only; the
    whole-trajectory gate averages it away.  (Acceptance criterion.)"""
    traj, p2, _ = _swap_trajectory()
    full = np.concatenate([traj.prompt, traj.tokens])
    logp, _, _ = score_tokens(BUNDLE, p2, jnp.asarray(full)[None],
                              prompt_len=len(traj.prompt))
    tv_tokens = 0.5 * np.abs(
        np.exp(np.asarray(logp[0]) - traj.log_beta) - 1.0)
    # Threshold at the trajectory-mean TV: the whole-trajectory gate
    # sits exactly on its boundary (weight 1), while segmentwise the
    # pre-swap segment (scored under the *new* policy) differs from the
    # post-swap one, so one segment lands above the mean.
    delta = 2.0 * float(tv_tokens.mean())
    payload = (tv_tokens, traj.versions)

    class _Item:
        def __init__(self, p):
            self.payload, self.meta = p, {}

    tok_item, whole_item = _Item(payload), _Item(payload)
    tok_dec = TokenwiseTVGate(
        delta, lambda p: p, mode="downweight").admit(tok_item)
    whole_dec = TVGatedAdmission(
        delta, lambda p: float(np.mean(p[0])),
        mode="downweight").admit(whole_item)
    assert whole_dec.admit and whole_dec.weight == 1.0
    assert tok_dec.admit and tok_dec.weight != whole_dec.weight
    segs = tok_item.meta["tv_segments"]
    assert [s["version"] for s in segs] == [0, 1]
    assert sum(s["tokens"] for s in segs) == traj.num_tokens
    assert any(s["weight"] < 1.0 for s in segs)


# --- engine-driven threaded regime ------------------------------------------


def test_threaded_engine_regime_tags_per_token_versions():
    """The rewired threaded regime drives the engine; queue items carry
    the full per-token version vector and the oldest-version tag."""
    store = PolicyStore(PARAMS, capacity=4)
    queue = TrajectoryQueue(maxsize=4)
    eng = ServeEngine(BUNDLE, store=store, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1.0,
                      seed=11)
    stream = [(PROMPTS[i % 3], 6) for i in range(4)]
    it = iter(stream)
    regime = make_regime(
        "threaded_engine", store, queue,
        lambda: next(it, None), engine=eng, max_items=4)
    regime.start()
    # Publish while the engine is still warming up its first dispatch:
    # every trajectory must then see the swap (deterministically).
    store.publish(jax.tree.map(lambda x: x + 0.001, PARAMS))
    try:
        consumed = []
        while (item := queue.get(learner_version=store.version,
                                 timeout=30.0)) is not None:
            consumed.append(item)
            store.publish(jax.tree.map(
                lambda x: x + 0.001, store.latest()[0]))
        assert len(consumed) == 4
        for item in consumed:
            versions = item.meta["versions"]
            assert len(versions) == item.payload.num_tokens
            assert item.behavior_version == min(versions)
            assert item.lag >= 0
        # learner published while serving: some trajectory saw a
        # non-zero version (the engine swapped in-flight)
        assert max(max(i.meta["versions"]) for i in consumed) > 0
    finally:
        regime.stop()


def test_threaded_engine_regime_requires_shared_store():
    store, other = PolicyStore(PARAMS, 2), PolicyStore(PARAMS, 2)
    eng = ServeEngine(BUNDLE, store=other, num_blocks=8, block_size=4,
                      max_batch=1, max_seq_len=32)
    with pytest.raises(ValueError, match="share"):
        make_regime("threaded_engine", store, TrajectoryQueue(),
                    lambda: None, engine=eng)


# --- tracing provenance (acceptance: trace == ServeStats, spans balance) ----


from repro.metrics.runtime_metrics import collect_serve_stats  # noqa: E402
from repro.obs import Tracer  # noqa: E402


def _assert_balanced(events):
    """Every sync B nests and closes; every async b gets its e."""
    stacks = {}
    opens = {}
    for ev in events:
        key = (ev.pid, ev.tid)
        if ev.ph == "B":
            stacks.setdefault(key, []).append(ev.name)
        elif ev.ph == "E":
            assert stacks.get(key), f"E {ev.name} on empty track {key}"
            assert stacks[key][-1] == ev.name, (
                f"E {ev.name} closes {stacks[key][-1]}")
            stacks[key].pop()
        elif ev.ph == "b":
            opens[(ev.name, ev.id)] = opens.get((ev.name, ev.id), 0) + 1
        elif ev.ph == "e":
            assert opens.get((ev.name, ev.id), 0) > 0, (
                f"e {ev.name} id={ev.id} never opened")
            opens[(ev.name, ev.id)] -= 1
    assert all(not s for s in stacks.values()), f"left open: {stacks}"
    assert all(n == 0 for n in opens.values()), f"async open: {opens}"


def _token_events(tr):
    return [e for e in tr.events() if e.ph == "i" and e.name == "token"]


def test_tracing_matches_stats_under_preemption_churn():
    """Full-detail trace of the preemption-churn config: spans balance,
    and the per-token event stream reproduces every request's tokens,
    versions, and the engine's aggregate counters exactly."""
    tr = Tracer(detail="full")
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=7, block_size=4,
                      max_batch=3, max_seq_len=64, temperature=1e-4,
                      seed=0, tracer=tr)
    reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
    trajs = {t.request_id: t for t in eng.run(max_steps=400)}
    assert eng.stats.preemptions > 0
    evs = tr.events()
    _assert_balanced(evs)

    toks = _token_events(tr)
    assert len(toks) == eng.stats.tokens_out == sum(BUDGETS)
    by_rid = {}
    for ev in toks:
        by_rid.setdefault(ev.args["rid"], []).append(ev)
    assert set(by_rid) == {r.request_id for r in reqs}
    for rid, seq in by_rid.items():
        np.testing.assert_array_equal(
            [e.args["tok"] for e in seq], trajs[rid].tokens)
        np.testing.assert_array_equal(
            [e.args["v"] for e in seq], trajs[rid].versions)

    preempts = [e for e in evs if e.ph == "i" and e.name == "preempt"]
    assert len(preempts) == eng.stats.preemptions
    retires = [e for e in evs if e.ph == "i" and e.name == "retire"]
    assert len(retires) == len(reqs)
    assert {e.args["rid"] for e in retires} == set(by_rid)

    # Latency histograms saw every emission: one TTFT per request, one
    # inter-token gap per remaining token (preemption gaps included).
    stats = collect_serve_stats(eng)
    assert stats["ttft_count"] == len(reqs)
    assert stats["inter_token_count"] == eng.stats.tokens_out - len(reqs)
    assert stats["request_latency_count"] == len(reqs)
    assert stats["queue_wait_count"] >= len(reqs) + eng.stats.preemptions


def test_tracing_swap_provenance_matches_versions():
    """In-flight swap: the trace's swap instant and per-token version
    stream agree with the trajectory's recorded provenance, and the
    swap-to-first-stale-token histogram fires exactly once."""
    tr = Tracer(detail="full")
    store = PolicyStore(SWAP_PARAMS, capacity=4)
    eng = ServeEngine(BUNDLE, store=store, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1.0,
                      seed=3, tracer=tr)
    eng.submit(PROMPTS[0], 12)
    for _ in range(5):
        assert not eng.step()
    store.publish(jax.tree.map(lambda x: x + 0.01, SWAP_PARAMS))
    traj = eng.run(max_steps=200)[0]
    _assert_balanced(tr.events())

    swaps = [e for e in tr.events() if e.ph == "i" and e.name == "swap"]
    assert len(swaps) == 1 == eng.stats.swaps
    assert swaps[0].args == {"old": 0, "new": 1}
    toks = _token_events(tr)
    np.testing.assert_array_equal(
        [e.args["v"] for e in toks], traj.versions)
    assert traj.versions[0] == 0 and traj.versions[-1] == 1
    # every post-swap token was emitted after the swap instant
    first_new = next(e for e in toks if e.args["v"] == 1)
    assert first_new.ts >= swaps[0].ts
    assert collect_serve_stats(eng)["swap_to_stale_count"] == 1


def test_tracing_speculative_rollback_accounting():
    """Adversarial draft: rollback instants account for exactly the
    drafted-minus-accepted tokens ServeStats reports."""
    tr = Tracer(detail="full")
    bad_draft = lambda req, k: np.zeros((k,), np.int32)  # noqa: E731
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1e-4,
                      seed=0, speculate_k=3, draft=bad_draft, tracer=tr)
    for r in PROMPTS[:2]:
        eng.submit(r, 8)
    eng.run(max_steps=400)
    _assert_balanced(tr.events())
    assert eng.stats.drafted_tokens > 0
    rejected = sum(
        e.args["rejected"] for e in tr.events()
        if e.ph == "i" and e.name == "rollback")
    assert rejected == eng.stats.drafted_tokens - eng.stats.accepted_tokens
    assert rejected > 0
    # host-callable drafts don't dispatch a model, so no "draft" span —
    # but every speculative round runs the fused verify.
    verifies = [e for e in tr.events()
                if e.ph == "B" and e.name == "verify"]
    assert len(verifies) > 0


def test_tracing_off_emits_nothing_and_matches_traced_run():
    """NULL_TRACER (the default) records nothing, and tracing does not
    perturb generation: greedy outputs are identical with and without
    a full-detail tracer attached."""
    from repro.obs import NULL_TRACER

    def _run(tracer):
        eng = ServeEngine(BUNDLE, PARAMS, num_blocks=7, block_size=4,
                          max_batch=3, max_seq_len=64, temperature=1e-4,
                          seed=0, tracer=tracer)
        reqs = [eng.submit(r, n) for r, n in zip(PROMPTS, BUDGETS)]
        trajs = {t.request_id: t for t in eng.run(max_steps=400)}
        return [trajs[r.request_id].tokens for r in reqs]

    before = len(NULL_TRACER)
    plain = _run(None)
    assert len(NULL_TRACER) == before == 0
    tr = Tracer(detail="full")
    traced = _run(tr)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)


ROUND_PHASES = ["serve.schedule", "serve.upload", "serve.dispatch",
                "serve.result_wait", "serve.record"]


def _host_spans(log_dir):
    """``serve.*`` annotations of a ``jax.profiler`` capture, as
    ``(start_ns, end_ns, name)`` in start order."""
    from jax.profiler import ProfileData

    path = sorted(log_dir.glob("**/*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for plane in data.planes if plane.name.startswith("/host")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("serve."))


def test_annotated_rounds_nest_their_phases_in_the_profile(tmp_path):
    """``annotate=True`` with no tracer: a profiler capture of a varlen
    round and a decode round holds one ``serve.step`` per round, with
    the round's phases nested in it in order, and the varlen round's
    result wait inside ``serve.chunked_round``."""
    eng = ServeEngine(BUNDLE, PARAMS, num_blocks=32, block_size=4,
                      max_batch=2, max_seq_len=64, temperature=1e-4,
                      seed=0, annotate=True)
    eng.submit(PROMPTS[0], 4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()                      # the prompt's tile: varlen
        eng.step()                      # one decode chunk
    finally:
        jax.profiler.stop_trace()
    assert eng.stats.tokens_out == 2
    spans = _host_spans(tmp_path)
    steps = [s for s in spans if s[2] == "serve.step"]
    assert len(steps) == 2
    for (a, b, _), outer in zip(steps, ("serve.chunked_round",
                                        "serve.decode")):
        inner = [s for s in spans if a <= s[0] and s[1] <= b
                 and s[2] != "serve.step"]
        assert [s[2] for s in inner] == \
            [ROUND_PHASES[0], outer] + ROUND_PHASES[1:]
        phases = [s for s in inner if s[2] in ROUND_PHASES]
        for prev, nxt in zip(phases, phases[1:]):
            assert prev[1] <= nxt[0]
        # the round's span encloses its upload, dispatch, result wait
        # and bookkeeping
        o0, o1, _ = inner[1]
        assert all(o0 <= s[0] and s[1] <= o1 for s in phases[1:])
    assert len(spans) == 2 * (len(ROUND_PHASES) + 2)
