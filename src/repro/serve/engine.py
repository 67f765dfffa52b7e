"""Continuous-batching decode engine with in-flight versioned weight swap.

One :meth:`ServeEngine.step` is one decode iteration for *all* running
slots: the scheduler first admits/preempts/extends (so the batch stays
full), admitted requests are prefilled into their pages, then a single
jitted ``decode_step_paged`` advances every active slot one token
through the paged-attention kernel.  Requests retire the moment they
emit EOS or hit their own ``max_new_tokens`` — nobody waits for the
slowest row, which is the entire throughput argument continuous
batching makes over the phase-locked ``rollout.sampler.generate`` loop
(kept as the static-batch fallback).

**In-flight weight swap**: when constructed over a
``runtime.PolicyStore``, the engine re-reads ``store.latest()`` every
``swap_interval`` steps — *between* decode steps, never inside one — so
a learner publish lands mid-generation.  Every emitted token records
the policy version that produced its logits; a finished trajectory
therefore carries a per-token version vector and per-token ``log_beta``
(the β_T term), exactly the provenance the paper's TV machinery needs
when the behavior policy changes *within* a trajectory
(``runtime.admission.TokenwiseTVGate`` consumes it per version
segment).

Preemption recomputes KV (re-prefill over prompt + already-emitted
tokens) rather than retracting tokens: emitted tokens may already be
streamed to a client and their recorded (log_beta, version) provenance
stays valid — the re-prefill only rebuilds cache rows.

**Speculative decode** (``speculate_k > 0``): a cheap *draft* policy
proposes ``k`` tokens per slot and the latest policy scores all of them
in one multi-token dispatch (``decode_step_paged_multi``), accepting a
prefix via the Leviathan accept rule (``rollout.sampler.
speculative_accept``).  The draft slot is either **self-speculation** —
a lagged PolicyStore version, pinned so learner publishes can't evict
it, which turns the very staleness the paper studies into actor-side
throughput — a separate small draft model, or a host callable (the
benchmark's zero-cost oracle).  Model drafts keep their *own* paged
pool addressed by the same block tables (draft K/V differ from verifier
K/V), so rollback after a rejection is a pure ``pos`` rewind on both:
rejected rows are simply overwritten by the next chunk, no page copies,
no retraction of emitted tokens, and preemption's recompute path is
untouched.  Emitted tokens are distributed exactly as the verifier's
policy, so per-token ``log_beta``/``version`` provenance — and
everything downstream that consumes it (TV-gate admission) — is
identical to non-speculative serving; speculative greedy decode is
token-exact with non-speculative greedy decode at any acceptance rate.

**Chunked ragged prefill** (``chunked_prefill=True``, default): an
admission's unmatched suffix is split into tiles of ``prefill_chunk``
rows and streamed through the same varlen paged kernel the decode and
verify steps use (``decode_step_paged_varlen``) — one dispatch per
round carries every decode-eligible slot's single-token row *and* the
pending prefill tiles as ragged ``(row_start, row_len)`` rows, bounded
by ``dispatch_budget`` tokens.  A long prompt therefore never blocks
in-flight decodes for a full prefill dispatch: decode rows ride every
round (they are reserved out of the budget first) and the prompt
streams in beside them, which is what bounds p99 inter-token latency
under bursty long-prompt load (``benchmarks.bench_serve --burst``
measures exactly that).  A partially-prefilled request holds its pages
but is not decode-eligible until its last chunk lands; greedy output
is token-exact with the unchunked engine, prefix cache, speculation
and sharding included.

**Batched prefill** (``chunked_prefill=False`` + ``batch_prefill=
True``): the legacy one-dispatch-per-padded-length prefill path, kept
behind a ``DeprecationWarning`` for comparison benchmarks; admissions
of the same padded prompt length stack into one prefill dispatch.

**Sharded serve** (``mesh=...``): the paged pool partitions its NB
(page) axis over the mesh's ``data`` axis; the scheduler places every
request's pages on ONE shard (balancing live slots per shard) and the
paged kernels dispatch through ``shard_map`` (``kernels.ops``) with
shard-local block tables — foreign slots mask to zero and a psum
recombines the batch, so sharded greedy output is **token-exact** with
the single-device engine, speculation and preemption included, and the
per-shard pool buffers still update in place.  ``mesh=None`` is the
single-shard special case of the same code path.

**Prefix caching** (``prefix_cache=True``): full KV pages are
content-addressed (chained hash over token ids, salted with the policy
version and arch identity) and refcounted; admissions whose committed
ids extend a resident prefix share those pages read-only and prefill
only the unmatched suffix through the multi-token paged step — best-of-N
fan-out pays ~1x prefill instead of Nx.  A match ending mid-page is
resolved by copy-on-write *at admission prefill* (the matched rows are
copied into the request's own fresh page before its divergent suffix
appends), so decode and speculative writes only ever touch exclusively
owned pages; an in-flight weight swap invalidates stale entries through
the version salt alone.  Greedy output is token-exact with the unshared
engine — speculation, preemption and sharding included (matches are
shard-local; the scheduler prefers the shard with the longest match).

**Adaptive speculation** (``speculate_adaptive=True``): a per-slot EMA
of the measured draft acceptance rate adapts the per-round draft
length between 1 and ``speculate_k`` — slots that keep rejecting stop
paying for long drafts; the chosen-k histogram lands in
``collect_serve_stats``.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import EOS, PAD
from repro.distributed.sharding import replicated, shard_paged_pool
from repro.kernels.ops import mesh_data_size
from repro.metrics.runtime_metrics import LagHistogram, collect_serve_stats
from repro.models.registry import ModelBundle
from repro.obs.registry import MetricsRegistry
from repro.obs.tracer import NULL_TRACER, Tracer, mirrored
from repro.models.transformer import (copy_page_rows,
                                      write_prefill_batch_to_pages)
from repro.rollout.sampler import _top_p_filter, speculative_accept
from repro.serve.paged_cache import (RECLAIMED, PrefixKey, make_allocator,
                                     prefix_key)
from repro.serve.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    RequestState,
)


@dataclass(frozen=True)
class ServedTrajectory:
    """A finished request with per-token provenance.

    ``versions[t]`` is the policy version whose logits produced
    ``tokens[t]`` — constant when no swap happened mid-request, a step
    function across swap boundaries otherwise.  ``behavior_version`` is
    the *oldest* of them (the conservative representative the runtime's
    max-lag admission keys on, matching the mixture regime's
    convention).
    """

    request_id: int
    prompt: np.ndarray          # [P] int32
    tokens: np.ndarray          # [N] int32 (includes EOS when emitted)
    log_beta: np.ndarray        # [N] float32 behavior log-probs
    versions: np.ndarray        # [N] int64 producing policy versions
    mask: np.ndarray            # [N] float32 (all ones; EOS is scored)
    finish_reason: str          # "eos" | "length"
    latency_s: float            # submit -> finish wall time
    num_preemptions: int

    @property
    def behavior_version(self) -> int:
        return int(self.versions.min()) if self.versions.size else 0

    @property
    def num_tokens(self) -> int:
        return int(self.tokens.shape[0])


@dataclass
class ServeStats:
    steps: int = 0               # scheduling rounds (one chunk each)
    decode_steps: int = 0        # decode iterations (1 per spec round)
    prefills: int = 0            # requests prefilled
    prefill_dispatches: int = 0  # prefill launches (< prefills when batched)
    finished: int = 0
    tokens_out: int = 0
    preemptions: int = 0
    swaps: int = 0
    occupancy_sum: float = 0.0   # emitting slots summed over decode steps
    # Speculative decode: drafted = k per active slot per round;
    # accepted counts draft tokens that survived verification
    # (corrections are emitted but not "accepted").
    spec_rounds: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    # Prefix cache: KV rows actually computed by prefill dispatches
    # (suffix-only under a prefix hit) and COW page copies performed.
    prefill_tokens: int = 0
    cow_copies: int = 0
    # Resilience: deadline-expired requests retired with a (possibly
    # empty) "timeout" trajectory, and speculation auto-disable events.
    timeouts: int = 0
    spec_autodisables: int = 0

    def as_dict(self) -> Dict[str, Any]:
        d = dict(self.__dict__)
        d["mean_occupancy"] = (
            self.occupancy_sum / self.decode_steps
            if self.decode_steps else 0.0
        )
        d["acceptance_rate"] = (
            self.accepted_tokens / self.drafted_tokens
            if self.drafted_tokens else 0.0
        )
        return d


class ModelDraft:
    """Draft policy with its own paged pool (self-spec or a small model).

    ``version`` is the PolicyStore version the params were pinned from
    (None for fixed-params drafts); ``version_offset`` non-None marks a
    *self-speculation* draft that re-resolves ``latest + offset`` after
    every verifier weight swap.  The pool shares the verifier's page
    ids/tables but holds this draft's own K/V (different weights write
    different rows), so scheduler allocation covers both pools at once.
    """

    def __init__(self, bundle: ModelBundle, params: Any,
                 version: Optional[int], version_offset: Optional[int],
                 num_blocks: int, block_size: int, mesh: Any = None
                 ) -> None:
        if bundle.decode_step_paged is None or bundle.init_paged_cache is None:
            raise ValueError(
                f"draft arch {bundle.cfg.name} cannot run the paged path")
        self.bundle = bundle
        self.params = (params if mesh is None
                       else jax.device_put(params, replicated(mesh)))
        self.version = version
        self.version_offset = version_offset
        # The draft pool shards exactly like the verifier pool (same NB
        # axis, same shard-local tables), so one placement decision
        # covers both.
        self.pages = shard_paged_pool(
            bundle.init_paged_cache(num_blocks, block_size), mesh)


class CallableDraft:
    """Host-side draft: ``fn(request, k) -> up-to-k int32 token ids``.

    Zero-cost proposals (n-gram lookups, the benchmark's replay oracle).
    The proposal is treated as a deterministic one-hot draft
    distribution, which keeps the accept rule exactly
    verifier-distribution-preserving.
    """

    version: Optional[int] = None
    version_offset = None

    def __init__(self, fn: Callable[[Request, int], Any]) -> None:
        self.fn = fn


def _jit(fn: Callable, name: Optional[str] = None, **kw) -> Callable:
    """``jax.jit`` under a stable program name (``fn``'s own, or
    ``name``): a profiler capture's XLA Modules line shows
    ``jit_<name>`` for each program the engine dispatches."""
    if name is not None:
        fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **kw)


class ServeEngine:
    """Paged-KV continuous-batching generation over a ModelBundle."""

    def __init__(
        self,
        bundle: ModelBundle,
        params: Any = None,
        *,
        num_blocks: int = 64,
        block_size: int = 8,
        max_batch: int = 4,
        max_seq_len: int = 256,
        decode_chunk: int = 1,
        store: Any = None,            # Optional[runtime.PolicyStore]
        swap_interval: int = 1,
        temperature: float = 1.0,
        top_p: float = 1.0,
        seed: int = 0,
        kernel_mode: Optional[str] = None,
        speculate_k: int = 0,
        draft: Any = None,
        batch_prefill: bool = True,
        chunked_prefill: bool = True,
        prefill_chunk: int = 16,
        dispatch_budget: int = 32,
        mesh: Any = None,
        speculate_adaptive: bool = False,
        prefix_cache: bool = False,
        window_reclaim: bool = True,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        annotate: bool = False,
        injector: Any = None,
        request_deadline_s: Optional[float] = None,
        spec_disable_after: int = 8,
    ) -> None:
        """``speculate_k > 0`` turns on speculative decode; ``draft`` is
        one of ``("version", -n)`` (self-speculation from the store's
        ring, pinned), ``("params", p)`` (same arch, fixed params),
        ``("model", bundle, params)`` (separate draft model), a callable
        ``fn(request, k) -> token ids``, or None (defaults to
        ``("version", -1)`` with a store, else the verifier's own params).

        ``mesh`` (a jax Mesh with a ``data`` axis) shards the paged
        pool's NB axis over that axis; ``num_blocks`` is the TOTAL pool
        and must divide by the data-axis size.  ``speculate_adaptive``
        adapts the per-round draft length in ``[1, speculate_k]`` from
        each slot's measured acceptance EMA.

        ``chunked_prefill=True`` (default) streams each admission's prefill
        as ragged tiles of ``prefill_chunk`` rows through the varlen
        kernel, unified with decode rows in one dispatch of at most
        ``dispatch_budget`` tokens (decode rows are reserved first and
        always all run; the budget throttles prefill tiles).
        ``chunked_prefill=False`` falls back to the deprecated
        batched-prefill path.

        ``prefix_cache=True`` content-addresses full KV pages (hash over
        token ids, salted with the policy version and arch identity):
        admissions whose prompt prefix is already resident share those
        pages read-only (refcounted) and prefill only the unmatched
        suffix, with copy-on-write when the match ends mid-page — greedy
        output stays token-exact with the unshared engine.
        ``window_reclaim`` (on by default, a no-op unless EVERY layer is
        windowed) releases pages entirely behind the widest sliding
        window back to the pool.

        ``tracer`` (an ``obs.Tracer``; default: the zero-cost
        ``NULL_TRACER``) records the request lifecycle and the spans of
        each round (``step`` enclosing ``schedule``, then ``decode``,
        ``chunked_round``, ``prefill``, ``suffix_prefill``, ``draft`` or
        ``verify``, each split into ``upload``, ``dispatch``,
        ``result_wait`` and ``record``); ``metrics`` (an
        ``obs.MetricsRegistry``; default: a fresh one) receives the
        engine's serve-time histograms (TTFT, inter-token, queue-wait,
        request latency) and the ``"serve"`` snapshot producer.
        ``annotate=True`` mirrors those spans into ``jax.profiler`` as
        ``serve.<name>`` annotations (``obs.mirrored``: the tracer's
        own, or annotations alone when there is no tracer), so a
        profiler capture shows each phase of a round beside the
        device's work.
        """
        if bundle.decode_step_paged is None:
            from repro.models.transformer import paged_arch_unsupported

            raise ValueError(
                f"{bundle.cfg.name}: {paged_arch_unsupported(bundle.cfg)}")
        if params is None and store is None:
            raise ValueError("need params or a PolicyStore")
        self.bundle = bundle
        self.store = store
        if annotate:
            tracer = mirrored(tracer)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.metrics.register_producer(
            "serve", lambda: collect_serve_stats(self))
        # Serve-time latency histograms: observed always (raw-sample
        # reservoirs are cheap), reported via collect_serve_stats.
        self._h_ttft = self.metrics.histogram("serve_ttft_s")
        # TTFT decomposition: queue-wait (submit -> the admission that
        # produced the first token) + prefill-compute (that admission ->
        # first token).  The two sum to TTFT exactly; a request
        # preempted before its first token books its earlier attempts
        # as queue time.
        self._h_ttft_queue = self.metrics.histogram("serve_ttft_queue_s")
        self._h_ttft_prefill = self.metrics.histogram(
            "serve_ttft_prefill_s")
        self._h_inter_token = self.metrics.histogram("serve_inter_token_s")
        self._h_queue_wait = self.metrics.histogram("serve_queue_wait_s")
        self._h_latency = self.metrics.histogram("serve_request_latency_s")
        self._h_swap_stale = self.metrics.histogram("serve_swap_to_stale_s")
        self._swap_mono: Optional[float] = None   # last in-flight swap
        # 0 = never poll the store: weights move only by direct
        # params/version assignment (the serve-backed trainer's
        # forced-lag producer pins snapshots this way).
        self.swap_interval = max(int(swap_interval), 0)
        if store is not None:
            self.params, self.version = store.latest()
        else:
            self.params, self.version = params, 0
        self.mesh = mesh
        self.num_shards = mesh_data_size(mesh)
        if num_blocks % self.num_shards != 0:
            raise ValueError(
                f"num_blocks {num_blocks} must divide over the mesh's "
                f"data axis ({self.num_shards} shards)")
        if mesh is not None:
            # Replicate the weights over the mesh up front; swapped-in
            # versions get the same placement in _maybe_swap.
            self.params = jax.device_put(self.params, replicated(mesh))
        self.block_size = block_size
        max_blocks_per_request = -(-max_seq_len // block_size)
        self.prefix_cache = bool(prefix_cache)
        self.allocator = make_allocator(
            num_blocks, block_size, self.num_shards,
            prefix_cache=self.prefix_cache, tracer=self.tracer)
        windows = [bundle.cfg.window_for_layer(layer)
                   for layer in range(bundle.cfg.n_layers)]
        self._reclaim_window = (
            max(windows) if window_reclaim and windows
            and all(w is not None for w in windows) else None)
        if injector is None:
            from repro.resilience.faults import NULL_INJECTOR

            injector = NULL_INJECTOR
        self.injector = injector
        self.scheduler = ContinuousBatchingScheduler(
            self.allocator, max_batch=max_batch,
            max_blocks_per_request=max_blocks_per_request,
            prefix_fn=self._prefix_key if self.prefix_cache else None,
            reclaim_window=self._reclaim_window,
            tracer=self.tracer,
            request_deadline_s=request_deadline_s,
            registry=self.metrics)
        self.pages = shard_paged_pool(
            bundle.init_paged_cache(num_blocks, block_size), mesh)
        self.max_batch = max_batch
        self._tables = np.zeros(
            (max_batch, max_blocks_per_request), np.int32)
        self._pos = np.zeros((max_batch,), np.int32)
        self._active = np.zeros((max_batch,), bool)
        self._last_tok = np.zeros((max_batch,), np.int32)
        self._slot_shard = np.zeros((max_batch,), np.int32)
        # Device-side cache of slot-state arrays that only change on
        # scheduling events (admit/preempt/retire/extend).  A host->
        # device transfer of even a [B] int32 costs tens of µs on CPU;
        # at one decode/verify dispatch per round that overhead is a
        # measurable slice of a small-model round, so arrays are
        # re-uploaded only when their host copy actually changed.
        self._dev_cache: Dict[str, Tuple[np.ndarray, jax.Array]] = {}
        self._key = jax.random.PRNGKey(seed)
        self.stats = ServeStats()
        self._kernel_mode = kernel_mode
        temp = max(float(temperature), 1e-6)
        self._temperature = temp
        self._top_p = float(top_p)

        def _sample(logits, key):
            logits = logits.astype(jnp.float32) / temp
            logits = _top_p_filter(logits, top_p)
            tok = jax.random.categorical(key, logits, axis=-1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
            return tok.astype(jnp.int32), lp

        self._sample = _sample
        chunk = max(int(decode_chunk), 1)
        self.decode_chunk = chunk

        def serve_decode(params, token, pages, tables, pos, active,
                         remaining, slot_shard, key):
            """`chunk` decode steps in one dispatch (lax.scan).

            Multi-step decode amortizes the per-step host round-trip —
            the cost that otherwise hands the phase-locked loop (whose
            whole decode is one fused scan) most of the continuous
            engine's structural win back.  Rows terminate *in-graph*
            (EOS or per-request budget via `remaining`); a retiring row
            idles masked until the chunk ends, bounding wasted work at
            chunk-1 steps per retirement.
            """
            def body(carry, k_t):
                token, pos, active, emitted, pages = carry
                out, pages = bundle.decode_step_paged(
                    params, token, pages, tables, pos, active,
                    kernel_mode=kernel_mode, mesh=mesh,
                    slot_shard=slot_shard)
                tok, lp = _sample(out.logits, k_t)
                mask = active
                tok = jnp.where(active, tok, jnp.int32(PAD))
                lp = jnp.where(active, lp, 0.0)
                pos = pos + active.astype(jnp.int32)
                emitted = emitted + active.astype(jnp.int32)
                active = jnp.logical_and(active, tok != EOS)
                active = jnp.logical_and(active, emitted < remaining)
                return (tok, pos, active, emitted, pages), (tok, lp, mask)

            keys = jax.random.split(key, chunk)
            carry = (token, pos, active, jnp.zeros_like(pos), pages)
            (_, _, _, _, pages), (toks, lps, masks) = jax.lax.scan(
                body, carry, keys)
            return toks, lps, masks, pages

        # Pages are donated and every op that touches them inside the
        # dispatch is in-place-able (decode_step_paged's hoisted layer
        # loop + kernels.ops.paged_kv_write), so the pool is updated
        # in place end to end: per-chunk cost is O(rows written), flat
        # in num_blocks (bench_serve --sweep-blocks measures it).
        self._decode = _jit(serve_decode, donate_argnums=(2,))
        # Prefill dispatches are keyed by (padded length, group size):
        # batched prefill stacks same-padded-length admissions into one
        # forward, so bursty admissions stop paying a dispatch each.
        self.batch_prefill = bool(batch_prefill)
        # Chunked ragged prefill (default): admissions stream through
        # the unified varlen dispatch instead of the legacy batched
        # prefill forward.  Varlen dispatches are keyed by the padded
        # round width so steady tile sizes reuse one trace.
        self.chunked_prefill = bool(chunked_prefill)
        if not self.chunked_prefill:
            warnings.warn(
                "chunked_prefill=False: the batched-prefill serve path "
                "is deprecated and kept only for comparison; chunked "
                "ragged prefill is the default",
                DeprecationWarning, stacklevel=2)
        self.prefill_chunk = max(int(prefill_chunk), 1)
        self.dispatch_budget = max(int(dispatch_budget), 1)
        self._varlen_fns: Dict[int, Any] = {}
        self._draft_varlen_fns: Dict[int, Any] = {}
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        self._draft_prefill_fns: Dict[Tuple[int, int], Any] = {}
        # Prefix-cache dispatches: suffix-only prefills keyed by (padded
        # suffix length, group size); COW copies keyed by group size
        # (jit retraces per pool shape, so one cache serves the
        # verifier and draft pools).
        self._suffix_fns: Dict[Tuple[int, int], Any] = {}
        self._draft_suffix_fns: Dict[Tuple[int, int], Any] = {}
        self._cow_fns: Dict[int, Any] = {}

        # -- speculative decode ---------------------------------------------
        self.speculate_k = max(int(speculate_k), 0)
        self.speculate_adaptive = bool(speculate_adaptive) and \
            self.speculate_k > 1
        self.draft: Any = None
        self._draft_lag_hist = LagHistogram()
        self._chosen_k_hist = LagHistogram()
        # Per-slot EMA of the measured acceptance rate; optimistic start
        # (1.0 = draft the full k) reset whenever a slot is re-admitted.
        self._accept_ema = np.ones((max_batch,), np.float64)
        self._accept_ema_alpha = 0.3
        # Graceful degradation: after `spec_disable_after` consecutive
        # rounds where the verifier rejected EVERY drafted token,
        # speculation turns itself off and the engine falls back to the
        # plain chunked decode path (the verifier's corrected tokens
        # keep the output exact either way — this is purely cutting the
        # wasted draft work of a hopeless draft).
        self.spec_disable_after = max(int(spec_disable_after), 1)
        self.spec_disabled = False
        self._all_reject_rounds = 0
        if self.speculate_k:
            if bundle.decode_step_paged_multi is None:
                raise ValueError(
                    f"{bundle.cfg.name}: multi-token verify unavailable "
                    "(paged path unsupported)")
            self.draft = self._build_draft(draft, num_blocks, block_size)
            # Draft/verify dispatches are keyed by the round's draft
            # length: adaptive speculation walks k in [1, speculate_k].
            self._draft_fns: Dict[int, Any] = {}
            self._verify_fns: Dict[int, Any] = {}

    # -- request intake ------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int] | np.ndarray,
        max_new_tokens: int,
        request_id: Optional[int] = None,
    ) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        kw = {} if request_id is None else {"request_id": request_id}
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens, **kw)
        self.scheduler.submit(req)
        return req

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    # -- internals -----------------------------------------------------------

    def _next_key(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def _dev(self, name: str, arr: np.ndarray) -> jax.Array:
        """Device copy of `arr`, re-uploaded only when it changed."""
        hit = self._dev_cache.get(name)
        if hit is not None and np.array_equal(hit[0], arr):
            return hit[1]
        val = jnp.asarray(arr)
        self._dev_cache[name] = (arr.copy(), val)
        return val

    def _maybe_swap(self) -> None:
        if self.store is None or not self.swap_interval:
            return
        if self.stats.steps % self.swap_interval != 0:
            return
        params, version = self.store.latest()
        if version != self.version:
            old = self.version
            if self.mesh is not None:
                params = jax.device_put(params, replicated(self.mesh))
            self.params, self.version = params, version
            self.stats.swaps += 1
            # Swap-to-first-stale-token latency: armed here, observed by
            # the next _record (whose token carries the new version).
            self._swap_mono = time.monotonic()
            tr = self.tracer
            if tr.enabled:
                tr.instant("swap", tid="engine", old=old, new=version)
            self._refresh_draft()

    # -- prefix cache ---------------------------------------------------------

    @staticmethod
    def _committed_ids(req: Request) -> np.ndarray:
        """prompt + all emitted tokens except the pending one — exactly
        the rows a (re)prefill must make resident."""
        if not req.tokens:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)])

    def _prefix_key(self, req: Request) -> PrefixKey:
        """Version-salted content address of `req`'s committed ids.

        The salt folds in the policy version and arch identity, so an
        in-flight weight swap invalidates every stale entry without a
        flush — KV rows are a function of (token ids, params, arch).
        Cached per (version, length): recomputed only after a swap or
        when emitted tokens extend the committed ids (re-admission).
        """
        ids = self._committed_ids(req)
        cached = getattr(req, "_pkey", None)
        if cached is not None and cached[0] == (self.version, len(ids)):
            return cached[1]
        cfg = self.bundle.cfg
        salt = (
            f"{cfg.name}|{cfg.arch_type}|L{cfg.n_layers}|d{cfg.d_model}"
            f"|h{cfg.n_heads}x{cfg.n_kv_heads}|w{cfg.sliding_window}"
            f"/{cfg.global_every}|v{self.version}|bs{self.block_size}"
        ).encode()
        key = prefix_key(ids, self.block_size, salt)
        req._pkey = ((self.version, len(ids)), key)
        return key

    # -- speculative draft slot ----------------------------------------------

    def _build_draft(self, spec: Any, num_blocks: int,
                     block_size: int) -> Any:
        if callable(spec):
            return CallableDraft(spec)
        if spec is None:
            spec = (("version", -1) if self.store is not None
                    else ("params", self.params))
        kind = spec[0]
        if kind == "version":
            if self.store is None:
                raise ValueError("draft=('version', n) needs a PolicyStore")
            offset = int(spec[1])
            if offset > 0:
                raise ValueError(f"draft version offset must be <= 0, "
                                 f"got {offset}")
            params, version = self.store.pin_lagged(offset)
            return ModelDraft(self.bundle, params, version, offset,
                              num_blocks, block_size, self.mesh)
        if kind == "params":
            return ModelDraft(self.bundle, spec[1], None, None,
                              num_blocks, block_size, self.mesh)
        if kind == "model":
            return ModelDraft(spec[1], spec[2], None, None,
                              num_blocks, block_size, self.mesh)
        raise ValueError(f"unknown draft spec {spec!r}")

    def _refresh_draft(self) -> None:
        """Re-pin the self-speculation draft at latest+offset after a
        verifier swap.  Draft pool rows written under the old draft
        weights stay (they only shape *proposals*; correctness rides on
        the verifier) and age out as decode overwrites them."""
        d = self.draft
        if not isinstance(d, ModelDraft) or d.version_offset is None:
            return
        # Atomic resolve+pin: a learner publish between a resolve and a
        # separate pin could evict the resolved version mid-handoff.
        params, target = self.store.pin_lagged(d.version_offset)
        if target == d.version:
            self.store.release(target)   # unchanged; drop the extra pin
            return
        self.store.release(d.version)
        if self.mesh is not None:
            params = jax.device_put(params, replicated(self.mesh))
        d.params, d.version = params, target

    def _draft_fn(self, k: int):
        fn = self._draft_fns.get(k)
        if fn is None:
            fn = self._draft_fns[k] = self._make_draft_fn(k)
        return fn

    def _verify_fn(self, k: int):
        fn = self._verify_fns.get(k)
        if fn is None:
            fn = self._verify_fns[k] = self._make_verify_fn(k)
        return fn

    def _make_draft_fn(self, k: int):
        """k draft decode steps in one dispatch over the draft pool."""
        bundle_d = self.draft.bundle
        sample = self._sample
        kernel_mode = self._kernel_mode
        mesh = self.mesh

        def serve_draft(params, token, pages, tables, pos, active, cap,
                        slot_shard, key):
            def body(carry, k_t):
                token, pos, pages = carry
                # Past-allocation steps go inactive: their write would
                # land in the table's pad pages (owned by someone else),
                # and their proposals can never be recorded anyway.
                step_active = jnp.logical_and(active, pos < cap)
                out, pages = bundle_d.decode_step_paged(
                    params, token, pages, tables, pos, step_active,
                    kernel_mode=kernel_mode, mesh=mesh,
                    slot_shard=slot_shard)
                tok, _ = sample(out.logits, k_t)
                tok = jnp.where(step_active, tok, jnp.int32(PAD))
                return (tok, pos + 1, pages), (tok, out.logits)

            keys = jax.random.split(key, k)
            (_, _, pages), (toks, logits) = jax.lax.scan(
                body, (token, pos, pages), keys)
            return toks.T, logits.transpose(1, 0, 2), pages

        return _jit(serve_draft, donate_argnums=(2,))

    def _make_verify_fn(self, k: int):
        """Single-dispatch multi-token verify + accept + pos arithmetic."""
        bundle = self.bundle
        kernel_mode = self._kernel_mode
        mesh = self.mesh
        temp, top_p = self._temperature, self._top_p

        def serve_verify(params, first_tok, draft_toks, draft_logits,
                         pages, tables, pos, active, cap, slot_shard, key):
            # Queries = [t0, d1..d_{k-1}]: logits after query i score
            # draft token d_{i+1}.  All k rows are written; a rejection
            # just rewinds pos and the next chunk overwrites them.
            queries = jnp.concatenate(
                [first_tok[:, None], draft_toks[:, :-1]], axis=1)
            out, pages = bundle.decode_step_paged_multi(
                params, queries, pages, tables, pos, active, cap,
                kernel_mode=kernel_mode, mesh=mesh, slot_shard=slot_shard)
            toks, lps, n_acc, n_emit = speculative_accept(
                out.logits, draft_toks, draft_logits, key,
                temperature=temp, top_p=top_p)
            toks = jnp.where(active[:, None], toks, jnp.int32(PAD))
            lps = jnp.where(active[:, None], lps, 0.0)
            n_acc = jnp.where(active, n_acc, 0)
            n_emit = jnp.where(active, n_emit, 0)
            return toks, lps, n_acc, n_emit, pages

        return _jit(serve_verify, donate_argnums=(4,))

    # -- prefill (batched admissions) ----------------------------------------

    def _prefill_admitted(self, admitted: List[Request],
                          finished: List[ServedTrajectory]) -> None:
        """(Re)compute KV rows for every admitted request; same-padded-
        length admissions share one prefill dispatch (batch_prefill).

        Prefix-cache hits take the *suffix* path instead: their matched
        rows are already resident in shared pages, so only the unmatched
        tail runs (plus a COW copy when the match ends mid-page).  Dense
        (unmatched) prefills dispatch first and suffix prefills follow
        in admission order — an admission can only match pages indexed
        by *earlier* admissions, so every page a suffix dispatch reads
        was written by an earlier dispatch of this round or a previous
        round.
        """
        if not admitted:
            return
        dense: List = []
        shared: List = []
        for req in admitted:
            ids = self._committed_ids(req)
            plen = int(ids.shape[0])
            item = (req, ids, plen)
            (shared if req.num_matched > 0 else dense).append(item)
        groups: Dict[int, List] = {}
        for req, ids, plen in dense:
            padded = -(-plen // self.block_size) * self.block_size
            groups.setdefault(padded, []).append((req, ids, plen))
        for padded in sorted(groups):
            items = groups[padded]
            size = len(items) if self.batch_prefill else 1
            for lo in range(0, len(items), size):
                self._prefill_group(padded, items[lo:lo + size], finished)
        # Only runs of requests sharing exactly the same source pages
        # (best-of-N siblings) batch into one suffix dispatch — such
        # requests cannot depend on each other's writes.
        i = 0
        while i < len(shared):
            j = i + 1
            if self.batch_prefill:
                while j < len(shared) and \
                        self._suffix_compatible(shared[i], shared[j]):
                    j += 1
            self._suffix_group(shared[i:j], finished)
            i = j

    @staticmethod
    def _suffix_compatible(a, b) -> bool:
        ra, _, pa = a
        rb, _, pb = b
        nsf = ra.num_shared_full
        return (pa == pb and ra.num_matched == rb.num_matched
                and ra.shard == rb.shard and nsf == rb.num_shared_full
                and ra.blocks[:nsf] == rb.blocks[:nsf]
                and ra.cow_src == rb.cow_src)

    def _cow_fn(self, n: int):
        fn = self._cow_fns.get(n)
        if fn is None:
            mesh = self.mesh

            def serve_cow(pages, src, dst, rows, home):
                return copy_page_rows(pages, src, dst, rows, home,
                                      mesh=mesh)

            fn = self._cow_fns[n] = _jit(serve_cow, donate_argnums=(0,))
        return fn

    def _suffix_group(self, items: List,
                      finished: List[ServedTrajectory]) -> None:
        """COW copies + suffix-only prefill for one compatible group."""
        n = len(items)
        req0, _, plen0 = items[0]
        m = req0.num_matched
        t = plen0 - m                      # unmatched suffix length
        t_pad = -(-t // 4) * 4             # pad for jit-cache reuse
        width = self._tables.shape[1]
        toks = np.full((n, t_pad), PAD, np.int32)
        tables = np.zeros((n, width), np.int32)
        pos = np.full((n,), m, np.int32)
        cap = np.zeros((n,), np.int32)
        home = np.zeros((n,), np.int32)
        for i, (req, ids, plen) in enumerate(items):
            toks[i, :t] = ids[m:]
            tables[i] = self.allocator.padded_table(req.blocks, width)
            cap[i] = plen
            home[i] = req.shard or 0
        if req0.cow_src is not None:
            # The match ends mid-page: copy the matched rows of the
            # shared source page into each request's own fresh page
            # (the table already points there), then drop the source
            # ref the scheduler reserved.
            src = np.zeros((n,), np.int32)
            dst = np.zeros((n,), np.int32)
            rows = np.zeros((n,), np.int32)
            for i, (req, ids, plen) in enumerate(items):
                src[i], rows[i] = req.cow_src
                dst[i] = req.blocks[req.num_shared_full]
            fn = self._cow_fn(n)
            args = (jnp.asarray(src), jnp.asarray(dst),
                    jnp.asarray(rows), jnp.asarray(home))
            self.pages = fn(self.pages, *args)
            if isinstance(self.draft, ModelDraft):
                self.draft.pages = fn(self.draft.pages, *args)
            for req, _, _ in items:
                self.allocator.release([req.cow_src[0]], req.shard or 0)
                req.cow_src = None
            self.stats.cow_copies += n
            if self.tracer.enabled:
                self.tracer.instant("cow_copy", tid="engine", n=n)
        key = (t_pad, n)
        fn = self._suffix_fns.get(key)
        if fn is None:
            fn = self._suffix_fns[key] = self._make_suffix()
        dfn = None
        if isinstance(self.draft, ModelDraft):
            dfn = self._draft_suffix_fns.get(key)
            if dfn is None:
                dfn = self._draft_suffix_fns[key] = \
                    self._make_suffix(draft=True)
        tr = self.tracer
        with tr.span("suffix_prefill", tid="engine", n=n, suffix=t):
            with tr.span("upload", tid="engine"):
                toks_d = jnp.asarray(toks)
                tables_d = jnp.asarray(tables)
                pos_d = jnp.asarray(pos)
                cap_d = jnp.asarray(cap)
                home_d = jnp.asarray(home)
                tlast = jnp.full((n,), t - 1, jnp.int32)
                rng = self._next_key()
            with tr.span("dispatch", tid="engine"):
                tok, lp, self.pages = fn(
                    self.params, toks_d, self.pages, tables_d, pos_d,
                    cap_d, home_d, tlast, rng)
                if dfn is not None:
                    self.draft.pages = dfn(
                        self.draft.params, toks_d, self.draft.pages,
                        tables_d, pos_d, cap_d, home_d)
            with tr.span("result_wait", tid="engine"):
                tok_np, lp_np = np.asarray(tok), np.asarray(lp)
            with tr.span("record", tid="engine"):
                self.stats.prefills += n
                self.stats.prefill_dispatches += 1
                self.stats.prefill_tokens += n * t
                for i, (req, ids, plen) in enumerate(items):
                    slot = req.slot
                    self._tables[slot] = tables[i]
                    self._pos[slot] = plen
                    req.num_prefilled = plen
                    if req.tokens:             # resume after preemption
                        self._last_tok[slot] = req.tokens[-1]
                    else:
                        self._record(req, int(tok_np[i]), float(lp_np[i]),
                                     finished)

    def _make_suffix(self, draft: bool = False):
        """Suffix-only prefill: T unmatched tokens through the
        multi-token paged step (writes their rows, attends over the
        shared prefix), sampling from the last true suffix position.
        The draft variant fills the draft pool and discards logits."""
        bundle = self.draft.bundle if draft else self.bundle
        sample = self._sample
        kernel_mode = self._kernel_mode
        mesh = self.mesh

        def serve_suffix_prefill(params, tokens, pages, tables, pos, cap,
                                 home, tlast=None, key=None):
            ones = jnp.ones((tokens.shape[0],), bool)
            out, pages = bundle.decode_step_paged_multi(
                params, tokens, pages, tables, pos, ones, cap,
                kernel_mode=kernel_mode, mesh=mesh, slot_shard=home)
            if draft:
                return pages
            last = jnp.take_along_axis(
                out.logits, tlast[:, None, None], axis=1)[:, 0]
            tok, lp = sample(last, key)
            return tok, lp, pages

        return _jit(serve_suffix_prefill, donate_argnums=(2,),
                    name="serve_draft_suffix_prefill" if draft else None)

    def _prefill_group(self, padded: int, items: List,
                       finished: List[ServedTrajectory]) -> None:
        n = len(items)
        rows = np.zeros((n, padded), np.int32)
        kv_valid = np.zeros((n, padded), bool)
        plens = np.zeros((n,), np.int32)
        home = np.zeros((n,), np.int32)
        tables = np.zeros((n, self._tables.shape[1]), np.int32)
        for i, (req, ids, plen) in enumerate(items):
            rows[i, :plen] = ids
            kv_valid[i, :plen] = True
            plens[i] = plen
            home[i] = req.shard or 0
            tables[i] = self.allocator.padded_table(
                req.blocks, self._tables.shape[1])
        key = (padded, n)
        fn = self._prefill_fns.get(key)
        if fn is None:
            fn = self._prefill_fns[key] = self._make_prefill(padded, n)
        dfn = None
        if isinstance(self.draft, ModelDraft):
            dfn = self._draft_prefill_fns.get(key)
            if dfn is None:
                dfn = self._draft_prefill_fns[key] = \
                    self._make_draft_prefill(padded, n)
        tr = self.tracer
        with tr.span("prefill", tid="engine", n=n, padded=padded):
            with tr.span("upload", tid="engine"):
                args = (jnp.asarray(rows), jnp.asarray(kv_valid),
                        jnp.asarray(tables), jnp.asarray(plens),
                        jnp.asarray(home))
                rng = self._next_key()
            with tr.span("dispatch", tid="engine"):
                toks, lps, self.pages = fn(self.params, *args, self.pages,
                                           rng)
                if dfn is not None:
                    self.draft.pages = dfn(self.draft.params, *args,
                                           self.draft.pages)
            with tr.span("result_wait", tid="engine"):
                toks_np, lps_np = np.asarray(toks), np.asarray(lps)
            with tr.span("record", tid="engine"):
                self.stats.prefills += n
                self.stats.prefill_dispatches += 1
                self.stats.prefill_tokens += int(plens.sum())
                for i, (req, ids, plen) in enumerate(items):
                    slot = req.slot
                    self._tables[slot] = tables[i]
                    self._pos[slot] = plen
                    req.num_prefilled = plen
                    if req.tokens:             # resume after preemption
                        self._last_tok[slot] = req.tokens[-1]
                    else:
                        self._record(req, int(toks_np[i]),
                                     float(lps_np[i]), finished)

    def _make_prefill(self, padded_len: int, n: int):
        bundle = self.bundle
        sample = self._sample
        mesh = self.mesh

        def serve_prefill(params, prompts, kv_valid, blocks, plens, home,
                          pages, key):
            out = bundle.forward(
                params, prompts, return_cache=True,
                cache_len=padded_len, kv_valid=kv_valid)
            # Donated pages + per-tile dynamic_update_slice writes: each
            # request's prefill lands in the pool without copying it
            # (under a mesh: only on its home shard, via shard_map).
            pages = write_prefill_batch_to_pages(
                out.cache["k"], out.cache["v"], pages, blocks, plens,
                home, mesh=mesh)
            last = jnp.take_along_axis(
                out.logits, (plens - 1)[:, None, None], axis=1)[:, 0]
            tok, lp = sample(last, key)
            return tok, lp, pages

        return _jit(serve_prefill, donate_argnums=(6,))

    def _make_draft_prefill(self, padded_len: int, n: int):
        bundle_d = self.draft.bundle
        mesh = self.mesh

        def serve_draft_prefill(params, prompts, kv_valid, blocks, plens,
                                home, pages):
            out = bundle_d.forward(
                params, prompts, return_cache=True,
                cache_len=padded_len, kv_valid=kv_valid)
            return write_prefill_batch_to_pages(
                out.cache["k"], out.cache["v"], pages, blocks, plens,
                home, mesh=mesh)

        return _jit(serve_draft_prefill, donate_argnums=(6,))

    def _record(self, req: Request, tok: int, lp: float,
                finished: List[ServedTrajectory]) -> None:
        """Book one emitted token; retire the request when done."""
        now = time.monotonic()
        if req.first_token_time is None:
            req.first_token_time = now
            self._h_ttft.observe(now - req.submit_time)
            if req.admit_time is not None:
                # Exact decomposition: queue + prefill == TTFT.
                self._h_ttft_queue.observe(
                    req.admit_time - req.submit_time)
                self._h_ttft_prefill.observe(now - req.admit_time)
        else:
            self._h_inter_token.observe(now - req.last_emit_time)
        req.last_emit_time = now
        if self._swap_mono is not None:
            # First token after an in-flight swap: how long until the
            # new policy's first served token reached a client.
            self._h_swap_stale.observe(now - self._swap_mono)
            self._swap_mono = None
        req.tokens.append(tok)
        req.log_beta.append(lp)
        req.versions.append(self.version)
        self.stats.tokens_out += 1
        tr = self.tracer
        if tr.full:
            # Per-token provenance stream: trace_report builds the
            # lag-at-emission histogram from exactly these events.
            lag = (self.store.version - self.version
                   if self.store is not None else 0)
            tr.instant("token", tid="tokens", rid=req.request_id,
                       v=self.version, lag=lag, tok=tok)
        if tok == EOS:
            self._finish(req, "eos", finished)
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length", finished)
        else:
            self._last_tok[req.slot] = tok

    def _finish(self, req: Request, reason: str,
                finished: List[ServedTrajectory]) -> None:
        slot = req.slot
        self.scheduler.retire(req, reason)
        self._clear_slot(slot)
        self.stats.finished += 1
        self._h_latency.observe(req.finish_time - req.submit_time)
        n = len(req.tokens)
        finished.append(ServedTrajectory(
            request_id=req.request_id,
            prompt=req.prompt,
            tokens=np.asarray(req.tokens, np.int32),
            log_beta=np.asarray(req.log_beta, np.float32),
            versions=np.asarray(req.versions, np.int64),
            mask=np.ones((n,), np.float32),
            finish_reason=reason,
            latency_s=req.finish_time - req.submit_time,
            num_preemptions=req.num_preemptions,
        ))

    @property
    def _spec_k_active(self) -> int:
        """Speculation depth for this round: 0 once auto-disabled."""
        return 0 if self.spec_disabled else self.speculate_k

    def _timeout_finish(self, req: Request,
                        finished: List[ServedTrajectory]) -> None:
        """Book a deadline-expired request (already retired by the
        scheduler) as a trajectory: whatever tokens it emitted, marked
        ``finish_reason="timeout"`` — an empty, fully-masked row when
        it never produced one."""
        self._clear_slot(req.slot)
        self.stats.finished += 1
        self.stats.timeouts += 1
        latency = (req.finish_time or time.monotonic()) - req.submit_time
        self._h_latency.observe(latency)
        n = len(req.tokens)
        finished.append(ServedTrajectory(
            request_id=req.request_id,
            prompt=req.prompt,
            tokens=np.asarray(req.tokens, np.int32),
            log_beta=np.asarray(req.log_beta, np.float32),
            versions=np.asarray(req.versions, np.int64),
            mask=np.ones((n,), np.float32),
            finish_reason="timeout",
            latency_s=latency,
            num_preemptions=req.num_preemptions,
        ))

    def _clear_slot(self, slot: Optional[int]) -> None:
        if slot is None:
            return
        self._active[slot] = False
        self._tables[slot] = 0
        self._pos[slot] = 0
        self._last_tok[slot] = 0

    # -- chunked ragged prefill ----------------------------------------------

    def _varlen_fn(self, t_pad: int, draft: bool = False):
        cache = self._draft_varlen_fns if draft else self._varlen_fns
        fn = cache.get(t_pad)
        if fn is None:
            fn = cache[t_pad] = self._make_varlen(t_pad, draft=draft)
        return fn

    def _make_varlen(self, t_pad: int, draft: bool = False):
        """One unified ragged dispatch: every slot contributes
        ``row_len[b]`` token rows starting at absolute position
        ``row_start[b]`` — a decode row is ``row_len == 1``, a prefill
        tile is ``row_len`` up to the chunk size, an idle/gated slot is
        ``row_len == 0``.  Verifier variant samples each slot's next
        token from the logits of its last live row; the draft variant
        only fills the draft pool (proposal rows must exist there for
        later speculative rounds) and discards logits."""
        bundle = self.draft.bundle if draft else self.bundle
        sample = self._sample
        kernel_mode = self._kernel_mode
        mesh = self.mesh

        def serve_varlen(params, tokens, pages, tables, row_start, row_len,
                         cap, slot_shard, key=None):
            out, pages = bundle.decode_step_paged_varlen(
                params, tokens, pages, tables, row_start, row_len, cap,
                kernel_mode=kernel_mode, mesh=mesh, slot_shard=slot_shard)
            if draft:
                return pages
            last = jnp.clip(row_len - 1, 0, t_pad - 1)
            logits = jnp.take_along_axis(
                out.logits, last[:, None, None], axis=1)[:, 0]
            tok, lp = sample(logits, key)
            return tok, lp, pages

        return _jit(serve_varlen, donate_argnums=(2,),
                    name="serve_draft_varlen" if draft else None)

    def _chunked_round(self, finished: List[ServedTrajectory]) -> bool:
        """One unified varlen round, or False when no prefill is pending
        (steady state: the caller falls through to the normal decode/
        speculative path, which this mode leaves untouched).

        Budgeting: every decode-eligible slot's single-token row is
        reserved out of ``dispatch_budget`` first — bounding the round's
        token count is only useful if in-flight requests keep emitting —
        and the remainder goes to prefill tiles of at most
        ``prefill_chunk`` rows, FIFO by admission order, with a one-row
        floor for the oldest ready tile so admission always progresses.

        Prefix-cache gating: an admission's pages are registered at
        admission but their rows land over future rounds, so a pending
        request whose shared (or COW-source) pages belong to another
        request still computing them waits until those rows land.  The
        gate is acyclic — a dependency always points at an *earlier*
        admission, so the oldest pending request is never gated — and a
        mid-prefill owner that aborts (preemption, deadline) preempts
        its gated dependents through the scheduler's ``_abort_prefill``.
        """
        pending = [r for r in self.scheduler.running if not r.prefill_done]
        if not pending:
            return False
        tr = self.tracer
        bs = self.block_size
        # Pages whose rows an in-flight prefill has not computed yet,
        # keyed to their unique computing owner (sharers only ever hold
        # such a page inside their own matched prefix, which is already
        # complete, so they never appear as owners).
        incomplete: Dict[Tuple[int, int], Request] = {}
        for r in pending:
            sh = r.shard or 0
            for j, b in enumerate(r.blocks):
                if b != RECLAIMED and (j + 1) * bs > r.num_prefilled:
                    incomplete[(sh, b)] = r

        def _gated(r: Request) -> bool:
            sh = r.shard or 0
            deps = list(r.blocks[:r.num_shared_full])
            if r.cow_src is not None:
                deps.append(r.cow_src[0])
            return any(incomplete.get((sh, b)) not in (None, r)
                       for b in deps)

        order = {id(r): i for i, r in
                 enumerate(self.scheduler._admission_order)}
        ready = sorted((r for r in pending if not _gated(r)),
                       key=lambda r: order[id(r)])
        decode_reqs = [r for r in self.scheduler.running if r.prefill_done]
        budget_left = self.dispatch_budget - len(decode_reqs)
        chunks: List[Tuple[Request, np.ndarray, int]] = []
        for r in ready:
            ids = self._committed_ids(r)
            left = int(ids.shape[0]) - r.num_prefilled
            n = min(self.prefill_chunk, left, budget_left)
            if n <= 0:
                if chunks:
                    continue
                n = 1    # floor: the oldest ready tile always advances
            budget_left -= n
            chunks.append((r, ids, n))
        # Deferred copy-on-write: a mid-page match is copied into the
        # request's own page right before its FIRST tile (cow_src is
        # cleared by the copy, so presence == not yet copied); the tile
        # then attends over the copied rows like any resident prefix.
        cow_items = [r for r, _, _ in chunks if r.cow_src is not None]
        if cow_items:
            n = len(cow_items)
            src = np.zeros((n,), np.int32)
            dst = np.zeros((n,), np.int32)
            rows = np.zeros((n,), np.int32)
            home = np.zeros((n,), np.int32)
            for i, r in enumerate(cow_items):
                src[i], rows[i] = r.cow_src
                dst[i] = r.blocks[r.num_shared_full]
                home[i] = r.shard or 0
            fn = self._cow_fn(n)
            args = (jnp.asarray(src), jnp.asarray(dst),
                    jnp.asarray(rows), jnp.asarray(home))
            self.pages = fn(self.pages, *args)
            if isinstance(self.draft, ModelDraft):
                self.draft.pages = fn(self.draft.pages, *args)
            for r in cow_items:
                self.allocator.release([r.cow_src[0]], r.shard or 0)
                r.cow_src = None
            self.stats.cow_copies += n
            if tr.enabled:
                tr.instant("cow_copy", tid="engine", n=n)
        t_max = max([n for _, _, n in chunks], default=1)
        t_pad = -(-t_max // 4) * 4     # pad for jit-cache reuse
        B = self.max_batch
        tokens = np.full((B, t_pad), PAD, np.int32)
        row_start = np.zeros((B,), np.int32)
        row_len = np.zeros((B,), np.int32)
        cap = np.zeros((B,), np.int32)
        for r in decode_reqs:
            s = r.slot
            tokens[s, 0] = self._last_tok[s]
            row_start[s] = self._pos[s]
            row_len[s] = 1
            cap[s] = len(r.blocks) * bs
        for r, ids, n in chunks:
            s = r.slot
            tokens[s, :n] = ids[r.num_prefilled:r.num_prefilled + n]
            row_start[s] = r.num_prefilled
            row_len[s] = n
            cap[s] = len(r.blocks) * bs
        n_tile_tokens = sum(n for _, _, n in chunks)
        fn = self._varlen_fn(t_pad)
        dfn = (self._varlen_fn(t_pad, draft=True)
               if isinstance(self.draft, ModelDraft) else None)
        with tr.span("chunked_round", tid="engine",
                     decode=len(decode_reqs), tiles=len(chunks),
                     tokens=int(row_len.sum())):
            with tr.span("upload", tid="engine"):
                tokens_d = jnp.asarray(tokens)
                rs_d = jnp.asarray(row_start)
                rl_d = jnp.asarray(row_len)
                cap_d = jnp.asarray(cap)
                tables_d = self._dev("tables", self._tables)
                shard_d = self._dev("slot_shard", self._slot_shard)
                rng = self._next_key()
            with tr.span("dispatch", tid="engine"):
                tok, lp, self.pages = fn(
                    self.params, tokens_d, self.pages, tables_d,
                    rs_d, rl_d, cap_d, shard_d, rng)
                if dfn is not None:
                    # Mirror the same rows into the draft pool (draft
                    # weights): later speculative rounds read them as
                    # resident context.
                    self.draft.pages = dfn(
                        self.draft.params, tokens_d, self.draft.pages,
                        tables_d, rs_d, rl_d, cap_d, shard_d)
            with tr.span("result_wait", tid="engine"):
                toks_np, lps_np = np.asarray(tok), np.asarray(lp)
            with tr.span("record", tid="engine"):
                self._record_chunked(chunks, decode_reqs, n_tile_tokens,
                                     toks_np, lps_np, finished)
        return True

    def _record_chunked(self, chunks: List, decode_reqs: List[Request],
                        n_tile_tokens: int, toks_np: np.ndarray,
                        lps_np: np.ndarray,
                        finished: List[ServedTrajectory]) -> None:
        """Book a varlen round's results: tiles advance their prefill
        cursor (a last tile emits the request's first token), decode
        rows emit one token each."""
        self.stats.prefill_dispatches += 1
        self.stats.prefill_tokens += n_tile_tokens
        if decode_reqs:
            self.stats.decode_steps += 1
            self.stats.occupancy_sum += float(len(decode_reqs))
        for r, ids, n in chunks:
            slot = r.slot
            r.num_prefilled += n
            self._pos[slot] = r.num_prefilled
            if r.num_prefilled >= int(ids.shape[0]):
                # Last chunk landed: the slot becomes decode-eligible
                # and the round's sampled token (from the final prompt
                # row's logits) is its first emission — unless the
                # request is resuming after preemption, whose pending
                # token was already recorded before the preemption.
                r.prefill_done = True
                self._active[slot] = True
                self.stats.prefills += 1
                if r.tokens:
                    self._last_tok[slot] = r.tokens[-1]
                else:
                    self._record(r, int(toks_np[slot]),
                                 float(lps_np[slot]), finished)
        for r in decode_reqs:
            slot = r.slot
            self._pos[slot] += 1
            self._record(r, int(toks_np[slot]), float(lps_np[slot]),
                         finished)

    # -- the decode loop -----------------------------------------------------

    def step(self) -> List[ServedTrajectory]:
        """One scheduling round + decode chunk (or speculative round);
        returns newly finished trajectories."""
        finished: List[ServedTrajectory] = []
        tr = self.tracer
        with tr.span("step", tid="engine"):
            with tr.span("schedule", tid="engine"):
                remaining = self._schedule(finished)
            if self.chunked_prefill and self._chunked_round(finished):
                # A unified varlen round ran (prefill tiles + one decode
                # token per eligible slot); speculation and the
                # multi-step decode chunk resume once no prefill is
                # pending.
                return finished
            if not self._active.any():
                return finished
            if self._spec_k_active:
                with tr.span("spec_round", tid="engine"):
                    self._spec_round(finished)
                return finished
            with tr.span("decode", tid="engine", chunk=self.decode_chunk):
                with tr.span("upload", tid="engine"):
                    token = jnp.asarray(self._last_tok)
                    args = (self._dev("tables", self._tables),
                            jnp.asarray(self._pos),
                            self._dev("active", self._active),
                            self._dev("remaining", remaining),
                            self._dev("slot_shard", self._slot_shard),
                            self._next_key())
                with tr.span("dispatch", tid="engine"):
                    toks, lps, masks, self.pages = self._decode(
                        self.params, token, self.pages, *args)
                with tr.span("result_wait", tid="engine"):
                    toks_np = np.asarray(toks)       # [chunk, B]
                    lps_np = np.asarray(lps)
                    masks_np = np.asarray(masks)
                with tr.span("record", tid="engine"):
                    self._record_decode(toks_np, lps_np, masks_np,
                                        finished)
        return finished

    def _schedule(self, finished: List[ServedTrajectory]) -> np.ndarray:
        """The round's host-side scheduling: swap, deadline sweep,
        admissions (and, off the chunked path, their prefill), the
        slot-state rebuild and the counter samples.  Returns each
        slot's remaining token budget."""
        tr = self.tracer
        self._maybe_swap()
        self.stats.steps += 1
        if self.injector.active:
            # Straggler injection: a matching stall sleeps here, with
            # the deadline clock still running — exactly how a hung
            # slot turns into a timeout retirement.
            self.injector.stall("engine_step", at_step=self.stats.steps)
            for req in self.scheduler.running:
                self.injector.stall("engine_step",
                                    at_step=self.stats.steps,
                                    slot=int(req.slot))
        # Deadline sweep BEFORE scheduling: expired waiting requests
        # never get admitted, expired running ones free their slot and
        # pages (draft pool included — it shares the block tables) for
        # this round's admissions.
        for req in self.scheduler.expire():
            self._timeout_finish(req, finished)
        lookahead = self._spec_k_active or self.decode_chunk
        admitted, _ = self.scheduler.schedule(lookahead=lookahead)
        self.stats.preemptions = self.scheduler.preemptions
        if admitted:
            now = time.monotonic()
            for req in admitted:
                self._h_queue_wait.observe(now - req.queued_time)
                req.admit_time = now
        for req in admitted:
            # Fresh occupant: the acceptance EMA of whoever held this
            # slot before says nothing about the new request.
            self._accept_ema[req.slot] = 1.0
        if self.chunked_prefill:
            # Admissions stream in as ragged tiles over the next rounds
            # (no prefill dispatch here): mark them pending and park the
            # write cursor at the first uncomputed row.
            for req in admitted:
                req.prefill_done = False
                self._pos[req.slot] = req.num_prefilled
        else:
            self._prefill_admitted(admitted, finished)
        # Rebuild slot state from the scheduler: preempted/retired slots
        # (their Request no longer knows its old index) go quiet, and
        # running rows pick up pages the extension pass just granted.
        # A mid-prefill request keeps its slot but is not decode-
        # eligible until its last chunk lands (prefill_done is always
        # True on the legacy path by this point).
        by_slot = {r.slot: r for r in self.scheduler.running}
        remaining = np.zeros((self.max_batch,), np.int32)
        for slot in range(self.max_batch):
            req = by_slot.get(slot)
            if req is None:
                self._clear_slot(slot)
            else:
                self._active[slot] = req.prefill_done
                self._slot_shard[slot] = req.shard or 0
                self._tables[slot] = self.allocator.padded_table(
                    req.blocks, self._tables.shape[1])
                remaining[slot] = req.max_new_tokens - len(req.tokens)
        if self.prefix_cache:
            self._assert_write_pages_private()
        if tr.enabled:
            # Counter tracks: load, pool occupancy (per shard), live
            # policy lag (publishes the engine hasn't swapped in yet).
            sched = self.scheduler
            tr.counter("serve_load", waiting=float(len(sched.waiting)),
                       running=float(len(sched.running)))
            alloc = self.allocator
            if getattr(alloc, "num_shards", 1) > 1:
                tr.counter("pool_free", **{
                    f"shard{s}": float(f)
                    for s, f in enumerate(alloc.free_by_shard())})
            else:
                tr.counter("pool_free", free=float(alloc.num_free))
            if self.store is not None:
                tr.counter("policy_lag",
                           lag=float(self.store.version - self.version))
        return remaining

    def _record_decode(self, toks_np: np.ndarray, lps_np: np.ndarray,
                       masks_np: np.ndarray,
                       finished: List[ServedTrajectory]) -> None:
        """Book a decode chunk's ``[chunk, B]`` results, each slot's
        tokens in order up to its first masked step."""
        self.stats.occupancy_sum += float(masks_np.sum())
        self.stats.decode_steps += self.decode_chunk
        for req in list(self.scheduler.running):
            slot = req.slot
            self._pos[slot] += int(masks_np[:, slot].sum())
            for t in range(self.decode_chunk):
                if not masks_np[t, slot]:
                    break
                self._record(req, int(toks_np[t, slot]),
                             float(lps_np[t, slot]), finished)

    def _assert_write_pages_private(self) -> None:
        """Invariant guard: the page a slot's next decode write lands in
        must be exclusively owned (ref 1).  Shared pages are read-only;
        matched full pages sit strictly below the write position and a
        mid-page match was COW'd at prefill — a violation here means a
        refcount/COW bug, caught before it corrupts another request.

        Two chunked-prefill exemptions.  Mid-prefill requests are
        skipped outright: their registered-but-not-yet-complete pages
        may already be shared by a *gated* later admission (one blocked
        until exactly these rows land) — the gate in ``_chunked_round``
        is what keeps the sharer from reading early.  And a deferred
        COW reservation is allowed on a write page: until the
        dependent's first tile performs the copy, its ``cow_src`` ref
        keeps the owner's partial page above 1 — safe because the copy
        reads rows strictly below the owner's write offset (the match
        limit excludes the owner's last committed token, let alone its
        future writes).
        """
        cow_pending: Dict[Tuple[int, int], int] = {}
        for r in self.scheduler.running:
            if r.cow_src is not None:
                k = ((r.shard or 0), r.cow_src[0])
                cow_pending[k] = cow_pending.get(k, 0) + 1
        for req in self.scheduler.running:
            if not req.prefill_done:
                continue
            idx = int(self._pos[req.slot]) // self.block_size
            if idx >= len(req.blocks):
                continue
            page = req.blocks[idx]
            if page >= 0:
                refs = self.allocator.ref(page, req.shard or 0)
                expect = 1 + cow_pending.get(((req.shard or 0), page), 0)
                if refs != expect:
                    raise RuntimeError(
                        f"request {req.request_id}: decode write page "
                        f"{page} has refcount {refs} (expected {expect})"
                        f" — copy-on-write invariant violated")

    def _choose_k(self) -> int:
        """Per-round draft length.

        Non-adaptive: the configured ``speculate_k``.  Adaptive: each
        slot targets ``1 + round(ema * (k_max - 1))`` from its own
        acceptance EMA and the round runs the mean target over active
        slots — one dispatch serves the whole batch, so per-slot k is
        a compromise; the mean neither starves high-acceptance slots
        (max would overdraft the bad ones) nor throttles them to the
        worst slot (min).
        """
        k_max = self.speculate_k
        if not self.speculate_adaptive:
            return k_max
        act = self._active
        if not act.any():
            return k_max
        targets = np.clip(
            np.rint(1.0 + self._accept_ema[act] * (k_max - 1)), 1, k_max)
        return int(np.clip(np.rint(targets.mean()), 1, k_max))

    def _note_spec_round(self, accepted: int, n_active: int) -> None:
        """Track consecutive all-reject rounds; auto-disable the draft
        once `spec_disable_after` of them land in a row (output is
        unaffected — the verifier's corrections always emit — but a
        draft that never lands a token is pure overhead)."""
        if n_active <= 0:
            return
        if accepted > 0:
            self._all_reject_rounds = 0
            return
        self._all_reject_rounds += 1
        if (self._all_reject_rounds >= self.spec_disable_after
                and not self.spec_disabled):
            self.spec_disabled = True
            self.stats.spec_autodisables += 1
            self.metrics.counter("spec_autodisable_total").inc()
            if self.tracer.enabled:
                self.tracer.instant(
                    "spec_autodisable", tid="engine",
                    rounds=self._all_reject_rounds,
                    k=self.speculate_k)

    def _spec_round(self, finished: List[ServedTrajectory]) -> None:
        """One draft-then-verify round: k cheap draft steps, one
        multi-token verifier dispatch, accept/rollback by pos rewind."""
        tr = self.tracer
        k = self._choose_k()
        self._chosen_k_hist.record(k)
        cap = np.zeros((self.max_batch,), np.int32)
        for req in self.scheduler.running:
            cap[req.slot] = len(req.blocks) * self.block_size
        if isinstance(self.draft, ModelDraft):
            with tr.span("draft", tid="engine", k=k):
                with tr.span("upload", tid="engine"):
                    token = jnp.asarray(self._last_tok)
                    args = (self._dev("tables", self._tables),
                            jnp.asarray(self._pos),
                            self._dev("active", self._active),
                            self._dev("cap", cap),
                            self._dev("slot_shard", self._slot_shard),
                            self._next_key())
                with tr.span("dispatch", tid="engine"):
                    draft_toks, draft_logits, self.draft.pages = \
                        self._draft_fn(k)(self.draft.params, token,
                                          self.draft.pages, *args)
        else:
            prop_np = np.zeros((self.max_batch, k), np.int32)
            for req in self.scheduler.running:
                prop = np.asarray(
                    self.draft.fn(req, k), np.int32).reshape(-1)[:k]
                prop_np[req.slot, :prop.shape[0]] = prop
            # One-hot proposal logits, built host-side (one transfer
            # instead of per-round device compare/where dispatches).
            vocab = self.bundle.cfg.vocab_size
            oh = np.full((self.max_batch, k, vocab), -1e9, np.float32)
            np.put_along_axis(oh, prop_np[..., None], 0.0, axis=-1)
            with tr.span("upload", tid="engine"):
                draft_toks = jnp.asarray(prop_np)
                draft_logits = jnp.asarray(oh)
        with tr.span("verify", tid="engine", k=k):
            with tr.span("upload", tid="engine"):
                token = jnp.asarray(self._last_tok)
                args = (self._dev("tables", self._tables),
                        jnp.asarray(self._pos),
                        self._dev("active", self._active),
                        self._dev("cap", cap),
                        self._dev("slot_shard", self._slot_shard),
                        self._next_key())
            with tr.span("dispatch", tid="engine"):
                toks, lps, n_acc, n_emit, self.pages = self._verify_fn(k)(
                    self.params, token, draft_toks, draft_logits,
                    self.pages, *args)
            with tr.span("result_wait", tid="engine"):
                toks_np, lps_np, n_acc_np, n_emit_np = jax.device_get(
                    (toks, lps, n_acc, n_emit))
            with tr.span("record", tid="engine"):
                self._record_spec(k, toks_np, lps_np, n_acc_np, n_emit_np,
                                  finished)

    def _record_spec(self, k: int, toks_np: np.ndarray, lps_np: np.ndarray,
                     n_acc_np: np.ndarray, n_emit_np: np.ndarray,
                     finished: List[ServedTrajectory]) -> None:
        """Book a verify round: acceptance statistics, then each slot's
        accepted prefix and correction token."""
        tr = self.tracer
        n_active = int(self._active.sum())
        self.stats.decode_steps += 1
        self.stats.occupancy_sum += float(n_active)
        self.stats.spec_rounds += 1
        self.stats.drafted_tokens += k * n_active
        accepted = int(n_acc_np[self._active].sum())
        self.stats.accepted_tokens += accepted
        self._note_spec_round(accepted, n_active)
        if tr.enabled:
            rejected = k * n_active - accepted
            if rejected:
                tr.instant("rollback", tid="engine", k=k,
                           rejected=rejected)
        if self.speculate_adaptive:
            # Acceptance EMA feeds the next round's adaptive k choice.
            a = self._accept_ema_alpha
            for slot in np.nonzero(self._active)[0]:
                rate = float(n_acc_np[slot]) / k
                self._accept_ema[slot] = (
                    (1.0 - a) * self._accept_ema[slot] + a * rate)
        lag = (None if self.draft.version is None
               else self.version - self.draft.version)
        for req in list(self.scheduler.running):
            slot = req.slot
            n_e = int(n_emit_np[slot])
            # Rollback = rewind: pos covers only the accepted prefix;
            # rejected rows are overwritten by the next round's writes.
            self._pos[slot] += n_e
            if lag is not None and n_e:
                self._draft_lag_hist.record(lag, n_e)
            for t in range(n_e):
                self._record(req, int(toks_np[slot, t]),
                             float(lps_np[slot, t]), finished)
                if req.state is not RequestState.RUNNING:
                    break    # EOS/budget retired it; drop the tail

    def run(self, max_steps: Optional[int] = None
            ) -> List[ServedTrajectory]:
        """Step until every submitted request finished (or max_steps)."""
        out: List[ServedTrajectory] = []
        steps = 0
        while self.has_work:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return out
