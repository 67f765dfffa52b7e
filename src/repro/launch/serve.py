"""Serving launcher: completion generation against an assigned
architecture (the actor side of the async RLVR loop).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-0.5b-reduced \\
      --engine continuous --requests 12 --mixed-lengths 4,8,16,32

``--arch qwen2.5-0.5b`` serves the published widths (24 layers, vocab
151,936; for a TPU), ``--arch qwen2.5-0.5b-reduced`` the 2-layer CPU
smoke reduction.

Two engines:

* ``--engine static`` — the phase-locked fixed-batch ``generate()``
  loop (prefill + lax.scan decode): every request waits for the
  slowest row.  Kept as the baseline/fallback.
* ``--engine continuous`` — the ``repro.serve`` continuous-batching
  engine: paged KV cache, per-request admission/retire between decode
  steps, and (with ``--runtime versioned``) in-flight weight swap from
  the PolicyStore.

Loads a checkpoint when given (--checkpoint), else serves random init —
the point on this host is exercising the serve engines; on TPU the same
paths run under the production mesh with the serve_step shardings
proven by the dry-run.

``--runtime versioned`` routes the weights through the async runtime's
versioned PolicyStore and reports the served policy version **per
request** (a continuous-batching request may straddle versions; its
summary shows the span, e.g. ``v0->v1``).
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _version_tag(versions) -> str:
    """Human summary of the per-token version vector of one request."""
    uniq = sorted(set(int(v) for v in versions))
    if len(uniq) == 1:
        return f"v{uniq[0]}"
    return f"v{uniq[0]}->v{uniq[-1]}"


def _serve_static(args, bundle, params, store, tok, prompts_np, answers):
    from repro.data.mathgen import verify
    from repro.rollout.sampler import generate

    behavior_version = None
    if store is not None:
        params, behavior_version = store.latest()
        print(f"serving policy version {behavior_version} "
              f"(retained: {store.retained_versions()})")
    gen_fn = jax.jit(lambda p, t, k: generate(
        bundle, p, t, k, max_new_tokens=args.max_new_tokens,
        temperature=args.temperature, top_p=args.top_p,
    ))
    key = jax.random.PRNGKey(args.seed + 2)
    res = gen_fn(params, jnp.asarray(prompts_np), key)   # warm
    jax.block_until_ready(res.tokens)
    t0 = time.time()
    res = gen_fn(params, jnp.asarray(prompts_np), key)
    jax.block_until_ready(res.tokens)
    dt = time.time() - t0
    n_tok = prompts_np.shape[0] * args.max_new_tokens
    tag = ("" if behavior_version is None
           else f" [policy v{behavior_version}]")
    print(f"decode: {n_tok} tokens in {dt*1e3:.1f} ms "
          f"({n_tok/dt:.0f} tok/s on this host){tag}")
    comp = np.asarray(res.completion)
    for i in range(min(len(answers), 8)):
        text = tok.decode(comp[i])
        r = verify(text, answers[i])
        vtag = ("" if behavior_version is None
                else f" [policy v{behavior_version}]")
        print(f"  [{i}] -> {text!r} (gold {answers[i]}, reward {r}){vtag}")


def _parse_draft(spec: str, args, bundle, params, tok):
    """--draft grammar: ``version:-n`` (self-speculation from the
    PolicyStore ring), ``model:<arch>`` (small registry draft model),
    ``self`` (verifier's own params; accept-all ceiling)."""
    import jax as _jax

    if spec.startswith("version:"):
        return ("version", int(spec.split(":", 1)[1]))
    if spec.startswith("model:"):
        from repro.configs import launch_config
        from repro.models.registry import build

        dcfg = launch_config(spec.split(":", 1)[1], vocab=tok.vocab_size)
        dbundle = build(dcfg)
        dparams = dbundle.init(_jax.random.PRNGKey(args.seed + 7))
        return ("model", dbundle, dparams)
    if spec == "self":
        return ("params", params)
    raise SystemExit(f"--draft {spec!r}: want version:-n, model:<arch> "
                     "or self")


def _shadow_admission(args, engine, store, bundle, trajs):
    """Replay retired trajectories through a lag controller's admission
    hook — verdict-only (nothing is removed from the serve output), so
    operators can preview what a trainer-side ``--controller`` would do
    to this traffic before wiring it into a training run.

    tv_gate scores each request's completion against the *latest*
    policy (the store head under ``--runtime versioned``, else the
    engine's params); tv_gate_tokenwise additionally segments by the
    request's own per-token version record, so mid-swap requests get
    the per-segment Eq. 8 treatment.  Verdicts land on the engine's
    metrics registry as
    ``serve_shadow_admission_total{controller,outcome,reason}``.
    """
    from repro.core.tv_filter import tv_estimate
    from repro.rollout.sampler import score_tokens
    from repro.runtime import make_controller, parse_controller_spec
    from repro.runtime.queue import TrajectoryItem

    spec = parse_controller_spec(args.controller)
    ref_version = store.version if store is not None else engine.version

    def _score(traj):
        params = store.latest()[0] if store is not None else engine.params
        prompt = np.asarray(traj.prompt)
        row = np.concatenate([prompt, np.asarray(traj.tokens)])
        log_pi, _, _ = score_tokens(
            bundle, params, jnp.asarray(row)[None, :], len(prompt))
        return log_pi

    def tv_fn(traj):
        log_pi = _score(traj)
        return float(tv_estimate(
            log_pi - jnp.asarray(traj.log_beta)[None, :],
            jnp.asarray(traj.mask)[None, :]))

    def token_tv_fn(traj):
        log_pi = np.asarray(_score(traj))[0]
        tv = 0.5 * np.abs(np.exp(log_pi - np.asarray(traj.log_beta)) - 1.0)
        valid = np.asarray(traj.mask) > 0
        return tv[valid], np.asarray(traj.versions)[valid]

    controller = make_controller(spec, tv_fn=tv_fn,
                                 token_tv_fn=token_tv_fn)
    counts = {}
    for t in trajs:
        versions = np.asarray(t.versions)
        oldest = int(versions.min()) if versions.size else ref_version
        newest = int(versions.max()) if versions.size else ref_version
        item = TrajectoryItem(
            payload=t, behavior_version=oldest,
            enqueue_learner_version=ref_version,
            behavior_version_newest=newest,
        )
        item.learner_version_at_consume = ref_version
        d = controller.admit(item)
        outcome = ("drop" if not d.admit
                   else "admit" if d.weight == 1.0 else "downweight")
        counts[(outcome, d.reason)] = counts.get((outcome, d.reason), 0) + 1
        engine.metrics.counter(
            "serve_shadow_admission_total", controller=controller.name,
            outcome=outcome, reason=d.reason).inc()
    total = len(trajs)
    print(f"  shadow controller {spec.canonical()!r} over {total} "
          f"retired requests (verdict-only, nothing dropped):")
    for (outcome, reason), n in sorted(counts.items()):
        print(f"    {outcome:<10} reason={reason:<24} {n}/{total}")


def _serve_continuous(args, bundle, params, store, tok, ds, mesh=None,
                      tracer=None, flush_state=None):
    from repro.data.mathgen import verify
    from repro.serve import ServeEngine

    lengths = [int(x) for x in args.mixed_lengths.split(",")] \
        if args.mixed_lengths else [args.max_new_tokens]
    draft = None
    if args.speculate:
        draft = _parse_draft(args.draft, args, bundle, params, tok)
    engine = ServeEngine(
        bundle, params if store is None else None, store=store,
        num_blocks=args.num_blocks, block_size=args.block_size,
        max_batch=args.max_batch, max_seq_len=args.max_seq_len,
        decode_chunk=args.decode_chunk,
        swap_interval=args.swap_interval, temperature=args.temperature,
        top_p=args.top_p, seed=args.seed + 2,
        speculate_k=args.speculate, draft=draft,
        batch_prefill=not args.no_batch_prefill,
        chunked_prefill=not args.no_chunked_prefill,
        prefill_chunk=args.prefill_chunk,
        dispatch_budget=args.dispatch_budget,
        mesh=mesh, speculate_adaptive=args.speculate_adaptive,
        prefix_cache=args.prefix_cache,
        tracer=tracer, annotate=args.profiler_annotations,
    )
    if flush_state is not None:
        flush_state["metrics"] = engine.metrics
    toks_np, prompts, answers = ds.sample_batch(args.requests)
    meta = {}
    for i in range(args.requests):
        row = toks_np[i]
        row = row[row != tok.pad_id]            # ragged: true prompt only
        for _ in range(max(args.best_of, 1)):
            req = engine.submit(row, lengths[i % len(lengths)])
            meta[req.request_id] = (prompts[i], answers[i])
    t0 = time.time()
    trajs = engine.run(max_steps=args.max_steps)
    dt = time.time() - t0
    from repro.metrics.runtime_metrics import collect_serve_stats

    stats = collect_serve_stats(engine)
    n_tok = stats["tokens_out"]
    print(f"continuous decode: {n_tok} tokens / {len(trajs)} requests in "
          f"{dt*1e3:.1f} ms ({n_tok/dt:.0f} tok/s on this host)")
    lat_tag = "latency n/a (nothing retired; raise --max-steps)"
    if stats["request_latency_count"]:
        lat_tag = (f"latency p50 {stats['request_latency_p50_ms']:.1f} ms "
                   f"p99 {stats['request_latency_p99_ms']:.1f} ms")
    print(f"  occupancy {stats['mean_occupancy']:.2f}/{args.max_batch}, "
          f"prefills {stats['prefills']} "
          f"({stats['prefill_dispatches']} dispatches), "
          f"preemptions {stats['preemptions']}, swaps {stats['swaps']}, "
          f"{lat_tag}")
    if stats["ttft_count"]:
        print(f"  ttft p50 {stats['ttft_p50_ms']:.1f} ms "
              f"p99 {stats['ttft_p99_ms']:.1f} ms, inter-token p50 "
              f"{stats['inter_token_p50_ms']:.2f} ms p99 "
              f"{stats['inter_token_p99_ms']:.2f} ms, queue-wait p50 "
              f"{stats['queue_wait_p50_ms']:.1f} ms")
    if stats.get("num_shards", 1) > 1:
        print(f"  sharded over {stats['num_shards']} shards: "
              f"free pages by shard {stats['pool_free_by_shard']}, "
              f"live slots by shard {stats['live_slots_by_shard']}")
    if stats.get("prefix_cache"):
        print(f"  prefix cache: hit rate "
              f"{stats['prefix_hit_rate']:.2f} "
              f"({stats['prefix_hits']}/{stats['prefix_queries']} "
              f"admissions), token hit rate "
              f"{stats['prefix_token_hit_rate']:.2f} "
              f"({stats['prefix_matched_tokens']} matched / "
              f"{stats['prefill_tokens']} computed), "
              f"cow copies {stats['cow_copies']}, "
              f"cached pages {stats['cached_pages']}, "
              f"evictions {stats['cache_evictions']}")
    if "reclaimed_window_pages" in stats:
        print(f"  window reclamation (W={stats['reclaim_window']}): "
              f"{stats['reclaimed_window_pages']} pages released")
    if args.speculate:
        dv = stats.get("draft_version")
        dtag = ("oracle/callable" if dv is None and engine.draft is not None
                and not hasattr(engine.draft, "pages")
                else f"v{dv}" if dv is not None else "fixed-params")
        print(f"  speculative k={args.speculate}: acceptance "
              f"{stats['acceptance_rate']:.2f} "
              f"({stats['accepted_tokens']}/{stats['drafted_tokens']} "
              f"drafted), draft {dtag}, lag hist "
              f"{stats.get('draft_version_lag_histogram', {})}")
        if args.speculate_adaptive:
            print(f"  adaptive k in [1, {args.speculate}]: chosen-k "
                  f"histogram {stats.get('chosen_k_histogram', {})}")
    for t in sorted(trajs, key=lambda t: t.request_id)[:8]:
        prompt_text, ans = meta[t.request_id]
        text = tok.decode(t.tokens)
        r = verify(text, ans)
        vtag = ("" if store is None
                else f" [policy {_version_tag(t.versions)}]")
        print(f"  [{t.request_id}] -> {text!r} ({t.num_tokens} tok, "
              f"{t.finish_reason}, gold {ans}, reward {r}){vtag}")
    if args.controller:
        _shadow_admission(args, engine, store, bundle, trajs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-0.5b")
    ap.add_argument("--engine", default="static",
                    choices=["static", "continuous"],
                    help="static: phase-locked batch generate(); "
                         "continuous: paged-KV continuous batching")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=None,
                    help="continuous: total requests (default --batch)")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--mixed-lengths", default=None,
                    help="continuous: comma list of per-request "
                         "max-new-tokens, cycled (e.g. 4,8,16,32)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="continuous: decode slots")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--max-steps", type=int, default=10_000)
    ap.add_argument("--decode-chunk", type=int, default=4,
                    help="continuous: decode steps per dispatch "
                         "(scheduling happens between chunks)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="continuous: speculative-decode draft length k "
                         "(0 = off); k drafted tokens are verified in "
                         "one multi-token dispatch")
    ap.add_argument("--draft", default="version:-1",
                    help="draft policy: version:-n (self-speculation "
                         "from the PolicyStore, needs --runtime "
                         "versioned), model:<arch> (small registry "
                         "draft), self (verifier params; accept-all)")
    ap.add_argument("--speculate-adaptive", action="store_true",
                    help="continuous: adapt the per-round draft length "
                         "in [1, --speculate] from each slot's measured "
                         "acceptance-rate EMA")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous: content-address full KV pages and "
                         "share resident prompt prefixes across requests "
                         "(refcounted read-only pages + copy-on-write); "
                         "prefill runs only the unmatched suffix")
    ap.add_argument("--best-of", type=int, default=1,
                    help="continuous: submit each prompt N times "
                         "(best-of-N fan-out — the access pattern "
                         "--prefix-cache collapses to ~1x prefill)")
    ap.add_argument("--no-batch-prefill", action="store_true",
                    help="continuous: prefill admissions one dispatch "
                         "per request (default stacks same-padded-"
                         "length admissions)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="continuous: disable chunked ragged prefill "
                         "and fall back to the DEPRECATED batched "
                         "prefill path (one blocking dispatch per "
                         "padded-length group)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="continuous: rows per prefill tile in the "
                         "unified varlen dispatch (chunked prefill)")
    ap.add_argument("--dispatch-budget", type=int, default=32,
                    help="continuous: max tokens per unified dispatch "
                         "while prefills are pending — decode rows are "
                         "reserved first, the rest goes to prefill "
                         "tiles (bounds inter-token latency under "
                         "long-prompt bursts)")
    ap.add_argument("--mesh", default=None,
                    help="shard the serve path over a device mesh, e.g. "
                         "'data=2': the paged pool partitions its page "
                         "axis, requests are placed per shard (CPU "
                         "hosts: set XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N first)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write an execution trace of the run: .json -> "
                         "Chrome/Perfetto trace_event format (load in "
                         "ui.perfetto.dev), .jsonl -> flat event lines; "
                         "either feeds benchmarks/trace_report.py")
    ap.add_argument("--trace-detail", default="spans",
                    choices=["off", "spans", "full"],
                    help="off: no tracer (zero overhead); spans: request "
                         "lifecycle + dispatch spans + counter tracks; "
                         "full: adds a per-emitted-token instant with "
                         "version/lag provenance")
    ap.add_argument("--profiler-annotations", action="store_true",
                    help="mirror the engine's spans (serve.step, "
                         "serve.schedule, serve.decode, ...) into jax."
                         "profiler.TraceAnnotation, so a jax.profiler "
                         "capture shows each phase of a round beside "
                         "the device's work")
    ap.add_argument("--swap-interval", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--level", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--runtime", default="direct",
                    choices=["direct", "versioned"],
                    help="versioned: serve through the PolicyStore "
                         "(staleness-taggable actor side of the runtime; "
                         "continuous engine swaps in-flight)")
    ap.add_argument("--controller", default=None, metavar="SPEC",
                    help="continuous: shadow-evaluate a lag controller "
                         "('name:key=val,...', same grammar as the "
                         "training launcher) over the retired requests "
                         "— verdicts and reasons only, nothing dropped")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append one metrics-registry snapshot as a "
                         "JSONL line at exit (flushed early on "
                         "SIGINT/SIGTERM)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.requests is None:
        args.requests = args.batch
    if args.controller and args.engine != "continuous":
        raise SystemExit("--controller needs --engine continuous "
                         "(shadow admission runs over retired requests)")

    from repro.obs.tracer import make_tracer
    from repro.resilience import install_flush_handlers, restore_handlers

    tracer = make_tracer(args.trace_detail if args.trace else "off")

    def _export_trace() -> None:
        if not args.trace:
            return
        from repro.obs.perfetto import export_perfetto, export_trace_jsonl

        if args.trace.endswith(".jsonl"):
            n = export_trace_jsonl(tracer, args.trace)
        else:
            n = export_perfetto(tracer, args.trace)
        print(f"trace: {n} events -> {args.trace} "
              f"(detail={args.trace_detail}, "
              f"ring-dropped={tracer.dropped})")

    # SIGINT/SIGTERM still leave the trace + metrics on disk.
    _flush_state = {"metrics": None}

    def _flush(signum: int) -> None:
        metrics = _flush_state.get("metrics")
        if metrics is not None and args.metrics_out:
            metrics.export_jsonl(args.metrics_out, signal=signum)
            print(f"metrics: flushed -> {args.metrics_out}")
        _export_trace()

    previous_handlers = install_flush_handlers(_flush)

    from repro.configs import launch_config
    from repro.data.mathgen import MathTaskDataset
    from repro.data.tokenizer import get_tokenizer
    from repro.models.registry import build
    from repro.checkpoint import load_checkpoint

    tok = get_tokenizer()
    cfg = launch_config(args.arch, vocab=tok.vocab_size)
    bundle = build(cfg)
    init_params = bundle.init(jax.random.PRNGKey(args.seed))
    params = init_params
    if args.checkpoint:
        params, step, meta = load_checkpoint(args.checkpoint, params)
        print(f"loaded checkpoint step={step} meta={meta}")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_debug_mesh, parse_mesh_spec

        sizes = parse_mesh_spec(args.mesh)
        if args.engine != "continuous":
            raise SystemExit("--mesh requires --engine continuous")
        try:
            mesh = make_debug_mesh(data=sizes["data"], model=sizes["model"])
        except ValueError as e:
            raise SystemExit(f"--mesh {args.mesh}: {e}")
        print(f"serving over mesh {dict(mesh.shape)} "
              f"({len(mesh.devices.flat)} devices)")

    store = None
    if args.runtime == "versioned":
        from repro.runtime import PolicyStore

        sharding = None
        if mesh is not None:
            from repro.distributed.sharding import replicated

            sharding = replicated(mesh)
        # v0 is the true random init; the checkpoint (if any) becomes v1.
        store = PolicyStore(init_params, capacity=2,
                            meta={"source": "init"}, sharding=sharding,
                            tracer=tracer)
        if args.checkpoint:
            store.publish(params, source="checkpoint",
                          checkpoint=args.checkpoint)

    ds = MathTaskDataset(prompt_len=32, level=args.level,
                         seed=args.seed + 1)
    if args.engine == "continuous":
        _serve_continuous(args, bundle, params, store, tok, ds, mesh=mesh,
                          tracer=tracer, flush_state=_flush_state)
    else:
        toks_np, prompts, answers = ds.sample_batch(args.batch)
        _serve_static(args, bundle, params, store, tok, toks_np, answers)
    _export_trace()
    if args.metrics_out and _flush_state.get("metrics") is not None:
        _flush_state["metrics"].export_jsonl(args.metrics_out)
        print(f"metrics: snapshot -> {args.metrics_out}")
    restore_handlers(previous_handlers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
