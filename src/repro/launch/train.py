"""Training launcher.

Two modes, matching the paper's two experimental regimes, both running on
the unified async actor-learner runtime (``--runtime`` selects the lag
regime, ``--controller`` the queue's lag controller as a
``"name:key=val,..."`` spec; the old ``--admission`` flags survive as
deprecation shims):

  # classic RL (simulated-async MuJoCo-analog, §5.1)
  PYTHONPATH=src python -m repro.launch.train rl \\
      --env pendulum --algorithm vaco --buffer-capacity 4 --phases 30 \\
      --runtime backward_mixture

  # genuinely concurrent producer thread + TV-gated admission
  PYTHONPATH=src python -m repro.launch.train rl \\
      --env pendulum --algorithm vaco --runtime threaded \\
      --controller "tv_gate:delta=0.2,mode=downweight" --phases 30

  # RLVR (forward-lag GRPO/VACO, §5.2) on the CPU-smoke reduction;
  # --arch qwen2.5-0.5b trains the published widths (for a TPU)
  PYTHONPATH=src python -m repro.launch.train rlvr \\
      --arch qwen2.5-0.5b-reduced --algorithm grpo_vaco --n-minibatches 8 \\
      --phases 20 --runtime forward_n

  # RLVR with the ServeEngine as the rollout producer: real per-token
  # {version, log_beta} provenance under a scripted 2-back lag
  PYTHONPATH=src python -m repro.launch.train rlvr \\
      --arch qwen2.5-0.5b-reduced --producer serve --forced-lag 2 \\
      --controller "tv_gate:delta=0.05,mode=downweight" --phases 10

On a real TPU cluster the same entry point runs under
``jax.distributed.initialize()`` with the production mesh from
launch/mesh.py; on this CPU host it runs the reduced configs end-to-end.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax


def _add_runtime_args(p, *, regimes, default_regime,
                      admissions=("pass_through", "max_lag", "tv_gate"),
                      ) -> None:
    p.add_argument("--runtime", default=default_regime, choices=regimes,
                   help="lag regime driving the actor-learner runtime")
    p.add_argument("--controller", default=None, metavar="SPEC",
                   help="lag controller spec 'name:key=val,...' — e.g. "
                        "'tv_gate:delta=0.2,mode=downweight', "
                        "'stable_async:c_max=2.0'; see "
                        "repro.runtime.available_controllers()")
    # Deprecated string-keyed admission flags; kept as shims over
    # --controller (explicit use warns and maps to the equivalent spec).
    p.add_argument("--admission", default=None,
                   choices=list(admissions),
                   help="DEPRECATED: use --controller 'name:...'")
    p.add_argument("--max-lag", type=int, default=None,
                   help="DEPRECATED: use --controller 'max_lag:max_lag=N'")
    p.add_argument("--admission-mode", default=None,
                   choices=["drop", "downweight"],
                   help="DEPRECATED: use --controller "
                        "'tv_gate:delta=...,mode=...'")
    p.add_argument("--queue-maxsize", type=int, default=4,
                   help="bounded queue size (threaded backpressure)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="write an execution trace (produce spans, "
                        "queue put/pop/drop, publish/pin, learner "
                        "steps): .json -> Perfetto, .jsonl -> flat "
                        "event lines for benchmarks/trace_report.py")
    p.add_argument("--trace-detail", default="spans",
                   choices=["off", "spans", "full"],
                   help="trace verbosity (off disables the tracer)")
    p.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="append one metrics-registry snapshot as a "
                        "JSONL line at exit (flushed early on "
                        "SIGINT/SIGTERM)")


def _resolve_controller(args, *, delta):
    """Controller spec text from --controller or the deprecated
    --admission/--max-lag/--admission-mode flags (explicit legacy use
    warns and maps to the equivalent spec).  None = config default."""
    legacy_used = (args.admission is not None
                   or args.max_lag is not None
                   or args.admission_mode is not None)
    if args.controller is not None:
        if legacy_used:
            raise SystemExit(
                "--controller conflicts with the deprecated --admission/"
                "--max-lag/--admission-mode flags; pass one or the other")
        return args.controller
    if not legacy_used:
        return None
    from repro.runtime import spec_from_legacy

    spec = spec_from_legacy(
        args.admission or "pass_through",
        max_lag=args.max_lag if args.max_lag is not None else 4,
        delta=delta,
        mode=args.admission_mode or "drop",
        warn=True,
    )
    return spec.canonical()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="mode", required=True)

    rl = sub.add_parser("rl", help="simulated-async classic RL (§5.1)")
    rl.add_argument("--env", default="pendulum")
    rl.add_argument("--algorithm", default="vaco",
                    choices=["vaco", "ppo", "ppo_kl", "spo", "impala"])
    rl.add_argument("--buffer-capacity", type=int, default=1)
    rl.add_argument("--n-actors", type=int, default=32)
    rl.add_argument("--rollout-steps", type=int, default=128)
    rl.add_argument("--phases", type=int, default=30)
    rl.add_argument("--seed", type=int, default=0)
    rl.add_argument("--delta", type=float, default=0.2)
    rl.add_argument("--forward-n", type=int, default=4,
                    help="items per frozen policy (forward_n regime)")
    rl.add_argument("--checkpoint-dir", default=None)
    _add_runtime_args(
        rl, regimes=["backward_mixture", "forward_n", "threaded"],
        default_regime="backward_mixture")

    rv = sub.add_parser("rlvr", help="forward-lag RLVR (§5.2)")
    rv.add_argument("--arch", default="qwen2.5-0.5b")
    rv.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth to N layers, keeping its "
                         "published widths (to fit one chip's memory)")
    rv.add_argument("--algorithm", default="grpo_vaco",
                    choices=["grpo", "grpo_vaco"])
    rv.add_argument("--n-minibatches", type=int, default=4)
    rv.add_argument("--phases", type=int, default=10)
    rv.add_argument("--level", type=int, default=0,
                    help="math curriculum level")
    rv.add_argument("--warmup-steps", type=int, default=300)
    rv.add_argument("--seed", type=int, default=0)
    rv.add_argument("--delta", type=float, default=0.05)
    rv.add_argument("--checkpoint-dir", default=None)
    rv.add_argument("--producer", default="legacy",
                    choices=["legacy", "serve"],
                    help="rollout producer: the synthetic forward-lag "
                         "generator, or the continuous-batching "
                         "ServeEngine (real per-token provenance)")
    rv.add_argument("--forced-lag", type=int, default=None,
                    help="serve producer: generate from the learner's "
                         "k-back snapshot (scripted lag)")
    rv.add_argument("--max-new-tokens", type=int, default=None,
                    help="completion length (default: hp default)")
    rv.add_argument("--engine-max-batch", type=int, default=8,
                    help="serve producer: engine decode batch size")
    rv.add_argument("--prompts-per-minibatch", type=int, default=16)
    rv.add_argument("--completions-per-prompt", type=int, default=4)
    rv.add_argument("--warmup-batch", type=int, default=64)
    rv.add_argument("--eval-prompts", type=int, default=256)
    rv.add_argument("--store-capacity", type=int, default=4,
                    help="policy snapshots the store keeps resident")
    # Resilience (see repro.resilience and README "Fault tolerance").
    rv.add_argument("--fault-plan", default="", metavar="PLAN",
                    help="fault-injection plan, ';'-joined "
                         "'kind:key=val,...' chunks — e.g. "
                         "'producer_crash:at_step=4;"
                         "nan_publish:at_publish=7'")
    rv.add_argument("--fault-seed", type=int, default=0,
                    help="seed for probabilistic faults + stall jitter")
    rv.add_argument("--watchdog-restarts", type=int, default=0,
                    help="supervise threaded producers: restart a "
                         "crashed producer up to N times with seeded "
                         "exponential backoff (0 = crash-fast)")
    rv.add_argument("--watchdog-backoff-ms", type=float, default=50.0,
                    help="watchdog restart backoff base (doubles per "
                         "attempt, jittered)")
    rv.add_argument("--request-deadline", type=float, default=None,
                    metavar="SECONDS",
                    help="serve producer: per-request wall-clock "
                         "budget; expired requests retire as "
                         "finish_reason='timeout' and free their pages")
    rv.add_argument("--no-finiteness-guard", action="store_true",
                    help="disable the NaN/Inf firewall (non-finite "
                         "publishes quarantined, non-finite learner "
                         "steps skipped + rolled back)")
    rv.add_argument("--guard-checkpoint-dir", default=None,
                    help="finiteness guard restores from the newest "
                         "checkpoint here (also written after every "
                         "finite step) instead of the in-memory copy")
    # tv_gate_tokenwise: Eq. 8 per producing-version segment, scored by
    # a tv_fn closed over the PolicyStore (ROADMAP item).  RLVR-only:
    # classic-RL rollout payloads carry no per-token version record.
    _add_runtime_args(
        rv, regimes=["forward_n", "threaded"],
        default_regime="forward_n",
        admissions=("pass_through", "max_lag", "tv_gate",
                    "tv_gate_tokenwise"))

    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

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

    # Graceful shutdown: SIGINT/SIGTERM stops producers and flushes the
    # trace/metrics buffers before exiting — an interrupted (or chaos-
    # killed) run still leaves its telemetry on disk.
    _flush_state = {"trainer": None}

    def _flush(signum: int) -> None:
        trainer = _flush_state.get("trainer")
        if trainer is not None:
            try:
                trainer.close()
            except Exception:
                pass
            if args.metrics_out:
                trainer.metrics.export_jsonl(
                    args.metrics_out, signal=signum)
                print(f"metrics: flushed -> {args.metrics_out}")
        _export_trace()

    previous_handlers = install_flush_handlers(_flush)

    if args.mode == "rl":
        from repro.train.runner_rl import AsyncRLRunConfig, run_async_rl
        from repro.train.trainer_rl import RLHyperparams

        res = run_async_rl(AsyncRLRunConfig(
            env_name=args.env, algorithm=args.algorithm,
            buffer_capacity=args.buffer_capacity,
            n_actors=args.n_actors, rollout_steps=args.rollout_steps,
            total_phases=args.phases, seed=args.seed,
            hp=RLHyperparams(delta=args.delta),
            runtime=args.runtime, forward_n=args.forward_n,
            queue_maxsize=args.queue_maxsize,
            controller=_resolve_controller(args, delta=args.delta),
            tracer=tracer if args.trace else None,
        ))
        print(json.dumps({
            "runtime": args.runtime,
            "returns": res.returns,
            "final_tv": res.final_tv,
            "runtime_stats": res.runtime_stats,
        }, indent=1))
        _export_trace()
        restore_handlers(previous_handlers)
        return 0

    # rlvr
    from repro.configs import launch_config
    from repro.data.mathgen import MathTaskDataset
    from repro.data.tokenizer import get_tokenizer
    from repro.models.registry import build
    from repro.train.trainer_rlvr import RLVRHyperparams, RLVRTrainer
    from repro.checkpoint import save_checkpoint

    tok = get_tokenizer()
    cfg = launch_config(args.arch, vocab=tok.vocab_size)
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    bundle = build(cfg)
    ds = MathTaskDataset(prompt_len=32, level=args.level)
    hp_kwargs = dict(
        algorithm=args.algorithm, n_minibatches=args.n_minibatches,
        warmup_steps=args.warmup_steps, delta=args.delta,
        runtime=args.runtime, queue_maxsize=args.queue_maxsize,
        controller=_resolve_controller(args, delta=args.delta),
        producer=args.producer, forced_lag=args.forced_lag,
        engine_max_batch=args.engine_max_batch,
        prompts_per_minibatch=args.prompts_per_minibatch,
        completions_per_prompt=args.completions_per_prompt,
        warmup_batch=args.warmup_batch,
        eval_prompts=args.eval_prompts,
        store_capacity=args.store_capacity,
        fault_plan=args.fault_plan, fault_seed=args.fault_seed,
        watchdog_restarts=args.watchdog_restarts,
        watchdog_backoff_ms=args.watchdog_backoff_ms,
        request_deadline_s=args.request_deadline,
        finiteness_guard=not args.no_finiteness_guard,
        guard_checkpoint_dir=args.guard_checkpoint_dir,
    )
    if args.max_new_tokens is not None:
        hp_kwargs["max_new_tokens"] = args.max_new_tokens
    hp = RLVRHyperparams(**hp_kwargs)
    trainer = RLVRTrainer(bundle, ds, hp, seed=args.seed, tracer=tracer)
    _flush_state["trainer"] = trainer
    wl = trainer.warmup()
    print(f"[warmup] loss={wl:.4f} acc={trainer.evaluate():.3f}")
    res = trainer.train(args.phases, eval_every=max(args.phases // 4, 1))
    step_summary = trainer.metrics.histogram("train_step_s").summary()
    print(json.dumps({
        "arch": cfg.name,
        "algorithm": args.algorithm,
        "runtime": args.runtime,
        "n_minibatches": args.n_minibatches,
        "eval_accuracy": res.eval_accuracy,
        "final_tv": res.phase_logs[-1].tv if res.phase_logs else None,
        "runtime_stats": res.runtime_stats,
        "train_step_ms": {
            "count": step_summary["count"],
            "mean": step_summary["mean"] * 1e3,
            "p50": step_summary["p50"] * 1e3,
            "p99": step_summary["p99"] * 1e3,
        },
    }, indent=1))
    _export_trace()
    if args.metrics_out:
        trainer.metrics.export_jsonl(args.metrics_out)
        print(f"metrics: snapshot -> {args.metrics_out}")
    if args.checkpoint_dir:
        path = save_checkpoint(
            args.checkpoint_dir, args.phases, trainer.state.params,
            meta={"arch": cfg.name})
        print(f"checkpoint: {path}")
    restore_handlers(previous_handlers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
