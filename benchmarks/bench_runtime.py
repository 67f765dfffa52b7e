"""Runtime throughput: threaded producer vs phase-locked collection.

The point of the async runtime is overlap — the producer generates the
next trajectory while the learner is still updating on the previous one.
This benchmark runs the identical workload (same env, actors, steps,
algorithm, phase count) under the phase-locked ``backward_mixture``
regime and the concurrent ``threaded`` regime and reports environment
steps per second for each plus the overlap speedup.

``--lag-sweep`` adds the lag-controller sweep: every registered
controller (pass_through, max_lag, tv_gate, gac, stable_async, asympo)
runs the serve-backed RLVR trainer — real engine rollouts with
per-token {version, log_beta} provenance — under scripted lag regimes
{fresh, forced max lag}, from one shared warm-started base policy, and
the final greedy eval accuracy plus the queue's drop/downweight rates
land in a per-controller reward-vs-lag table.  The derived
``tv_gate_advantage_at_max_lag`` / ``drop_rate_at_max_lag`` numbers are
what CI's regression gate enforces.

    PYTHONPATH=src python -m benchmarks.bench_runtime [--phases N]
    PYTHONPATH=src python -m benchmarks.bench_runtime --lag-sweep \\
        --steps-small --out results/bench/BENCH_runtime.json
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Any, Dict

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.train.runner_rl import AsyncRLRunConfig, run_async_rl


@contextlib.contextmanager
def _compilation_cache():
    """Persist XLA executables so the warm run actually warms the timed
    run: each run_async_rl builds fresh jit wrappers (whose per-wrapper
    caches are useless across calls), but the persistent cache is keyed
    on the HLO fingerprint and is shared.  The cache is the one the
    caller's ``main()`` enabled (``repro.launch.compile_cache``); inside
    this block even sub-second compiles are kept, and the threshold is
    restored on exit."""
    name = "jax_persistent_cache_min_compile_time_secs"
    saved = getattr(jax.config, name)
    jax.config.update(name, 0.0)
    try:
        yield
    finally:
        jax.config.update(name, saved)


def run(
    *,
    phases: int = 8,
    n_actors: int = 8,
    rollout_steps: int = 64,
    algorithm: str = "vaco",
    seed: int = 0,
) -> Dict[str, float]:
    """Returns {regime: env_steps_per_sec} plus the threaded speedup."""
    out: Dict[str, float] = {}
    with _compilation_cache():
        for regime in ("backward_mixture", "threaded"):
            cfg = AsyncRLRunConfig(
                env_name="pendulum", algorithm=algorithm,
                buffer_capacity=4, n_actors=n_actors,
                rollout_steps=rollout_steps, total_phases=phases,
                seed=seed, runtime=regime, get_timeout=120.0,
            )
            # Warm run populates the persistent executable cache, so the
            # timed run re-traces but skips XLA compilation.
            run_async_rl(AsyncRLRunConfig(**{**cfg.__dict__,
                                             "total_phases": 2}))
            t0 = time.perf_counter()
            res = run_async_rl(cfg)
            dt = time.perf_counter() - t0
            # Consumed items come from the queue's own counters (the
            # same snapshot live telemetry reports): `admitted` counts
            # gate-passing pops, i.e. exactly the items the learner
            # stepped on (a threaded producer may leave extras buffered
            # in `depth`; those did no learner work).
            qs = res.runtime_stats["queue"]
            consumed = qs["admitted"]
            if consumed != len(res.returns):
                print(f"warning: queue says {consumed} consumed items, "
                      f"learner logged {len(res.returns)} phases")
                consumed = len(res.returns)
            env_steps = consumed * n_actors * rollout_steps
            out[regime] = env_steps / dt
    out["threaded_speedup"] = (
        out["threaded"] / out["backward_mixture"]
        if out["backward_mixture"] else 0.0
    )
    return out


# Controller spec per sweep column.  max_lag's threshold sits below the
# forced lag so the stale regime is an all-drop column (drop-rate 1.0 —
# one of the gate's sanity bands); tv_gate runs downweight mode so it
# keeps consuming at max lag and the reward comparison vs pass_through
# is like-for-like in update count.
LAG_SWEEP_CONTROLLERS = (
    ("pass_through", "pass_through"),
    ("max_lag", "max_lag:max_lag=2"),
    ("tv_gate", "tv_gate:delta=0.05,mode=downweight"),
    ("gac", "gac:cos_min=0.25"),
    ("stable_async", "stable_async:c_max=2.0,var_max=0.5"),
    ("asympo", "asympo:pos_decay=0.8"),
)


def run_lag_sweep(
    *,
    phases: int = 8,
    warmup_steps: int = 120,
    max_lag: int = 3,
    seed: int = 0,
) -> Dict[str, Any]:
    """Final-reward-vs-lag for every lag controller, serve-produced.

    One tiny model is warm-started once (supervised format warmup);
    every (controller, lag) cell then trains from an identical copy of
    that base policy — same params, fresh optimizer moments, same
    pre-ramped PolicyStore — so the cells differ *only* in the
    controller and the scripted lag.  The store ring is pre-ramped with
    ``max_lag + 1`` publishes of the warm params so the forced-lag
    regime is at full staleness from the first minibatch (no warm-up
    ramp diluting the drop-rate columns).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.data.mathgen import MathTaskDataset
    from repro.data.tokenizer import get_tokenizer
    from repro.models.registry import build
    from repro.train.trainer_rlvr import (
        RLVRHyperparams,
        RLVRTrainer,
        RLVRTrainState,
        adamw_init,
    )

    tok = get_tokenizer()
    cfg = ModelConfig(
        name="lag-sweep", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=tok.vocab_size,
    )
    bundle = build(cfg)

    def make_hp(spec: str, lag: int) -> RLVRHyperparams:
        # Plain GRPO (no in-loss VACO filter): the admission controller
        # is the *only* staleness defence, so the sweep measures the
        # controllers, not the loss.  lr is ~10x the trainer default —
        # large enough that full-weight stale updates measurably damage
        # the warm-started policy within the sweep's update budget.
        return RLVRHyperparams(
            algorithm="grpo", lr=1e-3, n_minibatches=3,
            prompts_per_minibatch=4, completions_per_prompt=4,
            max_new_tokens=6, warmup_steps=warmup_steps,
            producer="serve", controller=spec, forced_lag=lag,
            store_capacity=max_lag + 1, max_refills=4,
            engine_max_batch=8, engine_num_blocks=48,
        )

    def make_ds() -> MathTaskDataset:
        return MathTaskDataset(prompt_len=16, level=0, pool_size=256,
                               seed=seed + 1)

    # Shared warmup: one supervised run produces the base policy every
    # sweep cell starts from.
    warm_tr = RLVRTrainer(bundle, make_ds(),
                          make_hp("pass_through", 0), seed=seed)
    warm_tr.warmup()
    warm_params = warm_tr.state.params
    base_acc = warm_tr.evaluate(128)

    lags = (0, max_lag)
    table: Dict[str, Dict[str, Any]] = {}
    for name, spec in LAG_SWEEP_CONTROLLERS:
        table[name] = {"spec": spec}
        for lag in lags:
            tr = RLVRTrainer(bundle, make_ds(), make_hp(spec, lag),
                             seed=seed)
            tr.state = RLVRTrainState(
                params=warm_params,
                opt_state=adamw_init(warm_params),
                updates=jnp.zeros((), jnp.int32),
            )
            # Pre-ramp the snapshot ring: resolve_lagged(-max_lag) hits
            # a real (identical) snapshot from the very first minibatch.
            for _ in range(max_lag + 1):
                tr.store.publish(warm_params, event="lag_sweep_preramp")
            res = tr.train(phases, eval_every=10**9)
            qs = res.runtime_stats["queue"]
            decided = qs["admitted"] + qs["dropped"]
            table[name][f"lag{lag}"] = {
                "final_reward": (res.eval_accuracy[-1]
                                 if res.eval_accuracy else None),
                "updates": len(res.phase_logs),
                "mean_minibatch_reward": (
                    float(np.mean([pl.mean_reward
                                   for pl in res.phase_logs]))
                    if res.phase_logs else None),
                "drop_rate": (qs["dropped"] / decided if decided else 0.0),
                "downweight_rate": (
                    qs["downweighted"] / decided if decided else 0.0),
                "drops_by_reason": qs["drops_by_reason"],
                "downweights_by_reason": qs["downweights_by_reason"],
            }

    def reward(name: str, lag: int) -> float:
        r = table[name][f"lag{lag}"]["final_reward"]
        return 0.0 if r is None else float(r)

    out: Dict[str, Any] = {
        "config": {"phases": phases, "warmup_steps": warmup_steps,
                   "max_lag": max_lag, "seed": seed,
                   "base_accuracy": base_acc},
        "controllers": table,
        # CI-gated deriveds: the Eq. 8 gate must not lose reward vs
        # ungated consumption of max-lag data, pass_through must never
        # drop, and the lag-2 eviction gate must drop (all of) the
        # forced-lag-3 stream.
        "tv_gate_advantage_at_max_lag": (
            reward("tv_gate", max_lag) - reward("pass_through", max_lag)),
        "drop_rate_at_max_lag": {
            name: table[name][f"lag{max_lag}"]["drop_rate"]
            for name, _ in LAG_SWEEP_CONTROLLERS
        },
    }
    return out


def print_lag_sweep(sweep: Dict[str, Any]) -> None:
    cfg = sweep["config"]
    lags = (0, cfg["max_lag"])
    print(f"\nlag sweep (base accuracy {cfg['base_accuracy']:.3f}, "
          f"forced lag {cfg['max_lag']}):")
    hdr = f"{'controller':<14}" + "".join(
        f"  reward@lag{lag}  drop@lag{lag}  dwgt@lag{lag}" for lag in lags)
    print(hdr)
    for name in sweep["controllers"]:
        row = f"{name:<14}"
        for lag in lags:
            cell = sweep["controllers"][name][f"lag{lag}"]
            r = cell["final_reward"]
            row += (f"  {'--' if r is None else f'{r:10.3f}':>11}"
                    f"  {cell['drop_rate']:10.2f}"
                    f"  {cell['downweight_rate']:9.2f}")
        print(row)
    print(f"tv_gate advantage at max lag: "
          f"{sweep['tv_gate_advantage_at_max_lag']:+.3f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phases", type=int, default=8)
    ap.add_argument("--n-actors", type=int, default=8)
    ap.add_argument("--rollout-steps", type=int, default=64)
    ap.add_argument("--algorithm", default="vaco")
    ap.add_argument("--lag-sweep", action="store_true",
                    help="also run every lag controller through the "
                         "serve-backed RLVR trainer across lag regimes "
                         "(reward-vs-lag + drop-rate table)")
    ap.add_argument("--steps-small", action="store_true",
                    help="lag sweep at CI-smoke scale (fewer phases / "
                         "shorter warmup); the committed baseline and "
                         "the fresh CI run must agree on this flag")
    ap.add_argument("--sweep-seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write a BENCH_runtime.json artifact (same "
                         "shape as benchmarks.run's) for the CI "
                         "regression gate")
    args = ap.parse_args()
    enable_compile_cache()
    res = run(phases=args.phases, n_actors=args.n_actors,
              rollout_steps=args.rollout_steps, algorithm=args.algorithm)
    for k, v in res.items():
        unit = "x" if k == "threaded_speedup" else " env steps/s"
        print(f"{k:18s} {v:10.1f}{unit}")
    sweep = None
    if args.lag_sweep:
        if args.steps_small:
            sweep = run_lag_sweep(phases=5, warmup_steps=80,
                                  seed=args.sweep_seed)
        else:
            sweep = run_lag_sweep(seed=args.sweep_seed)
        print_lag_sweep(sweep)
    if args.out:
        import json
        import os

        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        doc = {"benchmark": "runtime_throughput",
               "config": {"phases": args.phases,
                          "n_actors": args.n_actors,
                          "rollout_steps": args.rollout_steps,
                          "algorithm": args.algorithm},
               "env_steps_per_s": res}
        if sweep is not None:
            doc["lag_sweep"] = sweep
        with open(args.out, "w") as f:
            # Absolute env-steps/s are workload-dependent: the committed
            # baseline and CI's fresh run must use the same config for
            # the regression diff to mean anything.
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
