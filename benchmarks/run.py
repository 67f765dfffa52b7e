"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract).  Each
figure-level benchmark runs a CPU-scaled version of the paper's protocol
(full-scale knobs are exposed by the individual modules' CLIs);
``us_per_call`` is the wall time of the benchmark body, ``derived`` the
figure's headline metric.

The serve/runtime throughput benchmarks additionally write
machine-readable ``BENCH_serve.json`` / ``BENCH_runtime.json`` into
``--out-dir`` (default ``results/bench``); CI uploads the directory as
an artifact so regressions are diffable across runs.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--out-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _row(name: str, us: float, derived) -> None:
    print(f"{name},{us:.0f},{derived}", flush=True)


def bench_fig3_backward_lag(fast: bool) -> None:
    from benchmarks.fig3_backward_lag import run

    t0 = time.perf_counter()
    res = run(
        envs=["pendulum", "pointmass"] if fast else
             ["pendulum", "pointmass", "reacher"],
        algorithms=["vaco", "ppo"] if fast else
                   ["vaco", "ppo", "spo", "impala"],
        capacities=[1, 8],
        seeds=[0] if fast else [0, 1],
        n_actors=8 if fast else 16,
        rollout_steps=64 if fast else 96,
        phases=8 if fast else 16,
    )
    us = (time.perf_counter() - t0) * 1e6
    for cap_key in ("K=1", "K=8"):
        vaco_iqm = res[cap_key]["vaco"]["iqm"][0]
        ppo_iqm = res[cap_key]["ppo"]["iqm"][0]
        _row(f"fig3_backward_lag[{cap_key}]", us,
             f"vaco_iqm={vaco_iqm:.3f};ppo_iqm={ppo_iqm:.3f}")


def bench_fig4_sample_efficiency(fast: bool) -> None:
    from benchmarks.fig4_sample_efficiency import run_curves
    from repro.metrics.aggregate import iqm

    t0 = time.perf_counter()
    curves = run_curves(
        ["pendulum"], ["vaco", "ppo"], capacity=8,
        seeds=[0], phases=6 if fast else 12,
        n_actors=8, rollout_steps=64,
    )
    us = (time.perf_counter() - t0) * 1e6
    aucs = {a: float(np.mean(c)) for a, c in curves.items()}
    _row("fig4_sample_efficiency_auc", us,
         ";".join(f"{a}={v:.1f}" for a, v in aucs.items()))


def bench_fig5_rlvr(fast: bool) -> None:
    from benchmarks.fig5_rlvr_forward_lag import run_one

    for alg in ("grpo", "grpo_vaco"):
        t0 = time.perf_counter()
        r = run_one(
            "qwen2.5-0.5b", alg, n_minibatches=2 if fast else 4,
            phases=2 if fast else 4, seed=0, level=0,
            warmup_steps=60 if fast else 150,
        )
        us = (time.perf_counter() - t0) * 1e6
        _row(f"fig5_rlvr[{alg}]", us,
             f"acc={r['acc_final']:.3f};"
             f"rate_by_lag={r['filter_rate_by_staleness']}")


def bench_fig11_tv(fast: bool) -> None:
    from repro.train.runner_rl import AsyncRLRunConfig, run_async_rl

    t0 = time.perf_counter()
    tvs = {}
    for alg in ("vaco", "ppo"):
        res = run_async_rl(AsyncRLRunConfig(
            env_name="pendulum", algorithm=alg, buffer_capacity=8,
            n_actors=8, rollout_steps=64, total_phases=6))
        tvs[alg] = res.final_tv
    us = (time.perf_counter() - t0) * 1e6
    _row("fig11_tv_tracking", us,
         ";".join(f"{a}_tv={v:.4f}" for a, v in tvs.items())
         + ";vaco_target=0.100")


def _write_artifact(out_dir: str, name: str, payload) -> None:
    """Machine-readable benchmark artifact (CI uploads the directory)."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)


def bench_runtime_throughput(fast: bool, out_dir: str) -> None:
    """Threaded vs phase-locked actor-learner throughput."""
    from benchmarks.bench_runtime import run

    t0 = time.perf_counter()
    res = run(
        phases=4 if fast else 8,
        n_actors=4 if fast else 8,
        rollout_steps=32 if fast else 64,
    )
    us = (time.perf_counter() - t0) * 1e6
    _row("runtime_throughput", us,
         f"phase_locked={res['backward_mixture']:.0f}sps;"
         f"threaded={res['threaded']:.0f}sps;"
         f"speedup={res['threaded_speedup']:.2f}x")
    _write_artifact(out_dir, "BENCH_runtime.json", {
        "benchmark": "runtime_throughput",
        "us_per_call": us,
        "env_steps_per_s": res,
    })


def bench_serve_throughput(fast: bool, out_dir: str) -> None:
    """Continuous batching vs phase-locked serve at mixed lengths."""
    from benchmarks.bench_serve import run

    t0 = time.perf_counter()
    res = run(
        n_requests=12 if fast else 24,
        max_batch=4,
        lengths=(2, 4, 8, 48),
    )
    us = (time.perf_counter() - t0) * 1e6
    _row("serve_throughput", us,
         f"phase_locked={res['phase_locked']['tokens_per_s']:.0f}tps;"
         f"continuous={res['continuous']['tokens_per_s']:.0f}tps;"
         f"speedup={res['speedup_tokens_per_s']:.2f}x;"
         f"p99_ms={res['continuous']['latency_p99_ms']:.1f}")
    _write_artifact(out_dir, "BENCH_serve.json",
                    dict(res, benchmark="serve_throughput",
                         us_per_call=us))


def bench_theory() -> None:
    """Appendix B numerical validation (tabular MDP) as a benchmark.

    Runs in this process: a child started once JAX is live here could
    not reach an accelerator this process holds."""
    import pytest

    t0 = time.perf_counter()
    rc = pytest.main(["tests/test_theory.py", "-q", "--no-header", "-x",
                      "-p", "no:cacheprovider"])
    us = (time.perf_counter() - t0) * 1e6
    _row("appendixB_theory_validation", us, f"all_pass={rc == 0}")


def bench_kernels() -> None:
    from benchmarks.kernels_bench import bench_rows

    for name, us, derived in bench_rows():
        _row(f"kernel[{name}]", us, derived)


def bench_roofline() -> None:
    """Summarize dry-run roofline terms if results exist."""
    path = "results/dryrun_singlepod.json"
    if not os.path.exists(path):
        _row("roofline_summary", 0, "skipped(no results/dryrun_*.json)")
        return
    t0 = time.perf_counter()
    from benchmarks.roofline import analyze_records

    with open(path) as f:
        rows = analyze_records(json.load(f))
    us = (time.perf_counter() - t0) * 1e6
    n_by = {}
    for r in rows:
        n_by[r.dominant] = n_by.get(r.dominant, 0) + 1
    _row("roofline_summary", us,
         f"combos={len(rows)};" +
         ";".join(f"{k}_bound={v}" for k, v in sorted(n_by.items())))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smaller grids (CI-sized)")
    ap.add_argument("--out-dir", default="results/bench",
                    help="where BENCH_*.json artifacts are written")
    args, _ = ap.parse_known_args()
    fast = args.fast or os.environ.get("REPRO_BENCH_FAST", "1") == "1"

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    bench_kernels()
    bench_theory()
    bench_serve_throughput(fast, args.out_dir)
    bench_runtime_throughput(fast, args.out_dir)
    bench_fig11_tv(fast)
    bench_fig4_sample_efficiency(fast)
    bench_fig3_backward_lag(fast)
    bench_fig5_rlvr(fast)
    bench_roofline()


if __name__ == "__main__":
    main()
