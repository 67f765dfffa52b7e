"""Bring-up smoke: the serve engine and the RLVR learner on one TPU, at
qwen2.5-0.5b's published widths, with random weights made from a seed.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded serve path on four

Everything runs in this one process (a chip belongs to one process), and
the entry points are the launchers' own ``main()`` functions.  Phases on
one chip:

  device  fail unless JAX's first device is a TPU.
  serve   ``launch.serve`` answers 8 requests of mixed lengths with the
          continuous engine: chunked prefill, then the decode scan.
  parity  one varlen dispatch and one decode step through the platform's
          kernels (compiled Pallas) against ``kernel_mode="reference"``
          on the same inputs; logits must agree to ``PARITY_RTOL``.
  rlvr    ``launch.train rlvr --producer serve`` warms up, takes learner
          steps and publishes into its engine.

``--four-chips`` runs only the sharded path: the engine with its page
pool sharded over a 4-device mesh (``shard_map`` kernels) against the
single-device engine, greedy, and requires identical tokens.

Each phase prints its wall seconds, compile seconds, the work done and
the device's peak bytes so far.  After the launchers, their metrics
snapshots must show no degradation path firing (fallbacks, rollbacks,
quarantines, restarts, auto-disables): a kernel that yields NaN would
otherwise be rolled back or quarantined while the run exits 0.  The
last line of a run that passed is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen2.5-0.5b"
SEED = 0
# The learner keeps params, both Adam moments and the update's new copy
# of all three (the finiteness guard holds the old state), the store's
# two-slot ring and the engine's copy of the weights: about 9x the f32
# params.  At 24 layers that is 9 x 1.98 GB, more than one v5e's 16 GiB,
# so the learner phase cuts depth (widths and precision unchanged).  The
# peak also varies between runs by more than one copy of the params: on
# a v5e (limit 16.9 GB) it reached 15.8 and 16.5 GB at 16 layers and
# 16.6 GB at 14.  At 10 layers a copy is 1.14 GB, and the peak should
# stay about two copies below the limit.
RLVR_LAYERS = 10
# Relative logits gap allowed between the Pallas kernels and the jnp
# oracles, both at full f32 matmul precision.  Reordered f32 sums stay
# near 1e-6; one bf16 pass anywhere gives about 1e-3.
PARITY_RTOL = 1e-4
DEGRADATION_COUNTERS = (
    "admission_fallback_total",
    "learner_nonfinite_total",
    "publish_quarantined_total",
    "watchdog_restart_total",
    "spec_autodisable_total",
)
OUT = ROOT / "chiprun_out" / "chip_smoke"


class _CompileClock:
    """Seconds JAX spends in the backend compiler, from its own events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


def _require_tpu() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
            "this script runs only on the chip")


def _device() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _memory_stat(name: str) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get(name, -1))


def _phase(name: str, clock: _CompileClock, fn):
    t0, c0, n0 = time.monotonic(), clock.seconds, clock.count
    print(f"== phase {name}", flush=True)
    facts = fn() or {}
    gc.collect()
    line = {"phase": name,
            "wall_s": round(time.monotonic() - t0, 3),
            "compile_s": round(clock.seconds - c0, 3),
            "compiles": clock.count - n0,
            "peak_bytes_in_use": _memory_stat("peak_bytes_in_use"),
            **facts}
    print(json.dumps(line), flush=True)
    return line


def _metrics(path: Path) -> dict:
    lines = path.read_text().splitlines()
    if not lines:
        raise RuntimeError(f"{path}: no metrics snapshot written")
    return json.loads(lines[-1])


def _require_no_degradation(snap: dict, phase: str) -> dict:
    fired = {}
    for name, value in snap.get("counters", {}).items():
        if name.split("{", 1)[0] in DEGRADATION_COUNTERS and value:
            fired[name] = value
    if fired:
        raise RuntimeError(f"{phase}: degradation paths fired: {fired}")
    return {name: 0 for name in DEGRADATION_COUNTERS}


def _fresh(path: Path) -> Path:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.unlink(missing_ok=True)
    return path


# -- phases -------------------------------------------------------------------


def phase_serve() -> dict:
    from repro.launch import serve

    metrics = _fresh(OUT / "serve_metrics.jsonl")
    rc = serve.main([
        "--arch", ARCH, "--engine", "continuous", "--seed", str(SEED),
        "--requests", "8", "--mixed-lengths", "4,12,24,40",
        "--max-batch", "4", "--num-blocks", "64", "--max-seq-len", "96",
        "--prefill-chunk", "16", "--dispatch-budget", "32",
        "--decode-chunk", "4", "--metrics-out", str(metrics),
    ])
    if rc != 0:
        raise RuntimeError(f"launch.serve exited {rc}")
    snap = _metrics(metrics)
    stats = snap["serve"]
    if stats["finished"] != 8:
        raise RuntimeError(f"serve: {stats['finished']}/8 requests done")
    if not stats["prefill_dispatches"] or not stats["decode_steps"]:
        raise RuntimeError("serve: chunked prefill or decode never ran")
    return {"requests": stats["finished"],
            "tokens_out": stats["tokens_out"],
            "prefill_tokens": stats["prefill_tokens"],
            "prefill_dispatches": stats["prefill_dispatches"],
            "decode_steps": stats["decode_steps"],
            "degradation": _require_no_degradation(snap, "serve")}


def _parity_inputs(cfg, rng):
    b, t, block, blocks = 4, 16, 8, 32
    tables = rng.permutation(blocks)[:b * 4].reshape(b, 4)
    row_len = np.asarray([16, 9, 13, 0], np.int32)
    return dict(
        tokens=jnp.asarray(rng.integers(3, cfg.vocab_size, (b, t)),
                           jnp.int32),
        tables=jnp.asarray(tables, jnp.int32),
        row_start=jnp.zeros((b,), jnp.int32),
        row_len=jnp.asarray(row_len),
        cap=jnp.full((b,), 4 * block, jnp.int32),
        block=block, blocks=blocks)


def _rel_gap(got, want, live) -> float:
    got, want = np.asarray(got)[live], np.asarray(want)[live]
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _config():
    from repro.configs import launch_config
    from repro.data.tokenizer import get_tokenizer

    return launch_config(ARCH, vocab=get_tokenizer().vocab_size)


def _require_pallas(kernels: int, kernels_ref: int, n_layers: int) -> None:
    """The platform path is the compiled Pallas one: one row-write and
    one attention kernel per layer, and none on the reference side."""
    from repro.kernels import ops

    if ops.kernel_mode() != "pallas":
        raise RuntimeError(f"the platform's kernel mode is "
                           f"{ops.kernel_mode()!r}, not compiled 'pallas'")
    if kernels != 2 * n_layers or kernels_ref != 0:
        raise RuntimeError(
            f"Pallas kernels in the dispatch: platform {kernels} (want "
            f"{2 * n_layers}), reference {kernels_ref} (want 0)")


def phase_parity() -> dict:
    from repro.models.registry import build

    cfg = _config()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(SEED))
    x = _parity_inputs(cfg, np.random.default_rng(SEED))
    live = np.arange(16)[None, :] < np.asarray(x["row_len"])[:, None]

    def steps(mode):
        """Varlen prefill tiles, then one decode step on their pages.
        ``mode=None`` is the platform's choice, as the engine makes it."""
        varlen = jax.jit(
            lambda p, tok, pg, tb, rs, rl, cap:
            bundle.decode_step_paged_varlen(
                p, tok, pg, tb, rs, rl, cap, kernel_mode=mode),
            donate_argnums=(2,))
        decode = jax.jit(
            lambda p, tok, pg, tb, pos, act: bundle.decode_step_paged(
                p, tok, pg, tb, pos, act, kernel_mode=mode),
            donate_argnums=(2,))
        pages = bundle.init_paged_cache(x["blocks"], x["block"])
        args = (params, x["tokens"], pages, x["tables"], x["row_start"],
                x["row_len"], x["cap"])
        kernels = varlen.lower(*args).compile().as_text().count(
            'custom_call_target="tpu_custom_call"')
        out, pages = varlen(*args)
        last = jnp.take_along_axis(
            out.logits, jnp.maximum(x["row_len"] - 1, 0)[:, None, None],
            axis=1)[:, 0]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        dec, _ = decode(params, nxt, pages, x["tables"], x["row_len"],
                        x["row_len"] > 0)
        return out.logits, dec.logits, kernels

    with jax.default_matmul_precision("highest"):
        var_p, dec_p, kernels_p = steps(None)
        var_r, dec_r, kernels_r = steps("reference")
    _require_pallas(kernels_p, kernels_r, cfg.n_layers)
    active = np.asarray(x["row_len"]) > 0
    gaps = {"varlen_rel_gap": _rel_gap(var_p, var_r, live),
            "decode_rel_gap": _rel_gap(dec_p, dec_r, active)}
    for name, gap in gaps.items():
        if not gap <= PARITY_RTOL:
            raise RuntimeError(f"parity: {name} {gap:.3g} > {PARITY_RTOL}")
    return {"rows": int(live.sum()), "pallas_kernels": kernels_p,
            "rtol": PARITY_RTOL, **gaps}


def phase_rlvr() -> dict:
    from repro.launch import train

    metrics = _fresh(OUT / "rlvr_metrics.jsonl")
    phases, minibatches = 2, 2
    print(f"rlvr: depth cut to {RLVR_LAYERS} layers (published widths, "
          f"f32) to fit the learner's state in one chip's memory")
    rc = train.main([
        "rlvr", "--arch", ARCH, "--layers", str(RLVR_LAYERS),
        "--seed", str(SEED), "--producer", "serve",
        "--algorithm", "grpo_vaco", "--phases", str(phases),
        "--n-minibatches", str(minibatches), "--warmup-steps", "2",
        "--warmup-batch", "4", "--prompts-per-minibatch", "2",
        "--completions-per-prompt", "2", "--engine-max-batch", "4",
        "--max-new-tokens", "8", "--store-capacity", "2",
        "--eval-prompts", "8", "--metrics-out", str(metrics),
    ])
    if rc != 0:
        raise RuntimeError(f"launch.train exited {rc}")
    snap = _metrics(metrics)
    steps = snap["histograms"]["train_step_s"]["count"]
    if steps != phases * minibatches:
        raise RuntimeError(f"rlvr: {steps} learner steps, want "
                           f"{phases * minibatches}")
    swaps = snap["serve"]["swaps"]
    if swaps < phases:
        raise RuntimeError(f"rlvr: the engine swapped weights {swaps} "
                           f"times, want >= {phases}")
    return {"layers": RLVR_LAYERS, "learner_steps": steps,
            "policy_version": snap["train"]["policy_version"],
            "engine_swaps": swaps,
            "rollout_tokens": snap["serve"]["tokens_out"],
            "degradation": _require_no_degradation(snap, "rlvr")}


def phase_sharded() -> dict:
    """Greedy tokens of the 4-way NB-sharded engine == one device's."""
    from repro.data.mathgen import MathTaskDataset
    from repro.launch.mesh import make_debug_mesh
    from repro.models.registry import build
    from repro.serve import ServeEngine

    bundle = build(_config())
    params = bundle.init(jax.random.PRNGKey(SEED))
    toks, _, _ = MathTaskDataset(prompt_len=32, seed=SEED + 1).sample_batch(8)
    prompts = [row[row != 0] for row in toks]
    budgets = [4, 12, 24, 40] * 2

    def serve(mesh):
        eng = ServeEngine(
            bundle, params, num_blocks=128, block_size=8, max_batch=4,
            max_seq_len=96, decode_chunk=4, temperature=1e-6, seed=SEED,
            mesh=mesh)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        done = {t.request_id: t.tokens for t in eng.run(max_steps=10_000)}
        return [np.asarray(done[r.request_id]) for r in reqs], eng

    want, _ = serve(None)
    got, eng = serve(make_debug_mesh(data=4))
    diff = [i for i, (a, b) in enumerate(zip(want, got))
            if not np.array_equal(a, b)]
    if diff:
        raise RuntimeError(f"sharded greedy tokens differ in requests "
                           f"{diff}")
    return {"shards": eng.num_shards, "requests": len(want),
            "tokens": int(sum(len(t) for t in want)), "token_exact": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-serve phase")
    args = ap.parse_args(argv)
    enable_compile_cache()
    _require_tpu()
    dev = _device()
    print(json.dumps({"phase": "device", **dev,
                      "bytes_limit": _memory_stat("bytes_limit")}),
          flush=True)
    clock = _CompileClock()
    if args.four_chips:
        if dev["count"] < 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{dev['count']}")
        _phase("sharded", clock, phase_sharded)
    else:
        _phase("serve", clock, phase_serve)
        _phase("parity", clock, phase_parity)
        _phase("rlvr", clock, phase_rlvr)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
