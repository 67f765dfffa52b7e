"""Run one cell of the benchmark once, on the chip, and print its line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell, one run.  It fails, and prints no result, where
JAX's first device is not a TPU or fewer chips are found than the cell
asks for.  Set-up makes the weights on the device from the seed, builds
the engine as the cell's file sets it, compiles (or loads from the
persistent cache) every program the window uses and runs the traffic's
own set-up; then the window runs for ``--seconds``.  After the window the
device's peak memory is read, the engine is freed, and the reference
checks a sample of what was served.  The last line of standard output is
the result; the line before it carries the run's facts.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from harness import (HERE, RunRecord, cell_metrics, load_cell,  # noqa: E402
                     load_config, load_json, load_mix, load_module, now)


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[kind]


def read_metrics(entries: List[dict], run: RunRecord) -> Dict[str, Any]:
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(cell: Dict[str, Any], gap: Optional[float], failed: int
          ) -> Tuple[List[Dict[str, Any]], bool]:
    """The compared numbers beside their limits, and whether the run is
    correct: every number within its limit and no request failed."""
    limit = cell["check"]["max_logprob_gap"]
    checks = [{"name": "max_logprob_gap", "value": gap, "limit": limit,
               "ok": gap is not None and gap <= limit}]
    return checks, all(c["ok"] for c in checks) and failed == 0


def execute(entry: Dict[str, Any], cell: Dict[str, Any],
            conf: Dict[str, Any], mix: Dict[str, Any], seed: int,
            seconds: float, trace: bool, metrics: Dict[str, List[dict]],
            t_process: float, keep_trace: Optional[str] = None,
            control: bool = False) -> Dict[str, Any]:
    """One run of a cell, the device already checked; returns the result
    object, with the compared numbers under ``_checks`` and the run's
    records under ``_run`` for the caller to take off, and prints the
    facts line.  ``control`` also reads the control's gap on the same
    sample and judges it as the program's is judged, under
    ``_control_correct`` (``calibrate.py``)."""
    import jax

    import program
    import serving
    import weights

    clock = harness.CompileClock()
    chips = entry["chips"]
    kind = jax.devices()[0].device_kind
    rec = RunRecord(cell=cell, config=conf, seed=seed)
    if jax.devices()[0].platform == "tpu":
        rec.peaks = peaks_for(kind)
    facts = rec.facts
    facts.update(cell=cell["name"], seed=seed)
    t = now()
    w = weights.make(conf, seed)
    jax.block_until_ready(w)
    facts["weights_s"] = now() - t
    facts["weights_bytes"] = weights.nbytes(w)
    # The program runs at the matmul precision its configuration states.
    with jax.default_matmul_precision(conf["matmul_precision"]):
        eng = program.engine(conf, w, cell, seed=seed % (1 << 31),
                             annotate=True)
        rec.max_batch = cell["engine"]["max_batch"]
        rec.num_blocks = eng.allocator.num_blocks
        t = now()
        facts["programs_warmed"] = serving.warm_up(
            eng, cell["engine"]["prefill_chunk"], conf["vocab_size"], seed)
        facts["warm_up_s"] = now() - t
        gen = load_module("traffic", mix["generator"])
        client = serving.Client(eng, rec)
        loop = gen.Loop(client, gen.plan(mix, cell, seed, conf["vocab_size"],
                                         seconds), cell)
        t = now()
        loop.prepare()
        facts["traffic_setup_s"] = now() - t
        hist = {name: eng.metrics.histogram(name).count
                for name in ("serve_queue_wait_s",)}
        tokens0 = eng.stats.tokens_out
        compiles0, compile_s0 = clock.count, clock.seconds
        facts["setup_s"] = now() - t_process
        facts["setup_compiles"] = compiles0
        facts["setup_compile_s"] = compile_s0
        log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            jax.profiler.start_trace(log_dir)
        client.recording = True
        rec.t_start = now()
        with jax.profiler.TraceAnnotation("bench.window"):
            facts.update(loop.window(rec.t_start + seconds))
        rec.t_end = now()
        client.recording = False
        if trace:
            jax.profiler.stop_trace()
    facts["compiles_in_window"] = clock.count - compiles0
    facts["window_s"] = rec.window_s
    facts["rounds"] = len(rec.rounds)
    rec.tokens_out = eng.stats.tokens_out - tokens0
    for name, start in hist.items():
        rec.histograms[name] = serving.window_histogram(eng, name, start)
    device = harness.device_facts(chips)
    facts["bytes_limit"] = device.pop("bytes_limit")
    facts["memory_peak_bytes"] = device["memory_peak_bytes"]
    facts["requests_sent"] = len(rec.sent)
    facts["requests_finished"] = sum(
        1 for s in rec.sent if s.req.finish_time is not None)
    facts["tokens_out"] = rec.tokens_out
    result: Dict[str, Any] = {}
    if trace:
        import xplane

        path = xplane.find_xplane(log_dir)
        if keep_trace:
            shutil.copy(path, keep_trace)
            Path(keep_trace + ".txt").write_text(xplane.describe(path))
        rec.trace = xplane.reduce(path)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    values = read_metrics(metrics["per_layer" if trace else "end_to_end"],
                          rec)

    # -- correctness, with the program's state freed ------------------------
    picked = serving.sample_served(rec.sent, seed,
                                   cell["check"]["sample_requests"],
                                   gen.FINISHED_ONLY)
    del loop, client, eng
    gc.collect()
    t = now()
    gap = serving.check_logprobs(w, conf, picked)
    facts["reference_s"] = now() - t
    facts["tokens_checked"] = sum(len(s.req.tokens) for s in picked)
    failed = sum(1 for s in rec.sent
                 if s.req.finish_reason not in (None, "eos", "length"))
    checks, correct = judge(cell, gap, failed)
    if control:
        facts["control_gap"] = serving.check_logprobs(w, conf, picked,
                                                      control=True)
        result["_control_correct"] = judge(cell, facts["control_gap"],
                                           failed)[1]
    print(json.dumps({"facts": facts}), flush=True)
    result.update({
        "correct": correct,
        "attempted": len(rec.sent), "failed": failed,
        "metrics": values, "device": device})
    result["_checks"] = checks
    result["_run"] = rec
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the trace's xplane here, with a readable "
                         "summary beside it")
    args = ap.parse_args(argv)
    spec = load_json(harness.SPEC_FILE)
    entry = next((c for c in spec["workloads"]
                  if c["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = load_cell(entry["name"])
    conf = load_config(entry["config"])
    mix = load_mix(entry["traffic"])

    import program

    program.enable_compile_cache()
    harness.require_chips(entry["chips"])
    result = execute(entry, cell, conf, mix, args.seed, args.seconds,
                     bool(args.trace), cell_metrics(spec, entry["name"]),
                     T_PROCESS, args.keep_trace)
    result.pop("_run")
    harness.emit(result, result.pop("_checks"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
