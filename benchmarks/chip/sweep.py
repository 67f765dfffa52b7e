"""Find the highest rate an open-loop cell sustains, on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 30 --seed 5

Runs the cell once per rate, in this one process, as ``run.py`` would
with ``clients.rate_per_s`` set to that rate, and prints one JSON line
per rate: time to first token, and the backlog at the window's end
(requests waiting for a slot, and still running).  A rate is sustained
where nothing waits at the end and TTFT of the window's second half is
no worse than its first half's; the cell's file then fixes its rate at
about four fifths of the highest such rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import program  # noqa: E402
import readers  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    spec = harness.load_json(harness.SPEC_FILE)
    entry = next(c for c in spec["workloads"] if c["name"] == args.workload)
    base = harness.load_cell(entry["name"])
    conf = harness.load_config(entry["config"])
    mix = harness.load_mix(entry["traffic"])
    program.enable_compile_cache()
    harness.require_chips(entry["chips"])
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["clients"]["rate_per_s"] = rate
        res = run.execute(entry, cell, conf, mix, args.seed, args.seconds,
                          False, harness.cell_metrics(spec, entry["name"]),
                          time.monotonic())
        rec = res.pop("_run")
        half = rec.t_start + args.seconds / 2
        early = [s for s in rec.sent if s.due < half]
        late = [s for s in rec.sent if s.due >= half]
        sub = lambda xs: readers.nearest_rank(  # noqa: E731
            [((s.req.first_token_time or rec.t_end) - s.due) for s in xs],
            90)
        print(json.dumps({
            "sweep": args.workload, "rate_per_s": rate,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "ttft_p90_first_half_s": sub(early),
            "ttft_p90_second_half_s": sub(late),
            "waiting_at_end": rec.facts["waiting_at_end"],
            "running_at_end": rec.facts["running_at_end"],
            "requests_due": rec.facts["requests_due"],
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
