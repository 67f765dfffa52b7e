"""The readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 --seconds 20

For each seed, one run of the cell as ``run.py`` makes it (set-up, a
window of ``--seconds``, the sample of served requests), then on the same
sample both the program's widest log-probability gap to the reference
and the control's: the reference put in the program's place with each
product taken in three bfloat16 passes (the TPU's ``high``), the step
below the float32 at ``highest`` that the configuration states.  Each is
judged by the comparison ``run.py`` applies (``correct`` and
``control_correct``; the control's should be false).  All seeds run in
this one process, so programs compile once.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import program  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(harness.SPEC_FILE)
    entry = next(c for c in spec["workloads"] if c["name"] == args.workload)
    cell = harness.load_cell(entry["name"])
    conf = harness.load_config(entry["config"])
    mix = harness.load_mix(entry["traffic"])
    program.enable_compile_cache()
    harness.require_chips(entry["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.execute(entry, cell, conf, mix, seed, args.seconds, False,
                          {"end_to_end": [], "per_layer": []},
                          time.monotonic(), control=True)
        facts = res.pop("_run").facts
        check = res.pop("_checks")[0]
        print(json.dumps({"calibrate": args.workload, "seed": seed,
                          "program_gap": check["value"],
                          "correct": res["correct"],
                          "control_gap": facts["control_gap"],
                          "control_correct": res["_control_correct"],
                          "limit": check["limit"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
