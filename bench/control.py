"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload large.batch --seconds 5 --seeds 11 12 13

For each seed, one process runs the cell as configured (a sound run) and
replays its sample with the plain reference four more ways
(``bench/check.py``): the control, the reference in the system's place in
bfloat16, the precision below the configuration's float32; float32 with
filter + OC alone in bfloat16; an answer altered where it is produced;
and each replayed step returning its input design. With ``--runs both``
it also runs the program's own lower-precision path (``precision: bf16``:
bf16 weights and surrogate inputs), the forward's control. Each run
prints one JSON line with the compared numbers. A limit sits above every
sound reading and below the control's smallest (``PERF.md`` gives both).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--runs", choices=("both", "sound", "bf16_path"),
                    default="sound")
    args = ap.parse_args()
    kinds = {"both": ("sound", "bf16_path")}.get(args.runs, (args.runs,))
    for seed in args.seeds:
        for kind in kinds:
            over = {"precision": "bf16"} if kind == "bf16_path" else None
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               overrides=over, variants=kind == "sound",
                               log=lambda *a: None)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "run": kind, "correct": res["correct"],
                              "completed_per_s": res["metrics"].get(
                                  "designs_per_s"),
                              "checks": res["checks"],
                              "variants": res.get("variants")}), flush=True)


if __name__ == "__main__":
    main()
