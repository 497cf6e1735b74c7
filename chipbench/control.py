#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 chipbench/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...]

For each seed, in one process: the cell's own set-up, a short window at the
cell's own load through the timed path, and then on the same sample of the
window's requests (every token served to them) two readings against the float32 reference:

* the program's: the widest gap by which a served token's reference logit
  lies below the reference's best at its position;
* the control's: the widest gap of the token that the reference computed in
  float8 (the precision below the configuration's bfloat16) puts first at
  each of those positions.

Each seed's line also gives the harness's own verdict (``run.judge``, the
comparison every run makes) on the program and on the control in its
place.  The limit goes between the largest program reading and the
smallest control reading (``PERF.md`` gives the readings and the limit).  The
benchmark's own runs never run the control.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import cells as C  # noqa: E402
from chipbench import run as R  # noqa: E402


def readings(cell: C.Cell, seed: int, seconds: float) -> dict:
    rec = R.Recorder()
    eng = R.prepare(cell, seed, False, rec)
    served = R.serve(cell, seed, seconds, False, eng, rec,
                     time.perf_counter_ns())
    R.free_engine(eng)
    del eng
    gc.collect()
    numbers, details = R.check(cell, seed, served, control=True)
    ctrl = details["control"]
    return {"seed": seed, "program_widest_gap": numbers["widest_gap"][0],
            "control_widest_gap": ctrl["widest_gap"][0],
            "limit": numbers["widest_gap"][1],
            "program_correct": all(ok for _, _, ok in numbers.values()),
            "control_correct": all(ok for _, _, ok in ctrl.values()),
            "tokens_checked": numbers["tokens_checked"][0],
            "tokens_after_restore": numbers["tokens_after_restore"][0],
            "window_tokens": served.window_tokens,
            "rows": details["gaps"], "control_rows": details["control_gaps"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = C.find_cell(args.workload)
    R.require_chip(cell.chips)
    R.enable_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (R.NoChip, C.CellError) as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
