"""Readings that limits are set from: for each seed, one run of a cell
with its numbers compared, and the same numbers for the control.  Where the
configuration names a ``control_precision`` (the matmul precision one step
below its own), the control is the program's own path at that precision: a
second run of the cell served so, checked as any run is, which has to come
out not ``correct``.  Otherwise it is the reference one precision below the
configuration's, put in the program's place within the same run.  Not part
of a benchmark run.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3

Prints one JSON line per seed; the set-up of later seeds reuses the
programs the first compiled, so a dozen seeds fit one process.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from bench import harness

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell.load(ROOT, args.workload)
    low = cell.cfg["correct"].get("control_precision")
    for seed in args.seeds:
        line, checked, _ = harness.run_cell(
            ROOT, args.workload, seed, args.seconds, False,
            control=low is None)
        if low is None:
            control = checked["control"]
        else:
            ctl, _, _ = harness.run_cell(ROOT, args.workload, seed,
                                         args.seconds, False, precision=low)
            control = {"precision": low, "correct": ctl["correct"],
                       "compared": ctl["compared"]}
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "program": line["compared"], "control": control,
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "notes": line["notes"]}), flush=True)


if __name__ == "__main__":
    main()
