"""The benchmark's command: one run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up (weights from the seed, deployment, warm-up of every shape
the window uses; ``setup_s`` counts all of it from the process's start),
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of standard
output.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a traced run.  The numbers compared for
``correct`` and their limits are the last lines of standard error.  Anything
but a TPU with the chips the cell asks for exits non-zero with no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# a fixed place inside the checkout for the profiler's trace
TRACE_DIR = ROOT / ".bench_out" / "trace"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from bench import harness

    cache = enable_compile_cache()
    # every program, however quick to compile, is kept: set-up then finds
    # all of them after the first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {cache}", file=sys.stderr, flush=True)
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    line, checked, _ = harness.run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
        trace_dir=TRACE_DIR, t_start=T_START)
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    for c in checked["checks"]:
        print(c, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
