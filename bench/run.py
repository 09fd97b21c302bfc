"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The run needs the TPU
chips the cell asks for and exits non-zero, printing no result, without
them.  Set-up (weights made on the device from the seed, compilation or
the persistent compile cache in ``.jax_cache/``, the first three steps)
is timed as ``setup_s``; then the trainer runs for ``--seconds``.  With
``--trace 1`` the run reports the per-layer metrics, read from the
program's spans and a profiler trace of a few steps after the window.
The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each beside its limit, are the last
lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
