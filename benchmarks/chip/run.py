"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload olmo_1b.decode_batch \
        --seed 7 --seconds 30 --trace 0

Runs on the machine it is started on and needs the chips the cell asks
for: with no TPU, or too few chips, it exits nonzero before timing
anything. The last line of standard output is one JSON object (keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``); the numbers the
correctness check compares, each beside its limit, are also the last
lines of standard error. See ``harness.py`` for what a run does.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the script's own directory would shadow top-level modules
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    bench, cell, config, mix = harness.load_cell(args.workload, ROOT)
    result = harness.run_cell(bench, cell, config, mix, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START)
    if result is None:
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
