"""Readings that set a cell's correctness limit, in one process.

    python3 benchmarks/chip/control.py --workload olmo_1b.decode_batch \
        --seeds 1,2,3 --seconds 15

For each seed: one run of the cell as the benchmark makes it (same
set-up, traffic and check), with the fp8 control put in the program's
place on the same sample: the plain reference with every matmul operand
rounded to float8 e4m3 under a per-tensor scale, one precision step
below the configuration's bf16, whose top token at each served position
goes through the same verdict as a served token. Prints each seed's
result line (``correct`` is the control's verdict; ``program`` holds the
program's own verdict and gap), a row per seed and a JSON summary last.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    bench, cell, config, mix = harness.load_cell(args.workload, ROOT)
    rows = []
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = harness.run_cell(bench, cell, config, mix, seed=seed,
                             seconds=args.seconds, trace=False, t_start=t,
                             control=True)
        if r is None:
            return 2
        harness.print_result(r)
        row = {"seed": seed, "correct": r["program"]["correct"],
               "gap": r["program"]["logit_gap"],
               "control_correct": r["correct"],
               "control_gap": r["checks"]["logit_gap"]["value"],
               "attempted": r["attempted"], "failed": r["failed"],
               "metrics": {k: v["value"] for k, v in r["metrics"].items()},
               "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
        print("[control] " + json.dumps(row), flush=True)
        rows.append(row)
        t = time.perf_counter()
    gaps = [r["gap"] for r in rows if r["gap"] is not None]
    ctl = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_gap_max": max(gaps) if gaps else None,
                      "control_gap_min": min(ctl) if ctl else None,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
