"""Readings that a cell's limit is set from, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds 11,12,13 [--control-seeds 3]

For each seed it makes one whole run of the cell with tracing off (the
window at the cell's own load, then the check) and prints the widest
logit gap of the served tokens against the float32 reference: the
program's reading.  For the first ``--control-seeds`` seeds it also runs
the control, the reference computed in float8 (the step below the served
bfloat16), and prints the gap of the tokens that the control puts first
at the same positions: the control's reading, and ``control_correct``,
the verdict that the run's own comparison gives the control.  The cell's
limit lies between the largest program reading and the smallest control
reading, so ``control_correct`` has to read false.
Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "references"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    import jax
    import harness

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU found", file=sys.stderr)
        return 3
    root = pathlib.Path.cwd()
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload, root)
    ref = __import__(cell.cfg["reference"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(), ref.score,
                          control=i < args.control_seeds)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_correct": out.get("control_correct"),
                          "check": out["check"],
                          "metrics": {k: v["value"] for k, v
                                      in out["metrics"].items()},
                          "info": out["_info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
