"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, on a
machine with the chips the cell asks for.  It exits non-zero, with no
result line, where JAX finds no TPU or fewer chips than the cell needs.
With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window and the engine's counters.  Each compared number is
printed beside its limit as the last lines of standard error and under
``check``, the result line's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "references"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = pathlib.Path.cwd()
    if not (root / "BENCHMARK.json").is_file():
        print("run.py: no BENCHMARK.json here; run from the checkout's root",
              file=sys.stderr)
        return 2

    import jax
    import harness

    cell = harness.load_cell(args.workload, root)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    ref = __import__(cell.cfg["reference"])
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, ref.score)
    info = out.pop("_info")
    print(json.dumps(info), file=sys.stderr)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
