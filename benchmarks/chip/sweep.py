"""Find a cell's knee: offer its traffic at several fixed rates in one
process and print the end-to-end metrics and the backlog at each.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rates 2,4,6,8

Open-loop cells only.  One set-up (weights, warm-up) serves every rate;
each rate gets a fresh engine on the same weights.  The knee is the
highest rate whose requests still finish as fast as they arrive (no
backlog growing through the window); a cell is then fixed at a rate
below it.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    import harness
    import traffic
    from repro.serving.engine import ServingEngine

    root = pathlib.Path.cwd()
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU found", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload, root)
    dm = cell.dims
    kw = harness.engine_kwargs(cell.mix)
    mcfg, params = harness.served_model(cell, args.seed)
    warm = ServingEngine(mcfg, params=params, **kw)
    harness.warm_up(warm, cell, args.seed)
    del warm
    gc.collect()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_rps=rate)
        eng = ServingEngine(mcfg, params=params, **kw)
        plan = traffic.plan(mix, args.seed, dm.vocab, args.seconds)
        t0 = time.perf_counter()
        sent = harness.drive(eng, plan, mix, args.seconds)
        e2e = harness.end_to_end(sent, args.seconds)
        due = [s for s in sent if s.sched < args.seconds]
        half = [s for s in due if s.sched < args.seconds / 2]
        print(json.dumps({
            "rate_rps": rate, **{k: round(v, 3) for k, v in e2e.items()},
            "finished": sum(s.req.done for s in due),
            "finished_first_half": sum(s.req.done for s in half),
            "due_first_half": len(half),
            "backlog_at_end": len(eng.queue) + len(eng.preempted),
            "active_at_end": len(eng.active),
            "decode_rows_mean": eng.stats.decode_tokens
            / max(eng.stats.decode_steps, 1),
            "swaps_out": eng.stats.swaps_out,
            "wall_s": round(time.perf_counter() - t0, 2)}), flush=True)
        del eng
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
